"""Packed-lane GF(2^8) kernel exactness (ops/packed_gf.py).

Interpret mode runs the very kernel body on CPU; the hardware path is
compiled for a described v5e in tests/test_chip_compile.py and run on
the chip by chip_smoke.py.  Contract: bit-identical to the numpy
oracle for encode AND decode matrices, including the padding path.
"""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.gf.matrix import (
    isa_cauchy_matrix,
    make_decoding_matrix,
    reed_sol_vandermonde_coding_matrix,
)
from ceph_tpu.gf import matrix_vector_mul_region
from ceph_tpu.ops.gf_matmul import matrix_to_device_bitmatrix
from ceph_tpu.ops import packed_gf

rng = np.random.default_rng(0xCE9)


def _check(matrix, k, nbytes):
    bm = np.asarray(matrix_to_device_bitmatrix(matrix, 8))
    assert packed_gf.supports(bm, 8)
    regions = rng.integers(0, 256, (k, nbytes), dtype=np.uint8)
    want = matrix_vector_mul_region(matrix, regions, 8)
    got = np.asarray(
        packed_gf.packed_bitmatrix_regions(bm, regions, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("k,m", [(4, 2), (8, 3), (10, 4)])
def test_encode_matches_oracle(k, m):
    _check(reed_sol_vandermonde_coding_matrix(k, m, 8), k, 4096)


def test_cauchy_and_padding_tail():
    # 4100 bytes: not a multiple of the tile width -> padding path
    _check(isa_cauchy_matrix(6, 3), 6, 4100)


def test_decode_matrix_matches_oracle():
    k, m = 8, 3
    enc = reed_sol_vandermonde_coding_matrix(k, m, 8)
    dec, survivors = make_decoding_matrix(enc, [1, 6], k, 8)
    _check(np.asarray(dec), k, 2048)


def test_stripes_layout(monkeypatch):
    """The one stripes path of the packed kernel, the jax backend's
    ``matrix_stripe_shards`` (fold, word form, kernel, rows out),
    through the kernel's interpreter."""
    from ceph_tpu.ops import ec_backend

    k, m = 8, 3
    mat = reed_sol_vandermonde_coding_matrix(k, m, 8)
    stripes = rng.integers(0, 256, (5, k, 512), dtype=np.uint8)
    monkeypatch.setattr(ec_backend, "_on_tpu", lambda: True)
    monkeypatch.setattr(ec_backend.mesh, "default_mesh", lambda: None)
    built = packed_gf.prebuilt_word_call
    calls = []

    def interpreted(bm, w=8):
        calls.append(bm.shape)
        return built(bm, w, interpret=True)

    monkeypatch.setattr(packed_gf, "prebuilt_word_call", interpreted)
    _data, coding = ec_backend.get_jax_backend().matrix_stripe_shards(
        mat, stripes, 8
    )
    assert calls == [(m * 8, k * 8)]
    # coding shard j is chunk j of every stripe, concatenated
    got = np.stack(coding).reshape(m, 5, 512).transpose(1, 0, 2)
    for s in range(5):
        want = matrix_vector_mul_region(mat, stripes[s], 8)
        np.testing.assert_array_equal(got[s], want)


def test_supports_guard():
    mat = reed_sol_vandermonde_coding_matrix(4, 2, 8)
    bm = np.asarray(matrix_to_device_bitmatrix(mat, 8))
    assert packed_gf.supports(bm, 8)
    assert not packed_gf.supports(bm, 16)
    dense = np.ones((8, 64 * 40), dtype=np.uint8)  # popcount 2560 > 255
    assert not packed_gf.supports(dense, 8)
