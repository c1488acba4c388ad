"""Packed-lane GF(2^8) kernel exactness (ops/packed_gf.py).

Interpret mode runs the very kernel body on CPU; the hardware path is
compiled for a described v5e in tests/test_chip_compile.py and run on
the chip by chip_smoke.py.  Contract: bit-identical to the numpy
oracle for encode AND decode matrices, including the padding path.
"""

from __future__ import annotations

import itertools

import numpy as np
import pytest

from ceph_tpu.gf.matrix import (
    isa_cauchy_matrix,
    make_decoding_matrix,
    reed_sol_vandermonde_coding_matrix,
)
from ceph_tpu.gf import matrix_vector_mul_region
from ceph_tpu.layout import fold_stripes
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


@pytest.mark.parametrize(
    "b,chunk,block",
    [
        (2, 65536, (1, 64)),  # the encode cell's block: 1 x 64 rows of a 128 KiB chunk's 256
        (16, 4096, (8, 8)),  # the served pool's: 8 whole 4 KiB chunks
        (12, 4096, (8, 8)),  # an edge block of 4 stripes
        (3, 8192, (3, 16)),  # fewer stripes than a block would take
        (2, 512 * 72, (1, 64)),  # an edge block of 8 rows
        (1, 4096, (1, 8)),
    ],
)
@pytest.mark.parametrize("k,m", [(4, 2), (8, 3), (10, 4)])
def test_stripe_form_is_shard_form_byte_for_byte(k, m, b, chunk, block):
    """ONE (B, k, R, 128) operand read through the BlockSpecs against
    the k folded rows: the same kernel body, so the same bytes, and
    an output flattened IS the folded coding shard."""
    matrix = reed_sol_vandermonde_coding_matrix(k, m, 8)
    bm = np.asarray(matrix_to_device_bitmatrix(matrix, 8))
    stripes = rng.integers(0, 256, (b, k, chunk), dtype=np.uint8)
    folded = np.ascontiguousarray(fold_stripes(stripes))
    assert packed_gf._stripe_block(b, chunk // 512) == block
    words = packed_gf.stripe_words(stripes)
    assert words.shape == (b, k, chunk // 512, 128)
    assert np.shares_memory(words, stripes)
    call = packed_gf.prebuilt_word_call(bm, interpret=True)
    outs = call(words)
    assert [o.shape for o in outs] == [(b, chunk // 512, 128)] * m
    got = np.stack([np.asarray(o).reshape(-1).view(np.uint8) for o in outs])
    want = packed_gf.packed_bitmatrix_regions(bm, folded, interpret=True)
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(
        got, matrix_vector_mul_region(matrix, folded, 8)
    )


def test_the_cells_stripe_blocks_are_tile_words_a_step():
    """64 MiB at k=8 (chunk 128 KiB): 1 stripe x 64 rows, 256 steps a
    chunk position — the shard form's count; the served pool's 4 MiB
    at k=4 (chunk 4 KiB): 8 whole chunks a step."""
    assert packed_gf._stripe_block(64, 256) == (1, 64)
    assert packed_gf._stripe_block(256, 8) == (8, 8)
    for b, r in [(64, 256), (256, 8), (5, 16), (1, 8)]:
        tb, tr = packed_gf._stripe_block(b, r)
        assert tb * tr * 128 <= packed_gf.TILE_WORDS
        assert tr % 8 == 0 or tr == r  # whole (8, 128) tiles


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


def _rebuild_rows(k, m, lost, matrix=None):
    """The (len(lost), k) rows ``stripe.decode`` hands the backend."""
    from ceph_tpu.ec.stripe import reconstruction_rows

    if matrix is None:
        matrix = reed_sol_vandermonde_coding_matrix(k, m, 8)
    avail = [p for p in range(k + m) if p not in lost]
    rows, _survivors = reconstruction_rows(
        np.asarray(matrix), list(lost), avail, k, 8)
    return rows


def _check_decode(rows, nbytes):
    r, s = rows.shape
    regions = rng.integers(0, 256, (s, nbytes), dtype=np.uint8)
    call = packed_gf.prebuilt_decode_call(r, s, interpret=True)
    out = call(
        packed_gf.device_coefficients(rows),
        *[x.view(np.uint32).reshape(-1, packed_gf.LANES) for x in regions],
    )
    assert out.shape == (r, nbytes // 512, packed_gf.LANES)
    got = np.asarray(out).reshape(r, -1).view(np.uint8)
    np.testing.assert_array_equal(
        got, matrix_vector_mul_region(rows, regions, 8))


@pytest.mark.parametrize(
    "lost", [pytest.param(p, id=f"{p[0]}-{p[1]}")
             for p in itertools.combinations(range(11), 2)])
def test_decode_kernel_rebuilds_every_pair_of_k8m3(lost):
    """The rebuild's kernel, its coefficients an operand, against the
    field oracle for each of the 55 two-erasure patterns of jerasure
    k=8 m=3 (the ``decode_2e_1m`` cell's): 9 rows of 128 words, an
    edge block past the last."""
    _check_decode(_rebuild_rows(8, 3, lost), 512 * 9)


@pytest.mark.parametrize(
    "case",
    ["k4m2_one_lost", "isa_k10m4_four_lost", "zero_and_one", "tall_block"],
)
def test_decode_kernel_other_shapes(case):
    if case == "k4m2_one_lost":
        rows, nbytes = _rebuild_rows(4, 2, (1,)), 512 * 8
    elif case == "isa_k10m4_four_lost":
        rows = _rebuild_rows(10, 4, (0, 3, 10, 13), isa_cauchy_matrix(10, 4))
        nbytes = 512 * 3
    elif case == "zero_and_one":
        rows, nbytes = np.array([[0, 1, 0, 1], [1, 1, 0, 0]]), 512 * 2
    else:  # more rows than a block takes: two grid steps and an edge
        rows = _rebuild_rows(8, 3, (0, 9))
        nbytes = 4 * (packed_gf.TILE_WORDS + 3 * packed_gf.LANES)
    assert rows.shape[0] == {"k4m2_one_lost": 1, "isa_k10m4_four_lost": 4}.get(
        case, 2)
    _check_decode(np.asarray(rows), nbytes)


def test_one_decode_program_serves_every_pattern_of_a_shape():
    """The coefficients are data: two patterns of one shape trace one
    program, and each call is its own pattern's rebuild."""
    call = packed_gf.prebuilt_decode_call(2, 8, interpret=True)
    regions = rng.integers(0, 256, (8, 512 * 5), dtype=np.uint8)
    words = [x.view(np.uint32).reshape(-1, packed_gf.LANES) for x in regions]
    before = call._cache_size()
    for lost in [(2, 9), (0, 1)]:
        rows = _rebuild_rows(8, 3, lost)
        got = np.asarray(call(packed_gf.device_coefficients(rows), *words))
        np.testing.assert_array_equal(
            got.reshape(2, -1).view(np.uint8),
            matrix_vector_mul_region(rows, regions, 8))
    assert call._cache_size() == before + 1


def test_device_coefficients_are_cached_by_value():
    rows = _rebuild_rows(8, 3, (4, 7))
    a = packed_gf.device_coefficients(rows)
    assert packed_gf.device_coefficients(rows.copy()) is a
    assert a.dtype == np.int32 and a.shape == (16,)
    np.testing.assert_array_equal(np.asarray(a), rows.reshape(-1))


def test_supports_guard():
    mat = reed_sol_vandermonde_coding_matrix(4, 2, 8)
    bm = np.asarray(matrix_to_device_bitmatrix(mat, 8))
    assert packed_gf.supports(bm, 8)
    assert not packed_gf.supports(bm, 16)
    dense = np.ones((8, 64 * 40), dtype=np.uint8)  # popcount 2560 > 255
    assert not packed_gf.supports(dense, 8)
