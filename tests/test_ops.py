"""TPU backend (bit-matmul) byte-exactness vs the numpy oracle.

Every kernel result must match ceph_tpu.gf / the numpy EC backend
bit-for-bit — the contract the reference enforces with its erasure-code
corpus (src/test/erasure-code/ceph_erasure_code_non_regression.cc).
"""

import numpy as np
import pytest

from ceph_tpu import gf
from ceph_tpu.ec.backend import get_backend
from ceph_tpu.ec.registry import instance as registry
from ceph_tpu.ec.interface import ErasureCodeProfile

rng = np.random.default_rng(0xCE9)


def random_regions(k, nbytes):
    return rng.integers(0, 256, size=(k, nbytes), dtype=np.uint8)


@pytest.mark.parametrize("w", [8, 16, 32])
@pytest.mark.parametrize("k,m", [(2, 1), (4, 2), (8, 3), (10, 4)])
def test_matrix_regions_matches_oracle(w, k, m):
    matrix = (
        gf.reed_sol_vandermonde_coding_matrix(k, m, w)
        if w != 8
        else gf.isa_cauchy_matrix(k, m)
    )
    regions = random_regions(k, 256 * (w // 8))
    want = get_backend("numpy").matrix_regions(matrix, regions, w)
    got = get_backend("jax").matrix_regions(matrix, regions, w)
    np.testing.assert_array_equal(want, got)


@pytest.mark.parametrize("w,packetsize", [(8, 8), (4, 16), (7, 8)])
def test_bitmatrix_regions_matches_oracle(w, packetsize):
    k, m = 4, 2
    bm = rng.integers(0, 2, size=(m * w, k * w), dtype=np.uint8)
    regions = random_regions(k, 3 * w * packetsize)
    want = get_backend("numpy").bitmatrix_regions(bm, regions, w, packetsize)
    got = get_backend("jax").bitmatrix_regions(bm, regions, w, packetsize)
    np.testing.assert_array_equal(want, got)


def test_matrix_stripe_shards_batches_encode():
    k, m, w = 4, 2, 8
    matrix = gf.reed_sol_vandermonde_coding_matrix(k, m, w)
    stripes = rng.integers(0, 256, size=(5, k, 128), dtype=np.uint8)
    data, coding = get_backend("jax").matrix_stripe_shards(
        matrix, stripes, w
    )
    # a shard is chunk i of every stripe, concatenated
    got = np.stack(data + coding).reshape(k + m, 5, 128)
    np.testing.assert_array_equal(got[:k], stripes.transpose(1, 0, 2))
    np.testing.assert_array_equal(
        got[k:].transpose(1, 0, 2),
        get_backend("numpy").matrix_stripes(matrix, stripes, w),
    )
    for b in range(5):
        want = get_backend("numpy").matrix_regions(matrix, stripes[b], w)
        np.testing.assert_array_equal(want, got[k:, b])


PROFILES = [
    ("jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2"}),
    ("jerasure", {"technique": "reed_sol_van", "k": "8", "m": "3", "w": "16"}),
    ("jerasure", {"technique": "reed_sol_r6_op", "k": "5"}),
    ("jerasure", {"technique": "cauchy_good", "k": "4", "m": "2",
                  "packetsize": "32"}),
    ("jerasure", {"technique": "liberation", "k": "5", "w": "7",
                  "packetsize": "8"}),
    ("isa", {"technique": "reed_sol_van", "k": "8", "m": "3"}),
    ("isa", {"technique": "cauchy", "k": "10", "m": "4"}),
]


@pytest.mark.parametrize("plugin,profile", PROFILES)
def test_end_to_end_jax_equals_numpy(plugin, profile):
    """Full encode + all-single/double-erasure decode parity per family."""
    payload = rng.integers(0, 256, size=40000, dtype=np.uint8).tobytes()
    codes = {}
    for backend in ("numpy", "jax"):
        prof = ErasureCodeProfile({**profile, "backend": backend})
        codes[backend] = registry().factory(plugin, prof)
    ec_np, ec_jax = codes["numpy"], codes["jax"]
    k, m = ec_np.k, ec_np.m
    want_all = set(range(k + m))

    enc_np = ec_np.encode(want_all, payload)
    enc_jax = ec_jax.encode(want_all, payload)
    assert enc_np.keys() == enc_jax.keys()
    for i in enc_np:
        np.testing.assert_array_equal(enc_np[i], enc_jax[i], err_msg=f"chunk {i}")

    # erase every single chunk and one double pattern; decode must agree
    patterns = [[i] for i in range(k + m)] + [[0, k]]
    for erased in patterns:
        if len(erased) > m:
            continue
        avail = {i: c for i, c in enc_np.items() if i not in erased}
        dec_np = ec_np.decode(want_all, dict(avail))
        dec_jax = ec_jax.decode(want_all, dict(avail))
        for i in want_all:
            np.testing.assert_array_equal(
                dec_np[i], dec_jax[i], err_msg=f"erased={erased} chunk {i}"
            )
        for i in erased:
            np.testing.assert_array_equal(enc_np[i], dec_np[i])
