"""``stripe.encode`` hands out the backend's own rows (ISSUE 30): the k
folded data rows and the m coding rows ARE the shards, on the numpy
backend, the jax backend on the CPU (bitplane, and the virtual mesh at
64 stripes) and the packed path through the kernel's interpreter —
byte for byte the plain reference's, one ``ec_encode`` record a call,
one host copy of the input on the packed path, and the spans that
feed ``ec_plugin_host_ms_per_call`` still there."""

from __future__ import annotations

import itertools
import pathlib
import sys

import numpy as np
import pytest

import ceph_tpu.ops  # noqa: F401  registers the jax backend
from ceph_tpu.ec import ErasureCodeProfile, registry_instance
from ceph_tpu.ec.stripe import StripeInfo, encode, encode_batch
from ceph_tpu.ops import ec_backend, packed_gf
from ceph_tpu.ops.profiler import dispatch_profiler

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from benchmark.references import reed_sol_van_codec  # noqa: E402

PATHS = ("numpy", "jax", "packed")
SHAPES = [(4, 2), (8, 3)]
# stripes a call, and the chunk each is cut to (64 at a small one)
BATCHES = {1: 512, 2: 512, 3: 512, 64: 64}
WANTS = {
    "all": lambda k, m: set(range(k + m)),
    "data": lambda k, m: set(range(k)),
    "coding": lambda k, m: set(range(k, k + m)),
    "one": lambda k, m: {k - 1},
}


def _code(k, m, path):
    return registry_instance().factory("jerasure", ErasureCodeProfile(
        technique="reed_sol_van", k=str(k), m=str(m), w="8",
        backend="numpy" if path == "numpy" else "jax"))


@pytest.fixture
def on_path(monkeypatch):
    """Steers the jax backend onto the chip-only packed path, its
    kernel run by the interpreter (as tests/test_packed_gf.py and
    tests/test_stripe_decode.py do)."""
    def steer(path):
        if path != "packed":
            return
        monkeypatch.setattr(ec_backend, "_on_tpu", lambda: True)
        monkeypatch.setattr(ec_backend.mesh, "default_mesh", lambda: None)
        built = packed_gf.prebuilt_word_call
        monkeypatch.setattr(
            packed_gf, "prebuilt_word_call",
            lambda bm, w=8: built(bm, w, interpret=True))
    return steer


def _records():
    return dispatch_profiler().history("ec_encode")["entries"]


def _payload(k, b, chunk, seed=30):
    return np.random.default_rng(seed).integers(
        0, 256, b * k * chunk, dtype=np.uint8)


@pytest.mark.parametrize("want", WANTS)
@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("k,m", SHAPES)
@pytest.mark.parametrize("path", PATHS)
def test_the_backends_rows_are_the_shards(on_path, path, k, m, b, want):
    chunk = BATCHES[b]
    ec = _code(k, m, path)
    sinfo = StripeInfo(k, k * chunk)
    data = _payload(k, b, chunk)
    positions = WANTS[want](k, m)
    on_path(path)
    seen = len(_records())
    got = encode(sinfo, ec, data, positions)
    (rec,) = _records()[seen:]
    assert (rec["ops"], rec["stripes"], rec["bytes_in"]) == (1, b, data.nbytes)
    assert rec["backend"] == ("numpy" if path == "numpy" else "jax")

    reference = reed_sol_van_codec.encode_shards(data.tobytes(), k, m, chunk)
    assert set(got) == positions
    for p, shard in got.items():
        assert shard.ndim == 1 and shard.dtype == np.uint8
        assert shard.flags.c_contiguous and len(shard) == b * chunk
        assert np.array_equal(shard, reference[p]), p

    coding = [got[p] for p in sorted(positions) if p >= k]
    for one, other in itertools.combinations(coding, 2):
        assert not np.shares_memory(one, other)
    if path == "packed" and b > 1:
        # ONE host copy of the input: the fold (and a page of slack to
        # place it by), whose rows go up the link and come back as
        # the data shards
        rows = [got[p] for p in sorted(positions) if p < k]
        assert len({id(row.base) for row in rows}) <= 1
        for row in rows:
            assert row.base.nbytes == k * b * chunk + 4096
            assert not np.shares_memory(row, data)
        if rows:
            # ... started half a page off the input within a page
            first = min(p for p in positions if p < k)
            fold = rows[0].ctypes.data - first * b * chunk
            assert (fold - data.ctypes.data) % 4096 == 2048
        assert rec["bytes_uploaded"] == data.nbytes


@pytest.mark.parametrize("k,m", SHAPES)
@pytest.mark.parametrize("path", PATHS)
def test_encode_batch_of_two_buffers_is_two_encodes(on_path, path, k, m):
    chunk = 256
    ec = _code(k, m, path)
    sinfo = StripeInfo(k, k * chunk)
    buffers = [_payload(k, 3, chunk, seed=1), _payload(k, 2, chunk, seed=2)]
    on_path(path)
    singles = [encode(sinfo, ec, buf) for buf in buffers]
    batched = encode_batch(sinfo, ec, buffers)
    for single, both in zip(singles, batched):
        assert sorted(single) == sorted(both) == list(range(k + m))
        for p in single:
            assert single[p].shape == both[p].shape
            assert np.array_equal(single[p], both[p]), p


def test_one_stripe_folds_to_views_of_the_input(on_path):
    """B == 1: the stripe is its own fold, so nothing is copied and
    the data shards alias the caller's buffer, as they always have."""
    k, m, chunk = 4, 2, 512
    data = _payload(k, 1, chunk)
    on_path("packed")
    got = encode(StripeInfo(k, k * chunk), _code(k, m, "packed"), data)
    for p in range(k):
        assert np.shares_memory(got[p], data)
        assert np.array_equal(got[p], data[p * chunk:(p + 1) * chunk])


def test_the_spans_of_a_packed_encode_still_feed_the_host_metric(on_path):
    """``ec_fold`` round the row copies (a span a row), ``ec_unfold``
    and ``ec_assemble`` round what is left at those places: the
    counters the window's diff carries give
    ``ec_plugin_host_ms_per_call`` a number, and it is their sum."""
    k, m, b, chunk = 8, 3, 4, 512
    ec = _code(k, m, "packed")
    data = _payload(k, b, chunk)
    on_path("packed")

    class Calls:
        calls = 0

        def counters(self):
            return {"calls": self.calls}

    driver = Calls()
    before = harness.flat_counters(driver)
    encode(StripeInfo(k, k * chunk), ec, data)
    driver.calls = 1
    counters = harness.diff_counters(before, harness.flat_counters(driver))
    assert counters["l_stage_ec_fold_count"] == k
    assert counters["l_stage_ec_fold_ns"] > 0
    assert counters["l_stage_ec_unfold_count"] == 1
    assert counters["l_stage_ec_assemble_count"] == 1
    assert counters["dispatch.ec_encode.dispatches"] == 1
    read = harness.load_reader("layer_metrics", "ec_plugin_host_ms_per_call")
    host_ms = read({"counters": counters})
    assert host_ms == pytest.approx(1e-6 * sum(
        counters[f"l_stage_{name}_ns"]
        for name in ("ec_fold", "ec_unfold", "ec_assemble")))
    assert host_ms > 0
    # kernel_stats' byte counts are what they were: the input in, the
    # coding rows out of the matmul, every shard out of the encode
    shard = b * chunk
    for group, bytes_out in (("gf_matmul", m * shard), ("ec_encode", (k + m) * shard)):
        assert counters[f"l_tpu_{group}_calls"] == 1
        assert counters[f"l_tpu_{group}_bytes_in"] == data.nbytes
        assert counters[f"l_tpu_{group}_bytes_out"] == bytes_out
