"""``stripe.encode`` hands out the backend's own rows (ISSUE 30): the k
folded data rows and the m coding rows ARE the shards, on the numpy
backend, the jax backend on the CPU (bitplane, and the virtual mesh at
64 stripes) and the packed path through the kernel's interpreter —
byte for byte the plain reference's, one ``ec_encode`` record a call,
one host copy of the input on the packed path, and the spans that
feed ``ec_plugin_host_ms_per_call`` still there.  Where the chunk is
whole (8, 128) u32 tiles the packed path's upload is the caller's own
buffer, put before the fold (ISSUE 40); any other chunk is folded
first."""

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
# (stripes a call, the chunk each is cut to): below 4096 the packed
# path folds first; at whole tiles it puts the caller's buffer — one
# stripe, fewer stripes than a block takes, whole blocks, and an edge
# block in the stripes (12 and 6 by blocks of 8 and 4)
BATCHES = [
    (1, 512), (2, 512), (3, 512), (64, 64),
    (1, 4096), (3, 4096), (8, 4096), (12, 4096), (64, 4096),
    (1, 8192), (3, 8192), (6, 8192), (8, 8192),
]
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
@pytest.mark.parametrize("b,chunk", BATCHES)
@pytest.mark.parametrize("k,m", SHAPES)
@pytest.mark.parametrize("path", PATHS)
def test_the_backends_rows_are_the_shards(on_path, path, k, m, b, chunk, want):
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


@pytest.fixture
def order(monkeypatch):
    """What a packed encode does in what order: every ``device_put``
    (with its array) and every span the backend opens, as they
    happen."""
    import jax

    events = []
    put, stage = jax.device_put, ec_backend.tracing.stage

    def spied_put(x, *args, **kwargs):
        events.append(("put", x))
        return put(x, *args, **kwargs)

    def spied_stage(name, *args, **kwargs):
        events.append((name, None))
        return stage(name, *args, **kwargs)

    monkeypatch.setattr(jax, "device_put", spied_put)
    monkeypatch.setattr(ec_backend.tracing, "stage", spied_stage)
    return events


def _fold_counters():
    dump = ec_backend.kernel_stats().dump()
    return (dump.get("l_stage_ec_fold_ns", 0), dump.get("l_stage_ec_fold_count", 0),
            dump.get("l_tpu_ec_fold_overlapped_ns", 0))


@pytest.mark.parametrize("b,chunk", [(1, 4096), (3, 4096), (12, 4096), (8, 8192)])
@pytest.mark.parametrize("k,m", SHAPES)
def test_a_whole_tile_chunk_goes_up_as_the_callers_buffer_before_the_fold(
        on_path, order, k, m, b, chunk):
    """ONE put a call, of a view of the caller's data and no copy of
    it, issued before the first ``ec_fold`` span opens; the fold then
    runs with the upload issued, all of it (ISSUE 40)."""
    ec = _code(k, m, "packed")
    data = _payload(k, b, chunk)
    on_path("packed")
    fold_ns, fold_count, overlapped_ns = _fold_counters()
    seen = len(_records())
    got = encode(StripeInfo(k, k * chunk), ec, data)
    (rec,) = _records()[seen:]
    names = [name for name, _ in order]
    assert names == ["put"] + ["ec_fold"] * k + ["ec_unfold", "ec_assemble"]
    (put,) = [x for name, x in order if name == "put"]
    assert put.dtype == np.uint32 and put.shape == (b, k, chunk // 512, 128)
    assert np.shares_memory(put, data) and put.nbytes == data.nbytes
    assert rec["bytes_uploaded"] == data.nbytes
    after = _fold_counters()
    assert after[1] - fold_count == k
    assert after[2] - overlapped_ns == after[0] - fold_ns > 0
    reference = reed_sol_van_codec.encode_shards(data.tobytes(), k, m, chunk)
    assert all(np.array_equal(got[p], reference[p]) for p in range(k + m))


@pytest.mark.parametrize("b,chunk", [(1, 512), (3, 512), (64, 64), (2, 4096 + 512)])
def test_any_other_chunk_is_folded_first_a_row_at_a_time(on_path, order, b, chunk):
    """Fold-first, as it was: each row's put follows its own
    ``ec_fold`` span, k puts of folded rows that share nothing with
    the caller's data (but for one stripe, its own fold), and no
    nanosecond of the fold counts as overlapped."""
    k, m = 4, 2
    ec = _code(k, m, "packed")
    data = _payload(k, b, chunk)
    on_path("packed")
    fold_ns, _count, overlapped_ns = _fold_counters()
    got = encode(StripeInfo(k, k * chunk), ec, data)
    names = [name for name, _ in order]
    assert names == ["ec_fold", "put"] * k + ["ec_unfold", "ec_assemble"]
    for _name, row in (e for e in order if e[0] == "put"):
        assert row.shape == (1, b * chunk // 4)
        assert np.shares_memory(row, data) == (b == 1)
    after = _fold_counters()
    assert after[0] > fold_ns and after[2] == overlapped_ns
    assert "l_tpu_ec_fold_overlapped_ns" in ec_backend.kernel_stats().dump()
    reference = reed_sol_van_codec.encode_shards(data.tobytes(), k, m, chunk)
    assert all(np.array_equal(got[p], reference[p]) for p in range(k + m))


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


def test_ten_threads_at_the_served_shape_each_get_their_own_shards(
        on_path, monkeypatch):
    """A served pool's 4 MiB ``write_full`` (256 stripes of k=4 m=2,
    chunk 4096) from ten threads at once, as ten OSDs of one process
    meet ``_packed_stripes``, each under a daemon's own buffered
    tracer: every call's k + m shards are the reference's of ITS
    payload — the put's source is the caller's buffer until the coding
    rows are back, nothing is shared between calls — and the overlap
    counter is the one the set was built with (ISSUE 40, step 0 c)."""
    import threading

    from ceph_tpu.common import tracing
    from ceph_tpu.ops.kernel_stats import FOLD_OVERLAPPED_NS

    b, k, m, chunk, threads, calls = 256, 4, 2, 4096, 10, 20
    on_path("packed")
    backend = ec_backend.get_jax_backend()
    matrix = np.asarray(_code(k, m, "packed").matrix, dtype=np.int64)
    ks = ec_backend.kernel_stats()
    counter = ks.perf._counters[FOLD_OVERLAPPED_NS]
    declared = []
    ensure = ks._ensure_counter
    monkeypatch.setattr(
        ks, "_ensure_counter",
        lambda name, *a, **kw: (declared.append(name), ensure(name, *a, **kw)))
    before = _fold_counters()
    seen = len(_records())
    payloads = [_payload(k, b, chunk, seed=4000 + t) for t in range(threads)]
    wanted = [
        reed_sol_van_codec.encode_shards(p.tobytes(), k, m, chunk)
        for p in payloads
    ]
    wrong = []
    start = threading.Barrier(threads)

    def osd(t):
        tracer = tracing.Tracer(f"osd.{t}")
        stripes = payloads[t].reshape(b, k, chunk)
        start.wait(timeout=60)
        for call in range(calls):
            try:
                with tracer.start_span("osd_op", role="primary"):
                    data, coding = backend.matrix_stripe_shards(
                        matrix, stripes, 8)
            except Exception as e:  # noqa: BLE001 — the thread's failure is the test's
                wrong.append((t, call, repr(e)))
                return
            for p, shard in enumerate(data + coding):
                if not (
                    shard.ndim == 1 and shard.dtype == np.uint8
                    and shard.flags.c_contiguous
                    and np.array_equal(shard, wanted[t][p])
                ):
                    wrong.append((t, call, p))

    pool = [threading.Thread(target=osd, args=(t,)) for t in range(threads)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)  # hand the interpreter over fifty times as often
    try:
        for th in pool:
            th.start()
        for th in pool:
            th.join(timeout=300)
    finally:
        sys.setswitchinterval(switch)
    assert not any(th.is_alive() for th in pool)
    assert wrong == []
    assert len(_records()) - seen == threads * calls
    assert FOLD_OVERLAPPED_NS not in declared
    assert ks.perf._counters[FOLD_OVERLAPPED_NS] is counter
    after = _fold_counters()
    assert after[1] - before[1] == threads * calls * k
    assert after[2] - before[2] == after[0] - before[0] > 0
