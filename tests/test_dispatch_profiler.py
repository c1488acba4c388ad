"""Device-dispatch flight recorder (ops/profiler.py): ring bounds,
transfer/compute/sync attribution identities, pad-waste accounting at
the EC batch-axis and CRUSH lane-0 pad points, the deviceless host
fallback, the `dispatch history|summary` tell/admin-socket surfaces,
and — live — an op whose device-stage spans assemble under the mgr
tracing module with residency hits visibly cutting upload bytes."""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np
import pytest

from ceph_tpu import gf
from ceph_tpu.common.admin_socket import admin_command
from ceph_tpu.crush.builder import CrushMap
from ceph_tpu.crush.types import (
    CRUSH_BUCKET_STRAW2,
    PG_POOL_TYPE_ERASURE,
    PG_POOL_TYPE_REPLICATED,
    Tunables,
)
from ceph_tpu.ec.backend import NumpyBackend, get_backend
from ceph_tpu.msg.messenger import wait_for
from ceph_tpu.ops.kernel_stats import KernelStats, kernel_stats
from ceph_tpu.ops.profiler import DispatchProfiler, dispatch_profiler
from ceph_tpu.ops.residency import DeviceBuf
from ceph_tpu.ops.scrub_kernels import batch_crc32c
from ceph_tpu.osd import OSDMap, OSDMapMapping, PgPool

from test_osd_daemon import MiniCluster

sys.path.insert(
    0, str(pathlib.Path(__file__).resolve().parent.parent / "tools")
)

rng = np.random.default_rng(0xF11)


def _pad_wasted() -> int:
    return kernel_stats().perf.dump()["l_tpu_pad_bytes_wasted"]


def _last_seq() -> int:
    ents = dispatch_profiler().history()["entries"]
    return ents[-1]["seq"] if ents else 0


def _entries_after(seq: int, kind: str | None = None) -> list[dict]:
    ents = dispatch_profiler().history(kind=kind)["entries"]
    return [e for e in ents if e["seq"] > seq]


# -- ring bounds and commit semantics --------------------------------------


def test_ring_bounded_under_dispatch_storm():
    """A storm past capacity keeps the newest `capacity` entries,
    counts the overwrites, and bumps l_tpu_dispatch_ring_dropped."""
    ks = KernelStats()
    prof = DispatchProfiler(capacity=8, ks=ks)
    for i in range(50):
        with prof.dispatch("ec_encode", backend="cpu") as dp:
            dp.set_ops(i)
    h = prof.history()
    assert h["capacity"] == 8
    assert h["num_entries"] == 8
    assert h["dropped"] == 42
    # newest survive, oldest dropped, seq monotone
    assert [e["ops"] for e in h["entries"]] == list(range(42, 50))
    assert ks.perf.dump()["l_tpu_dispatch_ring_dropped"] == 42
    # totals survive the wrap (the bench diffs these)
    assert prof.totals()["ec_encode"]["dispatches"] == 50
    prof.clear()
    assert prof.history()["num_entries"] == 0
    assert prof.totals() == {}


def test_stage_attribution_and_commit_semantics():
    prof = DispatchProfiler(capacity=16, ks=KernelStats())
    with prof.dispatch("crc32c") as dp:
        dp.set_ops(3)
        dp.add_bytes_in(300)
        with dp.stage("upload"):
            time.sleep(0.002)
        with dp.stage("compute"):
            time.sleep(0.002)
        # stages reopen and accumulate (double-buffer loops)
        with dp.stage("upload"):
            time.sleep(0.002)
        with dp.stage("sync"):
            pass
    (e,) = prof.history()["entries"]
    assert e["transfer_s"] > 0 and e["compute_s"] > 0
    assert (
        e["transfer_s"] + e["compute_s"] + e["sync_s"]
        <= e["wall_s"] + 1e-6
    )
    # a stage-less record books its whole wall as compute so the
    # Σstages <= wall identity holds for host-path entries too
    with prof.dispatch("compare", backend="cpu"):
        time.sleep(0.001)
    host = prof.history(kind="compare")["entries"][-1]
    assert host["compute_s"] == host["wall_s"] > 0
    # an exception discards the record: the fallback path that
    # catches it records its own entry instead
    with pytest.raises(RuntimeError):
        with prof.dispatch("crush"):
            raise RuntimeError("UnsupportedMap analog")
    assert prof.history(kind="crush")["num_entries"] == 0


def test_history_filters_and_summary_rollup():
    prof = DispatchProfiler(capacity=16, ks=KernelStats())
    for kind, ops in (("ec_encode", 4), ("ec_encode", 6), ("crc32c", 2)):
        with prof.dispatch(kind) as dp:
            dp.set_ops(ops)
            dp.set_stripes(ops * 3)
            dp.add_bytes_in(1000)
            dp.add_upload(750)
            dp.add_resident(250)
    h = prof.history(kind="ec_encode", limit=1)
    assert h["num_entries"] == 1 and h["entries"][0]["ops"] == 6
    s = prof.summary()
    assert s["ring"] == {"capacity": 16, "entries": 3, "dropped": 0}
    enc = s["kinds"]["ec_encode"]
    assert enc["dispatches"] == 2
    assert enc["occupancy"] == 5.0  # (4 + 6) / 2
    assert enc["stripes_per_dispatch"] == 15.0
    assert enc["resident_byte_ratio"] == 0.25
    assert prof.summary(kind="crc32c")["kinds"].keys() == {"crc32c"}


# -- device attribution identities -----------------------------------------


def test_device_byte_attribution_identity():
    """On device (backend=jax) entries, uploaded + resident == input
    bytes — every logical payload byte is attributed to exactly one
    side of the link.  Host entries legitimately carry zero."""
    bufs = [
        rng.integers(0, 256, size=n, dtype=np.uint8).tobytes()
        for n in (4096, 5000, 300, 8192)
    ]
    mixed = [
        DeviceBuf(data=b) if i % 2 else b for i, b in enumerate(bufs)
    ]
    for buf in mixed:
        if isinstance(buf, DeviceBuf):
            buf.device()  # registered-resident: served where it lives
    seq = _last_seq()
    batch_crc32c(mixed, 0xFFFFFFFF, backend="device")
    new = _entries_after(seq, kind="crc32c")
    dev = [e for e in new if e["backend"] == "jax"]
    assert dev, f"no device crc32c entry recorded: {new}"
    e = dev[-1]
    assert e["bytes_in"] == sum(len(b) for b in bufs)
    assert e["bytes_uploaded"] + e["bytes_resident"] == e["bytes_in"]
    assert e["bytes_resident"] == sum(
        len(b) for i, b in enumerate(bufs) if i % 2
    )
    assert e["ops"] == len(bufs)
    assert (
        e["transfer_s"] + e["compute_s"] + e["sync_s"]
        <= e["wall_s"] + 1e-6
    )


def test_ec_batch_axis_pad_counted():
    """A 3-stripe encode buckets to 4 on the batch axis: the zero pad
    ((bb - b) * k * chunk device-visible bytes) lands in
    l_tpu_pad_bytes_wasted and on the dispatch record."""
    k, m, w, chunk = 4, 2, 8, 128
    matrix = gf.reed_sol_vandermonde_coding_matrix(k, m, w)
    stripes = rng.integers(0, 256, size=(3, k, chunk), dtype=np.uint8)
    before = _pad_wasted()
    seq = _last_seq()
    get_backend("jax").matrix_stripe_shards(matrix, stripes, w)
    assert _pad_wasted() - before == (4 - 3) * k * chunk
    ents = _entries_after(seq, kind="ec_encode")
    assert ents and ents[-1]["bytes_padded"] == (4 - 3) * k * chunk
    # a pow2 batch pads nothing
    before = _pad_wasted()
    get_backend("jax").matrix_stripe_shards(
        matrix,
        rng.integers(0, 256, size=(4, k, chunk), dtype=np.uint8),
        w,
    )
    assert _pad_wasted() == before


def test_crush_lane0_pad_counted():
    """pg_num=27 buckets to 32: the 5 repeated lane-0 PPS inputs are
    counted as pad waste on the device crush dispatch."""
    jewel = Tunables(0, 0, 50, 1, 1, 1, 0)
    m = CrushMap(tunables=jewel)
    hosts = []
    for h in range(4):
        items = list(range(h * 2, h * 2 + 2))
        hosts.append(
            m.add_bucket(
                CRUSH_BUCKET_STRAW2, 1, items, [0x10000] * 2,
                name=f"h{h}",
            )
        )
    m.add_bucket(
        CRUSH_BUCKET_STRAW2, 3, hosts,
        [m.buckets[b].weight for b in hosts], name="default",
    )
    rep = m.add_simple_rule("rep", "default", "host", mode="firstn")
    om = OSDMap.build(m, 8)
    om.add_pool(
        PgPool(pool_id=1, type=PG_POOL_TYPE_REPLICATED, size=3,
               pg_num=27, crush_rule=rep)
    )
    before = _pad_wasted()
    seq = _last_seq()
    OSDMapMapping().update(om, use_device=True)
    ents = _entries_after(seq, kind="crush")
    dev = [e for e in ents if e["backend"] == "jax"]
    if not dev:
        pytest.skip("device crush path unavailable on this map")
    e = dev[-1]
    itemsize = e["bytes_in"] // 27  # pps dtype width
    assert e["stripes"] == 27
    assert e["bytes_padded"] == (32 - 27) * itemsize
    assert _pad_wasted() - before >= e["bytes_padded"]


def test_numpy_backend_records_host_entries():
    """Deviceless fallback: the oracle batch seams still record host
    entries (backend=numpy, zero link bytes, wall booked as compute)
    so the dispatch plane stays populated without an accelerator."""
    k, m, w, chunk = 2, 1, 8, 64
    matrix = gf.reed_sol_vandermonde_coding_matrix(k, m, w)
    nb = NumpyBackend()
    seq = _last_seq()
    batches = [
        rng.integers(0, 256, size=(n, k, chunk), dtype=np.uint8)
        for n in (2, 3)
    ]
    outs = nb.matrix_stripes_batch(matrix, batches, w)
    assert len(outs) == 2
    ents = _entries_after(seq, kind="ec_encode")
    assert ents, "numpy encode batch recorded no entry"
    e = ents[-1]
    assert e["backend"] == "numpy"
    assert e["ops"] == 2 and e["stripes"] == 5
    assert e["bytes_in"] == sum(s.nbytes for s in batches)
    assert e["bytes_uploaded"] == 0 and e["bytes_resident"] == 0
    assert e["compute_s"] == e["wall_s"]
    # decode seam: row_sets of equal-length survivors, incl. a
    # DeviceBuf token (fetched host-side on this path)
    rows = [
        rng.integers(0, 256, size=2 * chunk, dtype=np.uint8)
        for _ in range(k)
    ]
    row_sets = [rows, [DeviceBuf(data=rows[0].tobytes()), rows[1]]]
    seq = _last_seq()
    nb.decode_stripes_batch(np.identity(k, dtype=np.uint8), row_sets, w, chunk)
    ents = _entries_after(seq, kind="ec_decode")
    assert ents and ents[-1]["backend"] == "numpy"
    assert ents[-1]["ops"] == 2


def _batched_call(entry: str, backend):
    """One call of a batched seam entry at a small k=4 m=2 geometry:
    (kind, ops, stripes, bytes in, bytes out) as the recorder and the
    kernel counters should report it."""
    k, m, w, chunk = 4, 2, 8, 64
    matrix = gf.reed_sol_vandermonde_coding_matrix(k, m, w)
    sizes = (3, 2)
    objects = [
        rng.integers(0, 256, size=(b, k, chunk), dtype=np.uint8)
        for b in sizes
    ]
    # an object's shards as stored: chunk i of every stripe, concatenated
    shards = [
        [np.ascontiguousarray(o[:, i, :]).reshape(-1) for i in range(k)]
        for o in objects
    ]
    if entry == "matrix_stripe_shards":
        _data, coding = backend.matrix_stripe_shards(matrix, objects[0], w)
        assert [len(c) for c in coding] == [3 * chunk] * m
        return "ec_encode", 1, 3, 3 * k * chunk, 3 * m * chunk
    if entry == "matrix_shards":
        rebuilt = backend.matrix_shards(matrix, shards[0], w, 3)
        assert [len(r) for r in rebuilt] == [3 * chunk] * m
        return "ec_decode", 1, 3, 3 * k * chunk, 3 * m * chunk
    if entry == "matrix_stripes_batch":
        outs = backend.matrix_stripes_batch(matrix, objects, w)
        kind = "ec_encode"
    else:
        outs = backend.decode_stripes_batch(matrix, shards, w, chunk)
        kind = "ec_decode"
    assert [o.shape for o in outs] == [(b, m, chunk) for b in sizes]
    return kind, 2, 5, 5 * k * chunk, 5 * m * chunk


@pytest.mark.parametrize("backend", ["jax", "numpy"])
@pytest.mark.parametrize(
    "entry",
    [
        "matrix_stripe_shards", "matrix_shards",
        "matrix_stripes_batch", "decode_stripes_batch",
    ],
)
def test_a_batched_entry_has_one_instrument(entry, backend):
    """One call of a batched seam entry leaves exactly ONE recorder
    entry, ``kind:backend``, with the call's ops, stripes and input
    bytes; on the device backend the same commit is the one
    ``l_tpu_gf_matmul_*`` call, with the entry's own byte count (the
    oracle never counted there)."""
    ks = kernel_stats()
    before = ks.dump()
    totals = dispatch_profiler().totals()
    seq = _last_seq()
    kind, ops, stripes, bytes_in, bytes_out = _batched_call(
        entry, get_backend(backend)
    )
    (rec,) = _entries_after(seq)
    assert (rec["kind"], rec["backend"]) == (kind, backend)
    assert (rec["ops"], rec["stripes"], rec["bytes_in"]) == (
        ops, stripes, bytes_in,
    )
    was = totals.get(kind, {})
    now = dispatch_profiler().totals()[kind]
    moved = {
        f: now[f] - was.get(f, 0)
        for f in ("dispatches", "ops", "stripes", "bytes_in")
    }
    assert moved == {
        "dispatches": 1, "ops": ops, "stripes": stripes,
        "bytes_in": bytes_in,
    }
    after = ks.dump()
    group = {
        f: after.get(f"l_tpu_gf_matmul_{f}", 0)
        - before.get(f"l_tpu_gf_matmul_{f}", 0)
        for f in ("calls", "bytes_in", "bytes_out")
    }
    lat = (
        after.get("l_tpu_gf_matmul_lat", {}).get("avgcount", 0)
        - before.get("l_tpu_gf_matmul_lat", {}).get("avgcount", 0)
    )
    if backend == "jax":
        assert group == {
            "calls": 1, "bytes_in": rec["bytes_in"], "bytes_out": bytes_out,
        }
        assert lat == 1
        assert rec["bytes_uploaded"] == rec["bytes_in"]
    else:
        assert group == {"calls": 0, "bytes_in": 0, "bytes_out": 0}
        assert lat == 0 and rec["compute_s"] == rec["wall_s"]


# the benchmark's harness reads this code's counters by name
# (benchmark/harness.flat_counters: every layer metric diffs them over
# the window).  The script below runs, in a process of its own, one
# stripe.encode, one stripe.decode, one encode_batch and one
# decode_batch on the jax backend -- once through the bitplane program
# and once through the chip's packed kernels, interpreted -- and prints
# the counters' names, the ones that count (not the clocks), and the
# programs JAX compiles when the same calls come again.
_SEAM_CALLS = """
import json
import numpy as np
import ceph_tpu.ops
from benchmark import harness
from ceph_tpu.ec import ErasureCodeProfile, registry_instance, stripe
from ceph_tpu.ops import ec_backend, packed_gf

clock = harness.CompileClock()
recorder = harness.Dispatches()
ec = registry_instance().factory("jerasure", ErasureCodeProfile(
    technique="reed_sol_van", k="4", m="2", w="8", backend="jax"))
sinfo = stripe.StripeInfo(4, 4 * 512)
rng = np.random.default_rng(0)
a, b = (rng.integers(0, 256, n * 4 * 512, dtype=np.uint8) for n in (4, 3))


class Driver:
    def counters(self):
        return {}


def four_calls():
    shards = stripe.encode(sinfo, ec, a)
    have = {p: s for p, s in shards.items() if p not in (1, 4)}
    stripe.decode(sinfo, ec, have, (1, 4))
    sets = stripe.encode_batch(sinfo, ec, [a, b])
    stripe.decode_batch(
        sinfo, ec,
        [{p: s for p, s in one.items() if p not in (1, 4)} for one in sets],
        (1, 4))


def both_kernels():
    four_calls()
    on_tpu, built = ec_backend._on_tpu, packed_gf.prebuilt_word_call
    rebuild = packed_gf.prebuilt_decode_call
    ec_backend._on_tpu = lambda: True
    packed_gf.prebuilt_word_call = lambda bm, w=8: built(bm, w, interpret=True)
    packed_gf.prebuilt_decode_call = lambda r, s: rebuild(r, s, interpret=True)
    try:
        four_calls()
    finally:
        ec_backend._on_tpu, packed_gf.prebuilt_word_call = on_tpu, built
        packed_gf.prebuilt_decode_call = rebuild


both_kernels()
counters = harness.flat_counters(Driver())
programs = clock.programs
both_kernels()
print(json.dumps({
    "counts": {k: v for k, v in counters.items() if not k.endswith(("_s", "_ns"))},
    "clocks": sorted(k for k in counters if k.endswith(("_s", "_ns"))),
    "programs": programs, "compiled_again": clock.programs - programs,
    "recorded": recorder.harvest(), "on_host": recorder.host_backend_entries(),
}))
"""

# taken from the parent of PR 32 (the tree before the one instrument):
# the names are the harness's contract, the numbers what the same
# eight calls moved there
_SEAM_COUNTS = {
    **{
        f"dispatch.{kind}.{field}": value
        for kind in ("ec_encode", "ec_decode")
        for field, value in {
            "dispatches": 4, "ops": 6, "stripes": 22, "bytes_in": 45056,
            "bytes_uploaded": 45056, "bytes_resident": 0,
            "bytes_padded": 4096,
        }.items()
    },
    "dispatch.ec_encode.compile_hits": 1,
    "dispatch.ec_encode.compile_misses": 2,
    "dispatch.ec_decode.compile_hits": 3,
    "dispatch.ec_decode.compile_misses": 1,
    "l_stage_ec_assemble_count": 2,
    "l_stage_ec_fold_count": 4,
    "l_stage_ec_plan_count": 2,
    "l_stage_ec_unfold_count": 1,
    "l_tpu_batch_decode_dispatches": 2,
    "l_tpu_batch_decode_ops_per_dispatch": 4,
    "l_tpu_batch_encode_dispatches": 2,
    "l_tpu_batch_encode_ops_per_dispatch": 4,
    # the chip's stripe.decode looks its coefficients up where the
    # bitplane program looked its bitmatrix up: a first sight, not a hit
    "l_tpu_compile_cache_hit": 8,
    "l_tpu_compile_cache_miss": 7,
    "l_tpu_dispatch_bytes_resident": 0,
    "l_tpu_dispatch_bytes_uploaded": 90112,
    "l_tpu_dispatch_count": 8,
    "l_tpu_dispatch_ops": 12,
    "l_tpu_dispatch_ring_dropped": 0,
    "l_tpu_dispatch_stripes": 44,
    "l_tpu_ec_decode_bytes_in": 45056,
    "l_tpu_ec_decode_bytes_out": 22528,
    "l_tpu_ec_decode_calls": 4,
    "l_tpu_ec_encode_bytes_in": 45056,
    "l_tpu_ec_encode_bytes_out": 67584,
    "l_tpu_ec_encode_calls": 4,
    # the rebuild that took the packed decode kernel
    "l_tpu_ec_decode_packed_calls": 1,
    "l_tpu_gf_matmul_bytes_in": 90112,
    "l_tpu_gf_matmul_bytes_out": 45056,
    "l_tpu_gf_matmul_calls": 8,
    "l_tpu_pad_bytes_wasted": 8192,
    "l_tpu_residency_bytes_resident": 0,
    "l_tpu_residency_evictions": 0,
    "l_tpu_residency_hits": 0,
    "l_tpu_residency_misses": 0,
}
_SEAM_CLOCKS = sorted(
    [f"dispatch.{kind}.{stage}_s"
     for kind in ("ec_encode", "ec_decode")
     for stage in ("compute", "sync", "transfer", "wall")]
    + [f"l_stage_{span}_ns"
       for span in ("ec_assemble", "ec_fold", "ec_plan", "ec_unfold")]
    # ISSUE 40: the fold's share spent with the call's upload issued
    + ["l_tpu_ec_fold_overlapped_ns"]
    # the process's CPU time, read in at every dump of the kernel set
    + ["l_process_cpu_ns"]
)
# the process's switches: what the whole process did, not the calls
_PROCESS_COUNTS = {"l_process_handovers", "l_process_preemptions"}


def test_the_harness_reads_the_counters_it_read_before():
    """``benchmark.harness.flat_counters`` after the four seam calls:
    no key gone, none new but the fold's overlap, the packed rebuilds
    and the process's usage, every count of the calls what the parent counted; the recorder's entries one a call, ``<kind>:jax``, in sequence; and the same calls again
    compile nothing (``CompileClock.programs`` stands)."""
    repo = pathlib.Path(__file__).resolve().parent.parent
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(repo))
    proc = subprocess.run(
        [sys.executable, "-c", _SEAM_CALLS], cwd=repo, env=env,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    said = json.loads(proc.stdout.strip().splitlines()[-1])
    counts = {k: v for k, v in said["counts"].items() if k not in _PROCESS_COUNTS}
    assert counts == _SEAM_COUNTS
    assert set(said["counts"]) - set(counts) == _PROCESS_COUNTS
    assert said["clocks"] == _SEAM_CLOCKS
    # 8 calls a kind over the two rounds, one entry each, none lost
    assert said["recorded"] == {"ec_encode:jax": 8, "ec_decode:jax": 8}
    assert said["on_host"] == {}
    assert said["programs"] > 0 and said["compiled_again"] == 0


# -- CLI grammar ------------------------------------------------------------


def test_tell_grammar_dispatch_commands():
    from ceph_tpu.tools.ceph_cli import _build_tell_args

    assert _build_tell_args(["dispatch", "history"]) == {
        "prefix": "dispatch history"
    }
    assert _build_tell_args(
        ["dispatch", "history", "kind=ec_encode", "limit=5"]
    ) == {"prefix": "dispatch history", "kind": "ec_encode", "limit": 5}
    assert _build_tell_args(["dispatch", "summary"]) == {
        "prefix": "dispatch summary"
    }


# -- live: spans, surfaces, residency --------------------------------------


def test_live_device_stage_spans_and_dispatch_surfaces(tmp_path):
    """Acceptance: an EC write's dev_upload/dev_compute/dev_sync
    spans assemble under the mgr tracing module beneath the primary's
    op span; `dispatch history|summary` answer over the admin socket
    AND a real MCommand tell; the l_tpu_dispatch_* counters ride perf
    dump; and residency hits visibly cut upload bytes (and the
    sync-bounded transfer wall) on a warm crc dispatch."""
    from ceph_tpu.mgr import Manager
    from ceph_tpu.msg.message import MCommand, MMonCommandReply
    from ceph_tpu.rados import Rados

    c = MiniCluster()
    mgr = None
    r = None
    try:
        asok = str(tmp_path / "osd.0.asok")
        c.start_osd(0, admin_socket_path=asok)
        for i in (1, 2):
            c.start_osd(i)
        c.wait_active()
        mgr = Manager(name="flight")
        mgr.start(c.mon_addr)

        r = Rados("flight-client").connect(*c.mon_addr)
        rc, _outb, outs = r.mon_command(
            {
                "prefix": "osd erasure-code-profile set",
                "name": "flightprof",
                "profile": [
                    "k=2", "m=1", "plugin=jerasure", "backend=jax",
                ],
            }
        )
        assert rc == 0, outs
        r.pool_create(
            "flightpool", pool_type=3, pg_num=1,
            erasure_code_profile="flightprof",
        )
        io = r.open_ioctx("flightpool")
        io.write_full("warm", b"w" * 4096)  # PG active, jit compiled
        io.write_full("flight-obj", b"\x5a" * 8192)

        client_spans = r.objecter.tracer.dump_traces()["spans"]
        assert client_spans, "objecter opened no root span"
        trace = client_spans[-1]["trace_id"]
        assert r.objecter.flush_spans_to_mgr() >= 1
        tmod = mgr.modules["tracing"]

        def device_stages_assembled():
            tmod.ingest_pending()
            tree = tmod.get_trace(trace)
            names = set()

            def walk(nodes):
                for n in nodes:
                    names.add(n["name"])
                    walk(n["children"])

            walk(tree["roots"])
            return {"dev_upload", "dev_compute", "dev_sync"} <= names

        assert wait_for(device_stages_assembled, 30.0), (
            "device-stage spans never assembled under the op trace: "
            f"{tmod.get_trace(trace)}"
        )
        # the stage spans hang off the PRIMARY's op subtree, tagged
        # with the dispatch kind
        tree = tmod.get_trace(trace)
        stage_nodes = []

        def collect(nodes):
            for n in nodes:
                if n["name"].startswith("dev_"):
                    stage_nodes.append(n)
                collect(n["children"])

        collect(tree["roots"])
        assert all(n["tags"]["backend"] == "jax" for n in stage_nodes)
        assert any(
            n["tags"]["kind"] == "ec_encode" for n in stage_nodes
        )

        # admin-socket surfaces: raw ring + rollup + perf counters
        hist = admin_command(
            asok, {"prefix": "dispatch history", "limit": 3}
        )["ok"]
        assert hist["num_entries"] <= 3
        assert all("transfer_s" in e for e in hist["entries"])
        summ = admin_command(asok, "dispatch summary")["ok"]
        assert "ec_encode" in summ["kinds"]
        assert summ["kinds"]["ec_encode"]["dispatches"] >= 1
        dump = admin_command(asok, "perf dump")["ok"]
        assert dump["tpu_kernels"]["l_tpu_dispatch_count"] >= 1
        assert "avgcount" in dump["tpu_kernels"][
            "l_tpu_dispatch_compute_lat"
        ]
        assert "buckets" in dump["tpu_kernels"][
            "l_tpu_dispatch_sync_lat_hist"
        ]

        # the tell surface, through a real MCommand to the daemon
        osd = next(iter(c.osds.values()))
        conn = c.client_msgr.connect(*osd.addr)
        reply = conn.call(
            MCommand(
                tid=c.client_msgr.new_tid(),
                cmd=json.dumps({"prefix": "dispatch summary"}),
            )
        )
        assert isinstance(reply, MMonCommandReply) and reply.rc == 0
        assert "ring" in json.loads(reply.outb)
        reply = conn.call(
            MCommand(
                tid=c.client_msgr.new_tid(),
                cmd=json.dumps(
                    {"prefix": "dispatch history", "limit": 2}
                ),
            )
        )
        assert isinstance(reply, MMonCommandReply) and reply.rc == 0
        assert json.loads(reply.outb)["num_entries"] <= 2

        # residency hits visibly reduce transfer: the same 2MB scrub
        # batch cold (host bytes -> uploaded) vs warm (registered-
        # resident DeviceBufs -> served in place).  Byte attribution
        # is deterministic; the sync-bounded transfer wall is noisy,
        # so it gets a few attempts.
        payloads = [
            rng.integers(0, 256, size=1 << 19, dtype=np.uint8)
            .tobytes()
            for _ in range(4)
        ]
        warm_bufs = [DeviceBuf(data=p) for p in payloads]
        for b in warm_bufs:
            b.device()
        import jax

        on_accel = jax.devices()[0].platform != "cpu"
        cold_e = warm_e = None
        for _ in range(5):
            seq = _last_seq()
            cold = batch_crc32c(payloads, backend="device")
            warm = batch_crc32c(warm_bufs, backend="device")
            assert (cold == warm).all()
            ce, we = [
                e
                for e in _entries_after(seq, kind="crc32c")
                if e["backend"] == "jax"
            ][-2:]
            assert ce["bytes_uploaded"] == sum(map(len, payloads))
            assert we["bytes_resident"] == sum(map(len, payloads))
            assert we["bytes_uploaded"] == 0
            cold_e, warm_e = ce, we
            if we["transfer_s"] < ce["transfer_s"]:
                break
        # the transfer-wall win is a real-link truth: on jax-cpu a
        # device_put is a memcpy while the resident path pays the
        # on-device permute gather, so only the byte attribution (the
        # deterministic half, asserted above) holds there
        if on_accel:
            assert warm_e["transfer_s"] < cold_e["transfer_s"], (
                f"resident batch never beat cold upload wall: "
                f"cold={cold_e['transfer_s']} "
                f"warm={warm_e['transfer_s']}"
            )
    finally:
        if r is not None:
            r.shutdown()
        if mgr is not None:
            mgr.shutdown()
        c.shutdown()
