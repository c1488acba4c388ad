"""The span plane end to end (ISSUE 26): one monotonic clock, self
time, intervals opened from earlier stamps, the stage counters every
finished span feeds, the profiler mirror, and the spans of a served EC
write and of a remap, each where the work happens."""

from __future__ import annotations

import pathlib
import sys
import threading
import time

import numpy as np
import pytest

from ceph_tpu.common import tracing
from ceph_tpu.ops.kernel_stats import kernel_stats

from test_osd_daemon import MiniCluster

REPO = pathlib.Path(__file__).resolve().parents[1]
for extra in (REPO, REPO / "tools"):
    if str(extra) not in sys.path:
        sys.path.insert(0, str(extra))


def _stage(name: str) -> tuple[int, int, int]:
    dump = kernel_stats().dump()
    return tuple(
        int(dump.get(f"l_stage_{name}_{suffix}", 0))
        for suffix in ("count", "ns", "self_ns")
    )


# -- the clock, self time, explicit stamps ------------------------------------


def test_self_time_is_duration_less_same_thread_children():
    """Children opened on the parent's thread while it is ambient come
    off its self time (they nest, so their sum is their union); a
    cross-daemon child — another tracer, another thread, joined by
    parent id alone — does not.  Self time is counted for the names
    that have a reader for it (``osd_op``) and for no other."""
    primary = tracing.Tracer("osd.0")
    replica = tracing.Tracer("osd.1")
    before = _stage("osd_op")
    with primary.start_span("osd_op", trace_id="T") as parent:
        with tracing.span("plane_child_a"):
            time.sleep(0.02)
            with tracing.span("plane_grandchild"):
                time.sleep(0.01)
        with tracing.span("plane_child_b"):
            time.sleep(0.01)

        def remote():
            with replica.start_span(
                "plane_remote", trace_id="T", parent_id=parent.span_id
            ):
                time.sleep(0.05)

        t = threading.Thread(target=remote)
        t.start()
        t.join()
        time.sleep(0.01)
    spans = {s["name"]: s for s in primary.drain() + replica.drain()}
    assert spans["plane_remote"]["parent_id"] == spans["osd_op"]["span_id"]
    assert spans["plane_remote"]["duration"] >= 0.05
    children = (
        spans["plane_child_a"]["duration"] + spans["plane_child_b"]["duration"]
    )
    count, ns, self_ns = (a - b for a, b in zip(_stage("osd_op"), before))
    assert count == 1
    assert ns == pytest.approx(spans["osd_op"]["duration"] * 1e9, abs=2)
    # the grandchild came off child_a, not twice off the parent; the
    # remote child (50 ms, more than the whole self time) came off nobody
    assert self_ns == pytest.approx(ns - children * 1e9, abs=5e3)
    assert self_ns >= 0.01 * 1e9
    dump = kernel_stats().dump()
    assert "l_stage_plane_child_a_ns" in dump
    assert "l_stage_plane_child_a_self_ns" not in dump


def test_an_interval_that_began_on_another_thread_is_recorded_whole():
    """A queue wait: stamped where the item is queued, recorded where a
    worker takes it (``record``: the end is now, or a second stamp)."""
    tr = tracing.Tracer("osd.2")
    queued = time.perf_counter()

    def worker():
        time.sleep(0.03)
        tr.record("plane_wait", "Q", queued)
        tr.record("plane_wait_whole", "Q", queued, queued + 0.5)
        tr.record("plane_no_trace", "", queued)  # no trace id: nothing

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    spans = {s["name"]: s for s in tr.drain()}
    assert set(spans) == {"plane_wait", "plane_wait_whole"}
    assert spans["plane_wait"]["duration"] >= 0.03
    assert spans["plane_wait_whole"]["duration"] == pytest.approx(0.5)
    for s in spans.values():  # duration is the difference of the two stamps
        assert s["end"] - s["start"] == pytest.approx(s["duration"], abs=1e-6)
        assert abs(s["start"] - time.time()) < 60  # and start is wall time


def test_an_unbuffered_tracer_counts_and_keeps_nothing():
    """``buffered`` off (a daemon with ``tracing_enabled`` false, a
    mapping nobody serves): spans still nest and feed the stage
    counters, and no dict is built or kept."""
    tr = tracing.Tracer("osd.7", buffered=False)
    before = _stage("osd_op"), _stage("plane_unkept")
    with tr.start_span("osd_op", trace_id="U") as op:
        with tracing.span("plane_unkept") as child:
            time.sleep(0.002)
        assert child.trace_id == "U" and child.parent_id == op.span_id
    tr.record("plane_unkept", "U", time.perf_counter() - 0.001)
    dump = tr.dump_traces()
    assert dump["num_spans"] == 0 and dump["spans_started"] == 3
    assert tr.drain() == []
    op_count, op_ns, op_self = (
        a - b for a, b in zip(_stage("osd_op"), before[0])
    )
    count, ns, _none = (a - b for a, b in zip(_stage("plane_unkept"), before[1]))
    assert (op_count, count) == (1, 2) and ns >= 3e6
    assert 0 < op_self < op_ns - 2e6
    tr.buffered = True  # the option may be turned on while it runs
    tr.record("plane_unkept", "U", time.perf_counter())
    assert tr.dump_traces()["num_spans"] == 1


def test_one_clock_a_stepped_wall_clock_moves_nothing(monkeypatch):
    tr = tracing.Tracer("osd.3")
    with tr.start_span("plane_stepped", trace_id="S") as sp:
        monkeypatch.setattr(time, "time", lambda: 0.0)  # the wall jumps
        sp.mark_event("mid")
    (span,) = tr.drain()
    assert 0 <= span["duration"] < 1
    assert span["start"] <= span["events"][0]["time"] <= span["end"]


def test_complete_moves_the_stage_counters_and_the_lint_accepts_them():
    import check_metrics

    tr = tracing.Tracer("osd.4")
    for name in ("osd_op", "sub_op_wait"):
        before = _stage(name)
        with tr.start_span(name, trace_id="C"):
            pass
        tr.record(name, "C", time.perf_counter() - 0.001)
        count, ns, self_ns = (a - b for a, b in zip(_stage(name), before))
        assert count == 2 and ns >= 1e6
        assert 0 < self_ns <= ns if name == "osd_op" else self_ns == 0
    assert check_metrics.check_stage_counters() == []
    assert check_metrics.check_perf_counters(kernel_stats().perf) == []
    # a name outside the exposition alphabet is folded, not refused
    tr.record("plane odd-name", "C", time.perf_counter())
    assert _stage("plane_odd_name")[0] == 1
    assert check_metrics.check_perf_counters(kernel_stats().perf) == []


def test_concurrent_completions_lose_no_count():
    """More threads than cores finish spans of one fresh name at once,
    under a shortened switch interval: the stage counters, the
    buffer's drop count and the started count all add up."""
    tr = tracing.Tracer("osd.6", max_spans=64)
    threads, each = 16, 400
    start = threading.Barrier(threads)

    def work():
        start.wait(10)
        for i in range(each):
            if i % 2:
                with tr.start_span("plane_storm", trace_id="X"):
                    pass
            else:
                tr.record("plane_storm", "X", time.perf_counter())

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=work) for _ in range(threads)]
        for t in workers:
            t.start()
        for t in workers:
            t.join(60)
        assert not any(t.is_alive() for t in workers)
    finally:
        sys.setswitchinterval(interval)
    total = threads * each
    count, ns, _none = _stage("plane_storm")
    assert count == total and ns > 0
    dump = tr.dump_traces()
    assert dump["spans_started"] == total
    assert dump["num_spans"] == 64 and dump["spans_dropped"] == total - 64


def test_wall_stamps_still_sort_a_cross_daemon_tree():
    """client -> primary -> replica, three tracers, no parent ids across
    daemons: ``assemble_tree`` sorts by the wall ``start`` derived from
    the monotonic stamps and attaches by role rank."""
    client = tracing.Tracer("client.a")
    primary = tracing.Tracer("osd.0")
    replica = tracing.Tracer("osd.1")
    with client.start_span("client_op", trace_id="W", role=tracing.ROLE_CLIENT):
        time.sleep(0.002)
        queued = time.perf_counter()
        time.sleep(0.002)
        primary.record(
            "osd_queue_wait", "W", queued, role=tracing.ROLE_PRIMARY
        )
        with primary.start_span(
            "osd_op", trace_id="W", role=tracing.ROLE_PRIMARY
        ):
            with tracing.span("store_commit"):
                time.sleep(0.002)
            with replica.start_span(
                "rep_op", trace_id="W", role=tracing.ROLE_REPLICA,
                parent_id="not-in-this-set",
            ):
                time.sleep(0.002)
    spans = client.drain() + replica.drain() + primary.drain()
    (root,) = tracing.assemble_tree(spans)
    assert root["name"] == "client_op"
    assert [c["name"] for c in root["children"]] == ["osd_queue_wait", "osd_op"]
    op = root["children"][1]
    assert sorted(c["name"] for c in op["children"]) == ["rep_op", "store_commit"]
    assert op["start"] >= root["children"][0]["end"] - 1e-6


# -- the profiler mirror -------------------------------------------------------


def test_a_profiler_session_holds_the_entered_spans(tmp_path):
    """With a session open, a span entered on a thread is a
    ``ceph:<name>`` event on the host plane, nested as the spans nest;
    a recorded (cross-thread) interval is not mirrored."""
    import jax
    import jax.numpy as jnp

    from benchmark import trace_reduce

    tr = tracing.Tracer("osd.5")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            with tr.start_span("osd_op", trace_id="P"):
                with tracing.span("ec_encode"):
                    jnp.ones(8).sum().block_until_ready()
                tr.record("osd_queue_wait", "P", time.perf_counter() - 0.01)
            with tracing.annotate("msgr_send"):
                pass
    finally:
        jax.profiler.stop_trace()
    planes = trace_reduce.load(trace_reduce.find_xplane(tmp_path))
    events = {
        ev[0]: ev
        for pname, lines in planes.items()
        if not trace_reduce.is_device_plane(pname)
        for evs in lines.values()
        for ev in evs
        if ev[0].startswith(("ceph:", "bench:"))
    }
    assert set(events) == {
        trace_reduce.WINDOW, "ceph:osd_op", "ceph:ec_encode", "ceph:msgr_send",
    }
    window, op, enc = (
        events[n] for n in (trace_reduce.WINDOW, "ceph:osd_op", "ceph:ec_encode")
    )
    assert window[1] <= op[1] <= enc[1] and enc[2] <= op[2] <= window[2]
    assert {s["name"] for s in tr.drain()} == {
        "osd_op", "ec_encode", "osd_queue_wait",
    }


# -- a served EC write ---------------------------------------------------------

# every span of the write path (ISSUE 26 table C), and which of them
# are opened under an ambient parent on the same thread
WRITE_SPANS = {
    "client_aio_wait", "client_op", "msgr_send", "msgr_recv",
    "osd_queue_wait", "osd_op", "ec_prepare", "ec_encode", "txn_build",
    "store_commit", "sub_op_wait", "sub_op_rtt", "rep_op",
}


@pytest.fixture(scope="module")
def ec_cluster():
    from ceph_tpu.rados import Rados

    import ceph_tpu.ops  # noqa: F401 — registers the jax EC backend

    c = MiniCluster()
    r = None
    try:
        for i in range(3):
            c.start_osd(i, op_queue="mclock")
        c.wait_active()
        r = Rados("span-plane").connect(*c.mon_addr)
        rc, _outb, outs = r.mon_command(
            {
                "prefix": "osd erasure-code-profile set",
                "name": "planeprof",
                "profile": ["k=2", "m=1", "plugin=jerasure", "backend=jax"],
            }
        )
        assert rc == 0, outs
        pool_id = r.pool_create(
            "planepool", pool_type=3, pg_num=1,
            erasure_code_profile="planeprof",
        )
        io = r.open_ioctx("planepool")
        io.write_full("warm", b"w" * 8192)  # PG active, program compiled
        pgid = f"{pool_id}.0"
        primary = next(
            o for o in c.osds.values()
            if pgid in o.pgs and o.pgs[pgid].primary == o.whoami
        )
        yield c, r, io, primary
    finally:
        if r is not None:
            r.shutdown()
        c.shutdown()


def _drain_all(c, r) -> list[dict]:
    spans = r.objecter.tracer.drain(1 << 20)
    for osd in c.osds.values():
        spans += osd.tracer.drain(1 << 20)
    return spans


def _children_fit(spans: list[dict]) -> None:
    """Within one daemon, the spans that name a parent lie inside it
    and their durations sum to no more than its own."""
    by_id = {s["span_id"]: s for s in spans}
    total: dict[str, float] = {}
    for s in spans:
        parent = by_id.get(s["parent_id"])
        if parent is None or parent["daemon"] != s["daemon"]:
            continue
        assert parent["start"] - 1e-6 <= s["start"], (s["name"], parent["name"])
        assert s["end"] <= parent["end"] + 1e-6, (s["name"], parent["name"])
        total[parent["span_id"]] = total.get(parent["span_id"], 0.0) + s["duration"]
    assert total
    for span_id, children in total.items():
        assert children <= by_id[span_id]["duration"] + 1e-6, by_id[span_id]["name"]


def test_a_served_ec_write_leaves_every_span_under_one_trace(ec_cluster):
    c, r, io, primary = ec_cluster
    _drain_all(c, r)
    payload = np.random.default_rng(26).integers(
        0, 256, 1 << 20, dtype=np.uint8
    ).tobytes()
    io.aio_write_full("plane-obj", payload).result(30)
    assert io.read("plane-obj") == payload

    def complete():
        # the reply leaves a moment before the primary's spans finish
        return any(
            s["name"] == "osd_op" and s["tags"].get("oid") == "plane-obj"
            for s in primary.tracer.dump_traces()["spans"]
        )

    deadline = time.monotonic() + 10
    while not complete():
        assert time.monotonic() < deadline
        time.sleep(0.02)
    spans = _drain_all(c, r)
    root = next(
        s for s in spans
        if s["name"] == "client_op" and s["tags"]["oid"] == "plane-obj"
        and s["tags"]["op"] != 1  # the write, not the read-back
    )
    mine = [s for s in spans if s["trace_id"] == root["trace_id"]]
    by_name: dict[str, list[dict]] = {}
    for s in mine:
        by_name.setdefault(s["name"], []).append(s)
    assert WRITE_SPANS <= set(by_name), WRITE_SPANS - set(by_name)
    assert {"dev_compute", "dev_sync"} <= set(by_name)
    # where each is recorded
    client = root["daemon"]
    osd = f"osd.{primary.whoami}"
    assert by_name["client_aio_wait"][0]["daemon"] == client
    assert by_name["client_aio_wait"][0]["end"] <= root["start"] + 1e-3
    for name in ("osd_queue_wait", "osd_op", "ec_prepare", "ec_encode",
                 "txn_build", "sub_op_wait"):
        assert {s["daemon"] for s in by_name[name]} == {osd}, name
    replicas = {s["daemon"] for s in by_name["rep_op"]}
    assert len(replicas) == 2 and osd not in replicas
    assert {s["daemon"] for s in by_name["store_commit"]} == replicas | {osd}
    # a message that carries the trace id leaves ONE span at each end
    # (an OSD<->OSD message: the session's envelope and its inner
    # frame together); replies carry none and leave none
    sends = {(s["daemon"], s["tags"]["type"]) for s in by_name["msgr_send"]}
    assert sends == {(client, "MOSDOp"), (osd, "MOSDRepOp")}
    recvs = {(s["daemon"], s["tags"]["type"]) for s in by_name["msgr_recv"]}
    assert recvs == {(osd, "MOSDOp")} | {(r, "MOSDRepOp") for r in replicas}
    assert len(by_name["msgr_send"]) == len(by_name["msgr_recv"]) == 3
    for s in by_name["msgr_send"] + by_name["msgr_recv"]:
        assert s["tags"]["bytes"] > (1 << 19)  # a shard or the object
    # tags the issue names
    (op,) = by_name["osd_op"]
    assert op["tags"]["created"] is True
    assert by_name["osd_queue_wait"][0]["tags"]["qos_class"] == "client"
    assert by_name["ec_encode"][0]["tags"]["ops"] == 1
    wait = by_name["sub_op_wait"][0]
    events = [e["event"] for e in wait["events"]]
    assert sum(e.startswith("sub_op_sent") for e in events) == 2
    assert sum(e.startswith("sub_op_commit_rec") for e in events) == 2
    _fan_out_then_wait(wait, by_name["sub_op_rtt"], osd)
    # the device stages are the encode's children, the encode the op's
    enc = by_name["ec_encode"][0]
    assert enc["parent_id"] == op["span_id"]
    assert all(s["parent_id"] == enc["span_id"] for s in by_name["dev_compute"])
    _children_fit(mine)
    # the path's stages, end to end, account for the client's span
    first = lambda name, daemon: next(  # noqa: E731
        s for s in by_name[name] if s["daemon"] == daemon
    )
    covered = (
        op["end"] - first("msgr_send", client)["start"]
    )
    assert 0.5 * root["duration"] <= covered <= root["duration"]
    # one tree, the client's span its root beside the aio wait
    roots = tracing.assemble_tree(mine)
    assert sorted(n["name"] for n in roots) == ["client_aio_wait", "client_op"]


def _fan_out_then_wait(wait: dict, rtts: list[dict], osd: str) -> None:
    """Every ``sub_op_sent`` of a ``sub_op_wait`` span comes before its
    first ``sub_op_commit_rec``, and each peer has one ``sub_op_rtt``
    of the primary's that began at its send and ended inside the
    wait."""
    events = [e["event"] for e in wait["events"]]
    peers = wait["tags"]["peers"]
    assert all(e.startswith("sub_op_sent") for e in events[:peers]), events
    assert all(e.startswith("sub_op_commit_rec") for e in events[peers:]), events
    sent = {e.split()[1] for e in events[:peers]}
    assert len(sent) == peers and sent == {e.split()[1] for e in events[peers:]}
    assert {f"osd.{s['tags']['osd']}" for s in rtts} == sent
    assert len(rtts) == peers
    for s in rtts:
        assert s["daemon"] == osd and s["tags"]["ok"] is True
        assert wait["start"] - 1e-6 <= s["start"] and s["end"] <= wait["end"] + 1e-6
    # the last reply closes the wait: the longest round trip is all
    # but the whole span, and overlapped ones sum to more than it
    assert max(s["duration"] for s in rtts) <= wait["duration"] + 1e-6
    assert sum(s["duration"] for s in rtts) >= 0.9 * wait["duration"]


@pytest.fixture(scope="module")
def wide_cluster():
    """Six OSDs: a k=4 m=2 pool (five peers a write) beside a
    replicated size-3 one (two), one PG each."""
    from test_ec_daemon import ECCluster

    c = ECCluster(6)
    try:
        c.create_ec_pool(
            "wide-ec", ["k=4", "m=2", "plugin=jerasure"], pg_num=1
        )
        c.rados.pool_create("wide-rep", pg_num=1, size=3)
        for pool in ("wide-ec", "wide-rep"):
            c.rados.open_ioctx(pool).write_full("warm", b"w" * 4096)
        yield c
    finally:
        c.shutdown()


@pytest.mark.parametrize(
    "pool, peers", [("wide-ec", 5), ("wide-rep", 2)],
    ids=["ec_k4m2", "replicated_size3"],
)
def test_every_sub_op_is_sent_before_the_first_wait(wide_cluster, pool, peers):
    """``_commit_and_replicate`` issues every ``MOSDRepOp`` and then
    waits (issue_repop): the order of the span's events, one
    ``sub_op_rtt`` a peer under the op's trace id, and the stage
    counter the benchmark's ``osd_subop_overlap`` reads."""
    c = wide_cluster
    io = c.rados.open_ioctx(pool)
    _drain_all(c, c.rados)
    before = _stage("sub_op_rtt"), _stage("sub_op_wait")
    payload = bytes(range(256)) * 64
    io.aio_write_full("fan-out", payload).result(30)
    assert io.read("fan-out") == payload

    def finished():
        spans = []
        for osd in c.osds.values():
            spans += osd.tracer.dump_traces()["spans"]
        ops = [
            s for s in spans
            if s["name"] == "osd_op" and s["tags"].get("oid") == "fan-out"
            and s["tags"].get("created") is not None
        ]
        return ops and [s for s in spans if s["trace_id"] == ops[0]["trace_id"]]

    deadline = time.monotonic() + 10
    while not (mine := finished()):
        assert time.monotonic() < deadline
        time.sleep(0.02)
    (wait,) = [s for s in mine if s["name"] == "sub_op_wait"]
    assert wait["tags"]["peers"] == peers
    rtts = [s for s in mine if s["name"] == "sub_op_rtt"]
    _fan_out_then_wait(wait, rtts, wait["daemon"])
    assert len([s for s in mine if s["name"] == "rep_op"]) == peers
    rtt, waited = (
        tuple(a - b for a, b in zip(_stage(name), was))
        for name, was in zip(("sub_op_rtt", "sub_op_wait"), before)
    )
    assert rtt[0] == peers and waited[0] == 1
    assert rtt[1] == pytest.approx(sum(s["duration"] for s in rtts) * 1e9, abs=20)


def test_coalesced_writes_share_one_ec_encode_with_the_device_stages(ec_cluster):
    """The coalescer encodes before any ``osd_op`` is open: its
    ``ec_encode`` is a span of its own under the first folded op's
    trace, tagged with the ops folded in, the ``dev_*`` stages its
    children."""
    import concurrent.futures

    c, r, io, primary = ec_cluster
    _drain_all(c, r)
    stalled, gate = threading.Event(), threading.Event()
    primary._workq.put(
        (
            "splitcall",
            lambda: stalled.set() or gate.wait(20),
            concurrent.futures.Future(),
        )
    )
    assert stalled.wait(10)  # the op strand is held: the burst queues
    futs = []
    for i in range(3):
        queued = primary._workq.qlen()
        futs.append(io.aio_write_full(f"burst-{i}", bytes([i]) * 16384))
        deadline = time.monotonic() + 10
        while primary._workq.qlen() <= queued:
            assert time.monotonic() < deadline, "op never queued"
            time.sleep(0.01)
    gate.set()
    for fut in futs:
        fut.result(30)
    time.sleep(0.2)
    spans = primary.tracer.drain(1 << 20)
    batch = [
        s for s in spans
        if s["name"] == "ec_encode" and s["tags"].get("ops", 1) > 1
    ]
    assert len(batch) == 1 and batch[0]["tags"]["ops"] == 3
    assert batch[0]["parent_id"] == "" and batch[0]["role"] == tracing.ROLE_PRIMARY
    stages = [s for s in spans if s["parent_id"] == batch[0]["span_id"]]
    assert {"dev_compute", "dev_sync"} <= {s["name"] for s in stages}
    ops = [s for s in spans if s["name"] == "osd_op"]
    assert len(ops) == 3
    assert batch[0]["trace_id"] in {s["trace_id"] for s in ops}
    # each op waited on the queue until the strand took the whole run
    waits = [s for s in spans if s["name"] == "osd_queue_wait"]
    assert len(waits) == 3 and len({round(s["end"], 4) for s in waits}) == 1
    for i in range(3):
        assert io.read(f"burst-{i}") == bytes([i]) * 16384


# -- a remap -------------------------------------------------------------------

REMAP_STAGES = [
    "crush_inputs", "dev_compute", "dev_sync", "crush_fallback",
    "fixup_exists", "fixup_upmap", "fixup_up", "fixup_affinity", "fixup_temp",
]


def _small_map(pg_num: int = 2048):
    from ceph_tpu.osd.osdmap import OSDMap, PgPool
    from ceph_tpu.tools.crushtool import build_hierarchy

    osdmap = OSDMap.build(build_hierarchy(64, 4), 64)
    osdmap.add_pool(
        PgPool(pool_id=1, type=1, size=3, pg_num=pg_num, crush_rule=0)
    )
    return osdmap


def test_a_remap_leaves_its_stage_spans_and_a_crush_record(monkeypatch):
    from ceph_tpu.crush import jaxmap
    from ceph_tpu.ops.profiler import dispatch_profiler
    from ceph_tpu.osd.mapping import OSDMapMapping

    monkeypatch.setattr(jaxmap, "CHUNK_LANES", 1 << 8)  # 8 parts
    ahead = jaxmap.PARTS_AHEAD
    osdmap = _small_map()
    tracer = tracing.Tracer("remap-host")
    mapping = OSDMapMapping(tracer=tracer)
    mapping.update(osdmap)  # compiles
    tracer.drain(1 << 20)
    osdmap.epoch += 1
    seq = max(
        (e["seq"] for e in dispatch_profiler().history()["entries"]), default=0
    )
    before = {n: _stage(n) for n in ("remap", "dev_sync", "fixup_up")}
    mapping.update(osdmap)
    spans = tracer.drain(1 << 20)
    (root,) = tracing.assemble_tree(spans)
    assert root["name"] == "remap" and root["tags"]["epoch"] == osdmap.epoch
    names = [c["name"] for c in root["children"]]
    assert list(dict.fromkeys(names)) == REMAP_STAGES
    # a part's inputs are worked out as it is issued; the part in hand
    # has ``ahead`` parts issued behind it when it is fetched, and the
    # host's stages on it come before the next part's fetch
    issue, host = ["crush_inputs", "dev_compute"], REMAP_STAGES[3:]
    want = issue * ahead
    for part in range(8):
        want += (issue if part + ahead < 8 else []) + ["dev_sync"] + host
    assert names == want
    by_name = {c["name"]: c for c in root["children"]}
    assert by_name["dev_sync"]["tags"]["kind"] == "crush"
    assert by_name["crush_fallback"]["tags"]["lanes"] == 0
    _children_fit(spans)
    covered = sum(c["duration"] for c in root["children"])
    assert covered >= 0.85 * root["duration"]
    # the flight recorder's stages are bracketed round each part, one
    # record a part: the fetches are the records' sync time, the issues
    # their compute time
    entries = [
        e for e in dispatch_profiler().history("crush")["entries"]
        if e["seq"] > seq
    ]
    assert len(entries) == 8
    assert all(e["backend"] == "jax" and e["stripes"] == 256 for e in entries)
    fetch = sum(
        c["duration"] for c in root["children"] if c["name"] == "dev_sync"
    )
    issue = sum(
        c["duration"] for c in root["children"] if c["name"] == "dev_compute"
    )
    sync_s = sum(e["sync_s"] for e in entries)
    compute_s = sum(e["compute_s"] for e in entries)
    assert sync_s > compute_s > 0
    assert sync_s == pytest.approx(fetch, rel=0.2)
    assert compute_s <= issue  # a span wraps its stage's bracket
    assert sync_s + compute_s <= sum(e["wall_s"] for e in entries)
    count, ns, _none = (
        a - b for a, b in zip(_stage("dev_sync"), before["dev_sync"])
    )
    assert count == 8 and ns == pytest.approx(fetch * 1e9, rel=0.01)
    assert _stage("remap")[0] - before["remap"][0] == 1
    # a mapping no host serves keeps nothing and counts all the same
    bare = OSDMapMapping()
    bare.update(osdmap)
    assert bare.tracer.dump_traces()["num_spans"] == 0
    assert _stage("remap")[0] - before["remap"][0] == 2
    assert _stage("fixup_up")[0] - before["fixup_up"][0] == 16


def test_osdmaptool_prints_the_stages_of_its_timed_remap(capsys):
    """The operator's reader of the remap spans: one line beside the
    rate, a figure a stage, ``other`` for what no child covers."""
    from ceph_tpu.tools import osdmaptool

    rc = osdmaptool.main(
        ["--test-map-pgs", "--build", "64:4", "--pg-num", "1024"]
    )
    out = capsys.readouterr().out
    assert rc == 0 and "pg mappings/sec [jax]" in out
    (line,) = [ln for ln in out.splitlines() if "remap stages (ms):" in ln]
    stages = dict(
        part.strip().rsplit(" ", 1)
        for part in line.split(":", 1)[1].split(",")
    )
    assert list(stages) == REMAP_STAGES + ["other"]
    values = {k: float(v) for k, v in stages.items()}
    assert all(v >= 0 for v in values.values()) and values["dev_sync"] > 0
    elapsed = float(out.split(" in ")[1].split("s =")[0])
    assert sum(values.values()) == pytest.approx(1e3 * elapsed, rel=0.1, abs=1.0)


def test_crushtool_records_a_crush_dispatch(capsys):
    """``crushtool --test`` on the jax backend leaves flight-recorder
    entries of kind ``crush`` (PERF.md bring-up finding 4)."""
    from ceph_tpu.ops.profiler import dispatch_profiler
    from ceph_tpu.tools import crushtool

    seq = max(
        (e["seq"] for e in dispatch_profiler().history()["entries"]), default=0
    )
    rc = crushtool.main(
        ["--build", "16:4", "--test", "--rule", "0", "--num-rep", "2",
         "--min-x", "0", "--max-x", "256", "--backend", "jax",
         "--show-statistics"]
    )
    capsys.readouterr()
    assert rc in (0, None)
    entries = [
        e for e in dispatch_profiler().history("crush")["entries"]
        if e["seq"] > seq
    ]
    assert len(entries) == 2  # the timed pass and the compile-free one
    for e in entries:
        assert e["backend"] == "jax" and e["stripes"] == 256
        assert e["sync_s"] > 0 and e["compute_s"] > 0
