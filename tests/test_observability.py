"""The observability plane (ISSUE 1): distributed tracing assembled
across daemons by the mgr tracing module, device-kernel telemetry in
perf dump + /metrics, the SLOW_OPS health watchdog, slow-op stage
attribution, Prometheus exposition hygiene, and the metrics-schema
lint — the blkin/ZTracer + prometheus-module roles end to end."""

from __future__ import annotations

import json
import pathlib
import sys
import time
import urllib.request

import numpy as np
import pytest

from ceph_tpu.common import tracing
from ceph_tpu.common.admin_socket import admin_command
from ceph_tpu.common.op_tracker import OpTracker
from ceph_tpu.msg.messenger import wait_for
from ceph_tpu.ops.kernel_stats import kernel_stats

from test_osd_daemon import MiniCluster

sys.path.insert(
    0, str(pathlib.Path(__file__).resolve().parent.parent / "tools")
)


# -- unit: spans and assembly ----------------------------------------------


def test_tracer_spans_and_ambient_children():
    tr = tracing.Tracer("osd.7")
    with tr.start_span(
        "osd_op", trace_id="t-1", role=tracing.ROLE_PRIMARY
    ) as root:
        root.mark_event("started")
        # ambient: deep layers open children without a tracer handle
        with tracing.span("ec_encode", tags={"oid": "o"}) as child:
            child.mark_event("device_sync")
    spans = tr.drain()
    assert len(spans) == 2
    by_name = {s["name"]: s for s in spans}
    assert by_name["ec_encode"]["parent_id"] == by_name["osd_op"]["span_id"]
    assert by_name["ec_encode"]["trace_id"] == "t-1"
    assert by_name["osd_op"]["role"] == "primary"
    assert tr.drain() == []  # drained


def test_tracer_buffer_bounded():
    tr = tracing.Tracer("osd.8", max_spans=4)
    for i in range(10):
        tr.start_span(f"s{i}", trace_id="t").finish()
    dump = tr.dump_traces()
    assert dump["num_spans"] == 4
    assert dump["spans_dropped"] == 6
    assert dump["spans"][-1]["name"] == "s9"


def test_assemble_tree_cross_daemon_role_ranks():
    """Spans from three daemons with NO cross-daemon parent ids form
    one tree: client root <- primary <- replica."""
    t0 = time.time()

    def span(name, daemon, role, start, parent=""):
        return {
            "trace_id": "T", "span_id": name, "parent_id": parent,
            "daemon": daemon, "name": name, "role": role,
            "start": start, "end": start + 0.01, "duration": 0.01,
            "tags": {}, "events": [],
        }

    spans = [
        span("client_op", "client.a", "client", t0),
        span("osd_op", "osd.0", "primary", t0 + 0.001),
        span("rep_op", "osd.1", "replica", t0 + 0.002),
        span("rep_put", "osd.0", "", t0 + 0.0015, parent="osd_op"),
    ]
    roots = tracing.assemble_tree(spans)
    assert len(roots) == 1 and roots[0]["name"] == "client_op"
    (prim,) = roots[0]["children"]
    assert prim["name"] == "osd_op"
    kids = {c["name"] for c in prim["children"]}
    assert kids == {"rep_op", "rep_put"}


def test_ambient_propagation_context():
    assert tracing.ambient_trace_id() == ""
    with tracing.propagate("wire-trace"):
        tr = tracing.Tracer("osd.9")
        s = tr.start_span("handler")
        assert s.trace_id == "wire-trace"
        s.finish()
    assert tracing.ambient_trace_id() == ""


# -- unit: slow-op views ---------------------------------------------------


def test_slow_op_summary_and_slowest_stage():
    trk = OpTracker()
    op = trk.create_op("stuck_op", trace="t")
    op.mark_event("queued")
    time.sleep(0.05)
    op.mark_event("reached_pg")  # the 50ms culprit stage
    assert trk.slow_op_summary(0.01)["num_slow_ops"] == 1
    assert trk.slow_op_summary(60.0)["num_slow_ops"] == 0
    op.finish()
    assert trk.slow_op_summary(0.0)["num_slow_ops"] == 0
    dump = trk.dump_historic_slow_ops(0.0)
    slow = dump["ops"][0]
    assert "slowest_stage" in slow
    assert slow["slowest_stage"]["gap"] >= 0.04
    assert "queued -> reached_pg" in slow["slowest_stage"]["event"]


# -- unit: kernel telemetry ------------------------------------------------


def test_kernel_stats_counter_shapes_in_perf_dump():
    """An EC encode/decode round trip lands in the l_tpu_ec_* group
    with the perf-dump shapes: u64 calls/bytes, {avgcount, sum}
    latency."""
    from ceph_tpu.ec import ErasureCodeProfile, registry_instance
    from ceph_tpu.ec.stripe import (
        StripeInfo,
        decode_concat,
        encode,
    )

    ks = kernel_stats()
    before = ks.dump()
    prof = ErasureCodeProfile(
        {"k": "2", "m": "1", "backend": "jax"}
    )
    ec = registry_instance().factory("jerasure", prof)
    sinfo = StripeInfo(2, 2 * ec.get_chunk_size(2 * 4096))
    data = np.arange(2 * sinfo.stripe_width, dtype=np.uint8) % 251
    shards = encode(sinfo, ec, data)
    out = decode_concat(
        sinfo, ec, {i: shards[i] for i in range(2)}
    )
    assert np.array_equal(np.asarray(out), data)

    dump = ks.dump()
    for group in ("ec_encode", "ec_decode"):
        calls = dump[f"l_tpu_{group}_calls"]
        assert calls > before.get(f"l_tpu_{group}_calls", 0)
        assert dump[f"l_tpu_{group}_bytes_in"] > 0
        assert dump[f"l_tpu_{group}_bytes_out"] > 0
        lat = dump[f"l_tpu_{group}_lat"]
        assert lat["avgcount"] >= 1 and lat["sum"] > 0
    # device bitmatrix cache: first use misses, reuse hits
    assert dump["l_tpu_compile_cache_miss"] >= 1


def test_crush_mapping_kernel_counters():
    from ceph_tpu.osd.mapping import OSDMapMapping

    from test_osd_daemon import _base_map

    ks = kernel_stats()
    before = ks.dump().get("l_tpu_crush_calls", 0)
    mapping = OSDMapMapping()
    mapping.update(_base_map(), use_device=False)
    dump = ks.dump()
    assert dump["l_tpu_crush_calls"] > before
    assert dump["l_tpu_crush_pgs"] >= 2
    assert dump["l_tpu_crush_lat"]["avgcount"] >= 1


# -- unit: metrics lint (CI satellite) -------------------------------------


def test_check_metrics_product_schemas_clean():
    import check_metrics

    assert check_metrics.check_all() == []


def test_check_metrics_catches_bad_schemas():
    import check_metrics

    from ceph_tpu.common.perf_counters import (
        PERFCOUNTER_HISTOGRAM,
        PerfCounters,
        _Counter,
    )

    bad = PerfCounters("bad set")  # space: invalid after flattening?
    bad._counters["op latency"] = _Counter("op latency", "u64")
    bad._counters["hist"] = _Counter(
        "hist", PERFCOUNTER_HISTOGRAM, bucket_bounds=()
    )
    errors = check_metrics.check_perf_counters(bad)
    assert any("invalid Prometheus" in e for e in errors)
    assert any("no bucket bounds" in e for e in errors)
    # cross-set collision after name flattening
    a = PerfCounters("osd.x")
    a._counters["op"] = _Counter("op", "u64")
    b = PerfCounters("osd_x")
    b._counters["op"] = _Counter("op", "u64")
    errors = check_metrics.check_all([a, b])
    assert any("collides" in e for e in errors)


# -- unit: prometheus hygiene ----------------------------------------------


def test_prometheus_sanitize_and_escape():
    from ceph_tpu.mgr import PrometheusModule

    assert (
        PrometheusModule.sanitize_name("l_tpu.ec-encode calls")
        == "l_tpu_ec_encode_calls"
    )
    assert PrometheusModule.sanitize_name("0bad") == "_0bad"
    assert PrometheusModule.escape_label('a"b\\c') == r"a\"b\\c"


# -- integration -----------------------------------------------------------


def _free_port_path(tmp_path, name):
    return str(tmp_path / name)


def test_trace_assembled_across_daemons_and_metrics(tmp_path):
    """Acceptance: one logical write op traced across >= 2 daemons is
    retrievable as ONE span tree from the mgr tracing module, and
    l_tpu_ec_* counters show up in `perf dump` (admin socket) and the
    /metrics exposition."""
    from ceph_tpu.mgr import Manager
    from ceph_tpu.rados import Rados
    from ceph_tpu.store.ec_store import ECStore

    c = MiniCluster()
    mgr = None
    r = None
    try:
        asok = _free_port_path(tmp_path, "osd.0.asok")
        c.start_osd(0, admin_socket_path=asok)
        for i in (1, 2):
            c.start_osd(i)
        c.wait_active()
        mgr = Manager(name="obs")
        mgr.start(c.mon_addr)

        # an EC encode/decode round trip so the process-global
        # l_tpu_ec_* counters are live before the daemons report
        ecs = ECStore(
            profile={"k": "2", "m": "1", "backend": "jax"}
        )
        ecs.put("obj", b"\x07" * 8192)
        assert ecs.get("obj") == b"\x07" * 8192

        # client op through the Objecter (the root span opener)
        r = Rados("obs-client").connect(*c.mon_addr)
        r.pool_create("obspool", pg_num=2, size=3)
        io = r.open_ioctx("obspool")
        io.write_full("traced-obj", b"follow the spans")

        client_spans = r.objecter.tracer.dump_traces()["spans"]
        assert client_spans, "objecter opened no root span"
        trace = client_spans[-1]["trace_id"]
        assert r.objecter.flush_spans_to_mgr() >= 1

        tmod = mgr.modules["tracing"]

        def assembled():
            tmod.ingest_pending()
            tree = tmod.get_trace(trace)
            roles = set()

            def walk(nodes):
                for n in nodes:
                    roles.add(n.get("role", ""))
                    walk(n["children"])

            walk(tree["roots"])
            return (
                len(tree["daemons"]) >= 2
                and {"client", "primary", "replica"} <= roles
            )

        assert wait_for(assembled, 30.0), (
            "mgr tracing module never assembled client+primary+"
            f"replica spans: {tmod.get_trace(trace)}"
        )
        tree = tmod.get_trace(trace)
        # ONE tree: the client root holds everything else beneath it
        assert len(tree["roots"]) == 1
        root = tree["roots"][0]
        assert root["role"] == "client"
        assert root["trace_id"] == trace
        # the primary's op span sits under the client, on a DIFFERENT
        # daemon, with the replica's span beneath it
        prim = [
            n for n in root["children"] if n["role"] == "primary"
        ]
        assert prim and prim[0]["daemon"] != root["daemon"]

        # perf dump over the real admin socket carries the kernel set
        dump = admin_command(asok, "perf dump")["ok"]
        assert "tpu_kernels" in dump
        assert dump["tpu_kernels"]["l_tpu_ec_encode_calls"] >= 1
        assert dump["tpu_kernels"]["l_tpu_ec_decode_calls"] >= 1
        assert "avgcount" in dump["tpu_kernels"]["l_tpu_ec_encode_lat"]
        # and dump_traces serves the (admin-socket) local span view
        tdump = admin_command(asok, "dump_traces")["ok"]
        assert "spans" in tdump

        # /metrics exposition: per-daemon l_tpu_ec_* series with one
        # HELP/TYPE header per family
        port = mgr.modules["prometheus"].port

        def metrics_have_kernels():
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=5
            ).read().decode()
            return "ceph_daemon_l_tpu_ec_encode_calls" in body

        assert wait_for(metrics_have_kernels, 20.0)
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        help_lines = [
            ln for ln in body.splitlines() if ln.startswith("# HELP")
        ]
        families = [ln.split()[2] for ln in help_lines]
        assert len(families) == len(set(families)), (
            "duplicate HELP header for a family"
        )
        # multiple per-daemon families each carry their own header
        assert "ceph_daemon_op" in families
        assert "ceph_daemon_l_tpu_ec_encode_calls" in families
    finally:
        if r is not None:
            r.shutdown()
        if mgr is not None:
            mgr.shutdown()
        c.shutdown()


def test_slow_ops_degrade_health_and_clear():
    """An op stuck past osd_op_complaint_time flips `ceph health` to
    HEALTH_WARN with a SLOW_OPS check; finishing the op clears it."""
    c = MiniCluster()
    try:
        osd = c.start_osd(0)
        for i in (1, 2):
            c.start_osd(i)
        c.wait_active()
        osd.config.set("osd_op_complaint_time", 0.3)

        def health():
            reply = c.monc.command({"prefix": "health"})
            return json.loads(reply.outb)

        assert wait_for(
            lambda: health()["status"] == "HEALTH_OK", 15.0
        )
        stuck = osd.op_tracker.create_op(
            "osd_op(stuck-op 1.0 blocked)", trace="stuck-op"
        )
        stuck.mark_event("queued")
        assert wait_for(
            lambda: health()["status"] == "HEALTH_WARN"
            and any(
                "SLOW_OPS" in chk for chk in health()["checks"]
            ),
            15.0,
        ), health()
        assert osd.perf.dump()["slow_ops"] >= 1
        stuck.finish()
        assert wait_for(
            lambda: health()["status"] == "HEALTH_OK", 15.0
        ), health()
    finally:
        c.shutdown()
