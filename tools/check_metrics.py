#!/usr/bin/env python
"""Metrics-schema lint — walk every registered PerfCounters schema
and fail on exporter-breaking declarations (run in tier-1 via
tests/test_observability.py, and standalone as
``python tools/check_metrics.py``).

Checks, per counter set:

- duplicate counter names within a set (the builder asserts at
  declaration time; dynamically-extended sets — KernelStats — can
  bypass it) and duplicate (set, counter) pairs across sets after the
  exporter's name transformation;
- names that the Prometheus exposition format rejects: anything
  outside ``[a-zA-Z_:][a-zA-Z0-9_:]*`` AFTER the mgr exporter's
  sanitization would silently collide or be dropped — the lint flags
  the raw name so the collision is fixed at the source;
- histogram counters with no bucket bounds (an unbounded histogram
  dumps an empty bucket array and renders as a zero-information
  series).

The walked schemas are the product's real ones: the OSD daemon's
counter block, the batched-mapping counters, and the device-kernel
telemetry plane (after forcing registration of every group).

The event-plane schemas are linted the same way: a real clog entry
(common/log_client.py) and a real crash report (common/crash.py) are
generated and checked for required fields, bounded sizes, and
label-safe values — the shapes the mon LogStore, the mgr crash
module, and the prometheus exporter all assume.
"""

from __future__ import annotations

import re
import sys

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")

# -- event-plane schema bounds ---------------------------------------------
CLOG_REQUIRED = ("name", "stamp", "channel", "prio", "message", "seq")
CLOG_PRIOS = {"debug", "info", "warn", "error", "sec"}
CLOG_MAX_MESSAGE = 4096
CLOG_MAX_CHANNEL = 64
CLOG_MAX_NAME = 64
# channels/names become Prometheus label values and CLI columns:
# printable, no control characters
_LABEL_SAFE_RE = re.compile(r"^[\x20-\x7e]*$")
_CHANNEL_RE = re.compile(r"^[a-zA-Z][a-zA-Z0-9_.-]*$")

# -- scrub-plane schema bounds ----------------------------------------------
# inconsistency records (osd/scrub.py make_record, the rados
# list-inconsistent-obj shape served by the primary's ScrubStore)
INCONSISTENT_REQUIRED = (
    "object", "errors", "union_shard_errors", "shards", "oid",
)
INCONSISTENT_MAX_SHARDS = 64
INCONSISTENT_MAX_NAME = 1024
# scrub counters the OSD schema must declare (the mgr exporter's
# ceph_osd_scrub_* families read exactly these)
SCRUB_COUNTERS = (
    "scrub_errors", "scrubs_active", "scrub_chunks",
    "scrub_deep_bytes", "scrub_last_age",
)

# fault-injection counters every messenger schema must declare
# (msg/faults.py build_msgr_perf → the ceph_msgr_fault_* families)
FAULT_COUNTERS = (
    "fault_dropped", "fault_delayed", "fault_duplicated",
    "fault_socket_failures",
)
# shared-stack worker telemetry the stack schema must declare
# (msg/stack.py build_stack_perf — aggregates plus the per-worker
# series, all riding stack_perf_dump() → MMgrReport → prometheus)
WORKER_COUNTERS = (
    "l_msgr_workers",
    "l_msgr_worker_connections",
    "l_msgr_worker_dispatch",
    "l_msgr_worker_loop_lag",
    "l_msgr_offload_threads",
    "l_msgr_offload_threads_peak",
)
WORKER_PER_INDEX_COUNTERS = (
    "l_msgr_worker{i}_connections",
    "l_msgr_worker{i}_dispatch",
    "l_msgr_worker{i}_loop_lag",
)
# fullness gauges the OSD schema must declare (the osd_stat_t carry
# feeding OSD_NEARFULL/OSD_FULL and the backoff visibility gauge)
FULLNESS_COUNTERS = (
    "stat_bytes", "stat_bytes_used", "stat_bytes_avail",
    "backoffs_active",
)
# device-residency + coalesced-encode families the kernel-stats
# schema must declare (ops/residency.py ensure_counters — the
# data-plane batching observability the e2e_batched bench reads)
RESIDENCY_COUNTERS = (
    "l_tpu_residency_hits",
    "l_tpu_residency_misses",
    "l_tpu_residency_evictions",
    "l_tpu_residency_bytes_resident",
    "l_tpu_batch_encode_dispatches",
    "l_tpu_batch_encode_ops_per_dispatch",
    "l_tpu_batch_decode_dispatches",
    "l_tpu_batch_decode_ops_per_dispatch",
)
# device-dispatch flight-recorder family the kernel-stats schema must
# declare (ops/profiler.py ensure_dispatch_counters — the
# transfer/compute/sync attribution plane the benchmark's harness and
# the `dispatch history|summary` tell surface read), plus the pad-waste
# counter kernel_stats registers at construction
DISPATCH_COUNTERS = (
    "l_tpu_dispatch_count",
    "l_tpu_dispatch_ops",
    "l_tpu_dispatch_stripes",
    "l_tpu_dispatch_bytes_uploaded",
    "l_tpu_dispatch_bytes_resident",
    "l_tpu_dispatch_ring_dropped",
    "l_tpu_dispatch_transfer_lat",
    "l_tpu_dispatch_transfer_lat_hist",
    "l_tpu_dispatch_compute_lat",
    "l_tpu_dispatch_compute_lat_hist",
    "l_tpu_dispatch_sync_lat",
    "l_tpu_dispatch_sync_lat_hist",
    "l_tpu_pad_bytes_wasted",
)
# tracing-plane stage counters (common/tracing.py Tracer._complete →
# ops/kernel_stats.py record_stage): every finished span of one of
# these names — the served write from the client's aio queue to the
# shard commit, the remap's stages, the EC seam's host copies round a
# plugin call, and a durable store's commit below store_commit (the
# WAL's barrier, apply and checkpoint, the block store's fsyncs) —
# feeds
# l_stage_<name>_{count,ns} (and _self_ns for a kernel_stats
# SELF_TIME_STAGES name, _cpu_ns for a RUSAGE_STAGES one, _handovers
# for a HANDOVER_STAGES one) that the benchmark's per-layer readers and
# /metrics read
STAGE_SPANS = (
    "client_aio_wait", "client_op", "msgr_send", "msgr_recv",
    "osd_queue_wait", "osd_op", "ec_prepare", "ec_encode", "txn_build",
    "store_commit", "sub_op_wait", "rep_op",
    "dev_upload", "dev_compute", "dev_sync",
    "remap", "crush_inputs", "crush_fallback", "fixup_exists",
    "fixup_upmap", "fixup_up", "fixup_affinity", "fixup_temp",
    "ec_fold", "ec_unfold", "ec_assemble", "ec_plan",
    "wal_barrier", "wal_apply", "wal_checkpoint", "store_fsync",
)
# the process's own usage the kernel-stats schema declares at
# construction and refreshes at every dump (ops/kernel_stats.py
# PROCESS_COUNTERS — the benchmark's host_cores_busy reads the first,
# perf dump and /metrics show all three)
PROCESS_COUNTERS = (
    "l_process_cpu_ns",
    "l_process_handovers",
    "l_process_preemptions",
)
# sharded bucket-index + reshard families the RGW schema must
# declare (rgw/index.py build_rgw_perf — the bench rgw_index section
# and the reshard-under-load tests read exactly these)
RGW_INDEX_COUNTERS = (
    "l_rgw_index_ops",
    "l_rgw_index_reads",
    "l_rgw_index_list_pages",
    "l_rgw_index_list_entries",
    "l_rgw_index_retries",
    "l_rgw_index_dual_writes",
    "l_rgw_index_stall_waits",
    "l_rgw_index_shards",
    "l_rgw_reshard_queued",
    "l_rgw_reshard_started",
    "l_rgw_reshard_completed",
    "l_rgw_reshard_entries_migrated",
    "l_rgw_reshard_passes",
    "l_rgw_reshard_in_progress",
)
# WAL-plane counters the wal_store schema must declare
# (store/wal_store.py build_wal_perf — the bench wal section, the
# chaos kill-storm verdict, and the mgr exporter read exactly these)
WAL_COUNTERS = (
    "l_os_wal_appends",
    "l_os_wal_append_bytes",
    "l_os_wal_deferred",
    "l_os_wal_deferred_bytes",
    "l_os_wal_barriers",
    "l_os_wal_group_records",
    "l_os_wal_barrier_waits",
    "l_os_wal_reads_from_log",
    "l_os_wal_applies",
    "l_os_wal_apply_errors",
    "l_os_wal_replay_records",
    "l_os_wal_checkpoints",
    "l_os_wal_pending_records",
    "l_os_wal_pending_bytes",
)
# process-runtime counters the supervisor schema must declare
# (proc/supervisor.py build_proc_perf — the respawn/crash-loop
# telemetry riding MMgrReport like every daemon's), and the dispatch
# backpressure pair the STACK schema must declare (msg/stack.py
# build_stack_perf — depth gauge + stall counter the bounded inbound
# queue maintains)
PROC_COUNTERS = (
    "l_proc_children",
    "l_proc_restarts",
    "l_proc_crash_loops",
)
# qa thrasher counters (qa/thrasher.py build_thrash_perf): the chaos
# smoke gate's event/violation/shrink accounting
THRASH_COUNTERS = (
    "l_thrash_events",
    "l_thrash_skipped_events",
    "l_thrash_violations",
    "l_thrash_shrink_steps",
)
# client op-path counters (osdc/objecter.py build_objecter_perf):
# the backoff-park visibility the full-OSD scenarios read
OBJECTER_COUNTERS = (
    "l_objecter_backoff_parks",
)
DISPATCH_QUEUE_COUNTERS = (
    "l_msgr_dispatch_queue_depth",
    "l_msgr_dispatch_queue_stalls",
)
# recovery-storm counters the OSD schema must declare (the
# l_osd_recovery_* block: batched decode rebuild progress + the
# survivor-read fan-in the LRC locality claim is measured from)
RECOVERY_COUNTERS = (
    "recovery_active",
    "recovery_pushes",
    "recovery_push_bytes",
    "recovery_batches",
    "recovery_batch_ops",
    "recovery_survivor_shards",
    "recovery_helper_bytes",
)

CRASH_REQUIRED = (
    "crash_id", "entity_name", "timestamp", "timestamp_iso",
    "exception", "backtrace", "dout_tail", "meta",
)
CRASH_ID_RE = re.compile(
    r"^\d{4}-\d{2}-\d{2}T\d{2}:\d{2}:\d{2}\.\d{6}Z_[0-9a-f-]{36}$"
)
CRASH_MAX_BACKTRACE_LINES = 100
CRASH_MAX_LINE = 2048
CRASH_MAX_DOUT_TAIL = 200


def check_clog_entry(entry) -> list[str]:
    """Lint one cluster-log entry (LogClient/MLog/LogStore shape)."""
    errors: list[str] = []
    if not isinstance(entry, dict):
        return ["clog entry: not a dict"]
    for field in CLOG_REQUIRED:
        if field not in entry:
            errors.append(f"clog entry: missing field {field!r}")
    prio = entry.get("prio")
    if prio is not None and prio not in CLOG_PRIOS:
        errors.append(f"clog entry: unknown prio {prio!r}")
    channel = str(entry.get("channel", ""))
    if len(channel) > CLOG_MAX_CHANNEL or not _CHANNEL_RE.match(
        channel or "-"
    ):
        errors.append(
            f"clog entry: channel {channel!r} unbounded or not "
            "label-safe"
        )
    name = str(entry.get("name", ""))
    if len(name) > CLOG_MAX_NAME or not _LABEL_SAFE_RE.match(name):
        errors.append(
            f"clog entry: name {name!r} unbounded or not label-safe"
        )
    message = entry.get("message", "")
    if not isinstance(message, str) or len(message) > CLOG_MAX_MESSAGE:
        errors.append("clog entry: message missing, non-str, or over "
                      f"{CLOG_MAX_MESSAGE} bytes")
    if not isinstance(entry.get("stamp", 0.0), (int, float)):
        errors.append("clog entry: stamp is not a number")
    if not isinstance(entry.get("seq", 0), int):
        errors.append("clog entry: seq is not an int")
    return errors


def check_crash_report(report) -> list[str]:
    """Lint one crash report (common/crash.py / mgr crash shape)."""
    errors: list[str] = []
    if not isinstance(report, dict):
        return ["crash report: not a dict"]
    for field in CRASH_REQUIRED:
        if field not in report:
            errors.append(f"crash report: missing field {field!r}")
    cid = str(report.get("crash_id", ""))
    if not CRASH_ID_RE.match(cid):
        errors.append(
            f"crash report: crash_id {cid!r} not <ISO stamp>_<uuid>"
        )
    entity = str(report.get("entity_name", ""))
    if len(entity) > CLOG_MAX_NAME or not _LABEL_SAFE_RE.match(entity):
        errors.append(
            f"crash report: entity_name {entity!r} unbounded or not "
            "label-safe"
        )
    bt = report.get("backtrace", [])
    if not isinstance(bt, list) or not all(
        isinstance(ln, str) for ln in bt
    ):
        errors.append("crash report: backtrace is not a list of str")
    else:
        if len(bt) > CRASH_MAX_BACKTRACE_LINES:
            errors.append(
                f"crash report: backtrace over "
                f"{CRASH_MAX_BACKTRACE_LINES} lines"
            )
        if any(len(ln) > CRASH_MAX_LINE for ln in bt):
            errors.append(
                f"crash report: backtrace line over {CRASH_MAX_LINE}"
            )
    tail = report.get("dout_tail", [])
    if not isinstance(tail, list) or len(tail) > CRASH_MAX_DOUT_TAIL:
        errors.append(
            f"crash report: dout_tail missing, non-list, or over "
            f"{CRASH_MAX_DOUT_TAIL} entries"
        )
    if not isinstance(report.get("timestamp", 0.0), (int, float)):
        errors.append("crash report: timestamp is not a number")
    if not isinstance(report.get("meta", {}), dict):
        errors.append("crash report: meta is not a dict")
    return errors


def check_inconsistent_record(rec) -> list[str]:
    """Lint one inconsistency record (ScrubStore / MScrubCommand
    list-inconsistent-obj shape)."""
    from ceph_tpu.osd.scrub import KNOWN_ERRORS

    errors: list[str] = []
    if not isinstance(rec, dict):
        return ["inconsistent record: not a dict"]
    for field in INCONSISTENT_REQUIRED:
        if field not in rec:
            errors.append(
                f"inconsistent record: missing field {field!r}"
            )
    obj = rec.get("object")
    if not isinstance(obj, dict) or not isinstance(
        obj.get("name"), str
    ):
        errors.append(
            "inconsistent record: object.name missing or non-str"
        )
    elif len(obj["name"]) > INCONSISTENT_MAX_NAME or not (
        _LABEL_SAFE_RE.match(obj["name"])
    ):
        errors.append(
            f"inconsistent record: object name {obj['name']!r} "
            "unbounded or not label-safe"
        )
    for key in ("errors", "union_shard_errors"):
        vocab = rec.get(key, [])
        if not isinstance(vocab, list):
            errors.append(f"inconsistent record: {key} not a list")
            continue
        for e in vocab:
            if e not in KNOWN_ERRORS:
                errors.append(
                    f"inconsistent record: unknown error code {e!r}"
                )
    shards = rec.get("shards", [])
    if not isinstance(shards, list):
        errors.append("inconsistent record: shards not a list")
        shards = []
    if len(shards) > INCONSISTENT_MAX_SHARDS:
        errors.append(
            f"inconsistent record: over {INCONSISTENT_MAX_SHARDS} "
            "shards"
        )
    for sh in shards:
        if not isinstance(sh, dict) or not isinstance(
            sh.get("osd"), int
        ):
            errors.append(
                "inconsistent record: shard entry without int osd"
            )
            continue
        for e in sh.get("errors", []):
            if e not in KNOWN_ERRORS:
                errors.append(
                    f"inconsistent record: shard {sh['osd']} unknown "
                    f"error code {e!r}"
                )
    return errors


def product_scrub_samples() -> list[str]:
    """Run the REAL compare paths over synthetic scrub maps and lint
    the records they produce — the shapes ScrubStore persists and
    list-inconsistent-obj serves."""
    from ceph_tpu.osd.scrub import compare_ec, compare_replicated

    errors: list[str] = []
    base = {
        "exists": True, "size": 11, "omap_digest": 1,
        "attrs_digest": 2, "data_digest": 3,
    }
    rec = compare_replicated(
        "o_probe",
        {0: dict(base), 1: dict(base), 2: dict(base, data_digest=9)},
        primary=0,
        deep=True,
    )
    if rec is None:
        errors.append("compare_replicated: planted mismatch unfound")
    else:
        errors.extend(check_inconsistent_record(rec))
    ec_ent = {
        "exists": True, "size": 8, "omap_digest": 1,
        "attrs_digest": 2, "data_digest": 3,
        "hinfo": {"size": 16, "hashes": [3, 3, 9]},
    }
    rec, _needs = compare_ec(
        "o_probe",
        {0: dict(ec_ent), 1: dict(ec_ent), 2: dict(ec_ent)},
        acting=[0, 1, 2],
        sinfo=None,
        deep=True,
    )
    if rec is None:
        errors.append("compare_ec: planted shard mismatch unfound")
    else:
        errors.extend(check_inconsistent_record(rec))
    return errors


def check_scrub_counters() -> list[str]:
    """The OSD schema must keep declaring the scrub counter block the
    exporter's ceph_osd_scrub_* families are built from."""
    from ceph_tpu.osd.daemon import build_osd_perf

    declared = set(build_osd_perf(0)._counters)
    return [
        f"osd schema: scrub counter {name!r} missing"
        for name in SCRUB_COUNTERS
        if name not in declared
    ]


def check_fault_counters() -> list[str]:
    """The fault-plane families: every messenger's l_msgr_fault_*
    block and the OSD's fullness gauges — the chaos scenarios and the
    OSD_NEARFULL/OSD_FULL checks read exactly these."""
    from ceph_tpu.msg.faults import build_msgr_perf
    from ceph_tpu.osd.daemon import build_osd_perf

    errors = []
    msgr_declared = set(build_msgr_perf("lint")._counters)
    errors.extend(
        f"msgr schema: fault counter {name!r} missing"
        for name in FAULT_COUNTERS
        if name not in msgr_declared
    )
    osd_declared = set(build_osd_perf(0)._counters)
    errors.extend(
        f"osd schema: fullness gauge {name!r} missing"
        for name in FULLNESS_COUNTERS
        if name not in osd_declared
    )
    return errors


def check_worker_counters() -> list[str]:
    """The shared-stack plane: build_stack_perf must keep declaring
    the l_msgr_worker_* family (aggregates + every per-worker index
    up to the declared worker count) the scale harness and the mgr
    exporter read."""
    from ceph_tpu.msg.stack import build_stack_perf

    n = 3
    declared = set(build_stack_perf(n)._counters)
    errors = [
        f"stack schema: worker counter {name!r} missing"
        for name in WORKER_COUNTERS
        if name not in declared
    ]
    for i in range(n):
        errors.extend(
            f"stack schema: per-worker counter "
            f"{tmpl.format(i=i)!r} missing"
            for tmpl in WORKER_PER_INDEX_COUNTERS
            if tmpl.format(i=i) not in declared
        )
    return errors


def check_proc_counters() -> list[str]:
    """The process runtime: build_proc_perf must keep declaring the
    l_proc_* family, and build_stack_perf the dispatch-backpressure
    pair — the supervisor tests, the chaos process-kill scenario,
    and the mgr exporter read exactly these."""
    from ceph_tpu.msg.stack import build_stack_perf
    from ceph_tpu.proc.supervisor import build_proc_perf

    errors = []
    declared = set(build_proc_perf()._counters)
    errors.extend(
        f"proc schema: counter {name!r} missing"
        for name in PROC_COUNTERS
        if name not in declared
    )
    stack_declared = set(build_stack_perf(1)._counters)
    errors.extend(
        f"stack schema: dispatch-queue counter {name!r} missing"
        for name in DISPATCH_QUEUE_COUNTERS
        if name not in stack_declared
    )
    return errors


def check_recovery_counters() -> list[str]:
    """The recovery-storm plane: the OSD schema's l_osd_recovery_*
    block (`perf dump` and /metrics serve exactly these)."""
    from ceph_tpu.osd.daemon import build_osd_perf

    declared = set(build_osd_perf(0)._counters)
    return [
        f"osd schema: recovery counter {name!r} missing"
        for name in RECOVERY_COUNTERS
        if name not in declared
    ]


def check_thrash_counters() -> list[str]:
    """The qa plane: build_thrash_perf must keep declaring the
    l_thrash_* family the smoke-thrash gate and repro reports
    count into."""
    from ceph_tpu.qa.thrasher import build_thrash_perf

    declared = set(build_thrash_perf()._counters)
    return [
        f"qa schema: counter {name!r} missing"
        for name in THRASH_COUNTERS
        if name not in declared
    ]


def check_objecter_counters() -> list[str]:
    """The client op path: build_objecter_perf must keep declaring
    the l_objecter_* family (backoff parks — the no-resend-storm
    witness the full-cluster scenarios assert on)."""
    from ceph_tpu.osdc.objecter import build_objecter_perf

    declared = set(build_objecter_perf()._counters)
    return [
        f"objecter schema: counter {name!r} missing"
        for name in OBJECTER_COUNTERS
        if name not in declared
    ]


def check_wal_counters() -> list[str]:
    """The WAL plane: build_wal_perf must keep declaring the
    l_os_wal_* family the bench wal section and the kill-storm chaos
    verdict read."""
    from ceph_tpu.store.wal_store import build_wal_perf

    declared = set(build_wal_perf()._counters)
    return [
        f"wal schema: counter {name!r} missing"
        for name in WAL_COUNTERS
        if name not in declared
    ]


def check_rgw_counters() -> list[str]:
    """The sharded-index plane: the gateway schema's
    ``l_rgw_index_*`` / ``l_rgw_reshard_*`` families, through the
    REAL builder."""
    from ceph_tpu.rgw.index import build_rgw_perf

    declared = set(build_rgw_perf("rgw")._counters)
    return [
        f"rgw schema: index counter {name!r} missing"
        for name in RGW_INDEX_COUNTERS
        if name not in declared
    ]


def check_residency_counters() -> list[str]:
    """The kernel-stats schema must keep declaring the residency and
    batched-encode families through the REAL registration helper
    (ops/residency.ensure_counters — the exact names the e2e_batched
    bench and the MMgrReport pipeline read)."""
    from ceph_tpu.ops.kernel_stats import KernelStats
    from ceph_tpu.ops.residency import ensure_counters

    ks = KernelStats()
    ensure_counters(ks)
    declared = set(ks.perf._counters)
    return [
        f"kernel schema: residency counter {name!r} missing"
        for name in RESIDENCY_COUNTERS
        if name not in declared
    ]


def check_dispatch_counters() -> list[str]:
    """The kernel-stats schema must keep declaring the
    flight-recorder family through the REAL registration helper
    (ops/profiler.ensure_dispatch_counters — the exact names the
    prometheus exporter reads), with
    the stage-latency histograms carrying real bucket bounds."""
    from ceph_tpu.ops.kernel_stats import KernelStats
    from ceph_tpu.ops.profiler import ensure_dispatch_counters

    ks = KernelStats()
    ensure_dispatch_counters(ks)
    declared = set(ks.perf._counters)
    errors = [
        f"kernel schema: dispatch counter {name!r} missing"
        for name in DISPATCH_COUNTERS
        if name not in declared
    ]
    for stage in ("transfer", "compute", "sync"):
        name = f"l_tpu_dispatch_{stage}_lat_hist"
        c = ks.perf._counters.get(name)
        if c is not None and not getattr(c, "bucket_bounds", ()):
            errors.append(
                f"kernel schema: {name} histogram has no bucket "
                "bounds"
            )
    return errors


def check_stage_counters() -> list[str]:
    """The tracing plane's stage family, through the REAL sink: a
    finished span of every product span name must register its
    ``l_stage_<name>_*`` counters on the kernel set as plain u64s —
    ``_self_ns`` where the name's self time has a reader, ``_cpu_ns``
    where its thread's CPU time has one, ``_handovers`` where its
    thread's switches have one, and nowhere else (the set itself is
    linted by check_perf_counters in the schema walk)."""
    from ceph_tpu.common.perf_counters import PERFCOUNTER_U64
    from ceph_tpu.ops.kernel_stats import (
        HANDOVER_STAGES,
        RUSAGE_STAGES,
        SELF_TIME_STAGES,
        KernelStats,
    )

    ks = KernelStats()
    for name in STAGE_SPANS:
        ks.record_stage(name, 0, 0, (0, 0))
    errors = [
        f"kernel schema: RUSAGE_STAGES name {name!r} is no product span"
        for name in sorted(RUSAGE_STAGES - set(STAGE_SPANS))
    ] + [
        f"kernel schema: HANDOVER_STAGES name {name!r} reads no usage"
        for name in sorted(HANDOVER_STAGES - RUSAGE_STAGES)
    ]
    only = {
        "self_ns": SELF_TIME_STAGES,
        "cpu_ns": RUSAGE_STAGES,
        "handovers": HANDOVER_STAGES,
    }
    for name in STAGE_SPANS:
        for suffix in ("count", "ns", "self_ns", "cpu_ns", "handovers"):
            counter = ks.perf._counters.get(f"l_stage_{name}_{suffix}")
            wanted = suffix not in only or name in only[suffix]
            if (counter is not None) != wanted:
                errors.append(
                    f"kernel schema: stage counter "
                    f"l_stage_{name}_{suffix} "
                    + ("missing" if wanted else "has no reader")
                )
            elif wanted and counter.kind != PERFCOUNTER_U64:
                errors.append(
                    f"kernel schema: l_stage_{name}_{suffix} is "
                    f"{counter.kind}, not u64"
                )
    return errors


def check_process_counters() -> list[str]:
    """The kernel-stats schema declares the process's usage from
    construction, as u64s its dump fills."""
    from ceph_tpu.common.perf_counters import PERFCOUNTER_U64
    from ceph_tpu.ops.kernel_stats import KernelStats

    ks = KernelStats()
    dump = ks.dump()
    errors = []
    for name in PROCESS_COUNTERS:
        counter = ks.perf._counters.get(name)
        if counter is None:
            errors.append(f"kernel schema: process counter {name!r} missing")
        elif counter.kind != PERFCOUNTER_U64:
            errors.append(f"kernel schema: {name} is {counter.kind}, not u64")
    if not dump.get("l_process_cpu_ns"):
        errors.append("kernel schema: l_process_cpu_ns reads 0 after a dump")
    return errors


def check_kernel_declared_counters() -> list[str]:
    """The packed kernels' counters are declared with the kernel-stats
    set, not by the calls that count into them: every daemon's dump
    has them, as u64s at 0 where no such call ran."""
    from ceph_tpu.common.perf_counters import PERFCOUNTER_U64
    from ceph_tpu.ops.kernel_stats import (
        DECODE_PACKED_CALLS,
        FOLD_OVERLAPPED_NS,
        KernelStats,
    )

    ks = KernelStats()
    dump = ks.dump()
    errors = []
    for name in (FOLD_OVERLAPPED_NS, DECODE_PACKED_CALLS):
        counter = ks.perf._counters.get(name)
        if counter is None or dump.get(name) != 0:
            errors.append(
                f"kernel schema: {name} is not declared at 0 with the set"
            )
        elif counter.kind != PERFCOUNTER_U64:
            errors.append(f"kernel schema: {name} is not a u64")
    return errors


def product_event_samples() -> list[str]:
    """Generate one real clog entry and one real crash report through
    the product code paths and lint them — the schemas daemons
    actually emit, not hand-written fixtures."""
    from ceph_tpu.common import crash as crash_util
    from ceph_tpu.common.log_client import LogClient

    errors: list[str] = []
    client = LogClient("osd.0")
    entry = client.queue("cluster", "warn", "lint probe entry")
    errors.extend(check_clog_entry(entry))
    try:
        raise RuntimeError("lint probe crash")
    except RuntimeError as e:
        report = crash_util.build_report("osd.0", e)
    errors.extend(check_crash_report(report))
    return errors


_LABEL_NAME_RE = re.compile(r"^[a-zA-Z_][a-zA-Z0-9_]*$")
_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?:\{(?P<labels>.*)\})?\s+(?P<value>\S+)$"
)
_LABEL_PAIR_RE = re.compile(
    r'\s*(?P<k>[a-zA-Z_][a-zA-Z0-9_]*)="(?P<v>(?:[^"\\]|\\.)*)"\s*(?:,|$)'
)


def check_prometheus_histograms(text: str) -> list[str]:
    """Lint rendered exposition text for histogram-family
    correctness: one HELP/TYPE per family, cumulative bucket
    monotonicity per labelset, a closing ``le="+Inf"`` bucket that
    equals ``_count``, a ``_sum``/``_count`` pair per labelset, and
    label-name safety.  Fed the exporter's real output in tier-1."""
    errors: list[str] = []
    types: dict[str, str] = {}
    helped: set[str] = set()
    # (family, labels-without-le) -> [(le, value)] in document order
    buckets: dict[tuple[str, tuple], list[tuple[str, float]]] = {}
    sums: set[tuple[str, tuple]] = set()
    counts: dict[tuple[str, tuple], float] = {}
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            fam = parts[2] if len(parts) > 2 else ""
            if fam in types:
                errors.append(f"line {lineno}: duplicate TYPE {fam}")
            types[fam] = parts[3] if len(parts) > 3 else ""
            continue
        if line.startswith("# HELP "):
            parts = line.split()
            fam = parts[2] if len(parts) > 2 else ""
            if fam in helped:
                errors.append(f"line {lineno}: duplicate HELP {fam}")
            helped.add(fam)
            continue
        if line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(f"line {lineno}: unparseable sample {line!r}")
            continue
        name = m.group("name")
        labels: dict[str, str] = {}
        raw = m.group("labels") or ""
        pos = 0
        while pos < len(raw):
            lm = _LABEL_PAIR_RE.match(raw, pos)
            if lm is None:
                errors.append(
                    f"line {lineno}: bad label syntax {raw!r}"
                )
                break
            labels[lm.group("k")] = lm.group("v")
            pos = lm.end()
        for k in labels:
            if not _LABEL_NAME_RE.match(k):
                errors.append(f"line {lineno}: bad label name {k!r}")
        try:
            value = float(m.group("value"))
        except ValueError:
            errors.append(
                f"line {lineno}: non-numeric value "
                f"{m.group('value')!r}"
            )
            continue
        for suffix, sink in (
            ("_bucket", "bucket"), ("_sum", "sum"), ("_count", "count"),
        ):
            fam = name[: -len(suffix)] if name.endswith(suffix) else None
            if fam and types.get(fam) == "histogram":
                key = (
                    fam,
                    tuple(
                        sorted(
                            (k, v)
                            for k, v in labels.items()
                            if k != "le"
                        )
                    ),
                )
                if sink == "bucket":
                    if "le" not in labels:
                        errors.append(
                            f"line {lineno}: bucket without le"
                        )
                    buckets.setdefault(key, []).append(
                        (labels.get("le", ""), value)
                    )
                elif sink == "sum":
                    sums.add(key)
                else:
                    counts[key] = value
                break
    for fam, typ in types.items():
        if typ != "histogram":
            continue
        fam_keys = [k for k in buckets if k[0] == fam]
        if not fam_keys:
            errors.append(f"{fam}: histogram family with no buckets")
        for key in fam_keys:
            rows = buckets[key]
            vals = [v for _le, v in rows]
            if any(b > a for a, b in zip(vals[1:], vals)):
                errors.append(
                    f"{fam}{dict(key[1])}: buckets not monotone"
                )
            if not rows or rows[-1][0] != "+Inf":
                errors.append(
                    f"{fam}{dict(key[1])}: no closing +Inf bucket"
                )
            elif key in counts and rows[-1][1] != counts[key]:
                errors.append(
                    f"{fam}{dict(key[1])}: +Inf bucket "
                    f"{rows[-1][1]} != _count {counts[key]}"
                )
            if key not in sums:
                errors.append(f"{fam}{dict(key[1])}: missing _sum")
            if key not in counts:
                errors.append(f"{fam}{dict(key[1])}: missing _count")
    return errors


def product_histogram_exposition() -> list[str]:
    """Render histogram families through the mgr exporter's REAL
    renderer from product-generated histograms (op tracker
    completions + a commit histogram) and lint the text."""
    from ceph_tpu.common.histogram import LogHistogram
    from ceph_tpu.common.op_tracker import OpTracker
    from ceph_tpu.mgr import histogram_exposition_lines

    tracker = OpTracker()
    for qos, typ, n in (
        ("client", "write", 3), ("client", "read", 2),
        ("gold", "write", 1),
    ):
        for _ in range(n):
            op = tracker.create_op(
                "lint probe", op_type=typ, qos_class=qos
            )
            op.mark_event("started")
            op.finish()
    commit = LogHistogram()
    for v in (1e-4, 2e-3, 0.5):
        commit.add(v)
    lines: list[str] = []
    series = [
        (
            {
                "ceph_daemon": "osd.0",
                "qos_class": key.split(".")[1],
                "op_type": key.split(".")[2],
            },
            snap,
        )
        for key, snap in sorted(
            tracker.histogram_perf_entries().items()
        )
    ]
    lines.extend(
        histogram_exposition_lines(
            "ceph_osd_op_latency_seconds",
            "op completion latency by qos class and op type",
            series,
        )
    )
    lines.extend(
        histogram_exposition_lines(
            "ceph_daemon_commit_lat_hist_seconds",
            "commit latency",
            [({"ceph_daemon": "osd.0"}, commit.snapshot())],
        )
    )
    # a real flight-recorder stage histogram through the same
    # renderer: commit one dispatch on a private profiler and render
    # its sync-latency distribution as the exporter would
    from ceph_tpu.ops.kernel_stats import KernelStats
    from ceph_tpu.ops.profiler import DispatchProfiler

    dks = KernelStats()
    dprof = DispatchProfiler(capacity=8, ks=dks)
    with dprof.dispatch("crc32c", backend="jax") as dp:
        dp.set_ops(1)
        with dp.stage("sync"):
            pass
    snap = dks.dump().get("l_tpu_dispatch_sync_lat_hist")
    if not isinstance(snap, dict) or "bounds" not in snap:
        return [
            "dispatch sync lat_hist dump is not a histogram "
            f"snapshot: {snap!r}"
        ]
    lines.extend(
        histogram_exposition_lines(
            "ceph_daemon_tpu_dispatch_sync_lat_seconds",
            "device dispatch sync-stage latency",
            [({"ceph_daemon": "osd.0"}, snap)],
        )
    )
    text = "\n".join(lines) + "\n"
    errors = check_prometheus_histograms(text)
    if "le=\"+Inf\"" not in text:
        errors.append("exporter output carries no +Inf bucket at all")
    return errors


# PG-stats plane families the pgmap renderer must emit (mgr/pgmap.py
# pgmap_exposition_lines — `ceph_pg_total` is deliberately ABSENT:
# the exporter already serves it from pg_summary, and a second
# emission would be a duplicate family)
PGMAP_FAMILIES = (
    "ceph_pg_degraded",
    "ceph_pg_misplaced",
    "ceph_pg_unfound",
    "ceph_pg_state",
    "ceph_pool_stored_bytes",
    "ceph_pool_objects",
)
# families other exporter paths own; the pgmap renderer must never
# emit them (cross-set collision = duplicate HELP/TYPE in /metrics)
PGMAP_RESERVED = ("ceph_pg_total", "ceph_pool_pg_num")


def product_pgmap_exposition() -> list[str]:
    """Render the pgmap + progress families through the REAL
    renderer (mgr/pgmap.py pgmap_exposition_lines) from a synthetic
    digest and lint the text: every family present exactly once with
    a HELP/TYPE pair, parseable samples, label-safe values, and no
    collision with the families the exporter serves elsewhere."""
    from ceph_tpu.mgr.pgmap import pgmap_exposition_lines

    digest = {
        "totals": {
            "objects": 24, "bytes": 49152, "degraded": 3,
            "misplaced": 1, "unfound": 0,
        },
        "pg_states": {"active+clean": 7, "active+degraded": 1},
        "pools": {
            1: {"name": "da\"ta", "objects": 24, "bytes": 49152},
            2: {"name": "rbd", "objects": 0, "bytes": 0},
        },
    }
    text = "\n".join(pgmap_exposition_lines(digest)) + "\n"
    errors: list[str] = []
    helped: dict[str, int] = {}
    typed: dict[str, str] = {}
    sampled: set[str] = set()
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        if line.startswith("# HELP "):
            fam = line.split()[2]
            helped[fam] = helped.get(fam, 0) + 1
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            typed[parts[2]] = parts[3] if len(parts) > 3 else ""
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(
                f"pgmap line {lineno}: unparseable sample {line!r}"
            )
            continue
        sampled.add(m.group("name"))
        try:
            float(m.group("value"))
        except ValueError:
            errors.append(
                f"pgmap line {lineno}: non-numeric value "
                f"{m.group('value')!r}"
            )
        raw = m.group("labels") or ""
        pos = 0
        while pos < len(raw):
            lm = _LABEL_PAIR_RE.match(raw, pos)
            if lm is None:
                errors.append(
                    f"pgmap line {lineno}: bad label syntax {raw!r}"
                )
                break
            if not _LABEL_NAME_RE.match(lm.group("k")):
                errors.append(
                    f"pgmap line {lineno}: bad label name "
                    f"{lm.group('k')!r}"
                )
            pos = lm.end()
    for fam in PGMAP_FAMILIES:
        if fam not in sampled:
            errors.append(f"pgmap family {fam} emitted no samples")
        if helped.get(fam, 0) != 1:
            errors.append(
                f"pgmap family {fam}: {helped.get(fam, 0)} HELP "
                "headers (want exactly 1)"
            )
        if typed.get(fam) != "gauge":
            errors.append(
                f"pgmap family {fam}: TYPE {typed.get(fam)!r} "
                "(want gauge)"
            )
    for fam in PGMAP_RESERVED:
        if fam in sampled or fam in typed:
            errors.append(
                f"pgmap renderer emits {fam}, which another "
                "exporter path owns (duplicate family in /metrics)"
            )
    return errors


def check_perf_counters(pc) -> list[str]:
    """Lint one PerfCounters set; returns human-readable errors."""
    from ceph_tpu.common.perf_counters import PERFCOUNTER_HISTOGRAM

    errors: list[str] = []
    seen: set[str] = set()
    for name, counter in pc._counters.items():
        where = f"{pc.name}.{name}"
        if name in seen:
            errors.append(f"{where}: duplicate counter name")
        seen.add(name)
        if counter.name != name:
            errors.append(
                f"{where}: registered under {counter.name!r}"
            )
        if not _NAME_RE.match(name.replace(".", "_")):
            errors.append(
                f"{where}: invalid Prometheus metric characters"
            )
        if counter.kind == PERFCOUNTER_HISTOGRAM and not list(
            counter.bucket_bounds
        ):
            errors.append(
                f"{where}: histogram with no bucket bounds"
            )
    if not _NAME_RE.match(pc.name.replace(".", "_")):
        errors.append(
            f"{pc.name}: set name has invalid Prometheus characters"
        )
    return errors


def product_counter_sets():
    """Every schema the product registers (import side effects force
    lazy groups into existence so the lint sees the real shape)."""
    from ceph_tpu.msg.faults import build_msgr_perf
    from ceph_tpu.msg.stack import build_stack_perf, default_workers
    from ceph_tpu.ops.kernel_stats import KernelStats
    from ceph_tpu.osd.daemon import build_osd_perf
    from ceph_tpu.osd.mapping import _build_perf as build_mapping_perf
    from ceph_tpu.osdc.objecter import build_objecter_perf
    from ceph_tpu.proc.supervisor import build_proc_perf
    from ceph_tpu.qa.thrasher import build_thrash_perf
    from ceph_tpu.rgw.index import build_rgw_perf
    from ceph_tpu.store.wal_store import build_wal_perf

    from ceph_tpu.ops.residency import ensure_counters

    ks = KernelStats()
    # force-register every group the instrumented modules use
    for group in ("ec_encode", "ec_decode", "gf_matmul",
                  "gf_bitmatrix", "crush"):
        ks.record(group)
    for suffix in ("pgs", "fallback_lanes", "host_ns", "host_overlapped_ns"):
        ks.counter("crush", suffix)
    # the packed encode's l_tpu_ec_fold_overlapped_ns and the packed
    # decode's l_tpu_ec_decode_packed_calls are declared by KernelStats
    # itself, beside the compile-cache pair (check_kernel_declared_counters)
    # residency + coalesced-encode families (ops/residency.py) join
    # the schema walk and the cross-set collision lint
    ensure_counters(ks)
    # flight-recorder family (ops/profiler.py) likewise
    from ceph_tpu.ops.profiler import ensure_dispatch_counters

    ensure_dispatch_counters(ks)
    # tracing-plane stage family (one triple a span name) likewise
    for name in STAGE_SPANS:
        ks.record_stage(name, 0, 0)
    return [
        build_osd_perf(0), build_mapping_perf(), ks.perf,
        build_msgr_perf("osd.0"),
        build_stack_perf(default_workers()),
        build_rgw_perf("rgw"),
        build_wal_perf(),
        build_proc_perf(),
        build_thrash_perf(),
        build_objecter_perf(),
    ]


def check_all(sets=None) -> list[str]:
    lint_events = sets is None
    sets = product_counter_sets() if sets is None else sets
    errors: list[str] = []
    cross: set[str] = set()
    for pc in sets:
        errors.extend(check_perf_counters(pc))
        for name in pc._counters:
            key = f"{pc.name}.{name}".replace(".", "_")
            if key in cross:
                errors.append(
                    f"{pc.name}.{name}: collides with another set "
                    "after exporter name-flattening"
                )
            cross.add(key)
    if lint_events:
        # product mode (no explicit sets): also lint the event-plane
        # and scrub-plane schemas the daemons really emit, and the
        # exporter's native histogram rendering
        errors.extend(product_event_samples())
        errors.extend(product_scrub_samples())
        errors.extend(check_scrub_counters())
        errors.extend(check_fault_counters())
        errors.extend(check_worker_counters())
        errors.extend(check_residency_counters())
        errors.extend(check_dispatch_counters())
        errors.extend(check_stage_counters())
        errors.extend(check_process_counters())
        errors.extend(check_kernel_declared_counters())
        errors.extend(check_proc_counters())
        errors.extend(check_thrash_counters())
        errors.extend(check_objecter_counters())
        errors.extend(check_recovery_counters())
        errors.extend(check_rgw_counters())
        errors.extend(check_wal_counters())
        errors.extend(product_histogram_exposition())
        errors.extend(product_pgmap_exposition())
    return errors


def main() -> int:
    errors = check_all()
    for err in errors:
        print(f"check_metrics: {err}", file=sys.stderr)
    if errors:
        print(f"check_metrics: {len(errors)} error(s)", file=sys.stderr)
        return 1
    print("check_metrics: all counter schemas clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
