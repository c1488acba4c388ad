"""What a span's thread used while it was open, and what the process
used over a window: the ``l_stage_<name>_cpu_ns`` counter of a
``RUSAGE_STAGES`` span and the ``_handovers`` one of a
``HANDOVER_STAGES`` span, the ``l_process_*`` counters of the kernel
set, and the benchmark's readers of them.  Only counts and ratios with
wide margins are asserted: the host may be loaded."""

from __future__ import annotations

import pathlib
import sys
import threading
import time

import pytest

from ceph_tpu.common import tracing
from ceph_tpu.ops.kernel_stats import (
    HANDOVER_STAGES,
    PROCESS_COUNTERS,
    RUSAGE_STAGES,
    KernelStats,
    kernel_stats,
)

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

STAGE = "fixup_up"  # a RUSAGE_STAGES name no other code runs here
OP = "osd_op"  # the HANDOVER_STAGES name: no daemon runs in this file


def _usage(name: str) -> tuple[int, int, int, int]:
    dump = kernel_stats().dump()
    return tuple(
        int(dump.get(f"l_stage_{name}_{suffix}", 0))
        for suffix in ("count", "ns", "cpu_ns", "handovers")
    )


def _since(name: str, before: tuple) -> tuple[int, ...]:
    return tuple(a - b for a, b in zip(_usage(name), before))


def test_a_span_that_waits_for_another_thread_counts_its_handovers():
    """Each round the span's thread hands a request to a peer thread
    and sleeps until the peer, a millisecond later, hands the answer
    back — the shape of every hand-over on an op's path, whoever holds
    what it waits for: a switch a round, and a span that mostly waits.
    (Handing over the interpreter itself to a spinning thread counts
    the same way, but how often the spinner is on a core to take it
    depends on the host's load.)"""
    assert OP in HANDOVER_STAGES <= RUSAGE_STAGES
    tracer = tracing.Tracer("rusage", buffered=False)
    ask, answer = threading.Event(), threading.Event()
    rounds = 100

    def peer():
        for _ in range(rounds):
            ask.wait(10)
            ask.clear()
            time.sleep(0.001)
            answer.set()

    helper = threading.Thread(target=peer, daemon=True)
    helper.start()
    before = _usage(OP)
    with tracer.start_span(OP, trace_id="R"):
        for _ in range(rounds):
            ask.set()
            answer.wait(10)
            answer.clear()
    helper.join(10)
    count, ns, cpu_ns, handovers = _since(OP, before)
    assert count == 1
    # a round in which this thread was off its core for the peer's
    # whole millisecond finds the answer there and does not sleep
    assert handovers >= rounds // 2
    assert cpu_ns <= ns / 2  # the CPU time moves a scheduler tick at a time


def test_reading_the_count_is_no_handover():
    """10,000 spans of the set, each reading its thread's usage twice,
    and nothing else wants the interpreter (the switch interval is
    stretched past the loop, so a stray thread of the host is not
    handed it either): no voluntary switch at all."""
    tracer = tracing.Tracer("rusage", buffered=False)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(30.0)
    try:
        time.sleep(0.01)  # a waiter of the old interval settles first
        before = _usage(OP)
        for _ in range(10_000):
            with tracer.start_span(OP, trace_id="Z"):
                pass
        count, _ns, _cpu_ns, handovers = _since(OP, before)
    finally:
        sys.setswitchinterval(interval)
    assert count == 10_000
    assert handovers == 0


def test_a_name_outside_the_set_reads_nothing_and_has_no_usage_counters():
    tracer = tracing.Tracer("rusage", buffered=False)
    with tracer.start_span("rusage_unlisted", trace_id="N") as span:
        time.sleep(0.002)
        assert span._usage0 is None
    dump = kernel_stats().dump()
    assert dump["l_stage_rusage_unlisted_count"] >= 1
    assert "l_stage_rusage_unlisted_cpu_ns" not in dump
    assert "l_stage_rusage_unlisted_handovers" not in dump


def test_a_name_of_the_set_without_a_switch_reader_counts_cpu_alone():
    """A fix-up span's CPU time has a reader, its switches none: it
    feeds ``_cpu_ns`` and has no ``_handovers`` counter."""
    assert STAGE in RUSAGE_STAGES - HANDOVER_STAGES
    tracer = tracing.Tracer("rusage", buffered=False)
    before = _usage(STAGE)
    with tracer.start_span(STAGE, trace_id="C"):
        t0 = time.thread_time()
        while time.thread_time() - t0 < 0.05:  # 50 ms on a core
            pass
    count, ns, cpu_ns, _handovers = _since(STAGE, before)
    assert count == 1
    assert 0.02e9 <= cpu_ns <= ns + 0.02e9  # a tick either way
    dump = kernel_stats().dump()
    assert f"l_stage_{STAGE}_cpu_ns" in dump
    assert f"l_stage_{STAGE}_handovers" not in dump


@pytest.mark.parametrize("how", ["finished_on_another_thread", "recorded"])
def test_a_span_not_finished_on_its_own_thread_feeds_no_usage(how):
    """The thread that finishes it is not the one that ran it (a span
    handed on), or nothing ran it at all (``record``, two stamps): its
    count and duration are fed, its usage is not."""
    tracer = tracing.Tracer("rusage", buffered=False)
    before = _usage(STAGE)
    if how == "recorded":
        tracer.record(STAGE, "X", time.perf_counter() - 0.01)
    else:
        span = tracer.start_span(STAGE, trace_id="X")
        with span:
            finisher = threading.Thread(
                target=lambda: (time.sleep(0.01), span.finish())
            )
            finisher.start()
            finisher.join(10)  # this thread sleeps: a switch it ran
    count, ns, cpu_ns, handovers = _since(STAGE, before)
    assert count == 1 and ns >= 0.01 * 1e9
    assert (cpu_ns, handovers) == (0, 0)


def test_process_usage_is_on_the_counter_set_from_construction():
    names = [name for name, _what in PROCESS_COUNTERS]
    assert names == [
        "l_process_cpu_ns", "l_process_handovers", "l_process_preemptions",
    ]
    ks = KernelStats()
    first = ks.dump()
    assert set(names) <= set(first) and set(names) <= set(kernel_stats().dump())
    t0 = time.thread_time()
    while time.thread_time() - t0 < 0.05:  # 50 ms on a core, however loaded
        pass
    time.sleep(0.01)  # this thread gives up its core: one voluntary switch
    second = ks.perf.dump()  # the admin socket's path reads it in too
    assert second["l_process_cpu_ns"] >= first["l_process_cpu_ns"] + 0.02e9
    assert second["l_process_handovers"] > first["l_process_handovers"]
    assert second["l_process_preemptions"] >= first["l_process_preemptions"]


# -- the benchmark's readers -------------------------------------------------

WRITE_PARENT = {
    "client.ops_done": 10,
    "l_stage_osd_op_count": 10, "l_stage_osd_op_ns": 2_000_000_000,
    "l_stage_osd_op_self_ns": 100_000_000,
    "l_stage_ec_prepare_ns": 300_000_000, "l_stage_ec_encode_ns": 500_000_000,
    "l_stage_txn_build_ns": 100_000_000,
}
WRITE_CHANGE = {
    **WRITE_PARENT,
    "l_process_cpu_ns": 30_000_000_000, "l_process_handovers": 15_000,
    "l_process_preemptions": 40,
    "l_stage_osd_op_cpu_ns": 500_000_000,
    "l_stage_ec_prepare_cpu_ns": 50_000_000,
    "l_stage_ec_encode_cpu_ns": 100_000_000,
    "l_stage_txn_build_cpu_ns": 50_000_000,
}
FIXUPS = ("exists", "upmap", "up", "affinity", "temp")
REMAP_PARENT = {
    "remaps": 2, **{f"l_stage_fixup_{s}_ns": 80_000_000 for s in FIXUPS},
}
REMAP_CHANGE = {
    **REMAP_PARENT,
    "l_process_cpu_ns": 9_000_000_000,
    **{f"l_stage_fixup_{s}_cpu_ns": 60_000_000 for s in FIXUPS},
}

READERS = [
    ("host_cores_busy.ecpool", WRITE_PARENT, WRITE_CHANGE, 1.5),
    ("host_cores_busy.crush", REMAP_PARENT, REMAP_CHANGE, 0.45),
    ("osd_op_cpu_pct", WRITE_PARENT, WRITE_CHANGE, 25.0),
    ("ec_seam_cpu_ms_per_op", WRITE_PARENT, WRITE_CHANGE, 20.0),
    ("crush_fixup_cpu_pct", REMAP_PARENT, REMAP_CHANGE, 75.0),
]


@pytest.mark.parametrize(
    "name, parent, change, want", READERS, ids=[r[0] for r in READERS]
)
def test_reader_is_silent_on_the_parent_and_reads_the_quotient(
    name, parent, change, want
):
    """A program without the counters (the parent of the change that
    added them) reads nothing; with them, the quotient the metric
    names, over a made-up window of 20 s."""
    read = harness.load_reader("layer_metrics", name)
    client = {"span_s": 20.0}
    assert read({"counters": dict(parent), "client": client}) is None
    assert read({"counters": dict(change), "client": client}) == pytest.approx(want)
    entry = next(
        m for m in harness.load_benchmark()["per_layer"] if m["name"] == name
    )
    assert entry["source"] in ("program_counter", "program_span")
