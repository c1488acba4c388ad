"""Device-kernel telemetry — the perf-counter plane for the TPU hot
paths (the l_osd_* PerfCounters idiom, src/common/perf_counters.h,
applied to the device kernels the paper pins its metrics on).

One process-global ``PerfCounters`` set named ``tpu_kernels`` holds a
counter group per kernel entry point:

    l_tpu_<group>_calls      u64   kernel invocations
    l_tpu_<group>_bytes_in   u64   input bytes handed to the device
    l_tpu_<group>_bytes_out  u64   output bytes produced
    l_tpu_<group>_lat        time  wall latency (device-sync bounded:
                                   callers time through the
                                   np.asarray/block_until_ready sync)

plus the compile-cache counters:

    l_tpu_compile_cache_hit / l_tpu_compile_cache_miss

and, because this set is the one every co-hosted daemon already
shares and reports, the stage counters that every finished tracing
span feeds (common/tracing.py ``Tracer._complete``), one pair per
span name, a third counter for the names whose self time has a reader
(``SELF_TIME_STAGES``), a fourth for the names whose thread CPU time
has one (``RUSAGE_STAGES``: a span entered as a context manager and
finished on its own thread; like ``_ns``, inclusive of its children)
and a fifth for those of them whose thread's switches have one
(``HANDOVER_STAGES``):

    l_stage_<name>_count      u64   spans of that name finished
    l_stage_<name>_ns         u64   their durations, summed (ns)
    l_stage_<name>_self_ns    u64   durations less same-thread children
    l_stage_<name>_cpu_ns     u64   the thread's CPU time (user + system)
    l_stage_<name>_handovers  u64   the thread's voluntary context
                                    switches: the times it gave up its
                                    core to wait (the interpreter's lock,
                                    a lock, a socket) and was woken again
                                    (0 under a kernel that counts none,
                                    as gVisor's)

and, beside ``l_stage_ec_fold_ns``, the share of it that a packed
encode spent with its whole upload issued (ops/ec_backend.py
``_packed_stripes``: all of it where the caller's buffer goes up in
stripe form, none where the fold comes first), declared with the set
and not by the calls that count into it:

    l_tpu_ec_fold_overlapped_ns   u64

and the whole-shard rebuilds that took the packed decode kernel
(ops/ec_backend.py ``matrix_shards``; the others take the bitplane
program), declared the same way:

    l_tpu_ec_decode_packed_calls  u64

and the whole process's usage, ``getrusage(RUSAGE_SELF)`` read at every
dump of the set and never on a hot path:

    l_process_cpu_ns          u64   CPU time of every thread (ns)
    l_process_handovers       u64   voluntary context switches
    l_process_preemptions     u64   involuntary ones: the scheduler took
                                    a runnable thread off its core

Groups registered by the instrumented modules: ``ec_encode`` /
``ec_decode`` (ec/stripe.py batched seam: one ``timed`` a seam
function), ``ec_repair`` (ec/stripe.py ``repair``: the counters
``l_tpu_ec_repair_{calls,helper_bytes,rebuilt_bytes}`` alone),
``gf_matmul`` (ops/ec_backend.py: a batched dispatch feeds
it from its flight-recorder entry's commit — ops/profiler.py
``dispatch(group=)`` — and the per-call region math through
``timed``), ``gf_bitmatrix`` (the same region math), ``crush``
(osd/mapping.py batched PG mapping, one ``timed`` a part of a pool,
with the extra counters ``l_tpu_crush_pgs`` (PGs mapped),
``l_tpu_crush_fallback_lanes`` (lanes the host oracle re-mapped) and,
from crush/jaxmap.py ``map_parts``, ``l_tpu_crush_host_ns`` /
``l_tpu_crush_host_overlapped_ns``: the host's time on the parts it
was handed, and the share of it spent with a later part on the device).

The set is a normal PerfCounters: daemons register it on their admin
socket collection (``perf dump``) and merge its dump into their
MMgrReport, so kernel telemetry flows through the existing
perf dump → MMgrReport → /metrics pipeline with no new plumbing.
Being process-global, co-hosted daemons (the test MiniCluster) share
one set — each reports the same process-wide kernel counters, the
same way they share the one JAX runtime.
"""

from __future__ import annotations

import re
import resource
import threading
import time

from ..common import tracing
from ..common.histogram import LATENCY_BUCKETS, LATENCY_MIN_S, log2_bounds
from ..common.perf_counters import (
    PERFCOUNTER_HISTOGRAM,
    PERFCOUNTER_TIME,
    PERFCOUNTER_U64,
    PerfCounters,
    _Counter,
)

# the shared log2 latency axis (common/histogram.py): every
# l_tpu_*_lat_hist uses it, so kernel latency histograms merge with
# the op-path ones under one bucket layout
# span names whose self time is counted: a name goes here with its
# reader (benchmark/layer_metrics/osd_op_self_ms_per_op.py)
SELF_TIME_STAGES = frozenset({"osd_op"})
# span names whose thread CPU time is counted, on the same rule: the op
# path's (benchmark/layer_metrics/osd_op_cpu_pct.py,
# ec_seam_cpu_ms_per_op.py) and a remap's host fix-ups
# (crush_fixup_cpu_pct.py)
RUSAGE_STAGES = frozenset({
    "osd_op", "ec_prepare", "ec_encode", "txn_build",
    "fixup_exists", "fixup_upmap", "fixup_up", "fixup_affinity",
    "fixup_temp",
})
# those whose thread's voluntary switches are counted too: an op's
# (perf dump; docs/OBSERVABILITY.md "Reading a slow write")
HANDOVER_STAGES = frozenset({"osd_op"})

_LAT_HIST_BOUNDS = log2_bounds(LATENCY_MIN_S, LATENCY_BUCKETS)

FOLD_OVERLAPPED_NS = "l_tpu_ec_fold_overlapped_ns"
DECODE_PACKED_CALLS = "l_tpu_ec_decode_packed_calls"
PROCESS_COUNTERS = (
    ("l_process_cpu_ns", "CPU time of the process's threads (ns)"),
    ("l_process_handovers", "voluntary context switches of the process"),
    ("l_process_preemptions", "involuntary context switches of the process"),
)


class _KernelCounters(PerfCounters):
    """The set, with the process's usage read in at each dump (perf
    dump, the MMgrReport, the benchmark's window)."""

    def dump(self) -> dict:
        ru = resource.getrusage(resource.RUSAGE_SELF)
        usage = (
            int((ru.ru_utime + ru.ru_stime) * 1e9), ru.ru_nvcsw, ru.ru_nivcsw,
        )
        with self._lock:
            for (name, _what), value in zip(PROCESS_COUNTERS, usage):
                self._counters[name].value = value
        return super().dump()


class KernelStats:
    def __init__(self, name: str = "tpu_kernels"):
        self.perf = _KernelCounters(name)
        self._lock = threading.Lock()
        self._cache_call_lock = threading.Lock()
        self._groups: set[str] = set()
        # span name -> its l_stage_* counters (count, ns, self_ns|None,
        # cpu_ns|None, handovers|None)
        self._stages: dict[str, tuple] = {}
        self._ensure_counter("l_tpu_compile_cache_hit", PERFCOUNTER_U64,
                             "device bitmatrix/table cache hits")
        self._ensure_counter("l_tpu_compile_cache_miss", PERFCOUNTER_U64,
                             "device bitmatrix/table cache misses")
        # pow2 shape bucketing buys compile-cache hits by padding:
        # the EC batch-axis zero pad, the CRUSH lane-0 repeat, the
        # crc filler rows.  This counts those device-visible bytes so
        # the trade stops being invisible.
        self._ensure_counter(
            "l_tpu_pad_bytes_wasted", PERFCOUNTER_U64,
            "device bytes padded in by pow2 shape bucketing"
        )
        self._ensure_counter(
            FOLD_OVERLAPPED_NS, PERFCOUNTER_U64,
            "ec_fold time spent with the call's upload issued (ns)"
        )
        self._ensure_counter(
            DECODE_PACKED_CALLS, PERFCOUNTER_U64,
            "whole-shard rebuilds run by the packed decode kernel"
        )
        for counter, what in PROCESS_COUNTERS:
            self._ensure_counter(counter, PERFCOUNTER_U64, what)

    def _ensure_counter(
        self, name: str, kind: str, desc: str, bounds: tuple = ()
    ) -> None:
        with self.perf._lock:
            if name not in self.perf._counters:
                c = _Counter(name, kind, desc, bucket_bounds=bounds)
                if kind == PERFCOUNTER_HISTOGRAM:
                    c.buckets = [0] * (len(bounds) + 1)
                self.perf._counters[name] = c

    def _ensure_group(self, group: str) -> None:
        with self._lock:
            if group in self._groups:
                return
            base = f"l_tpu_{group}"
            self._ensure_counter(
                f"{base}_calls", PERFCOUNTER_U64, f"{group} kernel calls"
            )
            self._ensure_counter(
                f"{base}_bytes_in", PERFCOUNTER_U64, f"{group} input bytes"
            )
            self._ensure_counter(
                f"{base}_bytes_out", PERFCOUNTER_U64, f"{group} output bytes"
            )
            self._ensure_counter(
                f"{base}_lat", PERFCOUNTER_TIME, f"{group} kernel latency"
            )
            # histogram variant of the sync-bounded latency: the avg
            # pair answers "mean", the log2 buckets answer "p99"
            self._ensure_counter(
                f"{base}_lat_hist",
                PERFCOUNTER_HISTOGRAM,
                f"{group} kernel latency distribution (log2 buckets)",
                bounds=_LAT_HIST_BOUNDS,
            )
            self._groups.add(group)

    # -- recording ---------------------------------------------------------
    def record(
        self,
        group: str,
        bytes_in: int = 0,
        bytes_out: int = 0,
        seconds: float = 0.0,
    ) -> None:
        self._ensure_group(group)
        base = f"l_tpu_{group}"
        self.perf.inc(f"{base}_calls")
        if bytes_in:
            self.perf.inc(f"{base}_bytes_in", int(bytes_in))
        if bytes_out:
            self.perf.inc(f"{base}_bytes_out", int(bytes_out))
        self.perf.tinc(f"{base}_lat", seconds)
        self.perf.hinc(f"{base}_lat_hist", seconds)

    def record_cache(self, hits: int, misses: int) -> None:
        if hits:
            self.perf.inc("l_tpu_compile_cache_hit", hits)
        if misses:
            self.perf.inc("l_tpu_compile_cache_miss", misses)

    def counted_cache_call(self, cached_fn, *args):
        """Call an ``functools.lru_cache``-wrapped function and record
        the hit/miss it produced.  The snapshot-call-snapshot runs
        under one lock so concurrent callers cannot double- or
        zero-count against the shared cache_info (misses — the
        expensive bitmatrix builds — serialize; hits are dict
        lookups, so the lock is cheap where it matters)."""
        with self._cache_call_lock:
            before = cached_fn.cache_info()
            out = cached_fn(*args)
            after = cached_fn.cache_info()
            self.record_cache(
                after.hits - before.hits, after.misses - before.misses
            )
        return out

    def record_stage(
        self, name: str, ns: int, self_ns: int, usage: tuple | None = None
    ) -> None:
        """One finished tracing span of ``name`` (the sink
        common/tracing.py calls, on every span of the process): count
        it and add its duration (and, for a ``SELF_TIME_STAGES``
        name, its self time; for a ``RUSAGE_STAGES`` one, the CPU time
        its thread used, where it read it — ``usage`` is ``(cpu_ns,
        handovers)`` — and for a ``HANDOVER_STAGES`` one its switches) to
        the ``l_stage_<name>_*`` counters, registered on first sight —
        one lock acquisition for all."""
        stage = self._stages.get(name)
        if stage is None:
            stage = self._ensure_stage(name)
        count, total, own, cpu, handovers = stage
        with self.perf._lock:
            count.value += 1
            total.value += ns
            if own is not None:
                own.value += self_ns
            if usage is not None and cpu is not None:
                cpu.value += usage[0]
                if handovers is not None:
                    handovers.value += usage[1]

    def _ensure_stage(self, name: str) -> tuple:
        base = "l_stage_" + re.sub(r"\W", "_", name)
        wanted = [
            (f"{base}_count", "spans finished", True),
            (f"{base}_ns", "span durations, summed (ns)", True),
            (
                f"{base}_self_ns",
                "span durations less same-thread child spans (ns)",
                name in SELF_TIME_STAGES,
            ),
            (
                f"{base}_cpu_ns",
                "CPU time of the span's thread over the span (ns)",
                name in RUSAGE_STAGES,
            ),
            (
                f"{base}_handovers",
                "voluntary context switches of the span's thread",
                name in HANDOVER_STAGES,
            ),
        ]
        for counter, what, kept in wanted:
            if kept:
                self._ensure_counter(
                    counter, PERFCOUNTER_U64, f"{name} {what}"
                )
        stage = tuple(
            self.perf._counters[n] if kept else None
            for n, _what, kept in wanted
        )
        with self._lock:
            self._stages[name] = stage
        return stage

    def record_pad(self, nbytes: int) -> None:
        """Count shape-bucketing pad bytes (device-visible bytes that
        carry no payload)."""
        if nbytes:
            self.perf.inc("l_tpu_pad_bytes_wasted", int(nbytes))

    def counter(self, group: str, suffix: str, kind=PERFCOUNTER_U64,
                desc: str = "", bounds: tuple = ()):
        """Register an extra per-group counter (e.g. crush's
        l_tpu_crush_pgs) and return its full name."""
        name = f"l_tpu_{group}_{suffix}"
        self._ensure_counter(name, kind, desc, bounds=bounds)
        return name

    def timed(self, group: str, bytes_in: int = 0):
        """Context manager timing one kernel call; the caller must
        sync the device inside the block (np.asarray /
        block_until_ready) so the latency is real, not dispatch."""
        return _KernelTimer(self, group, bytes_in)

    def dump(self) -> dict:
        return self.perf.dump()


class _KernelTimer:
    __slots__ = ("_ks", "_group", "_bytes_in", "bytes_out", "_t0")

    def __init__(self, ks: KernelStats, group: str, bytes_in: int):
        self._ks = ks
        self._group = group
        self._bytes_in = bytes_in
        self.bytes_out = 0

    def __enter__(self) -> "_KernelTimer":
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *exc) -> bool:
        if exc_type is None:
            self._ks.record(
                self._group,
                bytes_in=self._bytes_in,
                bytes_out=self.bytes_out,
                seconds=time.perf_counter() - self._t0,
            )
        return False


_instance: KernelStats | None = None
_instance_lock = threading.Lock()


def kernel_stats() -> KernelStats:
    """The process-global collector (like the one JAX runtime the
    kernels themselves share)."""
    global _instance
    if _instance is None:
        with _instance_lock:
            if _instance is None:
                _instance = KernelStats()
    return _instance


def _record_stage(
    name: str, ns: int, self_ns: int, usage: tuple | None
) -> None:
    kernel_stats().record_stage(name, ns, self_ns, usage)


# from the moment the device plane is loaded, every finished span of
# the process counts into the shared set
tracing.set_stage_sink(_record_stage, RUSAGE_STAGES)
