"""Distributed tracing — spans with parent ids across daemons (the
blkin/ZTracer seat, src/common/zipkin_trace.h + blkin's span model).

The repo already carries trace ids on every sub-op message
(msg/message.py MOSDRepOp.trace / MECSubWrite.trace, stamped with the
client reqid) but nothing ever collected them: dump_historic_ops on
two daemons could be joined by hand and that was the whole story.
This module is the missing collection plane:

- ``Span`` — one timed stage on one daemon: (trace_id, span_id,
  parent_id, daemon, name, start/end, tags, events).  The trace id is
  the client reqid, exactly the id the wire already carries.
- ``Tracer`` — per-daemon span factory + bounded buffer of finished
  spans.  ``dump_traces`` serves the buffer over the admin socket
  (the `dump_historic_ops`-shaped local view); ``drain`` hands
  batches to the MMgrReport push so the mgr ``tracing`` module can
  assemble one logical op's spans from DIFFERENT daemons into a
  single tree.
- ambient context — a thread-local (tracer, span) stack so deep
  layers (stores, codecs) open child spans without threading a
  tracer parameter through every signature, the same trick
  store/remote.py's ``trace_context`` plays for sub-op trace ids.

Span buffers are bounded (drop-oldest) — tracing must never be the
thing that OOMs a daemon.

One clock: every stamp is ``time.perf_counter()`` — monotonic, and the
clock of the OSD's ``perf.tinc`` walls and of the flight recorder — so
a duration is the difference of two such stamps.  The wall
``start``/``end`` that ``dump_traces`` and ``assemble_tree`` sort by
are those stamps plus one process-constant offset.  An interval that
begins on one thread and ends on another (a queue wait), or before
its trace id is known (a frame read), is recorded whole from its two
stamps (``Tracer.record``).

The buffer is the collection plane's, and a tracer whose ``buffered``
is off (``tracing_enabled`` false on a daemon; a host with no surface
that would serve one) builds no dict and keeps nothing.  Two views
ride every span whether it is buffered or not:

- a span entered as a context manager also enters
  ``jax.profiler.TraceAnnotation("ceph:<name>")``, so a profiler
  session holds the program's host work on the profiler's own clock
  (jax is only looked up, never imported, here, and the class is
  kept once found; with no session the annotation is a TraceMe
  no-op).  Cross-thread waits are not mirrored: a gap is labelled by
  what the host was doing;
- on completion a span hands its name, duration and SELF time (the
  duration less the child spans opened on its thread while it was
  ambient) to the process-wide ``l_stage_<name>_*`` counters, where
  the device plane has attached its counter set
  (``ops/kernel_stats.py``).  A span of a name that set declares
  (``RUSAGE_STAGES``), entered as a context manager and finished on
  the thread that entered it, also hands over what its thread used
  between the two, from one ``getrusage(RUSAGE_THREAD)`` at each end:
  its CPU time (``ru_utime + ru_stime``, which the kernel moves a
  scheduler tick at a time, so a window's sum is right and one short
  span's is not) and its voluntary context switches (``ru_nvcsw``).
  The call keeps the interpreter's lock: reading the count is no
  hand-over.
"""

from __future__ import annotations

import contextlib
import itertools
import os
import resource
import sys
import threading
import time
from collections import deque

_now = time.perf_counter
# monotonic stamp + _WALL = wall time, for the surfaces that sort
# spans of different daemons (one offset per process, never re-read)
_WALL = time.time() - time.perf_counter()

# role ranks used by the mgr's cross-daemon tree assembly: a span
# with no resolvable parent attaches under the nearest earlier span
# of a lower rank (client root <- primary op <- replica/shard subop)
ROLE_CLIENT = "client"
ROLE_PRIMARY = "primary"
ROLE_REPLICA = "replica"
ROLE_SHARD = "shard"
ROLE_RANK = {ROLE_CLIENT: 0, ROLE_PRIMARY: 1, ROLE_REPLICA: 2, ROLE_SHARD: 2}

_ambient = threading.local()  # .stack: list[(Tracer, Span)]


# span and trace ids: a random prefix per process and a counter (no
# system call a span)
_ID_PREFIX = os.urandom(4).hex()
_id_seq = itertools.count(1)


def _new_id() -> str:
    return f"{_ID_PREFIX}{next(_id_seq):06x}"


def _thread_usage() -> tuple[float, int]:
    """This thread's CPU seconds and voluntary context switches so far."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return ru.ru_utime + ru.ru_stime, ru.ru_nvcsw


class Span:
    """One timed stage; finished spans become plain dicts in the
    tracer's buffer (the wire/admin-socket shape)."""

    __slots__ = (
        "_tracer", "trace_id", "span_id", "parent_id", "daemon",
        "name", "role", "t0", "t1", "tags", "events", "_done",
        "_parent", "_child_s", "_mirror", "_usage0",
    )

    def __init__(
        self,
        tracer: "Tracer",
        name: str,
        trace_id: str,
        parent_id: str = "",
        role: str = "",
        tags: dict | None = None,
        start: float | None = None,
        parent: "Span | None" = None,
    ):
        self._tracer = tracer
        self.trace_id = trace_id
        self.span_id = _new_id()
        self.parent_id = parent_id
        self.daemon = tracer.daemon
        self.name = name
        self.role = role
        self.t0 = _now() if start is None else start
        self.t1 = 0.0
        self.tags = dict(tags or {})
        self.events: list[tuple[float, str]] = []
        self._done = False
        # the ambient span this one was opened under, on its thread:
        # its self time is what this span's duration comes off
        self._parent = parent
        self._child_s = 0.0
        self._mirror = None
        # (thread id, its CPU seconds, its voluntary switches) at
        # enter, for a RUSAGE_STAGES name
        self._usage0 = None

    # wall-clock views of the monotonic stamps
    @property
    def start(self) -> float:
        return self.t0 + _WALL

    @property
    def end(self) -> float:
        return self.t1 + _WALL if self._done else 0.0

    @property
    def duration(self) -> float:
        return (self.t1 if self._done else _now()) - self.t0

    def mark_event(self, event: str) -> None:
        self.events.append((_now(), event))

    def set_tag(self, key: str, value) -> None:
        self.tags[key] = value

    def finish(self, end: float | None = None) -> None:
        if self._done:
            return
        self._done = True
        self.t1 = _now() if end is None else end
        usage = None
        if self._usage0 is not None:
            thread, cpu_s, switches = self._usage0
            # another thread's usage is not this span's
            if thread == threading.get_ident():
                cpu_now, switches_now = _thread_usage()
                usage = (int((cpu_now - cpu_s) * 1e9), switches_now - switches)
        if self._parent is not None:
            self._parent._child_s += self.t1 - self.t0
        self._tracer._complete(self, usage)

    def __enter__(self) -> "Span":
        _push(self._tracer, self)
        annotation = _annotation or _profiler_annotation()
        if annotation is not None:
            self._mirror = annotation("ceph:" + self.name)
            self._mirror.__enter__()
        if self.name in _rusage_stages:
            self._usage0 = (threading.get_ident(), *_thread_usage())
        return self

    def __exit__(self, exc_type, *exc) -> bool:
        if exc_type is not None:
            self.mark_event(f"exception: {exc_type.__name__}")
        if self._mirror is not None:
            self._mirror.__exit__(None, None, None)
            self._mirror = None
        _pop(self)
        self.finish()
        return False

    def dump(self) -> dict:
        return {
            "trace_id": self.trace_id,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "daemon": self.daemon,
            "name": self.name,
            "role": self.role,
            "start": self.start,
            "end": self.end or _now() + _WALL,
            "duration": self.duration,
            "tags": dict(self.tags),
            "events": [
                {"time": t + _WALL, "event": e} for t, e in self.events
            ],
        }


class _NullSpan:
    """No ambient tracer: ``span()`` still returns a context manager
    so instrumented code needs no conditionals."""

    __slots__ = ()

    def mark_event(self, event: str) -> None:
        pass

    def set_tag(self, key: str, value) -> None:
        pass

    def finish(self, end: float | None = None) -> None:
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NULL_SPAN = _NullSpan()


class Tracer:
    """Per-daemon span factory + bounded finished-span buffer."""

    def __init__(
        self, daemon: str, max_spans: int = 2048, buffered: bool = True
    ):
        self.daemon = daemon
        # off: finished spans feed the stage counters and the
        # profiler mirror only (no dict, nothing kept); a daemon
        # follows its tracing_enabled option with this
        self.buffered = buffered
        self._lock = threading.Lock()
        self._buffer: deque[dict] = deque(maxlen=max_spans)
        self._seq = itertools.count()
        self.spans_started = 0
        self.spans_dropped = 0  # buffer overwrites (drop-oldest)

    def start_span(
        self,
        name: str,
        trace_id: str = "",
        parent_id: str = "",
        role: str = "",
        tags: dict | None = None,
    ) -> Span:
        """New span; with no explicit trace/parent it continues the
        ambient span's trace (child) or starts a fresh trace (root)."""
        amb = current_span()
        if not trace_id:
            if isinstance(amb, Span):
                trace_id = amb.trace_id
            else:
                trace_id = ambient_trace_id() or _new_id()
        parent = None
        if not parent_id and isinstance(amb, Span) and (
            amb.trace_id == trace_id
        ):
            parent_id = amb.span_id
            parent = amb
        with self._lock:
            self.spans_started += 1
        return Span(
            self, name, trace_id, parent_id, role, tags, parent=parent
        )

    def record(
        self,
        name: str,
        trace_id: str,
        start: float,
        end: float | None = None,
        role: str = "",
        tags: dict | None = None,
    ) -> None:
        """One finished interval from explicit ``perf_counter`` stamps
        (``end`` = now): for code that cannot hold a span open — the
        messenger's coroutines, a wait whose trace id arrives with
        the message.  Never ambient, never mirrored, no self-time
        link; a no-op without a trace id."""
        if not trace_id:
            return
        with self._lock:
            self.spans_started += 1
        Span(self, name, trace_id, "", role, tags, start).finish(end)

    def _complete(self, span: Span, usage: tuple | None) -> None:
        if self.buffered:
            entry = span.dump()
            with self._lock:
                if len(self._buffer) == self._buffer.maxlen:
                    self.spans_dropped += 1
                self._buffer.append(entry)
        sink = _stage_sink
        if sink is not None:
            seconds = span.t1 - span.t0
            sink(
                span.name,
                int(seconds * 1e9),
                int(max(seconds - span._child_s, 0.0) * 1e9),
                usage,
            )

    # -- consumers ---------------------------------------------------------
    def drain(self, limit: int = 512) -> list[dict]:
        """Pop up to ``limit`` finished spans for an MMgrReport batch."""
        out: list[dict] = []
        with self._lock:
            while self._buffer and len(out) < limit:
                out.append(self._buffer.popleft())
        return out

    def dump_traces(self, trace_id: str = "") -> dict:
        """Admin-socket view of the (undrained) local buffer."""
        with self._lock:
            spans = [
                s for s in self._buffer
                if not trace_id or s["trace_id"] == trace_id
            ]
        return {
            "num_spans": len(spans),
            "spans_started": self.spans_started,
            "spans_dropped": self.spans_dropped,
            "spans": spans,
        }

    def register_admin_commands(self, admin_socket) -> None:
        admin_socket.register_command(
            "dump_traces",
            lambda args: self.dump_traces(str(args.get("trace", ""))),
            "show buffered trace spans (optional arg: trace)",
        )


# -- stage counters and the profiler mirror --------------------------------

# ``sink(name, ns, self_ns, usage)``: ops/kernel_stats.py attaches its
# process-wide counter set here when it is first built, with the span
# names whose thread usage it counts (``usage`` is then (cpu_ns,
# voluntary switches) or None, else always None).  A process that
# never loads the device plane has no such set, and no surface that
# would show one, and pays one ``is None`` a span.
_stage_sink = None
_rusage_stages: frozenset = frozenset()


def set_stage_sink(sink, rusage_stages: frozenset) -> None:
    global _stage_sink, _rusage_stages
    _stage_sink = sink
    _rusage_stages = rusage_stages


_annotation = None  # jax.profiler.TraceAnnotation, once jax is loaded


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` if this process has imported
    jax (never imported from here), else None.  Found once and kept:
    only a process still without jax looks again, one ``sys.modules``
    lookup a span entered."""
    global _annotation
    profiler = getattr(sys.modules.get("jax"), "profiler", None)
    _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def annotate(name: str, on: bool = True):
    """The profiler mirror alone, ``ceph:<name>``, for a section that
    is recorded from explicit stamps (``Tracer.record``) but runs
    without yielding on this thread; ``on`` false (the message belongs
    to no trace) gives the null context."""
    annotation = (_annotation or _profiler_annotation()) if on else None
    if annotation is None:
        return NULL_SPAN
    return annotation("ceph:" + name)


# -- ambient context --------------------------------------------------------


def _stack() -> list:
    s = getattr(_ambient, "stack", None)
    if s is None:
        s = _ambient.stack = []
    return s


def _push(tracer: Tracer, span: Span) -> None:
    _stack().append((tracer, span))


def _pop(span: Span) -> None:
    s = _stack()
    for i in range(len(s) - 1, -1, -1):
        if s[i][1] is span:
            del s[i]
            return


def current_span():
    """The innermost ambient span on this thread (or NULL_SPAN)."""
    s = _stack()
    return s[-1][1] if s else NULL_SPAN


def ambient_trace_id() -> str:
    """Trace id propagated by the transport (messenger dispatch) for
    handlers that run with no ambient span yet."""
    return getattr(_ambient, "trace_id", "")


@contextlib.contextmanager
def propagate(trace_id: str):
    """Install a wire-carried trace id as this thread's ambient —
    the msg/messenger.py dispatch hook: any span a handler opens
    without an explicit trace id joins the sender's trace."""
    prev = getattr(_ambient, "trace_id", "")
    _ambient.trace_id = trace_id
    try:
        yield
    finally:
        _ambient.trace_id = prev


def carry_wait(name: str, start: float) -> None:
    """A wait that began at ``start`` on another thread has just ended
    on this one, before the op it belongs to has a trace id: keep
    (name, start, now) on the thread for whoever mints the id."""
    _ambient.wait = (name, start, _now())


def take_wait() -> tuple[str, float, float] | None:
    """The carried wait of this thread, once (None if there is none)."""
    wait = getattr(_ambient, "wait", None)
    _ambient.wait = None
    return wait


def current_tracer() -> Tracer | None:
    s = _stack()
    return s[-1][0] if s else None


def span(name: str, tags: dict | None = None, role: str = ""):
    """Child span of the ambient span — a no-op without one.  The
    store layers use this so their per-stage spans ride whichever
    daemon op is executing above them, without API changes."""
    tracer = current_tracer()
    if tracer is None:
        return NULL_SPAN
    return tracer.start_span(name, role=role, tags=tags)


# stages whose time has a reader whoever the caller is (the EC seam's
# host copies): with no daemon above them their spans are roots here
# -- nothing is kept, the stage counters and the profiler mirror see
# them all the same
_STAGE_TRACER = Tracer("stage", buffered=False)


def stage(name: str, tags: dict | None = None):
    """Child span of the ambient span as :func:`span` gives it, and
    without one (a tool, the benchmark's plugin driver) a root span of
    a process-wide unbuffered tracer, so ``l_stage_<name>_*`` and the
    ``ceph:<name>`` mirror count the stage under every caller."""
    tracer = current_tracer() or _STAGE_TRACER
    return tracer.start_span(name, tags=tags)


# -- cross-daemon tree assembly (shared by the mgr tracing module) ----------


def assemble_tree(spans: list[dict]) -> list[dict]:
    """Spans (from ANY number of daemons) of one trace → span tree.

    Parent resolution: an explicit parent_id wins when that span is
    present; otherwise the span attaches under the nearest
    earlier-starting span with a strictly lower role rank (client 0 <
    primary 1 < replica/shard 2) — the cross-daemon links the wire
    does not carry.  Unresolvable spans become roots."""
    by_id = {s["span_id"]: dict(s, children=[]) for s in spans}
    nodes = sorted(by_id.values(), key=lambda s: s["start"])
    roots: list[dict] = []
    for node in nodes:
        parent = by_id.get(node["parent_id"])
        if parent is None or parent is node:
            rank = ROLE_RANK.get(node["role"], 99)
            best = None
            for cand in nodes:
                if cand is node or cand["start"] > node["start"]:
                    continue
                crank = ROLE_RANK.get(cand["role"], 99)
                if crank < rank and (
                    best is None or cand["start"] >= best[0]
                ):
                    best = (cand["start"], cand)
            parent = best[1] if best else None
        if parent is None:
            roots.append(node)
        else:
            parent["children"].append(node)
    return roots
