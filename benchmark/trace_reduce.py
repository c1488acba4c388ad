"""From a profiler trace (``.xplane.pb``) to the device's busy and idle
time, device time by operation, and the longest idle gaps with what
the host was doing in each.

The traced window is the span of the host event ``bench:window`` that
the harness opens around it; device events are clipped to it.  Busy is
the union of the intervals in which an operation ran on the device,
averaged over the device planes that ran anything.  A gap is labelled
with the innermost ``bench:*`` host annotation open at its middle, or
``unattributed``.  Read with nothing but JAX
(``jax.profiler.ProfileData``).
"""

from __future__ import annotations

import pathlib
import re

WINDOW = "bench:window"
PREFIX = "bench:"
# lines of a device plane that repeat, at a coarser grain, what the
# operation lines already hold
COARSE_LINES = ("Steps", "XLA Modules", "Framework Name Scope", "Source code")


_HLO = re.compile(r"^(%[\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])?")
_DETAIL = re.compile(r'(?:custom_call_target="|kind=)([\w.\-]+)')


def short_name(name: str) -> str:
    """An HLO instruction's text cut to its name, result type and kind
    (``%fusion.13 u8[2,1048576] kOutput``); any other name as it is,
    to 96 characters."""
    m = _HLO.match(name)
    if not m:
        return name[:96]
    detail = _DETAIL.search(name)
    parts = [m.group(1), m.group(2) or "", detail.group(1) if detail else ""]
    return " ".join(p for p in parts if p)[:96]


def find_xplane(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path):
    """Planes of a trace as plain data:
    {plane: {line: [(name, start_ns, end_ns), ...]}}."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes: dict[str, dict[str, list]] = {}
    for plane in data.planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = float(ev.start_ns)
                events.append((ev.name, start, start + float(ev.duration_ns)))
    return planes


def union(intervals) -> list[tuple[float, float]]:
    """Sorted, merged intervals."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(a, b) for a, b in merged]


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CUSTOM" not in name.upper()


def device_events(lines: dict) -> list:
    """A device plane's operation events: the "XLA Ops" line where
    there is one, else every line that is not a coarser summary."""
    if "XLA Ops" in lines:
        return list(lines["XLA Ops"])
    return [
        ev
        for name, events in lines.items()
        if name not in COARSE_LINES
        for ev in events
    ]


def reduce(planes: dict, top: int = 10) -> dict:
    """busy_s, window_s, idle share, device seconds by operation name
    and the longest idle gaps of one loaded trace."""
    host = []  # (name, start, end) of the benchmark's own annotations
    for pname, lines in planes.items():
        if is_device_plane(pname):
            continue
        for events in lines.values():
            host.extend(ev for ev in events if ev[0].startswith(PREFIX))
    windows = [ev for ev in host if ev[0] == WINDOW]
    if not windows:
        raise ValueError(f"the trace has no {WINDOW} event")
    w0, w1 = windows[0][1], windows[0][2]
    window_ns = w1 - w0
    if window_ns <= 0:
        raise ValueError("the traced window has no length")

    by_op: dict[str, float] = {}
    busy_per_plane = []
    all_busy = []
    for pname, lines in planes.items():
        if not is_device_plane(pname):
            continue
        clipped = []
        for name, start, end in device_events(lines):
            start, end = max(start, w0), min(end, w1)
            if end > start:
                clipped.append((start, end))
                by_op[name] = by_op.get(name, 0.0) + (end - start)
        merged = union(clipped)
        if merged:
            busy_per_plane.append(sum(b - a for a, b in merged))
            all_busy.extend(merged)
    busy_ns = sum(busy_per_plane) / len(busy_per_plane) if busy_per_plane else 0.0

    # gaps: where no device plane ran anything
    gaps = []
    cursor = w0
    for start, end in union(all_busy):
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    if cursor < w1:
        gaps.append((cursor, w1))
    labels = [ev for ev in host if ev[0] != WINDOW]
    by_label: dict[str, float] = {}
    for start, end in gaps:
        mid = (start + end) / 2
        open_now = [ev for ev in labels if ev[1] <= mid < ev[2]]
        label = (
            min(open_now, key=lambda ev: ev[2] - ev[1])[0][len(PREFIX):]
            if open_now
            else "unattributed"
        )
        by_label[label] = by_label.get(label, 0.0) + (end - start)

    def ranked(table):
        rows = sorted(table.items(), key=lambda kv: -kv[1])[:top]
        return [[short_name(name), ns / 1e9] for name, ns in rows]

    return {
        "busy_s": busy_ns / 1e9,
        "window_s": window_ns / 1e9,
        "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
        "device_planes": len(busy_per_plane),
        "device_ops": ranked(by_op),
        "idle_gaps": ranked(by_label),
    }


def reduce_dir(trace_dir) -> dict:
    return reduce(load(find_xplane(trace_dir)))
