#!/usr/bin/env python
"""Render a bench artifact's device-dispatch breakdown as per-kind
text tables, so a BENCH_rNN diff is human-readable instead of a JSON
stare (``python tools/profile_report.py bench_out.json``).

The flight recorder (ops/profiler.py) attributes every device
dispatch's wall time to transfer/compute/sync and carries batch
occupancy, pad waste from pow2 shape bucketing, and the
uploaded-vs-resident byte split; bench.py embeds one breakdown dict
per device section (``e2e_batched``/``recovery``/``ec_families``/
``crush``).  This tool finds every embedded breakdown in an artifact
(any depth — the layout may grow) and prints one table per section:

    section: e2e_batched  [backend=jax-tpu]
    kind        disp   occ  transfer  compute     sync  pad%  res%
    ec_encode     20  12.4    42.1ms   18.3ms    3.2ms   0.5  78.2
    ...

Reads stdin when no path is given, so it composes with shell diffs:
``jq .e2e_batched bench_out.json | python tools/profile_report.py``.
"""

from __future__ import annotations

import json
import sys

# the six contract keys every breakdown dict carries (bench satellite:
# they must emit on a JAX_PLATFORMS=cpu run too)
BREAKDOWN_KEYS = (
    "transfer_ms", "compute_ms", "sync_ms",
    "occupancy", "pad_waste_ratio", "resident_byte_ratio",
)

_COLS = (
    ("kind", 12), ("disp", 6), ("occ", 7), ("stripes/d", 10),
    ("transfer", 11), ("compute", 11), ("sync", 11),
    ("pad%", 7), ("res%", 7), ("hit%", 7),
)


def is_breakdown(node) -> bool:
    return isinstance(node, dict) and all(
        k in node for k in BREAKDOWN_KEYS
    )


def find_breakdowns(node, path="") -> list[tuple[str, dict]]:
    """Every embedded breakdown dict in the artifact, with its JSON
    path — depth-first so section order matches the file."""
    found: list[tuple[str, dict]] = []
    if is_breakdown(node):
        return [(path or "(root)", node)]
    if isinstance(node, dict):
        for k, v in node.items():
            found.extend(find_breakdowns(v, f"{path}.{k}" if path else k))
    elif isinstance(node, list):
        for i, v in enumerate(node):
            found.extend(find_breakdowns(v, f"{path}[{i}]"))
    return found


def _fmt_ms(v: float) -> str:
    return f"{float(v):.3f}ms"


def _fmt_pct(v: float) -> str:
    return f"{100.0 * float(v):.1f}"


def _row(cells) -> str:
    return "  ".join(
        str(c).ljust(w) if i == 0 else str(c).rjust(w)
        for i, ((_n, w), c) in enumerate(zip(_COLS, cells))
    ).rstrip()


def _kind_cells(name: str, d: dict) -> list[str]:
    lookups = d.get("compile_hits", 0) + d.get("compile_misses", 0)
    return [
        name,
        d.get("dispatches", 0),
        f"{float(d.get('occupancy', 0.0)):.1f}",
        f"{float(d.get('stripes_per_dispatch', 0.0)):.1f}",
        _fmt_ms(d.get("transfer_ms", 0.0)),
        _fmt_ms(d.get("compute_ms", 0.0)),
        _fmt_ms(d.get("sync_ms", 0.0)),
        _fmt_pct(d.get("pad_waste_ratio", 0.0)),
        _fmt_pct(d.get("resident_byte_ratio", 0.0)),
        (
            _fmt_pct(d.get("compile_hits", 0) / lookups)
            if lookups
            else "-"
        ),
    ]


def render_breakdown(path: str, bd: dict) -> str:
    lines = [
        f"section: {path}  [backend={bd.get('backend', '?')}]"
    ]
    header = _row([name for name, _w in _COLS])
    lines.append(header)
    lines.append("-" * len(header))
    kinds = bd.get("kinds") or {}
    for kind in sorted(kinds):
        lines.append(_row(_kind_cells(kind, kinds[kind])))
    if not kinds:
        lines.append("(no device dispatches recorded)")
    else:
        lines.append(_row(_kind_cells("TOTAL", bd)))
    return "\n".join(lines)


def render(artifact: dict) -> str:
    """The whole artifact → one table per embedded breakdown (empty
    string when the artifact predates the flight recorder)."""
    parts = [
        render_breakdown(path, bd)
        for path, bd in find_breakdowns(artifact)
    ]
    return "\n\n".join(parts)


def main(argv) -> int:
    if len(argv) > 1:
        with open(argv[1]) as f:
            artifact = json.load(f)
    else:
        artifact = json.load(sys.stdin)
    text = render(artifact)
    if not text:
        print(
            "profile_report: no dispatch breakdowns in this artifact "
            "(pre-flight-recorder bench?)",
            file=sys.stderr,
        )
        return 1
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
