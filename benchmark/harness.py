"""The benchmark's harness: finds a cell's configuration, traffic mix,
driver and per-layer readers by the names in ``BENCHMARK.json``, keeps
the window's clock and arithmetic, watches for compilations and host
dispatches, and prints the result line.

Nothing here knows a cell by name: a new cell is an entry in
``BENCHMARK.json`` and, where its traffic is new, one file in
``workloads/``; a new configuration one file in ``configs/``; a new
per-layer metric one file in ``layer_metrics/``.
"""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import re
import sys

HERE = pathlib.Path(__file__).resolve().parent
REPO = HERE.parent

NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")

# flight-recorder kinds that have to run on the device, and the
# backends that mean they did not
DEVICE_KINDS = ("ec_encode", "ec_decode", "crush")
HOST_BACKENDS = ("cpu", "numpy")


class BenchmarkError(RuntimeError):
    """The run cannot stand as a measurement: no result line."""


# -- finding things by name ---------------------------------------------


def load_benchmark() -> dict:
    return json.loads((REPO / "BENCHMARK.json").read_text())


def _load_json(folder: str, name: str) -> dict:
    if not NAME_RE.match(name):
        raise BenchmarkError(f"{name!r} is not a name")
    path = HERE / folder / f"{name}.json"
    if not path.is_file():
        raise BenchmarkError(f"no {folder}/{name}.json")
    return json.loads(path.read_text())


def _load_module(folder: str, name: str):
    if not NAME_RE.match(name):
        raise BenchmarkError(f"{name!r} is not a name")
    path = HERE / folder / f"{name}.py"
    if not path.is_file():
        raise BenchmarkError(f"no {folder}/{name}.py")
    modname = "benchmark_%s_%s" % (folder, re.sub(r"\W", "_", name))
    spec = importlib.util.spec_from_file_location(modname, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[modname] = module
    spec.loader.exec_module(module)
    return module


def load_cell(bench: dict, workload: str) -> dict:
    """One cell: its entry, its configuration's file, its traffic mix
    and the metrics it reports."""
    entries = [w for w in bench["workloads"] if w["name"] == workload]
    if not entries:
        raise BenchmarkError(f"BENCHMARK.json has no workload {workload!r}")
    entry = entries[0]
    config = _load_json("configs", entry["config"])
    traffic = _load_json("workloads", entry["traffic"])

    def mine(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "name": workload,
        "chips": entry["chips"],
        "config_name": entry["config"],
        "config": config,
        "traffic": traffic,
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def load_driver(name: str):
    return _load_module("drivers", name).Driver


def load_reference(name: str):
    return _load_module("references", name)


def load_reader(folder: str, name: str):
    """A metric's reader, ``read(run) -> number | None``:
    ``end_to_end/<name>.py`` or ``layer_metrics/<name>.py``.  A quantity
    split by the end-to-end metric it moves (``device_idle_pct.crush``,
    ``device_idle_pct.ecpool``) is read by its stem's file
    (``device_idle_pct.py``) unless the full name has one of its own."""
    stem = name.split(".")[0]
    if stem != name and not (HERE / folder / f"{name}.py").is_file():
        name = stem
    return _load_module(folder, name).read


# -- the window's arithmetic --------------------------------------------


def percentile(values, q: float) -> float:
    """The q-th percentile by linear interpolation between the two
    nearest ranks (numpy's default), of any non-empty sequence."""
    ordered = sorted(values)
    if not ordered:
        raise ValueError("percentile of no values")
    pos = (len(ordered) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(ordered) - 1)
    return ordered[lo] + (ordered[hi] - ordered[lo]) * (pos - lo)


def client_numbers(ops) -> dict:
    """What a client saw of a window of ops, each a tuple
    (t_submit, t_done, amount, ok): the amount acknowledged over the time
    from the first submit to the last completion, and the latency of
    every op — one that failed counts its whole wait and no bytes.
    ``amount`` is in the driver's own unit (bytes, mappings)."""
    if not ops:
        raise BenchmarkError("the window completed no op")
    t_first = min(op[0] for op in ops)
    t_last = max(op[1] for op in ops)
    span = t_last - t_first
    if span <= 0:
        raise BenchmarkError("the window has no length")
    latencies = [(op[1] - op[0]) * 1e3 for op in ops]
    return {
        "span_s": span,
        "amount": sum(op[2] for op in ops if op[3]),
        "p50_ms": percentile(latencies, 50),
        "p95_ms": percentile(latencies, 95),
        "attempted": len(ops),
        "failed": sum(1 for op in ops if not op[3]),
    }


# -- what must not happen inside a window -------------------------------


class CompileClock:
    """Seconds JAX spent compiling, how many programs it compiled or
    fetched, and persistent-cache hits, from JAX's own monitoring
    events (as ``chip_smoke.py`` does)."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.programs = 0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.compile_s += secs
            self.programs += 1

    def _on_event(self, name, **_kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1

    def snapshot(self) -> dict:
        return {
            "compile_s": self.compile_s,
            "programs": self.programs,
            "cache_hits": self.cache_hits,
        }


class Dispatches:
    """Flight-recorder entries by ``kind:backend`` since construction,
    and per-kind totals; harvested by sequence number so a wrapped
    ring is noticed."""

    def __init__(self):
        from ceph_tpu.ops.profiler import dispatch_profiler

        self.prof = dispatch_profiler()
        self.counts: dict[str, int] = {}
        self.seq = max(
            (e["seq"] for e in self.prof.history()["entries"]), default=0
        )

    def harvest(self) -> dict[str, int]:
        entries = [
            e for e in self.prof.history()["entries"] if e["seq"] > self.seq
        ]
        if entries:
            if entries[0]["seq"] != self.seq + 1:
                raise BenchmarkError(
                    "dispatch ring wrapped between harvests: entries "
                    f"{self.seq + 1}..{entries[0]['seq'] - 1} lost"
                )
            self.seq = entries[-1]["seq"]
        for e in entries:
            key = f"{e['kind']}:{e['backend']}"
            self.counts[key] = self.counts.get(key, 0) + 1
        return dict(self.counts)

    def host_backend_entries(self) -> dict[str, int]:
        return {
            key: n
            for key, n in self.harvest().items()
            if key.split(":")[0] in DEVICE_KINDS
            and key.split(":")[1] in HOST_BACKENDS
        }


def flat_counters(driver) -> dict:
    """Every program counter a per-layer reader may diff over the
    window: flight-recorder totals as ``dispatch.<kind>.<field>``,
    the ``l_tpu_*`` kernel counters, and what the driver adds."""
    from ceph_tpu.ops.kernel_stats import kernel_stats
    from ceph_tpu.ops.profiler import dispatch_profiler

    out: dict[str, float] = {}
    for kind, tot in dispatch_profiler().totals().items():
        for field, val in tot.items():
            out[f"dispatch.{kind}.{field}"] = val
    for key, val in kernel_stats().dump().items():
        if isinstance(val, (int, float)):
            out[key] = val
    out.update(driver.counters())
    return out


def diff_counters(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after}


# -- the result line ----------------------------------------------------


def device_record(devices, memory_peak: int | None) -> dict:
    dev = devices[0]
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(devices),
        "memory_peak_bytes": memory_peak,
    }


def memory_peak_bytes(devices) -> int | None:
    peaks = []
    for dev in devices:
        stats = dev.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def verdict(compared: dict) -> bool:
    """``compared`` maps a short name to (number, limit): correct when
    every number is within its limit."""
    return all(value <= limit for value, limit in compared.values())


def print_compared(compared: dict) -> None:
    for name, (value, limit) in compared.items():
        mark = "ok" if value <= limit else "OVER"
        print(f"compared {name}: {value} (limit {limit}) {mark}", file=sys.stderr)
    sys.stderr.flush()


def result_line(
    *, correct, attempted, failed, metrics, units, device, compared,
    breakdown=None,
) -> str:
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {
            name: {"value": value, "unit": units[name]}
            for name, value in metrics.items()
        },
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = {
        name: {"value": value, "limit": limit}
        for name, (value, limit) in compared.items()
    }
    return json.dumps(line)
