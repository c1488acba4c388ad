#!/usr/bin/env python3
"""The benchmark's one command: one cell, once.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (boot, data, warm-up, compilation) is timed as ``setup_s``; then
the cell's driver runs its window; then, with the window closed and the
device's memory peak read, the plain reference decides ``correct``.
``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
traces a short window of its own (the length is in the traffic mix's
``trace`` entry and on an earlier line) and prints the per-layer ones.
The last line of standard output is the result.  The run fails, and
prints no result, without a TPU of a known kind, when a device kind of
dispatch ran on a host backend, and when anything compiled inside the
window.  ``--allow-cpu`` (for the tests' rehearsal at tiny sizes)
lifts the first and prints no device metric.
"""

from __future__ import annotations

import os
import sys
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, peaks, trace_reduce  # noqa: E402


def parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--allow-cpu", action="store_true",
                   help="tests only: rehearse without a TPU")
    return p.parse_args(argv)


def note(**fields) -> None:
    """An earlier line of standard output (never the last)."""
    print(json.dumps(fields), flush=True)


def configure_compile_cache(jax) -> str:
    """The program's own rule for where the cache lives
    (``JAX_COMPILATION_CACHE_DIR`` where set, else one fixed path in
    the checkout); here every program is kept, however small or quick."""
    from ceph_tpu.common.compile_cache import configure_compile_cache as where

    cache = where()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache


def start_trace(jax, trace_dir) -> None:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)


def run(args, t_start: float, after_setup=None) -> int:
    """One run.  ``after_setup(driver)`` is ``control.py``'s way in: it
    breaks the timed path once set-up has passed; the benchmark's own
    runs pass none."""
    bench = harness.load_benchmark()
    cell = harness.load_cell(bench, args.workload)
    traffic, config = cell["traffic"], cell["config"]
    seconds = float(args.seconds if args.seconds is not None else bench["run_seconds"])
    max_units = None
    if args.trace:
        limits = traffic.get("trace", {})
        seconds = min(seconds, float(limits.get("seconds", seconds)))
        max_units = limits.get("units")

    # the flight recorder's ring holds a whole window
    os.environ.setdefault("CEPH_TPU_DISPATCH_RING", "1000000")
    import jax

    devices = jax.devices()
    dev = devices[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not args.allow_cpu:
        raise harness.BenchmarkError(
            f"needs a TPU, JAX found {len(devices)} x {dev.platform} "
            f"({dev.device_kind})"
        )
    chip_peaks = None
    if on_chip:
        if len(devices) != cell["chips"]:
            raise harness.BenchmarkError(
                f"{cell['name']} asks for {cell['chips']} chips, "
                f"JAX found {len(devices)}"
            )
        try:
            chip_peaks = peaks.peaks_for(dev.device_kind)
        except KeyError as e:
            raise harness.BenchmarkError(str(e)) from None
    cache_dir = configure_compile_cache(jax)
    clock = harness.CompileClock()
    disp = harness.Dispatches()

    driver_cls = harness.load_driver(traffic["driver"])
    reference = harness.load_reference(config["reference"])
    workdir = pathlib.Path(tempfile.mkdtemp(prefix="bench."))
    cwd = os.getcwd()
    os.chdir(workdir)  # unix-socket paths below it stay short
    driver = driver_cls(
        config, traffic, args.seed, pathlib.Path("cluster"),
        jax.profiler.TraceAnnotation, reference,
    )
    trace = None
    try:
        driver.setup()
        setup_s = time.perf_counter() - t_start
        note(phase="setup", workload=cell["name"], seed=args.seed,
             setup_s=setup_s, compile_cache=cache_dir, **clock.snapshot())
        if after_setup is not None:
            after_setup(driver)
        before = harness.flat_counters(driver)
        programs = clock.programs
        tracing = bool(args.trace) and on_chip
        if tracing:
            start_trace(jax, workdir / "trace")
        try:
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
                window = driver.window(seconds, max_units)
        finally:
            if tracing:
                jax.profiler.stop_trace()
        after = harness.flat_counters(driver)
        spans = driver.spans() if hasattr(driver, "spans") else []
        if clock.programs != programs:
            raise harness.BenchmarkError(
                f"{clock.programs - programs} programs compiled or were "
                "fetched inside the window: a shape was not warmed up"
            )
        host = disp.host_backend_entries()
        if host:
            raise harness.BenchmarkError(
                f"device kinds of dispatch ran on a host backend: {host}"
            )
        memory_peak = harness.memory_peak_bytes(devices)
        if tracing:
            trace = trace_reduce.reduce_dir(workdir / "trace")
            note(phase="trace", traced_window_s=trace["window_s"],
                 units=window["units"], device_planes=trace["device_planes"])
        t_check = time.perf_counter()
        compared = driver.check()
        note(phase="check", seconds=time.perf_counter() - t_check)
    finally:
        driver.close()
        os.chdir(cwd)
        shutil.rmtree(workdir, ignore_errors=True)

    client = harness.client_numbers(window["ops"])
    record = {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "setup_s": setup_s,
        "seconds": seconds,
        "ops": window["ops"],
        "units": window["units"],
        "client": client,
        "counters": harness.diff_counters(before, after),
        "spans": spans,
        "trace": trace,
        "peaks": chip_peaks,
    }
    wanted = cell["per_layer"] if args.trace else cell["end_to_end"]
    folder = "layer_metrics" if args.trace else "end_to_end"
    metrics, units = {}, {}
    for metric in wanted:
        if not on_chip and metric["source"] == "device_trace":
            continue
        value = harness.load_reader(folder, metric["name"])(record)
        if value is not None:
            metrics[metric["name"]] = value
            units[metric["name"]] = metric["unit"]

    device = harness.device_record(devices, memory_peak)
    breakdown = None
    if trace is not None:
        if trace["busy_s"] <= 0:
            raise harness.BenchmarkError(
                "no operation ran on the device in the traced window"
            )
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        breakdown = {
            "device_ops": trace["device_ops"],
            "idle_gaps": trace["idle_gaps"],
        }
    compared["failed_ops"] = (client["failed"], 0)
    harness.print_compared(compared)
    print(harness.result_line(
        correct=harness.verdict(compared), attempted=client["attempted"],
        failed=client["failed"], metrics=metrics, units=units,
        device=device, compared=compared, breakdown=breakdown,
    ), flush=True)
    return 0


def main(argv=None, t_start: float | None = None) -> int:
    args = parse_args(argv)
    try:
        return run(args, T_START if t_start is None else t_start)
    except harness.BenchmarkError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 1


if __name__ == "__main__":
    with contextlib.suppress(BrokenPipeError):
        sys.exit(main())
