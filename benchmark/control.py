#!/usr/bin/env python3
"""The controls and planted faults that ``correct`` has to fail.

    python3 benchmark/control.py --workload <cell> --seed <n> --seconds <s> --fault <name>

Runs the cell as ``run.py`` does, and once set-up has passed breaks the
timed path underneath with the driver's ``fault_<name>``: ``control``
(the plain reference put in the program's place with one stated
guarantee broken), ``state_unchanged``, ``half_batch``,
``altered_answer``, as far as the driver has them.  The result line
must then read ``"correct": false``; the exit code is 0 when it does
and 3 when the fault went unseen.  The benchmark's own runs never come
here.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import pathlib
import sys
import time

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness, run as bench_run  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--fault", required=True)
    own, rest = p.parse_known_args(argv)
    fault = own.fault
    args = bench_run.parse_args(rest)
    undo = []

    def plant(driver):
        plant_fault = getattr(driver, f"fault_{fault}", None)
        if plant_fault is None:
            raise harness.BenchmarkError(
                f"driver {type(driver).__module__} has no fault {fault!r}"
            )
        undo.append(plant_fault())

    out = io.StringIO()
    try:
        with contextlib.redirect_stdout(out):
            rc = bench_run.run(args, time.perf_counter(), after_setup=plant)
    except harness.BenchmarkError as e:
        print(f"control: {e}", file=sys.stderr)
        return 1
    finally:
        for restore in undo:
            restore()
        sys.stdout.write(out.getvalue())
    if rc != 0:
        return rc
    last = json.loads(out.getvalue().strip().splitlines()[-1])
    seen = last["correct"] is False
    print(f"control: fault {fault!r} {'seen' if seen else 'NOT SEEN'}", file=sys.stderr)
    return 0 if seen else 3


if __name__ == "__main__":
    sys.exit(main())
