"""The eleven per-layer metrics that read the tracing plane's stage
counters and the flight recorder's stages (ISSUE 26), rehearsed through ``run.py --allow-cpu --trace 1``
at the tiny sizes of the benchmark's own tests: each prints a number
in its own cell and in no other."""

import contextlib
import io
import json
import pathlib
import sys
import time

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

from test_benchmark import _tiny  # noqa: E402

WRITE, REMAP = "ecpool_k4m2.write_4m", "crush_10k.remap_1m"
STAGE_METRICS = {
    "client_aio_wait_ms_per_op": WRITE,
    "msgr_ms_per_op": WRITE,
    "osd_queue_wait_ms_per_op": WRITE,
    "osd_op_self_ms_per_op": WRITE,
    "osd_commit_ms_per_op": WRITE,
    "osd_subop_wait_ms_per_op": WRITE,
    "ec_seam_ms_per_op": WRITE,
    "ec_dispatch_host_ms_per_object": WRITE,
    "crush_issue_ms_per_remap": REMAP,
    "crush_fetch_ms_per_remap": REMAP,
    "crush_fixup_ms_per_remap": REMAP,
}


@pytest.fixture(scope="module")
def rehearsed():
    """The last line of one traced rehearsal of each cell, sizes cut as
    ``test_benchmark.tiny`` cuts them."""
    files = {
        ("configs", "ecpool_k4m2"): _tiny(
            "configs", "ecpool_k4m2", osds=4, pg_num=8,
            profile={"k": 2, "m": 1},
        ),
        ("configs", "crush_10k"): _tiny(
            "configs", "crush_10k", build="64:4", pool={"pg_num": 1024}
        ),
        ("workloads", "write_4m"): _tiny(
            "workloads", "write_4m", object_bytes=2 << 20, payload_pool=5,
            warm_ops=4, in_flight=4, check_sample=4,
        ),
        ("workloads", "remap_1m"): _tiny(
            "workloads", "remap_1m", check_sample=512
        ),
    }
    original = harness._load_json
    lines = {}
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(
            harness, "_load_json",
            lambda folder, name: files.get((folder, name))
            or original(folder, name),
        )
        for cell in (WRITE, REMAP):
            # pytest's own capture fixtures are function-scoped
            out = io.StringIO()
            with contextlib.redirect_stdout(out):
                rc = bench_run.main(
                    ["--workload", cell, "--seed", str(2**31 + 26),
                     "--seconds", "1", "--trace", "1", "--allow-cpu"],
                    time.perf_counter(),
                )
            assert rc == 0
            lines[cell] = json.loads(out.getvalue().strip().splitlines()[-1])
    return lines


def test_the_rehearsals_are_correct(rehearsed):
    for cell, line in rehearsed.items():
        assert line["correct"] is True and line["failed"] == 0, cell


@pytest.mark.parametrize("metric", sorted(STAGE_METRICS))
def test_a_stage_metric_prints_in_its_own_cell_only(rehearsed, metric):
    own = STAGE_METRICS[metric]
    entry = [m for m in harness.load_benchmark()["per_layer"] if m["name"] == metric]
    assert len(entry) == 1 and entry[0]["workloads"] == [own]
    for cell, line in rehearsed.items():
        if cell == own:
            value = line["metrics"][metric]
            assert value["unit"] == "ms" and value["value"] > 0, value
        else:
            assert metric not in line["metrics"]


def test_a_reader_finds_nothing_where_the_program_has_no_such_counter():
    """A run whose program has no such counter (the parent commit has
    no stage counter at all): every reader returns None and does not
    raise; so does one whose denominator is 0."""
    parent_run = {"counters": {"client.ops_done": 10, "remaps": 2}}
    for metric in STAGE_METRICS:
        assert harness.load_reader("layer_metrics", metric)(parent_run) is None
    no_ops = {
        "counters": {
            "client.ops_done": 0, "remaps": 0, "dispatch.crush.sync_s": 1.0,
            "dispatch.crush.compute_s": 1.0, "l_stage_msgr_send_ns": 5,
        }
    }
    for metric in STAGE_METRICS:
        assert harness.load_reader("layer_metrics", metric)(no_ops) is None


def test_the_crush_stage_readers_divide_the_recorders_stages():
    run = {
        "counters": {
            "remaps": 2, "dispatch.crush.compute_s": 0.07,
            "dispatch.crush.sync_s": 2.4,
        }
    }
    issue = harness.load_reader("layer_metrics", "crush_issue_ms_per_remap")
    fetch = harness.load_reader("layer_metrics", "crush_fetch_ms_per_remap")
    assert issue(run) == pytest.approx(35.0)
    assert fetch(run) == pytest.approx(1200.0)
