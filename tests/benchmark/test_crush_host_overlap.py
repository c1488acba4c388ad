"""``crush_host_overlap_pct`` (ISSUE 37): the share of a remap's host
work on fetched parts that ran with a later part on the device —
``l_tpu_crush_host_overlapped_ns`` over ``l_tpu_crush_host_ns``."""

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

from benchmark import control, harness  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

from test_benchmark import _tiny  # noqa: E402

REMAP = "crush_10k.remap_1m"
METRIC = "crush_host_overlap_pct"


@pytest.fixture(scope="module")
def read():
    return harness.load_reader("layer_metrics", METRIC)


@pytest.mark.parametrize(
    "host_ns, overlapped_ns, pct",
    [
        (16 * 14_000_000, 15 * 14_000_000, 93.75),  # 15 of 16 even parts
        (40_000_000, 0, 0.0),  # one part: nothing issued ahead
        (200_000_000, 200_000_000, 100.0),
        (0, 0, 0.0),  # the counters and no remap in the window
    ],
)
def test_the_reader_is_the_overlapped_share_of_the_host_time(
    read, host_ns, overlapped_ns, pct
):
    counters = {
        "remaps": 2, "l_tpu_crush_host_ns": host_ns,
        "l_tpu_crush_host_overlapped_ns": overlapped_ns,
    }
    assert read({"counters": counters}) == pytest.approx(pct)


@pytest.mark.parametrize(
    "counters",
    [
        {"remaps": 2},  # the parent commit: neither counter
        {"remaps": 2, "l_tpu_crush_host_ns": 5},
        {"remaps": 2, "l_tpu_crush_host_overlapped_ns": 5},
    ],
    ids=["no_counters", "no_overlapped", "no_host"],
)
def test_the_reader_finds_nothing_without_both_counters(read, counters):
    assert read({"counters": counters}) is None


def test_the_metric_is_the_remap_cells():
    """The entry as ISSUE 37 wrote it, listed in cells of the remap
    driver alone: ``jaxmap.map_parts`` counts where ``OSDMapMapping``
    draws its parts."""
    bench = harness.load_benchmark()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == METRIC]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "mapping stages",
        "moves": "pg_mappings_per_s",
    }
    assert REMAP in entry["workloads"]
    traffic = {w["name"]: w["traffic"] for w in bench["workloads"]}
    for cell in entry["workloads"]:
        mix = harness._load_json("workloads", traffic[cell])
        assert mix["driver"] == "crush_remap", cell


@pytest.fixture()
def four_parts(monkeypatch):
    """``cut(pg_num)``: the remap cell cut to so many PGs on 64 OSDs,
    every one checked, and the kernel's part to a quarter of them:
    four parts a remap."""
    from ceph_tpu.crush import jaxmap

    def cut(pg_num: int = 1024) -> None:
        files = {
            ("configs", "crush_10k"): _tiny(
                "configs", "crush_10k", build="64:4", pool={"pg_num": pg_num}
            ),
            ("workloads", "remap_1m"): _tiny(
                "workloads", "remap_1m", check_sample=pg_num
            ),
        }
        original = harness._load_json
        monkeypatch.setattr(
            harness, "_load_json",
            lambda folder, name: files.get((folder, name))
            or original(folder, name),
        )
        monkeypatch.setattr(jaxmap, "CHUNK_LANES", pg_num // 4)

    return cut


def _last_line(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_a_rehearsed_remap_of_four_parts_is_correct_and_reads_a_share(four_parts):
    """Through ``run.py --trace 1``: every PG right against the plain
    reference with the tables written a part at a time, and the three
    parts that have one behind them overlapped."""
    four_parts()
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(
            ["--workload", REMAP, "--seed", str(2**31 + 37), "--seconds", "1",
             "--trace", "1", "--allow-cpu"],
            time.perf_counter(),
        )
    line = _last_line(out.getvalue())
    assert rc == 0 and line["correct"] is True and line["failed"] == 0
    assert line["compared"]["wrong_pgs"]["value"] == 0
    value = line["metrics"][METRIC]
    assert value["unit"] == "%" and 0 < value["value"] < 100, value
    for other in ("crush_stage_ms_per_remap", "crush_fixup_ms_per_remap",
                  "crush_issue_ms_per_remap", "crush_fetch_ms_per_remap"):
        assert line["metrics"][other]["value"] > 0, other


@pytest.mark.parametrize(
    "fault, pg_num",
    # the float64 control moves PGs only at a size (test_benchmark's
    # own control runs 16,384 too)
    [("control", 16384), ("altered_answer", 1024), ("state_unchanged", 1024)],
)
def test_the_part_safe_faults_bite_at_four_parts(four_parts, capsys, fault, pg_num):
    """``fault_half_batch`` indexes a whole pool's rows and is sound at
    one part only (PERF.md §7); the other three are seen at any."""
    four_parts(pg_num)
    rc = control.main(
        ["--workload", REMAP, "--seed", "7", "--seconds", "0.2", "--trace", "0",
         "--allow-cpu", "--fault", fault]
    )
    line = _last_line(capsys.readouterr().out)
    assert rc == 0 and line["correct"] is False
    assert line["compared"]["wrong_pgs"]["value"] > 0
