"""``osd_subop_overlap`` (ISSUE 34): the summed per-peer ``sub_op_rtt``
spans over the ``sub_op_wait`` spans that hold them."""

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import harness  # noqa: E402

from test_stage_metrics import REMAP, WRITE, rehearsed  # noqa: E402,F401


@pytest.fixture(scope="module")
def read():
    return harness.load_reader("layer_metrics", "osd_subop_overlap")


@pytest.mark.parametrize(
    "rtt_ns, wait_ns, overlap",
    [
        (5 * 36_000_000, 5 * 36_000_000, 1.0),  # one after another
        (5 * 60_000_000, 60_000_000, 5.0),  # k+m-1 = 5, all at once
        (170_000_000, 60_000_000, 170 / 60),
    ],
)
def test_the_reader_divides_the_round_trips_by_the_wait(read, rtt_ns, wait_ns, overlap):
    run = {
        "counters": {
            "client.ops_done": 1, "l_stage_sub_op_rtt_ns": rtt_ns,
            "l_stage_sub_op_rtt_count": 5, "l_stage_sub_op_wait_ns": wait_ns,
        }
    }
    assert read(run) == pytest.approx(overlap)


@pytest.mark.parametrize(
    "counters",
    [
        {"client.ops_done": 10},  # no stage counter at all
        # the parent commit: a sub_op_wait and no sub_op_rtt
        {"client.ops_done": 10, "l_stage_sub_op_wait_ns": 1_830_000_000},
        {"client.ops_done": 10, "l_stage_sub_op_rtt_ns": 1_830_000_000},
    ],
    ids=["no_counters", "no_sub_op_rtt", "no_sub_op_wait"],
)
def test_the_reader_finds_nothing_without_both_spans(read, counters):
    assert read({"counters": counters}) is None


def test_a_window_that_opened_no_sub_op_wait_has_nothing_on_the_wire(read):
    """A window of reads in a program that has both names (the set-up's
    writes registered them): no division by zero, no sub-op, 0."""
    counters = {
        "client.ops_done": 10, "l_stage_sub_op_wait_ns": 0,
        "l_stage_sub_op_rtt_ns": 0,
    }
    assert read({"counters": counters}) == 0.0


def test_the_metric_is_the_write_cells_alone():
    (entry,) = [
        m for m in harness.load_benchmark()["per_layer"]
        if m["name"] == "osd_subop_overlap"
    ]
    assert entry == {
        "name": "osd_subop_overlap", "unit": "x", "better": "higher",
        "source": "program_span", "layer": "OSD op path",
        "moves": "client_MBps", "workloads": [WRITE],
    }


def test_a_rehearsed_write_window_reads_between_one_and_its_peers(rehearsed):  # noqa: F811
    """Through ``run.py --trace 1`` at the rehearsal's k=2 m=1: two
    peers a write, so at least the 1.0 of round trips in turn (each
    ``sub_op_rtt`` starts inside the wait, so a little under) and at
    most 2; the remap cell has no such line."""
    value = rehearsed[WRITE]["metrics"]["osd_subop_overlap"]
    assert value["unit"] == "x" and 0.9 <= value["value"] <= 2.0, value
    assert "osd_subop_overlap" not in rehearsed[REMAP]["metrics"]
