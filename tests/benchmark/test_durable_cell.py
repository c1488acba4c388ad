"""``ecpool_k4m2_durable.write_4m`` (ISSUE 36): the served pool of
``ecpool_k4m2`` on WAL-fronted BlockStore told to sync, and the five
readers of the "object store" layer — rehearsed through ``run.py
--allow-cpu --trace 1`` at the tiny sizes of the benchmark's own tests:
each gives a number behind a durable store and nothing on memstore or
on a program without its span."""

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

import test_benchmark  # noqa: E402
from test_benchmark import _tiny, tiny  # noqa: E402,F401

MEMSTORE, DURABLE = "ecpool_k4m2", "ecpool_k4m2_durable"
WRITE, CELL = f"{MEMSTORE}.write_4m", f"{DURABLE}.write_4m"
COUNTER_READERS = {"wal_records_per_barrier": "ops/barrier",
                   "wal_bytes_per_client_byte": "bytes/byte"}
SPAN_READERS = {"wal_barrier_ms_per_op": "wal_barrier",
                "wal_apply_ms_per_op": "wal_apply",
                "store_fsync_ms_per_op": "store_fsync"}
STORE_LAYER = sorted([*COUNTER_READERS, *SPAN_READERS])


def check_the_durable_cell_reports_the_store_layer(bench: dict) -> None:
    """The cell is the memstore cell's pool and traffic on the durable
    store, listed wherever the memstore cell is, and the "object store"
    layer's five metrics list it — whichever further cells a later PR
    has appended."""
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config_name"] == DURABLE
    assert cell["config"]["objectstore"] == "blockstore_wal"
    assert cell["config"]["reduced"] == []
    assert cell["traffic"] == harness.load_cell(bench, WRITE)["traffic"]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if WRITE in metric.get("workloads", []):
            assert CELL in metric["workloads"], metric["name"]
    entries = {m["name"]: m for m in bench["per_layer"] if m["layer"] == "object store"}
    assert set(STORE_LAYER) <= set(entries)
    for name in STORE_LAYER:
        entry = entries[name]
        assert CELL in entry["workloads"] and WRITE not in entry["workloads"]
        assert entry["source"] == (
            "program_counter" if name in COUNTER_READERS else "program_span")
        assert entry["moves"] in ("client_MBps", "op_p95_ms")


def test_the_durable_cell_reports_the_store_layer():
    check_the_durable_cell_reports_the_store_layer(harness.load_benchmark())
    # and with the next cell appended as the tests append it
    check_the_durable_cell_reports_the_store_layer(
        test_benchmark.with_a_further_cell(test_benchmark.BENCH, STORE_LAYER))


def test_the_deployment_is_the_memstore_pool_with_the_guarantee_moved():
    memstore = harness._load_json("configs", MEMSTORE)
    durable = harness._load_json("configs", DURABLE)
    for key in ("reference", "osds", "pg_num", "profile", "stripe_unit", "reduced"):
        assert durable[key] == memstore[key], key
    assert durable["guarantees"][:2] == memstore["guarantees"]
    (survives,) = durable["guarantees"][2:]
    assert "loss of every OSD process" in survives and "fsynced before its ack" in survives
    assert "durability" in memstore["not_claimed"]
    assert "host" in durable["not_claimed"] and "skipped" in durable["not_claimed"]
    (entry,) = [c for c in harness.load_benchmark()["configs"] if c["name"] == DURABLE]
    assert entry["source"] == durable["source"] and "bluestore" in entry["source"]


def _traced(capsys, cell):
    args = bench_run.parse_args(
        ["--workload", cell, "--seed", str(2**31 + 36), "--seconds", "1",
         "--trace", "1", "--allow-cpu"])
    assert bench_run.run(args, time.perf_counter()) == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.fixture
def tiny_durable(tiny):  # noqa: F811
    """The durable configuration's own file, cut as ``tiny`` cuts the
    memstore one; the five metrics listed for the memstore cell too, so
    that its line would hold whatever a reader gave there."""
    tiny[("configs", DURABLE)] = _tiny(
        "configs", DURABLE, osds=4, pg_num=8, profile={"k": 2, "m": 1})
    for metric in harness.load_benchmark()["per_layer"]:
        if metric["name"] in STORE_LAYER:
            metric["workloads"].append(WRITE)
    return tiny


def test_a_rehearsed_durable_window_reads_every_layer_of_the_store(tiny_durable, capsys):
    last = _traced(capsys, CELL)
    assert last["correct"] is True and last["failed"] == 0
    assert all(pair == {"value": 0, "limit": 0} for pair in last["compared"].values())
    assert {"lost_after_crash", "fsck_errors", "stores_unchecked"} <= set(last["compared"])
    metrics = last["metrics"]
    for name in STORE_LAYER:
        assert metrics[name]["value"] > 0, name
        assert metrics[name]["unit"] == COUNTER_READERS.get(name, "ms")
    # every host-read layer of the memstore cell, on this side of the store too
    cell = harness.load_cell(harness.load_benchmark(), WRITE)
    assert {m["name"] for m in cell["per_layer"] if m["source"] != "device_trace"} <= set(metrics)
    # k+m = 3 records an op, each a barrier of its own or a shared one
    assert 1.0 <= metrics["wal_records_per_barrier"]["value"] <= 3.0
    # (k+m)/k = 1.5 of shard bytes, and a little of attributes and log entries
    assert 1.5 <= metrics["wal_bytes_per_client_byte"]["value"] < 1.6
    # the fsyncs are paid inside the applies, which the commit waits for
    assert metrics["store_fsync_ms_per_op"]["value"] < metrics["wal_apply_ms_per_op"]["value"]
    assert metrics["wal_apply_ms_per_op"]["value"] < metrics["osd_commit_ms_per_op"]["value"]
    # and the memstore cell, in the same process, reads none of the five
    assert not set(STORE_LAYER) & set(_traced(capsys, WRITE)["metrics"])


def test_the_product_is_told_to_sync_and_does(tiny_durable, capsys):
    drivers = []
    args = bench_run.parse_args(["--workload", CELL, "--seed", "36", "--seconds", "0.5",
                                 "--allow-cpu"])
    assert bench_run.run(args, time.perf_counter(), after_setup=drivers.append) == 0
    capsys.readouterr()
    (driver,) = drivers
    assert len(driver.abandoned) == 4
    for osd in driver.abandoned:
        assert osd.store.sync is True and osd.store.inner.sync is True


@pytest.mark.parametrize("name", STORE_LAYER)
def test_a_store_reader_finds_nothing_where_there_is_nothing_to_read(name):
    read = harness.load_reader("layer_metrics", name)
    client = {"amount": 40 << 20}
    # a memstore window; a window that acknowledged nothing
    assert read({"counters": {"client.ops_done": 10}, "client": client}) is None
    assert read({"counters": {"client.ops_done": 0, "os_wal.barriers": 0},
                 "client": {"amount": 0}}) is None
    # the parent commit behind a durable store: the log's counters and no span
    parent = {"counters": {"client.ops_done": 10, "os_wal.appends": 60,
                           "os_wal.barriers": 50, "os_wal.append_bytes": 63 << 20,
                           "l_stage_store_commit_ns": 10**9},
              "client": client}
    if name in SPAN_READERS:
        assert read(parent) is None
        parent["counters"][f"l_stage_{SPAN_READERS[name]}_ns"] = 250_000_000
        assert read(parent) == pytest.approx(25.0)
    else:
        assert read(parent) == pytest.approx(
            {"wal_records_per_barrier": 1.2, "wal_bytes_per_client_byte": 1.575}[name])
