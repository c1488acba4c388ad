"""The plugin-boundary cells' tests (tier-1, CPU, tiny sizes): the codec
reference against itself and the program, a rehearsal of both
``ec_plugin_k8m3`` cells through ``run.py``, the controls and planted
faults, the 55-pattern decode window, and the new per-layer readers."""

import itertools
import json
import pathlib
import sys
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import control, harness, peaks  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

ENCODE = "ec_plugin_k8m3.encode_1m"
DECODE = "ec_plugin_k8m3.decode_2e_1m"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
ALL_PAIRS = list(itertools.combinations(range(11), 2))
NEW_READERS = (
    "ec_plugin_host_ms_per_call", "ec_plugin_upload_ms_per_call",
    "ec_plugin_fetch_ms_per_call", "ec_plugin_device_ns_per_byte",
    "ec_decode_roofline",
)


def _file(folder, name, **changes):
    data = json.loads((REPO / "benchmark" / folder / f"{name}.json").read_text())
    data.update(changes)
    return data


@pytest.fixture
def tiny(monkeypatch):
    """The cells' own files cut for a CPU: 8 KiB buffers (1 KiB
    chunks), 4 a call, k, m, w and the technique as they are."""
    small = dict(stripes_per_call=4, payload_pool=3, check_sample=3)
    files = {
        ("configs", "ec_plugin_k8m3"): _file(
            "configs", "ec_plugin_k8m3", buffer_bytes=8192, chunk_bytes=1024
        ),
        ("workloads", "encode_1m"): _file("workloads", "encode_1m", **small),
        ("workloads", "decode_2e_1m"): _file("workloads", "decode_2e_1m", **small),
    }
    original = harness._load_json
    monkeypatch.setattr(
        harness, "_load_json",
        lambda folder, name: files.get((folder, name)) or original(folder, name),
    )
    return files


def _driver(tiny, cell, seed=5):
    import jax

    loaded = harness.load_cell(BENCH, cell)
    cls = harness.load_driver(loaded["traffic"]["driver"])
    return cls(
        loaded["config"], loaded["traffic"], seed, pathlib.Path("unused"),
        jax.profiler.TraceAnnotation,
        harness.load_reference(loaded["config"]["reference"]),
    )


# -- the codec reference ------------------------------------------------------


@pytest.mark.parametrize("k,m,pairs", [(8, 3, 55), (4, 2, 15)])
def test_codec_reference_decodes_its_own_encode_for_every_pair(k, m, pairs):
    from ceph_tpu.ec import ErasureCodeProfile, registry_instance

    ref = harness.load_reference("reed_sol_van_codec")
    ec = registry_instance().factory("jerasure", ErasureCodeProfile(
        technique="reed_sol_van", k=str(k), m=str(m), w="8"))
    assert ec.backend.name == "numpy"
    assert np.asarray(ec.matrix).reshape(m, k).tolist() == ref.generator_matrix(k, m)[k:]
    unit, stripes = 64, 3
    payload = np.random.default_rng(k).integers(
        0, 256, k * unit * stripes, dtype=np.uint8).tobytes()
    shards = ref.encode_shards(payload, k, m, unit)
    # the program's plugin, a stripe at a time
    for s in range(stripes):
        mine = ec.encode(set(range(k + m)), payload[s * k * unit:(s + 1) * k * unit])
        assert all(np.array_equal(mine[p], shards[p][s * unit:(s + 1) * unit])
                   for p in range(k + m))
    lost_pairs = list(itertools.combinations(range(k + m), 2))
    assert len(lost_pairs) == pairs
    for lost in lost_pairs:
        have = {p: s for p, s in enumerate(shards) if p not in lost}
        back = ref.decode_shards(have, k, m, unit)
        assert sorted(back) == list(range(k + m))
        assert all(np.array_equal(back[p], shards[p]) for p in range(k + m)), lost
        chunks = {p: s[:unit] for p, s in have.items()}
        prog = ec.decode(set(lost), chunks)
        assert all(np.array_equal(prog[p], back[p][:unit]) for p in lost), lost
        # the control breaks the guarantee for every pair
        broken = ref.decode_shards(have, k, m, unit, guarantee="broken")
        assert any(not np.array_equal(broken[p], shards[p]) for p in lost), lost
    broken = ref.encode_shards(payload, k, m, unit, guarantee="broken")
    assert [np.array_equal(a, b) for a, b in zip(broken, shards)] == (
        [True] * (k + m - 1) + [False])
    with pytest.raises(ValueError):
        ref.decode_shards({p: shards[p] for p in range(k - 1)}, k, m, unit)


def test_codec_reference_imports_nothing_of_the_program():
    text = (REPO / "benchmark" / "references" / "reed_sol_van_codec.py").read_text()
    imports = [ln.split()[1] for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert sorted(imports) == ["__future__", "functools", "numpy"]


# -- run.py, rehearsed --------------------------------------------------------

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.mark.parametrize("workload,trace", [
    (ENCODE, 0), (ENCODE, 1), (DECODE, 0), (DECODE, 1),
])
def test_run_prints_the_contracts_last_line(tiny, capsys, workload, trace):
    rc = bench_run.main(
        ["--workload", workload, "--seed", str(2**31 + 29), "--seconds", "0.5",
         "--trace", str(trace), "--allow-cpu"], time.perf_counter())
    assert rc == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert set(last) == RESULT_KEYS and list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert {name: pair["value"] for name, pair in last["compared"].items()} == {
        "wrong_chunks": 0, "chunks_unchecked": 0, "calls_undispatched": 0,
        "failed_ops": 0}
    cell = harness.load_cell(harness.load_benchmark(), workload)
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    host_read = {m["name"] for m in wanted if m["source"] != "device_trace"}
    if trace:
        assert host_read == {
            "ec_plugin_host_ms_per_call", "ec_plugin_upload_ms_per_call",
            "ec_plugin_fetch_ms_per_call"}
    else:
        assert host_read == {"client_MBps", "op_p95_ms", "setup_s"}
    assert set(last["metrics"]) == host_read
    for metric in last["metrics"].values():
        assert metric["value"] > 0
    for name, pair in last["compared"].items():
        assert f"compared {name}: {pair['value']} (limit {pair['limit']})" in captured.err


@pytest.mark.parametrize("workload", [ENCODE, DECODE])
@pytest.mark.parametrize("fault", ["control", "altered_answer"])
def test_a_planted_fault_is_seen(tiny, capsys, workload, fault):
    from ceph_tpu.ec import stripe

    before = (stripe.encode, stripe.decode)
    rc = control.main(["--workload", workload, "--seed", "7", "--seconds", "0.3",
                       "--trace", "0", "--allow-cpu", "--fault", fault])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] is False
    assert last["compared"]["wrong_chunks"]["value"] > 0
    assert last["compared"]["chunks_unchecked"]["value"] == 0
    assert (stripe.encode, stripe.decode) == before  # the fault is undone


def test_a_decode_window_over_every_pair_compiles_nothing(tiny):
    driver = _driver(tiny, DECODE)
    clock = harness.CompileClock()
    disp = harness.Dispatches()
    driver.setup()
    programs = clock.programs
    first = driver.next_call
    assert first == 55 + 2  # every pair once, then warm_calls drawn ones
    assert [driver._lost(c) for c in range(55)] == ALL_PAIRS
    window = driver.window(seconds=None, max_units=600)
    assert clock.programs == programs
    drawn = [driver._lost(first + i) for i in range(window["units"])]
    assert set(drawn) == set(ALL_PAIRS)
    # drawn with replacement, as the tool's -E random: a block of 55
    # calls repeats pairs and misses others
    assert len(set(drawn[:55])) < 55
    # one record a call, all of them on the device backend, and none of
    # the program's encode: the survivors are the reference's shards
    assert disp.harvest() == {"ec_decode:jax": 57 + 600}
    assert driver.check() == {"wrong_chunks": (0, 0), "chunks_unchecked": (0, 0),
                              "calls_undispatched": (0, 0)}
    driver.close()


def test_the_decode_draws_are_the_seeds_and_uniform(tiny):
    driver, other, again = (_driver(tiny, DECODE, seed=s) for s in (5, 6, 5))
    for d in (driver, other, again):
        d.setup()
    calls = range(57, 57 + 5500)
    mine = [driver._lost(c) for c in calls]
    assert mine == [again._lost(c) for c in calls]
    assert mine != [other._lost(c) for c in calls]
    counts = np.bincount([ALL_PAIRS.index(p) for p in mine], minlength=55)
    assert counts.min() >= 60 and counts.max() <= 145  # mean 100 a pair
    # independent draws: a pair follows itself about once in 55 calls
    repeats = sum(a == b for a, b in zip(mine, mine[1:]))
    assert 55 <= repeats <= 150
    for d in (driver, other, again):
        d.close()


def test_the_survivors_are_the_references_shards(tiny, monkeypatch):
    """Set-up of the decode cell encodes nothing with the program: a
    wrong parity row in the codec cannot make its own survivors."""
    from ceph_tpu.ec import stripe

    def never(*_a, **_k):
        raise AssertionError("the program's encode made the survivors")

    monkeypatch.setattr(stripe, "encode", never)
    driver = _driver(tiny, DECODE)
    driver.setup()
    ref = driver.reference
    payload = np.frombuffer(
        np.random.default_rng(5).bytes(driver.call_bytes), dtype=np.uint8)
    want = ref.encode_shards(payload, 8, 3, 1024)
    assert sorted(driver.shard_sets[0]) == list(range(11))
    assert all(np.array_equal(driver.shard_sets[0][p], want[p]) for p in range(11))
    driver.close()


def test_the_check_covers_the_last_call_and_a_seeded_sample(tiny):
    driver = _driver(tiny, ENCODE)
    driver.setup()
    first = driver.next_call
    driver.window(seconds=None, max_units=40)
    calls = [call for call, _out in driver.kept]
    assert len(calls) == 3 and calls[-1] == first + 39
    assert all(first <= c < first + 40 for c in calls)
    assert driver.counters() == {"calls": 40}
    # the sample is of the calls before the last: never one call twice,
    # whatever the window's length and the draws
    for n in (1, 2, 3, 4, 8, 9, 17):
        start = driver.next_call
        driver.window(seconds=None, max_units=n)
        kept = [call for call, _out in driver.kept]
        assert len(set(kept)) == len(kept) == min(3, n) and kept[-1] == start + n - 1
        assert driver.check()["chunks_unchecked"] == (0, 0)
    driver.window(seconds=None, max_units=40)
    # a shard that is missing or short is unchecked, never passed over
    call, out = driver.kept[0]
    out.pop(10)
    out[0] = out[0][:-1]
    compared = driver.check()
    assert compared["chunks_unchecked"] == (2 * 4, 0)
    assert compared["wrong_chunks"] == (0, 0)


def test_a_program_without_the_seams_decode_fails_the_decode_cell_by_itself(
    tiny, monkeypatch
):
    """The parent commit has no ``stripe.decode``: the decode cell
    fails there at its first warm-up call, with no gate of the
    driver's in the way."""
    from ceph_tpu.ec import stripe

    monkeypatch.delattr(stripe, "decode")
    driver = _driver(tiny, DECODE)
    with pytest.raises(AttributeError, match="decode"):
        driver.setup()


def test_the_driver_sets_nothing_for_its_process(tiny, monkeypatch):
    """The encode cell runs on a program with no allocator module (the
    parent, or a later one that needs none), and the driver names no
    process-wide policy of its own."""
    import sys as _sys

    from ceph_tpu.ec import backend

    monkeypatch.setitem(_sys.modules, "ceph_tpu.common.allocator", None)
    monkeypatch.setattr(backend.allocator, "keep_large_blocks", lambda: True)
    driver = _driver(tiny, ENCODE)
    driver.setup()
    driver.window(seconds=None, max_units=3)
    assert driver.check()["wrong_chunks"] == (0, 0)
    driver.close()
    source = (REPO / "benchmark" / "drivers" / "ec_plugin.py").read_text()
    assert "allocator" not in source and "mallopt" not in source


# -- the new readers ----------------------------------------------------------


def _plugin_run(mode):
    counters = {
        "calls": 4,
        "l_stage_ec_fold_ns": 8_000_000, "l_stage_ec_assemble_ns": 4_000_000,
        f"dispatch.ec_{mode}.transfer_s": 0.02, f"dispatch.ec_{mode}.sync_s": 0.01,
    }
    traffic = {"driver": "ec_plugin", "mode": mode}
    if mode == "decode":
        traffic["erasures"] = 2
    return {
        "counters": counters, "traffic": traffic,
        "config": {"profile": {"k": 8, "m": 3}},
        "client": {"amount": 4 * 64 * 2**20},
        "trace": {"busy_s": 0.004, "idle_pct": 99.0}, "peaks": peaks.PEAKS["TPU v5 lite"],
    }


def test_new_readers_on_a_plugin_run():
    read = {name: harness.load_reader("layer_metrics", name) for name in NEW_READERS}
    run = _plugin_run("decode")
    assert read["ec_plugin_host_ms_per_call"](run) == pytest.approx(3.0)
    assert read["ec_plugin_upload_ms_per_call"](run) == pytest.approx(5.0)
    assert read["ec_plugin_fetch_ms_per_call"](run) == pytest.approx(2.5)
    assert read["ec_plugin_device_ns_per_byte"](run) == pytest.approx(
        0.004e9 / (4 * 64 * 2**20))
    assert read["ec_decode_roofline"](run) == pytest.approx(
        100 * (4 * 64 * 2**20 * 1.25 / 819e9) / 0.004)
    # the stem's file reads the third split of the idle share
    assert harness.load_reader("layer_metrics", "device_idle_pct.ecplugin")(run) == 99.0
    # the encode cell has no decode roofline, and reads its own kind's stages
    enc = _plugin_run("encode")
    assert read["ec_decode_roofline"](enc) is None
    assert read["ec_plugin_upload_ms_per_call"](enc) == pytest.approx(5.0)
    # a program that brackets no stage or opens no span gives nothing
    enc["counters"] = {"calls": 4, "dispatch.ec_encode.transfer_s": 0.0,
                       "dispatch.ec_encode.sync_s": 0.0}
    for name in ("ec_plugin_host_ms_per_call", "ec_plugin_upload_ms_per_call",
                 "ec_plugin_fetch_ms_per_call"):
        assert read[name](enc) is None


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_on_another_drivers_run(name):
    read = harness.load_reader("layer_metrics", name)
    for counters, traffic in (
        ({"client.ops_done": 70, "dispatch.ec_encode.transfer_s": 0.3,
          "dispatch.ec_encode.sync_s": 0.2, "l_stage_ec_fold_ns": 5},
         {"driver": "rados_bench", "mode": "write"}),
        ({"remaps": 2, "dispatch.crush.sync_s": 0.8}, {"driver": "crush_remap"}),
    ):
        run = {"counters": counters, "traffic": traffic,
               "config": {"profile": {"k": 4, "m": 2}},
               "client": {"amount": 10**9}, "trace": {"busy_s": 0.01},
               "peaks": peaks.PEAKS["TPU v5 lite"]}
        assert read(run) is None


def test_decode_roofline_reader_never_returns_a_zero_share():
    read = harness.load_reader("layer_metrics", "ec_decode_roofline")
    run = _plugin_run("decode")
    run["trace"] = None
    assert read(run) is None
    run["trace"] = {"busy_s": 0.0}
    assert read(run) is None
    run["trace"] = {"busy_s": 0.01}
    run["client"]["amount"] = 0
    assert read(run) is None
    run["client"]["amount"] = 10**9
    assert read(run) == pytest.approx(100 * 1.25e9 / 819e9 / 0.01)


def test_the_new_cells_are_entries_appended_and_nothing_else_moved():
    cells = [w["name"] for w in BENCH["workloads"]]
    assert cells[-2:] == [ENCODE, DECODE] and BENCH["configs"][-1]["name"] == "ec_plugin_k8m3"
    by_name = {m["name"]: m for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for name in ("client_MBps", "op_p95_ms"):
        assert by_name[name]["workloads"] == ["ecpool_k4m2.write_4m", ENCODE, DECODE]
    assert by_name["ec_encode_roofline"]["workloads"] == ["ecpool_k4m2.write_4m", ENCODE]
    assert by_name["ec_decode_roofline"]["workloads"] == [DECODE]
    assert by_name["device_idle_pct.ecplugin"]["workloads"] == [ENCODE, DECODE]
    config = harness._load_json("configs", "ec_plugin_k8m3")
    assert config["profile"] == {"plugin": "jerasure", "technique": "reed_sol_van",
                                 "k": 8, "m": 3, "w": 8, "backend": "jax"}
    assert config["buffer_bytes"] == 1 << 20 and config["reduced"] == []
    assert {"stripes_per_call", "backend", "chunk_mapping"} <= set(config["assumed"])
    decode = harness._load_json("workloads", "decode_2e_1m")
    assert (decode["erasures"], decode["stripes_per_call"], decode["in_flight"]) == (2, 64, 1)
