"""``ec_clay_k8m4d11.repair_1e_1m`` (ISSUE 38; tier-1, CPU, tiny sizes):
the CLAY reference against the archived chunks, itself and the
program; a rehearsal of the cell through ``run.py``; the controls; the
12-position window; the three readers it brings; and where the cell
is listed."""

import base64
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

import test_benchmark  # noqa: E402

CONFIG, CELL = "ec_clay_k8m4d11", "ec_clay_k8m4d11.repair_1e_1m"
DECODE = "ec_plugin_k8m3.decode_2e_1m"
NEW_READERS = (
    "ec_repair_roofline", "ec_repair_read_fraction", "ec_repair_plan_ms_per_call",
)
SHARED_READERS = (
    "ec_plugin_upload_ms_per_call", "ec_plugin_fetch_ms_per_call",
    "ec_plugin_device_ns_per_byte", "device_idle_pct.ecplugin",
)
CORPUS = ("clay_k8_m4_d11_s262144_b83709b883", "clay_k4_m2_d5_s16384_240cc27a2f")


def _file(folder, name, **changes):
    data = json.loads((REPO / "benchmark" / folder / f"{name}.json").read_text())
    data.update(changes)
    return data


@pytest.fixture
def tiny(monkeypatch):
    """The cell's own files cut for a CPU: 16 KiB buffers (2 KiB
    chunks, sub-chunks of 32 bytes), 4 a call; the profile as it is."""
    files = {
        ("configs", CONFIG): _file(
            "configs", CONFIG, buffer_bytes=16384, chunk_bytes=2048
        ),
        ("workloads", "repair_1e_1m"): _file(
            "workloads", "repair_1e_1m", stripes_per_call=4, check_sample=3,
            warm_calls=2,
        ),
    }
    original = harness._load_json
    monkeypatch.setattr(
        harness, "_load_json",
        lambda folder, name: files.get((folder, name)) or original(folder, name),
    )
    return files


def _driver(tiny, seed=5):
    import jax

    loaded = harness.load_cell(harness.load_benchmark(), CELL)
    cls = harness.load_driver(loaded["traffic"]["driver"])
    return cls(
        loaded["config"], loaded["traffic"], seed, pathlib.Path("unused"),
        jax.profiler.TraceAnnotation,
        harness.load_reference(loaded["config"]["reference"]),
    )


def _cut(ref, shards, lost, k, m, d, chunk, helpers=None):
    """The fragments of ``helpers`` (all others by default) as the
    reference's ``repair_reads`` names them."""
    planes = ref.geometry(k, m, d)[1] ** ref.geometry(k, m, d)[2]
    sub = chunk // planes
    runs = ref.repair_reads(lost, k, m, d)
    return {
        h: np.concatenate(
            [shards[h].reshape(-1, planes, sub)[:, a : a + n] for a, n in runs],
            axis=1,
        ).reshape(-1)
        for h in (helpers if helpers is not None else range(k + m))
        if h != lost
    }


# -- the reference ------------------------------------------------------------


@pytest.mark.parametrize("name", CORPUS)
def test_reference_encodes_the_archived_chunks(name):
    """``corpus/clay_*``: twelve (six) chunks archived from the
    non-regression tool; the reference gives every one, and repairs
    every one from the reads it names."""
    from ceph_tpu.tools.ec_non_regression import default_payload

    ref = harness.load_reference("clay_codec")
    entry = json.loads((REPO / "corpus" / f"{name}.json").read_text())
    k, m, d = (int(entry["profile"][key]) for key in "kmd")
    archived = [
        np.frombuffer(base64.b64decode(entry["chunks"][str(i)]), dtype=np.uint8)
        for i in range(k + m)
    ]
    chunk = len(archived[0])
    mine = ref.encode_shards(default_payload(entry["size"]), k, m, chunk, d=d)
    assert [np.array_equal(a, b) for a, b in zip(mine, archived)] == [True] * (k + m)
    for lost in range(k + m):
        fragments = _cut(ref, archived, lost, k, m, d, chunk)
        assert sum(len(f) for f in fragments.values()) * (d - k + 1) == d * chunk
        back = ref.repair_shard(fragments, lost, k, m, chunk, d=d)
        assert np.array_equal(back, archived[lost]), lost


@pytest.mark.parametrize("k,m,d", [(8, 4, 11), (4, 2, 5), (5, 2, 6), (8, 4, 10), (8, 4, 9)])
def test_reference_agrees_with_the_program_and_its_control_differs(k, m, d):
    """Three stripes, every lost position; nu > 0 at (5, 2, 6), aloof
    nodes at d = 10 and 9.  The reads are the program's
    ``minimum_to_decode``; the broken guarantee is seen in every
    stripe of every position."""
    from ceph_tpu.ec import ErasureCodeProfile, registry_instance, stripe

    ref = harness.load_reference("clay_codec")
    ec = registry_instance().factory(
        "clay", ErasureCodeProfile(k=str(k), m=str(m), d=str(d)))
    assert (d, ec.q, ec.t, ec.nu) == ref.geometry(k, m, d)
    width = ec.get_chunk_size(1) * k
    sinfo = stripe.StripeInfo(k, width)
    chunk = sinfo.chunk_size
    payload = np.random.default_rng(d).integers(0, 256, 3 * width, dtype=np.uint8)
    shards = stripe.encode(sinfo, ec, payload)
    mine = ref.encode_shards(payload.tobytes(), k, m, chunk, d=d)
    assert all(np.array_equal(mine[i], shards[i]) for i in range(k + m))
    for lost in range(k + m):
        minimum = ec.minimum_to_decode({lost}, set(range(k + m)) - {lost})
        assert len(minimum) == d
        assert all(
            [tuple(r) for r in runs] == ref.repair_reads(lost, k, m, d)
            for runs in minimum.values())
        fragments = _cut(ref, mine, lost, k, m, d, chunk, helpers=sorted(minimum))
        back = ref.repair_shard(fragments, lost, k, m, chunk, d=d)
        assert np.array_equal(back, mine[lost]), lost
        assert np.array_equal(stripe.repair(sinfo, ec, fragments, lost), back), lost
        broken = ref.repair_shard(
            fragments, lost, k, m, chunk, d=d, guarantee="broken")
        assert (broken.reshape(3, -1) != back.reshape(3, -1)).any(axis=1).all(), lost
    broken = ref.encode_shards(payload.tobytes(), k, m, chunk, d=d, guarantee="broken")
    assert [np.array_equal(a, b) for a, b in zip(broken, mine)] == (
        [True] * k + [False] * m)
    with pytest.raises(ValueError):
        ref.repair_shard(_cut(ref, mine, 0, k, m, d, chunk, helpers=range(k)),
                         0, k, m, chunk, d=d)
    with pytest.raises(ValueError):
        ref.encode_shards(payload.tobytes(), k, m, chunk, d=d, guarantee="bent")


def test_reference_imports_nothing_of_the_program():
    text = (REPO / "benchmark" / "references" / "clay_codec.py").read_text()
    imports = [ln.split()[1] for ln in text.splitlines()
               if ln.startswith(("import ", "from "))]
    assert sorted(imports) == ["__future__", "functools", "numpy"]


# -- run.py, rehearsed --------------------------------------------------------

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


@pytest.mark.parametrize("trace", [0, 1])
def test_run_prints_the_contracts_last_line(tiny, capsys, trace):
    rc = bench_run.main(
        ["--workload", CELL, "--seed", str(2**31 + 38), "--seconds", "0.5",
         "--trace", str(trace), "--allow-cpu"], time.perf_counter())
    assert rc == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert set(last) == RESULT_KEYS and list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert {name: pair["value"] for name, pair in last["compared"].items()} == {
        "wrong_chunks": 0, "chunks_unchecked": 0, "calls_undispatched": 0,
        "failed_ops": 0}
    if trace:
        assert set(last["metrics"]) == {
            "ec_plugin_upload_ms_per_call", "ec_plugin_fetch_ms_per_call",
            "ec_repair_read_fraction", "ec_repair_plan_ms_per_call"}
        assert last["metrics"]["ec_repair_read_fraction"]["value"] == 11 / 32
        assert last["attempted"] == 8
    else:
        assert set(last["metrics"]) == {"client_MBps", "op_p95_ms", "setup_s"}
    for metric in last["metrics"].values():
        assert metric["value"] > 0
    for name, pair in last["compared"].items():
        assert f"compared {name}: {pair['value']} (limit {pair['limit']})" in captured.err


@pytest.mark.parametrize("fault", ["control", "altered_answer"])
def test_a_planted_fault_is_seen(tiny, capsys, fault):
    from ceph_tpu.ec import stripe

    before = stripe.repair
    rc = control.main(["--workload", CELL, "--seed", "7", "--seconds", "0.3",
                       "--trace", "0", "--allow-cpu", "--fault", fault])
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0 and last["correct"] is False
    assert last["compared"]["wrong_chunks"]["value"] > 0
    assert last["compared"]["chunks_unchecked"]["value"] == 0
    assert stripe.repair is before  # the fault is undone


def test_a_window_over_every_position_compiles_nothing(tiny):
    driver = _driver(tiny)
    clock = harness.CompileClock()
    disp = harness.Dispatches()
    driver.setup()
    programs = clock.programs
    first = driver.next_call
    assert first == 12 + 2  # every position once, then warm_calls drawn ones
    assert [driver._lost(c) for c in range(12)] == list(range(12))
    window = driver.window(seconds=None, max_units=150)
    assert clock.programs == programs
    drawn = [driver._lost(first + i) for i in range(window["units"])]
    assert set(drawn) == set(range(12))
    assert len(set(drawn[:12])) < 12  # with replacement
    # one record a call, on the device backend, and none of the
    # program's encode: the helpers' shards are the reference's
    assert disp.harvest() == {"ec_decode:jax": 14 + 150}
    assert driver.check() == {"wrong_chunks": (0, 0), "chunks_unchecked": (0, 0),
                              "calls_undispatched": (0, 0)}
    per_call = 11 * (2048 // 4) * 4  # 11 helpers x 16 of 64 sub-chunks x 4 stripes
    assert driver.counters() == {
        "calls": 150, "helper_bytes": 150 * per_call, "rebuilt_bytes": 150 * 4 * 2048}
    # the draws are the seed's
    again, other = _driver(tiny), _driver(tiny, seed=6)
    assert list(_drawn(again)) == list(_drawn(driver))
    assert list(_drawn(other)) != list(_drawn(driver))
    driver.close()


def _drawn(driver):
    """The first 200 drawn positions of a driver, set up if need be."""
    if not hasattr(driver, "lost"):
        driver.setup()
    return driver.lost[:200]


def test_the_fragments_are_the_references_and_the_check_holds_both(tiny, monkeypatch):
    """Set-up encodes nothing with the program; the check counts a
    chunk that differs from the reference's encode OR its repair, and
    a short answer as unchecked."""
    from ceph_tpu.ec import stripe

    def never(*_a, **_k):
        raise AssertionError("the program's encode made the helpers' shards")

    monkeypatch.setattr(stripe, "encode", never)
    driver = _driver(tiny)
    driver.setup()
    ref = driver.reference
    payload = np.random.default_rng(5).bytes(driver.call_bytes)
    want = ref.encode_shards(payload, 8, 4, 2048, d=11)
    assert all(np.array_equal(driver.shard_sets[0][p], want[p]) for p in range(12))
    assert sorted(driver.fragments[0][3]) == [p for p in range(12) if p != 3]
    driver.window(seconds=None, max_units=9)
    assert len(driver.kept) == 3 and driver.kept[-1][0] == driver.next_call - 1
    call, out = driver.kept[0]
    driver.kept[0] = (call, out[:-1])
    assert driver.check()["chunks_unchecked"] == (4, 0)
    altered = np.array(out)
    altered[2048 + 5] ^= 1  # the second stripe's chunk
    driver.kept[0] = (call, altered)
    assert driver.check()["wrong_chunks"] == (1, 0)
    driver.close()


def test_a_program_without_the_seams_repair_fails_the_cell_at_once(tiny, monkeypatch):
    """The parent commit has no ``stripe.repair``: set-up fails there
    before a payload is made, with no gate of the driver's in the way."""
    from ceph_tpu.ec import stripe

    monkeypatch.delattr(stripe, "repair")
    driver = _driver(tiny)
    t0 = time.perf_counter()
    with pytest.raises(AttributeError, match="repair"):
        driver.setup()
    assert time.perf_counter() - t0 < 1.0 and not hasattr(driver, "shard_sets")
    driver.close()


# -- the readers --------------------------------------------------------------


def _repair_run():
    amount = 4 * 64 * 2**20
    return {
        "counters": {
            "calls": 4, "helper_bytes": amount * 11 // 32, "rebuilt_bytes": amount // 8,
            "l_tpu_ec_repair_helper_bytes": amount * 11 // 32,
            "l_stage_ec_repair_plan_ns": 200_000,
            "dispatch.ec_decode.transfer_s": 0.004, "dispatch.ec_decode.sync_s": 0.02,
        },
        "traffic": {"driver": "ec_repair", "mode": "decode", "erasures": 1,
                    "reads": "minimum"},
        "config": {"profile": {"plugin": "clay", "k": 8, "m": 4, "d": 11}},
        "client": {"amount": amount},
        "trace": {"busy_s": 0.008, "idle_pct": 70.0},
        "peaks": peaks.PEAKS["TPU v5 lite"],
    }


def test_new_readers_on_a_repair_run():
    read = {n: harness.load_reader("layer_metrics", n) for n in NEW_READERS + SHARED_READERS}
    run = _repair_run()
    amount = run["client"]["amount"]
    assert read["ec_repair_roofline"](run) == pytest.approx(
        100 * (amount * 15 / 32 / 819e9) / 0.008)
    assert read["ec_repair_read_fraction"](run) == 0.34375
    assert read["ec_repair_plan_ms_per_call"](run) == pytest.approx(0.05)
    # the four it shares with the plugin cells read a repair's entries
    assert read["ec_plugin_upload_ms_per_call"](run) == pytest.approx(1.0)
    assert read["ec_plugin_fetch_ms_per_call"](run) == pytest.approx(5.0)
    assert read["ec_plugin_device_ns_per_byte"](run) == pytest.approx(0.008e9 / amount)
    assert read["device_idle_pct.ecplugin"](run) == 70.0
    # whole chunks of k helpers read as 1.0
    run["counters"]["l_tpu_ec_repair_helper_bytes"] = amount
    assert read["ec_repair_read_fraction"](run) == 1.0
    # a program without the counter or the span (the parent) gives nothing
    del run["counters"]["l_tpu_ec_repair_helper_bytes"]
    del run["counters"]["l_stage_ec_repair_plan_ns"]
    assert read["ec_repair_read_fraction"](run) is None
    assert read["ec_repair_plan_ms_per_call"](run) is None
    # the whole-shard decode's roofline is not a repair's, nor the reverse
    assert harness.load_reader("layer_metrics", "ec_decode_roofline")(run) == (
        pytest.approx(100 * (amount * 1.125 / 819e9) / 0.008))


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_finds_nothing_on_another_drivers_run(name):
    read = harness.load_reader("layer_metrics", name)
    for counters, traffic, profile in (
        ({"client.ops_done": 70, "dispatch.ec_encode.transfer_s": 0.3},
         {"driver": "rados_bench", "mode": "write"}, {"k": 4, "m": 2}),
        ({"remaps": 2, "dispatch.crush.sync_s": 0.8}, {"driver": "crush_remap"}, None),
        ({"calls": 9, "dispatch.ec_decode.sync_s": 0.1},
         {"driver": "ec_plugin", "mode": "decode", "erasures": 2}, {"k": 8, "m": 3}),
    ):
        run = {"counters": counters, "traffic": traffic,
               "config": {"profile": profile} if profile else {},
               "client": {"amount": 10**9}, "trace": {"busy_s": 0.01},
               "peaks": peaks.PEAKS["TPU v5 lite"]}
        assert read(run) is None


def test_repair_roofline_reader_never_returns_a_zero_share():
    read = harness.load_reader("layer_metrics", "ec_repair_roofline")
    run = _repair_run()
    run["trace"] = None
    assert read(run) is None
    run["trace"] = {"busy_s": 0.0}
    assert read(run) is None
    run["trace"] = {"busy_s": 0.01}
    run["client"]["amount"] = 0
    assert read(run) is None
    run["client"]["amount"] = 10**9
    assert read(run) == pytest.approx(100 * (10**9 * 15 / 32) / 819e9 / 0.01)


# -- where the cell is listed -------------------------------------------------


def check_the_repair_cell_is_listed(bench: dict) -> None:
    """What PR 38 added is there — never what a whole list is: a later
    cell is appended to the same lists."""
    cell = harness.load_cell(bench, CELL)
    assert cell["chips"] == 1 and cell["config_name"] == CONFIG
    assert CONFIG in [c["name"] for c in bench["configs"]]
    by_name = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name in ("client_MBps", "op_p95_ms") + SHARED_READERS:
        assert {DECODE, CELL} <= set(by_name[name]["workloads"]), name
    for name in NEW_READERS:
        entry = by_name[name]
        assert CELL in entry["workloads"] and DECODE not in entry["workloads"]
        assert entry["moves"] == "client_MBps"
    assert by_name["ec_repair_roofline"]["source"] == "device_trace"
    assert by_name["ec_repair_roofline"]["layer"] == by_name["ec_decode_roofline"]["layer"]
    assert by_name["ec_repair_read_fraction"]["source"] == "program_counter"
    assert by_name["ec_repair_plan_ms_per_call"]["source"] == "program_span"
    # a whole-shard decode's roofline and host spans are not a repair's
    for name in ("ec_decode_roofline", "ec_plugin_host_ms_per_call"):
        assert CELL not in by_name[name]["workloads"]
    reported = {m["name"] for m in cell["end_to_end"]}
    assert reported == {"client_MBps", "op_p95_ms", "setup_s"}


def test_the_repair_cell_is_entries_appended_and_files_added():
    bench = harness.load_benchmark()
    check_the_repair_cell_is_listed(bench)
    # and with the next cell appended as the tests append it
    check_the_repair_cell_is_listed(test_benchmark.with_a_further_cell(bench))


def test_the_deployment_is_the_documented_clay_profile():
    config = harness._load_json("configs", CONFIG)
    assert config["profile"] == {
        "plugin": "clay", "k": 8, "m": 4, "d": 11, "scalar_mds": "jerasure",
        "technique": "reed_sol_van", "backend": "jax"}
    assert (config["buffer_bytes"], config["chunk_bytes"], config["sub_chunks"]) == (
        1 << 20, 131072, 64)
    assert config["reference"] == "clay_codec" and config["reduced"] == []
    assert config["architecture"] is None and len(config["guarantees"]) == 3
    assert {"stripes_per_call", "chunk_mapping", "payload", "fragments",
            "allocator"} <= set(config["assumed"])
    (entry,) = [c for c in harness.load_benchmark()["configs"] if c["name"] == CONFIG]
    assert entry["source"] == config["source"] and len(entry["source"]) <= 200
    assert "erasure-code-clay" in entry["source"] and "BASELINE" in entry["source"]
    traffic = harness._load_json("workloads", "repair_1e_1m")
    assert {key: traffic[key] for key in (
        "driver", "mode", "erasures", "reads", "stripes_per_call", "in_flight",
        "payload_pool", "warm_calls", "check_sample", "trace")} == {
        "driver": "ec_repair", "mode": "decode", "erasures": 1, "reads": "minimum",
        "stripes_per_call": 64, "in_flight": 1, "payload_pool": 2, "warm_calls": 8,
        "check_sample": 4, "trace": {"units": 8}}
    # the published geometry: q = 4, t = 3, nu = 0
    assert harness.load_reference("clay_codec").geometry(8, 4, 11) == (11, 4, 3, 0)
