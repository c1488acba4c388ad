"""The benchmark's own tests (tier-1, CPU, tiny sizes): the trace
reducer on a recorded TPU trace, the window's arithmetic, the loader
and the names in ``BENCHMARK.json``, one rehearsal of ``run.py`` for
each driver, the plain references against the program, and the
controls and planted faults that ``correct`` has to fail."""

import json
import pathlib
import re
import sys
import time

import numpy as np
import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from benchmark import control, harness, peaks, trace_reduce  # noqa: E402
from benchmark import run as bench_run  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"
BENCH = json.loads((REPO / "BENCHMARK.json").read_text())
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]
READ_CELL = "ecpool_k4m2.rand_read_tiny"


def _tiny(folder, name, **changes):
    data = json.loads((REPO / "benchmark" / folder / f"{name}.json").read_text())
    for key, val in changes.items():
        if isinstance(val, dict):
            data[key] = {**data[key], **val}
        else:
            data[key] = val
    return data


@pytest.fixture
def tiny(monkeypatch):
    """The cells' own files with their sizes cut for a CPU: 4 OSDs,
    k=2 m=1, 2 MiB objects (256 stripes, one encode group an object, as
    at the real size); ``--build 64:4``, 1024 PGs."""
    ec = _tiny("configs", "ecpool_k4m2", osds=4, pg_num=8, profile={"k": 2, "m": 1})
    small = dict(object_bytes=2 << 20, payload_pool=5, warm_ops=4,
                 in_flight=4, check_sample=4)
    files = {
        ("configs", "ecpool_k4m2"): ec,
        ("configs", "crush_10k"): _tiny(
            "configs", "crush_10k", build="64:4", pool={"pg_num": 1024}
        ),
        ("workloads", "write_4m"): _tiny("workloads", "write_4m", **small),
        # ``rados bench rand``: no cell yet (PERF.md, Open questions), so
        # the mix and its entry exist here only, as data a later PR adds
        ("workloads", "rand_read_tiny"): _tiny(
            "workloads", "write_4m", **small, mode="rand_read", prefill_objects=8
        ),
        ("workloads", "remap_1m"): _tiny("workloads", "remap_1m", check_sample=512),
    }
    original = harness._load_json
    monkeypatch.setattr(
        harness, "_load_json",
        lambda folder, name: files.get((folder, name)) or original(folder, name),
    )
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": READ_CELL, "config": "ecpool_k4m2", "traffic": "rand_read_tiny",
        "chips": 1, "why": "tests only",
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "ecpool_k4m2.write_4m" in metric.get("workloads", []) and (
            metric["name"] != "ec_objects_per_dispatch"
        ):
            metric["workloads"].append(READ_CELL)
    monkeypatch.setattr(harness, "load_benchmark", lambda: bench)
    return files


def _last_line(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


# -- the trace reducer ----------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    """Four rounds of a bf16 matmul and a reduction on one TPU v5e
    inside ``bench:window``, each submitted under ``bench:client_submit``
    and awaited, with a 20 ms sleep, under ``bench:client_wait``
    (my chip run, PR 25)."""
    return trace_reduce.load(DATA / "small_tpu_v5e.xplane.pb")


def test_reducer_finds_the_device_plane_and_the_window(recorded):
    assert [p for p in recorded if trace_reduce.is_device_plane(p)] == [
        "/device:TPU:0"
    ]
    assert "XLA Ops" in recorded["/device:TPU:0"]
    out = trace_reduce.reduce(recorded)
    assert out["device_planes"] == 1
    assert out["window_s"] == pytest.approx(0.116275533, rel=1e-9)


def test_reducer_busy_idle_and_per_op_times(recorded):
    out = trace_reduce.reduce(recorded)
    assert out["busy_s"] == pytest.approx(0.000347165, rel=1e-6)
    assert out["idle_pct"] == pytest.approx(99.70142901860531, rel=1e-9)
    ops = dict(out["device_ops"])
    assert ops["%fusion bf16[2048,2048] kOutput"] == pytest.approx(0.000272633)
    assert ops["%add_reduce_fusion bf16[] kLoop"] == pytest.approx(3.9738e-05)
    # no share passes the whole, and the ops add up to at least the union
    assert sum(ops.values()) >= out["busy_s"] > max(ops.values())


def test_reducer_labels_gaps_by_the_annotation_open(recorded):
    out = trace_reduce.reduce(recorded)
    gaps = dict(out["idle_gaps"])
    assert gaps["client_wait"] == pytest.approx(0.062437564)
    assert gaps["unattributed"] == pytest.approx(0.053490804)
    assert sum(gaps.values()) + out["busy_s"] == pytest.approx(out["window_s"])


def test_reducer_on_made_up_planes():
    planes = {
        "/host:CPU": {"python": [
            ("bench:window", 0.0, 1000.0), ("bench:client_wait", 500.0, 900.0),
            ("other", 0.0, 1000.0),
        ]},
        "/device:TPU:0": {
            "XLA Ops": [("%a = f32[2] add()", 100.0, 300.0), ("%b = f32[2] add()", 200.0, 400.0),
                        ("%a = f32[2] add()", 950.0, 1200.0)],
            "XLA Modules": [("jit_f", 0.0, 1000.0)],
        },
        "/device:CUSTOM:Megascale Trace": {"x": [("noise", 0.0, 1000.0)]},
    }
    out = trace_reduce.reduce(planes)
    assert out["busy_s"] == pytest.approx(350e-9)  # union, clipped to the window
    assert out["idle_pct"] == pytest.approx(65.0)
    assert dict(out["device_ops"]) == {"%a f32[2]": pytest.approx(250e-9),
                                       "%b f32[2]": pytest.approx(200e-9)}
    assert dict(out["idle_gaps"]) == {"client_wait": pytest.approx(550e-9),
                                      "unattributed": pytest.approx(100e-9)}
    with pytest.raises(ValueError):
        trace_reduce.reduce({"/host:CPU": {"python": [("x", 0.0, 1.0)]}})


def test_an_empty_device_plane_reads_fully_idle():
    planes = {"/host:CPU": {"python": [("bench:window", 0.0, 1000.0)]},
              "/device:TPU:0": {"XLA Ops": []}}
    out = trace_reduce.reduce(planes)
    assert out["busy_s"] == 0 and out["idle_pct"] == 100.0


# -- the window's arithmetic ------------------------------------------------


def test_percentile_matches_numpy():
    rng = np.random.default_rng(0)
    vals = list(rng.random(257))
    for q in (0, 50, 95, 99, 100):
        assert harness.percentile(vals, q) == pytest.approx(np.percentile(vals, q))
    assert harness.percentile([3.0], 95) == 3.0
    with pytest.raises(ValueError):
        harness.percentile([], 50)


def _steady(n=200, lat=0.1, gap=0.01, size=4_000_000, stall_at=None, stall=0.0):
    ops, t = [], 0.0
    for i in range(n):
        extra = stall if stall_at is not None and i >= stall_at else 0.0
        ops.append((t + extra, t + extra + lat, size, True))
        t += gap
    return ops


def test_window_arithmetic_and_a_stall_moves_both_metrics():
    base = harness.client_numbers(_steady())
    assert base["span_s"] == pytest.approx(199 * 0.01 + 0.1)
    assert base["amount"] == 200 * 4_000_000
    assert base["p95_ms"] == pytest.approx(100.0)
    # one second in which nothing is acknowledged: ten ops wait it out
    stalled = _steady()
    for i in range(100, 112):
        t_sub, t_done, size, ok = stalled[i]
        stalled[i] = (t_sub, t_done + 1.0, size, ok)
    stalled = stalled[:112] + [
        (a + 1.0, b + 1.0, s, ok) for a, b, s, ok in stalled[112:]
    ]
    got = harness.client_numbers(stalled)
    assert got["amount"] / got["span_s"] < 0.7 * base["amount"] / base["span_s"]
    assert got["p95_ms"] > 5 * base["p95_ms"]


def test_a_failed_op_counts_no_bytes_and_its_whole_wait():
    ops = _steady(20)
    ops[5] = (ops[5][0], ops[5][0] + 9.0, ops[5][2], False)
    got = harness.client_numbers(ops)
    assert got["failed"] == 1 and got["attempted"] == 20
    assert got["amount"] == 19 * 4_000_000
    assert got["p95_ms"] > 100.0
    with pytest.raises(harness.BenchmarkError):
        harness.client_numbers([])


# -- the files and the names ------------------------------------------------


@pytest.mark.parametrize("folder", ["configs", "workloads"])
def test_every_data_file_loads(folder):
    files = sorted((REPO / "benchmark" / folder).glob("*.json"))
    assert files
    for path in files:
        data = harness._load_json(folder, path.stem)
        assert isinstance(data, dict)
        if folder == "configs":
            assert harness.load_reference(data["reference"])
            assert data["guarantees"] and "reduced" in data and "assumed" in data
        else:
            assert harness.load_driver(data["driver"])


@pytest.mark.parametrize("folder", ["layer_metrics", "end_to_end"])
def test_every_reader_loads_and_is_named_in_the_benchmark(folder):
    key = "per_layer" if folder == "layer_metrics" else "end_to_end"
    named = {m["name"] for m in BENCH[key]}
    files = {p.name[:-3] for p in (REPO / "benchmark" / folder).glob("*.py")}
    # a quantity split by what it moves is read by its stem's file
    assert files == {n if n in files else n.split(".")[0] for n in named}
    for name in sorted(named):
        assert callable(harness.load_reader(folder, name))


def test_a_split_metric_is_read_by_its_stem_unless_it_has_a_file(tmp_path, monkeypatch):
    folder = tmp_path / "layer_metrics"
    folder.mkdir()
    (folder / "idle.py").write_text("def read(run):\n    return 'stem'\n")
    (folder / "idle.b.py").write_text("def read(run):\n    return 'own'\n")
    monkeypatch.setattr(harness, "HERE", tmp_path)
    assert harness.load_reader("layer_metrics", "idle.a")({}) == "stem"
    assert harness.load_reader("layer_metrics", "idle.b")({}) == "own"
    assert harness.load_reader("layer_metrics", "idle")({}) == "stem"
    with pytest.raises(harness.BenchmarkError):
        harness.load_reader("layer_metrics", "busy.a")


def test_names_units_and_shape_of_benchmark_json():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names))
    for m in METRICS:
        assert harness.NAME_RE.match(m["name"]), m["name"]
        assert harness.UNIT_RE.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert (REPO / c["file"]).is_file() and c["file"].startswith("benchmark/")
        assert json.loads((REPO / c["file"]).read_text())["reduced"] == c["reduced"]
    cells = [w["name"] for w in BENCH["workloads"]]
    assert len(cells) == len(set(cells))
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 0 < len(w["why"]) <= 200
        for key in ("name", "config", "traffic"):
            assert harness.NAME_RE.match(w[key])
    for text in [c["source"] for c in BENCH["configs"]] + BENCH["command"]:
        assert 0 < len(text) <= 200 and "\n" not in text and "\t" not in text


def test_every_cell_reports_what_the_contract_asks():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in BENCH["workloads"]:
        cell = harness.load_cell(BENCH, w["name"])
        reported = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in reported and len(reported) >= 2
        assert cell["per_layer"]
        for m in cell["per_layer"]:
            assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
            assert m["moves"] in reported and m["moves"] != "setup_s"
            if m["name"].endswith("_roofline"):
                assert m["unit"] == "%"
    with pytest.raises(harness.BenchmarkError):
        harness.load_cell(BENCH, "no_such.cell")


def test_no_file_under_paths_has_a_name_outside_the_alphabet():
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for root in BENCH["paths"]:
        for path in (REPO / root).rglob("*"):
            if "__pycache__" in path.parts or path.suffix == ".pyc":
                continue
            assert ok.match(str(path.relative_to(REPO))), path


def test_peaks_know_the_v5e_and_refuse_the_unknown():
    row = peaks.peaks_for("TPU v5 lite")
    assert (row["bf16_TFLOPs"], row["int8_TOPs"], row["hbm_GBps"], row["hbm_GB"]) == (
        197.0, 393.0, 819.0, 16.0)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


def test_roofline_reader_never_returns_a_zero_share():
    reader = harness.load_reader("layer_metrics", "ec_encode_roofline")
    run = {"trace": None, "peaks": peaks.PEAKS["TPU v5 lite"],
           "config": {"profile": {"k": 4, "m": 2}}, "client": {"amount": 10**9}}
    assert reader(run) is None
    run["trace"] = {"busy_s": 0.0}
    assert reader(run) is None
    run["trace"] = {"busy_s": 0.01}
    assert reader(run) == pytest.approx(100 * 1.5e9 / 819e9 / 0.01)


def test_span_readers_split_an_ops_latency_into_queue_and_wire():
    queue = harness.load_reader("layer_metrics", "client_queue_p50_ms")
    wire = harness.load_reader("layer_metrics", "client_wire_p50_ms")
    # five ops of 1.0 s, the Objecter's share 0.1 .. 0.5 s; a name read twice
    ops = [(0.0, 1.0, 1, True, n) for n in ("a", "b", "c", "a", "d")]
    spans = [{"name": "client_op", "oid": n, "duration_s": d}
             for n, d in (("a", 0.1), ("b", 0.2), ("c", 0.3), ("a", 0.4), ("d", 0.5))]
    spans.append({"name": "other", "oid": "a", "duration_s": 9.0})
    run = {"ops": ops, "spans": spans}
    assert wire(run) == pytest.approx(300.0)
    assert queue(run) == pytest.approx(700.0)
    # an op with no span is left out; nothing to read gives nothing
    assert queue({"ops": ops[:2], "spans": spans[1:2]}) == pytest.approx(800.0)
    assert queue({"ops": ops, "spans": []}) is None and wire({"spans": []}) is None
    assert queue({"ops": [(0.0, 1.0, 1, True)], "spans": spans}) is None


# -- run.py, rehearsed -------------------------------------------------------

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


def test_run_refuses_a_cpu(capsys):
    rc = bench_run.main(["--workload", "ecpool_k4m2.write_4m", "--seed", "1",
                         "--seconds", "1", "--trace", "0"])
    captured = capsys.readouterr()
    assert rc != 0 and captured.out == "" and "needs a TPU" in captured.err


@pytest.mark.parametrize("workload,trace", [
    ("ecpool_k4m2.write_4m", 0), ("ecpool_k4m2.write_4m", 1),
    (READ_CELL, 0), (READ_CELL, 1),
    ("crush_10k.remap_1m", 0), ("crush_10k.remap_1m", 1),
])
def test_run_prints_the_contracts_last_line(tiny, capsys, workload, trace):
    rc = bench_run.main(
        ["--workload", workload, "--seed", str(2**31 + 11), "--seconds", "1",
         "--trace", str(trace), "--allow-cpu"], time.perf_counter())
    assert rc == 0
    captured = capsys.readouterr()
    last = json.loads(captured.out.strip().splitlines()[-1])
    assert set(last) == RESULT_KEYS and list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0 and last["attempted"] > 0
    assert set(last["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    cell = harness.load_cell(harness.load_benchmark(), workload)
    wanted = cell["per_layer"] if trace else cell["end_to_end"]
    host_read = {m["name"] for m in wanted if m["source"] != "device_trace"}
    assert set(last["metrics"]) == host_read  # no device metric without a device
    for name, metric in last["metrics"].items():
        assert set(metric) == {"value", "unit"} and metric["value"] >= 0
    # each number compared stands beside its limit, on stderr's last lines too
    for name, pair in last["compared"].items():
        assert f"compared {name}: {pair['value']} (limit {pair['limit']})" in captured.err


def test_a_host_backend_dispatch_fails_the_run(tiny, capsys):
    from ceph_tpu.ops.profiler import dispatch_profiler

    def host_dispatch(_driver):
        with dispatch_profiler().dispatch("crush", backend="cpu") as dp:
            dp.set_ops(1)

    args = bench_run.parse_args(["--workload", "crush_10k.remap_1m", "--seconds",
                                 "0.2", "--allow-cpu"])
    with pytest.raises(harness.BenchmarkError, match="host backend"):
        bench_run.run(args, time.perf_counter(), after_setup=host_dispatch)
    assert "correct" not in capsys.readouterr().out


def test_a_compilation_inside_the_window_fails_the_run(tiny, capsys):
    import jax
    import jax.numpy as jnp

    def compile_in_window(driver):
        window = driver.window

        def slow(seconds, max_units=None):
            jax.jit(lambda x: x * 3 + 0.12345)(jnp.ones(7)).block_until_ready()
            return window(seconds, max_units)

        driver.window = slow

    args = bench_run.parse_args(["--workload", "crush_10k.remap_1m", "--seconds",
                                 "0.2", "--allow-cpu"])
    with pytest.raises(harness.BenchmarkError, match="inside the window"):
        bench_run.run(args, time.perf_counter(), after_setup=compile_in_window)
    assert "correct" not in capsys.readouterr().out


# -- the plain references, against the program ----------------------------------


@pytest.mark.parametrize("k,m", [(4, 2), (2, 1), (8, 3)])
def test_ec_reference_agrees_with_the_program(k, m):
    from ceph_tpu.osd.ec_pg import ECCodec

    ref = harness.load_reference("reed_sol_van")
    codec = ECCodec({"plugin": "jerasure", "technique": "reed_sol_van",
                     "k": str(k), "m": str(m)})
    assert np.asarray(codec.ec.matrix).reshape(m, k).tolist() == ref.coding_matrix(k, m)
    payload = np.random.default_rng(k).integers(0, 256, 100_000, dtype=np.uint8).tobytes()
    shards, _meta = codec.encode_object(payload)
    mine = ref.encode_shards(payload, k, m, 4096)
    assert [bytes(s) for s in mine] == [shards[i] for i in range(k + m)]
    # the control breaks the guarantee, and only it
    broken = ref.encode_shards(payload, k, m, 4096, guarantee="broken")
    assert [bytes(a) == bytes(b) for a, b in zip(broken, mine)] == [True] * (k + m - 1) + [False]


@pytest.mark.parametrize("build,pg_num", [((64, 4, 0), 1024), ((240, 6, 5), 777)])
def test_crush_reference_agrees_with_the_scalar_oracle(build, pg_num):
    from ceph_tpu.crush.ln import crush_ln
    from ceph_tpu.osd.mapping import OSDMapMapping
    from ceph_tpu.osd.osdmap import OSDMap, PgPool
    from ceph_tpu.tools.crushtool import build_hierarchy

    ref = harness.load_reference("crush_straw2")
    table = ref.ln_minus_table()
    assert (table + (1 << 48) == crush_ln(np.arange(65536, dtype=np.uint32))).all()
    rng = np.random.default_rng(5)
    osdmap = OSDMap.build(build_hierarchy(*build), build[0])
    weight = np.full(build[0], 0x10000)
    churn = rng.choice(build[0], build[0] // 8, replace=False)
    weight[churn[::2]] = 0
    weight[churn[1::2]] = rng.integers(1, 0x10000, len(churn[1::2]))
    osdmap.osd_weight = [int(w) for w in weight]
    osdmap.add_pool(PgPool(pool_id=1, type=1, size=3, pg_num=pg_num, crush_rule=0))
    mapping = OSDMapMapping()
    mapping.update(osdmap, use_device=False)
    up, primary = ref.replicated_up_acting(
        ref.Hierarchy(*build), np.arange(pg_num), pool_id=1, pgp_num=pg_num,
        size=3, domain=1, osd_weight=weight, osd_up=osdmap.osd_up)
    assert np.array_equal(up, mapping.up[1]) and np.array_equal(up, mapping.acting[1])
    assert np.array_equal(primary, mapping.up_primary[1])


def test_crush_control_differs_from_the_reference():
    """A float64 logarithm in place of upstream's tables moves some PGs:
    20 of 65,536 on the 64-OSD map (50 of 65,536 on the 10,000-OSD one)."""
    ref = harness.load_reference("crush_straw2")
    h = ref.Hierarchy(64, 4)
    x = ref.hash32_2(np.arange(65536), 1)
    weight = np.full(64, 0x10000)
    exact, _ = ref.chooseleaf_firstn(h, x, 3, 1, weight)
    control_rows, _ = ref.chooseleaf_firstn(h, x, 3, 1, weight, log="float")
    assert 1 <= int((exact != control_rows).any(axis=1).sum()) <= 200


# -- controls and planted faults: correct has to come out false ----------------


@pytest.mark.parametrize("workload,fault,number", [
    ("ecpool_k4m2.write_4m", "control", "wrong_shards"),
    ("ecpool_k4m2.write_4m", "altered_answer", "wrong_shards"),
    ("ecpool_k4m2.write_4m", "state_unchanged", "wrong_shards"),
    ("crush_10k.remap_1m", "state_unchanged", "wrong_pgs"),
    ("crush_10k.remap_1m", "half_batch", "wrong_pgs"),
    ("crush_10k.remap_1m", "altered_answer", "wrong_pgs"),
])
def test_a_planted_fault_is_seen(tiny, capsys, workload, fault, number):
    rc = control.main(["--workload", workload, "--seed", "7", "--seconds", "0.5",
                       "--trace", "0", "--allow-cpu", "--fault", fault])
    last = _last_line(capsys)
    assert rc == 0 and last["correct"] is False
    assert last["compared"][number]["value"] > last["compared"][number]["limit"]


def test_the_crush_control_is_seen_through_the_harness(tiny, capsys):
    """The float64 control in the kernel's place, at a size where it
    moves PGs: 16,384 PGs on the 64-OSD map, every one compared."""
    tiny[("configs", "crush_10k")]["pool"]["pg_num"] = 16384
    tiny[("workloads", "remap_1m")]["check_sample"] = 16384
    rc = control.main(["--workload", "crush_10k.remap_1m", "--seed", "7", "--seconds",
                       "0.1", "--trace", "0", "--allow-cpu", "--fault", "control"])
    last = _last_line(capsys)
    assert rc == 0 and last["correct"] is False
    assert last["compared"]["wrong_pgs"]["value"] > 0


def test_an_unknown_fault_is_refused(tiny, capsys):
    rc = control.main(["--workload", "crush_10k.remap_1m", "--seconds", "0.1",
                       "--allow-cpu", "--fault", "no_such_fault"])
    assert rc == 1 and "correct" not in capsys.readouterr().out
