"""chip_smoke.py's phases, tiny, on the CPU (rehearsal 1 of the
on-chip-measurement guide kept as a test): wrong paths, arguments and
control flow show here, at no chip time.  Only the script's ``main``
insists on a TPU and on the real sizes."""

import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


@pytest.fixture(scope="module")
def books():
    return chip_smoke.CompileClock(), chip_smoke.Dispatches()


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    captured = capsys.readouterr()
    assert "needs a TPU" in captured.err
    assert captured.out == ""  # no result line


def test_phases_tiny_on_cpu(books, tmp_path, capsys):
    clock, disp = books
    with chip_smoke.phase("A:ec", clock, disp) as rec:
        rec.update(chip_smoke.phase_ec(
            3, "cpu", size=65536, batch=4, encode_iters=2, decode_iters=2,
        ))
    assert rec["bytes_compared"] > 0
    with chip_smoke.phase("B:crush", clock, disp) as rec:
        rec.update(chip_smoke.phase_crush(
            3, "cpu", build="64:4:4", max_x=3000, pg_num=512, sample=64,
        ))
    assert rec["oracle_inputs_compared"] >= 64
    with chip_smoke.phase("C:pool", clock, disp) as rec:
        rec.update(chip_smoke.phase_pool(
            3, "cpu", str(tmp_path), objects=8, obj_size=1 << 16,
            writers=2, degraded_sample=3, timeout=120,
        ))
    assert rec["health"] == "HEALTH_OK"
    assert rec["bytes_read_degraded"] > 0
    verdict = chip_smoke.check_dispatches(disp, "cpu")
    for kind in ("ec_encode", "crc32c", "crush"):
        assert verdict["by_kind"][f"{kind}:jax"] > 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [ln for ln in lines if ln.startswith('{"phase": "C:pool"')]


def test_host_backend_dispatch_fails_the_check(books):
    _clock, disp = books
    from ceph_tpu.ops.profiler import dispatch_profiler

    with dispatch_profiler().dispatch("crc32c", backend="cpu") as dp:
        dp.set_ops(1)
    with pytest.raises(RuntimeError, match="host-backend"):
        chip_smoke.check_dispatches(disp, "cpu")
    disp.counts.pop("crc32c:cpu")


def test_mesh_phase_on_four_virtual_devices(monkeypatch):
    """Rehearsal 2: the --chips 4 path on four of conftest's virtual
    CPU devices, shards asserted on four distinct devices."""
    from ceph_tpu.ops import mesh

    monkeypatch.setenv("CEPH_TPU_MESH", "1")
    monkeypatch.setenv("CEPH_TPU_MESH_DEVICES", "4")
    mesh._reset_default_mesh_for_tests()
    try:
        rec = chip_smoke.phase_mesh(
            3, 4, batches=(8, 7), chunk=4096, build="64:4:4",
            inputs=3000, sample=64,
        )
    finally:
        mesh._reset_default_mesh_for_tests()
    assert rec["mesh_devices"] == 4
