"""Device-mesh execution plane (ops/mesh.py + osd/sharded_mapping.py).

The contract under test: sharding a batch across the mesh NEVER
changes a byte — sharded CRUSH mapping and EC encode are identical to
the single-device paths, including ragged batch sizes that don't
divide the device count — plus per-device telemetry, product routing
(ec_backend / osd mapping go through the mesh when >1 device exists).

conftest.py pins the suite to an 8-device virtual CPU mesh
(``--xla_force_host_platform_device_count=8``).
"""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.crush import jaxmap
from ceph_tpu.ops import mesh as meshmod
from ceph_tpu.ops.kernel_stats import kernel_stats
from ceph_tpu.osd.sharded_mapping import (
    ShardedPGMapper,
    mesh_batch_do_rule,
    sharded_batch_do_rule,
)
from ceph_tpu.tools.crushtool import build_hierarchy


@pytest.fixture()
def fresh_default_mesh(monkeypatch):
    """Re-probe the process default mesh around a test and restore
    the unprobed state afterwards (the next caller re-probes)."""
    meshmod._reset_default_mesh_for_tests()
    yield monkeypatch
    meshmod._reset_default_mesh_for_tests()


def test_discovery_and_mesh_construction():
    assert meshmod.device_count() == 8  # conftest's virtual mesh
    full = meshmod.build_mesh()
    assert full.n == 8 and full.platform == "cpu"
    sub = meshmod.build_mesh(3)
    assert sub.n == 3
    assert sub.cache_key() != full.cache_key()
    with pytest.raises(ValueError):
        meshmod.DeviceMesh([])


def test_default_mesh_env_gates(fresh_default_mesh):
    fresh_default_mesh.setenv("CEPH_TPU_MESH", "0")
    assert meshmod.default_mesh() is None
    meshmod._reset_default_mesh_for_tests()
    fresh_default_mesh.setenv("CEPH_TPU_MESH", "1")
    fresh_default_mesh.setenv("CEPH_TPU_MESH_DEVICES", "2")
    dm = meshmod.default_mesh()
    assert dm is not None and dm.n == 2
    # probed once: the same object comes back
    assert meshmod.default_mesh() is dm


def test_pad_to_devices_ragged():
    a = np.arange(10)
    padded, n = meshmod.pad_to_devices(a, 8)
    assert n == 10 and padded.shape[0] == 16
    assert (padded[10:] == a[-1]).all()  # pad repeats a VALID lane
    same, n2 = meshmod.pad_to_devices(np.arange(16), 8)
    assert n2 == 16 and same.shape[0] == 16


@pytest.mark.parametrize(
    "n_pgs",
    # 1 and 7 pad to the same (8,) shape — one compile covers both;
    # the big ragged sweep is a slow-tier extra (each new padded
    # shape is a fresh XLA compile on the virtual mesh)
    [1, 7, 101, pytest.param(1024 + 5, marks=pytest.mark.slow)],
)
def test_sharded_mapping_byte_identity_ragged(n_pgs):
    """The acceptance bar: sharded == single-device, byte for byte,
    on PG counts that do NOT divide the 8-device mesh."""
    m = build_hierarchy(64, 8, 4)
    cm = jaxmap.compile_map(m)
    xs = np.arange(n_pgs)
    res1, cnt1 = jaxmap.batch_do_rule(cm, 0, xs, 3)
    dmesh = meshmod.build_mesh()
    res2, cnt2 = sharded_batch_do_rule(cm, 0, xs, 3, dmesh=dmesh)
    assert res2.shape == (n_pgs, 3)
    assert np.array_equal(res1, res2)
    assert np.array_equal(cnt1, cnt2)


@pytest.mark.parametrize(
    "n_dev",
    # tier-1 keeps one ragged submesh (3) and the full mesh (8);
    # every other size is a fresh compile — slow tier
    [
        pytest.param(1, marks=pytest.mark.slow),
        pytest.param(2, marks=pytest.mark.slow),
        3,
        pytest.param(5, marks=pytest.mark.slow),
        8,
    ],
)
def test_sharded_mapping_any_device_count(n_dev):
    """Device-count-agnostic: every submesh size gives the same
    table (37 PGs is ragged for every n_dev > 1 here)."""
    m = build_hierarchy(32, 4, 2)
    cm = jaxmap.compile_map(m)
    xs = np.arange(37)
    res1, cnt1 = jaxmap.batch_do_rule(cm, 0, xs, 3)
    dmesh = meshmod.build_mesh(n_dev)
    res2, cnt2 = sharded_batch_do_rule(cm, 0, xs, 3, dmesh=dmesh)
    assert np.array_equal(res1, res2) and np.array_equal(cnt1, cnt2)


def test_sharded_mapping_with_reweights_and_oracle_check():
    """Non-default reweight vector through the sharded path, every
    lane checked against the exact host oracle."""
    m = build_hierarchy(16, 4, 2)
    cm = jaxmap.compile_map(m)
    weights = np.full(16, 0x10000, np.int32)
    weights[3] = 0x4000
    weights[7] = 0
    xs = np.arange(53)
    dmesh = meshmod.build_mesh()
    res, cnt = sharded_batch_do_rule(
        cm, 0, xs, 3, weights=weights, dmesh=dmesh
    )
    wl = [int(w) for w in weights]
    for x in range(53):
        oracle = m.do_rule(0, x, 3, wl)
        assert cnt[x] == len(oracle)
        assert res[x].tolist()[: len(oracle)] == oracle


def test_sharded_pg_mapper_wrapper():
    # same map shape + PG count as the any_device_count[8] case, so
    # the sharded program is a jit-cache hit, not a fresh compile
    m = build_hierarchy(32, 4, 2)
    mapper = ShardedPGMapper(m, meshmod.build_mesh())
    res, cnt = mapper.map_pgs(0, np.arange(37), 3)
    ref = jaxmap.batch_do_rule(jaxmap.compile_map(m), 0, np.arange(37), 3)
    assert np.array_equal(res, ref[0]) and np.array_equal(cnt, ref[1])


@pytest.mark.parametrize("batch", [1, 13, 64 + 3])
def test_sharded_ec_encode_byte_identity_ragged(batch):
    import jax.numpy as jnp

    from ceph_tpu import gf
    from ceph_tpu.ops.gf_matmul import (
        gf_matrix_stripes,
        matrix_to_device_bitmatrix,
    )

    mat = gf.reed_sol_vandermonde_coding_matrix(4, 2, 8)
    bm = matrix_to_device_bitmatrix(mat, 8)
    rng = np.random.default_rng(7)
    stripes = rng.integers(0, 256, size=(batch, 4, 512), dtype=np.uint8)
    ref = np.asarray(gf_matrix_stripes(bm, jnp.asarray(stripes), w=8))
    out = meshmod.sharded_matrix_stripes(
        bm, stripes, 8, meshmod.build_mesh()
    )
    assert out.dtype == np.uint8 and np.array_equal(ref, out)


def test_ec_backend_routes_through_mesh(fresh_default_mesh):
    """Product wiring: the registered jax EC backend's batched
    stripe encode shards across the default mesh when >1 device
    exists (and the batch is worth splitting) — identical shards to
    the mesh-disabled path, and the dispatch lands in the mesh
    telemetry counters."""
    from ceph_tpu.ec import ErasureCodeProfile, registry_instance
    from ceph_tpu.ec.stripe import StripeInfo
    from ceph_tpu.ec.stripe import encode as stripe_encode

    prof = ErasureCodeProfile({"k": "2", "m": "1", "backend": "jax"})
    ec = registry_instance().factory("jerasure", prof)
    sinfo = StripeInfo(2, 2 * ec.get_chunk_size(2 * 1024))
    nstripes = 11  # ragged for the 8-device mesh
    data = (
        np.arange(nstripes * sinfo.stripe_width, dtype=np.uint8) % 251
    )

    fresh_default_mesh.setenv("CEPH_TPU_MESH", "0")
    single = stripe_encode(sinfo, ec, data)
    meshmod._reset_default_mesh_for_tests()
    fresh_default_mesh.setenv("CEPH_TPU_MESH", "1")
    assert meshmod.default_mesh() is not None  # 8 virtual devices
    before = kernel_stats().dump().get("l_tpu_mesh_ec_encode_calls", 0)
    sharded = stripe_encode(sinfo, ec, data)
    after = kernel_stats().dump().get("l_tpu_mesh_ec_encode_calls", 0)
    assert after > before, "encode did not route through the mesh"
    assert set(single) == set(sharded)
    for i in single:
        assert bytes(bytes(single[i])) == bytes(bytes(sharded[i]))


def test_per_device_telemetry_counters():
    """Every sharded dispatch lands per-device counters
    (l_tpu_mesh_dev<i>_calls/_bytes) plus the group rollup, flowing
    through the same kernel-stats plane as every other kernel."""
    ks = kernel_stats()
    before = ks.dump()
    m = build_hierarchy(32, 4, 2)
    cm = jaxmap.compile_map(m)
    dmesh = meshmod.build_mesh()
    # 37 PGs again: jit-cache hit, the test measures counters only
    sharded_batch_do_rule(cm, 0, np.arange(37), 3, dmesh=dmesh)
    dump = ks.dump()
    assert (
        dump["l_tpu_mesh_crush_calls"]
        > before.get("l_tpu_mesh_crush_calls", 0)
    )
    for i in range(8):
        name = f"l_tpu_mesh_dev{i}_calls"
        assert dump[name] > before.get(name, 0), name
        assert dump[f"l_tpu_mesh_dev{i}_bytes"] > before.get(
            f"l_tpu_mesh_dev{i}_bytes", 0
        )


def test_mesh_batch_do_rule_product_dispatch(fresh_default_mesh):
    """The osd/mapping entry point: shards over the default mesh
    when it exists, degrades to the single-device call when not —
    same bytes either way."""
    # 37 PGs on the (32,4,2) map: both the single-device and the
    # 8-mesh programs are jit-cache hits from the earlier tests
    m = build_hierarchy(32, 4, 2)
    cm = jaxmap.compile_map(m)
    xs = np.arange(37)
    fresh_default_mesh.setenv("CEPH_TPU_MESH", "0")
    res_off, cnt_off = mesh_batch_do_rule(cm, 0, xs, 3)
    meshmod._reset_default_mesh_for_tests()
    fresh_default_mesh.setenv("CEPH_TPU_MESH", "1")
    res_on, cnt_on = mesh_batch_do_rule(cm, 0, xs, 3)
    assert np.array_equal(res_off, res_on)
    assert np.array_equal(cnt_off, cnt_on)
