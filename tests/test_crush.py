"""CRUSH oracle tests.

The golden file tests/data/crush_do_rule_golden.txt.gz holds 3000
mappings produced by the reference C implementation (mapper.c compiled
as-is, maps built with builder.c) over five scenarios covering all
bucket algorithms, firstn+indep, chooseleaf recursion, three tunables
profiles, fractional reweights and out devices.  The Python oracle must
reproduce every line.
"""

from __future__ import annotations

import gzip
import pathlib

import numpy as np
import pytest

from ceph_tpu.crush.builder import CrushMap
from ceph_tpu.crush.hashing import (
    crush_hash32,
    crush_hash32_2,
    crush_hash32_3,
    crush_hash32_4,
    crush_hash32_5,
)
from ceph_tpu.crush.ln import crush_ln
from ceph_tpu.crush.types import (
    CRUSH_BUCKET_LIST,
    CRUSH_BUCKET_STRAW,
    CRUSH_BUCKET_STRAW2,
    CRUSH_BUCKET_TREE,
    CRUSH_BUCKET_UNIFORM,
    CRUSH_ITEM_NONE,
    CRUSH_RULE_CHOOSELEAF_FIRSTN,
    CRUSH_RULE_CHOOSELEAF_INDEP,
    CRUSH_RULE_CHOOSE_FIRSTN,
    CRUSH_RULE_CHOOSE_INDEP,
    CRUSH_RULE_EMIT,
    CRUSH_RULE_SET_CHOOSELEAF_TRIES,
    CRUSH_RULE_SET_CHOOSE_TRIES,
    CRUSH_RULE_TAKE,
    Rule,
    RuleStep,
    Tunables,
)

DATA = pathlib.Path(__file__).parent / "data"


# -- primitives ------------------------------------------------------------


def test_hash_anchors():
    """Anchors computed from the reference hash.c compiled standalone."""
    assert crush_hash32(0) == 398764043
    assert crush_hash32(12345) == 3450610134
    assert crush_hash32_2(0, 0) == 430787817
    assert crush_hash32_2(12345, 67890) == 257117510
    assert crush_hash32_3(0, 0, 0) == 2050749362
    assert crush_hash32_4(0, 1, 2, 3) == 4068496190
    assert crush_hash32_5(0, 1, 2, 3, 4) == 3258139504


def test_hash_vectorized_matches_scalar():
    rng = np.random.default_rng(0)
    a = rng.integers(0, 1 << 32, 256, dtype=np.uint32)
    b = rng.integers(0, 1 << 32, 256, dtype=np.uint32)
    c = rng.integers(0, 1 << 32, 256, dtype=np.uint32)
    vec = crush_hash32_3(a, b, c)
    for i in range(0, 256, 17):
        assert int(vec[i]) == crush_hash32_3(
            int(a[i]), int(b[i]), int(c[i])
        )


def test_crush_ln_anchors():
    """Anchors from the reference crush_ln + crush_ln_table.h."""
    anchors = {
        0: 0,
        1: 17592186044416,
        2: 27882955186109,
        255: 140737488355328,
        256: 140836779814266,
        4095: 211106232532992,
        32767: 263882790666240,
        32768: 263883565195424,
        43981: 271353073090888,
        65534: 281474932780304,
        65535: 281474708275200,
    }
    for u, expect in anchors.items():
        assert crush_ln(u) == expect, u
    arr = np.array(sorted(anchors), dtype=np.uint32)
    got = crush_ln(arr)
    assert got.tolist() == [anchors[int(u)] for u in arr]


def test_crush_ln_monotonic():
    vals = crush_ln(np.arange(0x10000, dtype=np.uint32))
    d = np.diff(vals)
    assert (d >= 0).sum() >= 0xFFFE  # one table-sentinel dip at the top


# -- golden scenario replication ------------------------------------------

# straw_calc_version=0 everywhere: the reference's crush_create() leaves
# it 0 (builder.c:15-25 memset + set_optimal_crush_map, which does not
# touch it)
JEWEL = Tunables(0, 0, 50, 1, 1, 1, 0)
ARGONAUT = Tunables(2, 5, 19, 0, 0, 0, 0)
FIREFLY = Tunables(0, 0, 50, 1, 1, 0, 0)


def _add_two_rules(m: CrushMap, root: int, domain_type: int) -> None:
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


def _two_level(tun, algs, nhosts, per_host, wfun, root_alg) -> CrushMap:
    m = CrushMap(tunables=tun)
    hosts = []
    for h in range(nhosts):
        items = [h * per_host + i for i in range(per_host)]
        weights = [wfun(h, i) for i in range(per_host)]
        hosts.append(m.add_bucket(algs[h % len(algs)], 1, items, weights))
    hw = [m.buckets[b].weight for b in hosts]
    root = m.add_bucket(root_alg, 3, hosts, hw)
    _add_two_rules(m, root, 1)
    return m


def _scenarios() -> dict[int, CrushMap]:
    m0 = CrushMap(tunables=JEWEL)
    root = m0.add_bucket(
        CRUSH_BUCKET_STRAW2,
        3,
        list(range(10)),
        [(i + 1) * 0x10000 // 2 for i in range(10)],
    )
    _add_two_rules(m0, root, 0)
    return {
        0: m0,
        1: _two_level(
            JEWEL,
            [CRUSH_BUCKET_STRAW2],
            5,
            4,
            lambda h, i: 0x10000 + i * 0x4000,
            CRUSH_BUCKET_STRAW2,
        ),
        2: _two_level(
            JEWEL,
            [
                CRUSH_BUCKET_UNIFORM,
                CRUSH_BUCKET_LIST,
                CRUSH_BUCKET_TREE,
                CRUSH_BUCKET_STRAW,
                CRUSH_BUCKET_STRAW2,
            ],
            5,
            4,
            lambda h, i: 0x18000 if h % 5 == 0 else 0x10000 + i * 0x6000,
            CRUSH_BUCKET_STRAW2,
        ),
        3: _two_level(
            ARGONAUT,
            [CRUSH_BUCKET_STRAW],
            6,
            3,
            lambda h, i: 0x10000 * (1 + (h + i) % 3),
            CRUSH_BUCKET_STRAW,
        ),
        4: _two_level(
            FIREFLY,
            [CRUSH_BUCKET_STRAW2],
            4,
            5,
            lambda h, i: 0x8000 * (1 + (i % 4)),
            CRUSH_BUCKET_STRAW2,
        ),
    }


def reference_weight_vector(n: int) -> list[int]:
    w = []
    for i in range(n):
        v = 0x10000
        if i % 7 == 3:
            v = 0x8000
        if i % 11 == 5:
            v = 0
        w.append(v)
    return w


def test_do_rule_matches_reference_c():
    maps = _scenarios()
    golden = gzip.open(
        DATA / "crush_do_rule_golden.txt.gz", "rt"
    ).read().splitlines()
    checked = 0
    for line in golden:
        head, _, tail = line.partition(" ->")
        scen_s, rule_s, x_s, max_s = head.split()
        scen = int(scen_s[1:])
        rule = int(rule_s[1:])
        x = int(x_s.split("=")[1])
        rmax = int(max_s.split("=")[1])
        expect = [int(v) for v in tail.split()]
        m = maps[scen]
        got = m.do_rule(
            rule, x, rmax, reference_weight_vector(m.max_devices)
        )
        assert got == expect, (scen, rule, x, rmax, got, expect)
        checked += 1
    assert checked == 3000


# -- behavioral properties -------------------------------------------------


def test_straw2_distribution_proportional():
    """P(item) ∝ weight over many inputs (mapper.c:293-307 design)."""
    m = CrushMap(tunables=JEWEL)
    weights = [0x10000, 0x20000, 0x40000, 0x80000]
    root = m.add_bucket(CRUSH_BUCKET_STRAW2, 3, [0, 1, 2, 3], weights)
    _add_two_rules(m, root, 0)
    counts = np.zeros(4)
    n = 8000
    for x in range(n):
        (osd,) = m.do_rule(0, x, 1)
        counts[osd] += 1
    frac = counts / n
    expect = np.array(weights, dtype=float) / sum(weights)
    assert np.abs(frac - expect).max() < 0.02


def test_indep_positional_stability():
    """EC mappings keep surviving positions when a device goes out:
    the outer host choice and the chooseleaf descent of unaffected
    hosts see identical r' sequences, so only the lost shard moves."""
    m = _scenarios()[1]
    moved = 0
    for x in range(50):
        full = m.do_rule(1, x, 5)
        lost = full[2]
        weights = [0x10000] * m.max_devices
        if lost == CRUSH_ITEM_NONE:
            continue
        weights[lost] = 0
        degraded = m.do_rule(1, x, 5, weights)
        assert lost not in degraded
        for pos in range(5):
            if pos != 2:
                assert degraded[pos] == full[pos], (x, pos, full, degraded)
        if degraded[2] not in (lost, CRUSH_ITEM_NONE):
            moved += 1
    assert moved > 0  # the lost shard does get re-homed


def test_firstn_no_duplicates_and_failure_domains():
    m = _scenarios()[1]
    for x in range(100):
        res = m.do_rule(0, x, 3)
        assert len(res) == len(set(res))
        hosts = {osd // 4 for osd in res}
        assert len(hosts) == len(res)  # one osd per host


def test_out_device_never_chosen():
    m = _scenarios()[0]
    weights = [0x10000] * 10
    weights[7] = 0
    for x in range(200):
        assert 7 not in m.do_rule(0, x, 3, weights)


def test_add_simple_rule_and_find_rule():
    m = CrushMap(tunables=JEWEL)
    hosts = []
    for h in range(3):
        hosts.append(
            m.add_bucket(
                CRUSH_BUCKET_STRAW2,
                1,
                [h * 2, h * 2 + 1],
                [0x10000, 0x10000],
                name=f"host{h}",
            )
        )
    root = m.add_bucket(
        CRUSH_BUCKET_STRAW2,
        3,
        hosts,
        [m.buckets[b].weight for b in hosts],
        name="default",
    )
    rno = m.add_simple_rule("ec_rule", "default", "host", mode="indep")
    assert m.find_rule(rno, 3, 4) == rno
    res = m.do_rule(rno, 1234, 3)
    assert len(res) == 3
    placed = [r for r in res if r != CRUSH_ITEM_NONE]
    assert len({p // 2 for p in placed}) == len(placed)


# -- choose_args golden (weight-set + id-remap maps) -----------------------


def build_choose_args_scenario():
    """The map tests/data/gen_choose_args_golden.c builds: two-level
    straw2 (5 hosts x 4 devices), host0 carrying a 2-position
    weight_set, host2 an ids remap, and the root a 1-position
    weight_set — the mgr balancer's crush-compat shapes
    (crush.h:248-293)."""
    from ceph_tpu.crush.types import ChooseArg

    m = CrushMap(tunables=JEWEL)
    hosts = []
    for h in range(5):
        items = [h * 4 + i for i in range(4)]
        weights = [0x10000 + i * 0x4000 for i in range(4)]
        hosts.append(m.add_bucket(CRUSH_BUCKET_STRAW2, 1, items, weights))
    hw = [m.buckets[b].weight for b in hosts]
    root = m.add_bucket(CRUSH_BUCKET_STRAW2, 3, hosts, hw)
    _add_two_rules(m, root, 1)
    m.set_choose_args({
        hosts[0]: ChooseArg(
            weight_set=[
                [0x8000 + i * 0x2000 for i in range(4)],
                [0x20000 - i * 0x3000 for i in range(4)],
            ]
        ),
        hosts[2]: ChooseArg(ids=[1008, 1009, 1010, 1011]),
        root: ChooseArg(
            weight_set=[[0x40000 + i * 0x10000 for i in range(5)]]
        ),
    })
    return m


def iter_choose_args_golden():
    import re

    golden = gzip.open(
        DATA / "crush_choose_args_golden.txt.gz", "rt"
    ).read().splitlines()
    for line in golden:
        tag, rule, nrep, x, res = re.match(
            r"(\w+) (\d+) (\d+) (\d+) \[(.*)\]", line
        ).groups()
        want = [int(v) for v in res.split(",")] if res else []
        yield tag, int(rule), int(nrep), int(x), want


def test_choose_args_matches_reference_c():
    """Oracle vs compiled reference C over weight-set/id-remap maps —
    both with choose_args applied ('ca' lines) and without ('nc'),
    anchoring the position semantics (firstn: running outpos; indep:
    frame outpos, i.e. slot in the leaf recursion)."""
    from ceph_tpu.crush.mapper import crush_do_rule

    m = build_choose_args_scenario()
    weight = reference_weight_vector(20)
    checked = 0
    for tag, rule, nrep, x, want in iter_choose_args_golden():
        ca = m.choose_args if tag == "ca" else {}
        got = crush_do_rule(m, rule, x, nrep, weight, choose_args=ca)
        assert got == want, (tag, rule, nrep, x, want, got)
        checked += 1
    assert checked == 1200


# -- device classes (shadow trees) -----------------------------------------


def build_class_map():
    """3 hosts x 4 devices, alternating hdd/ssd devices; per-class
    rules via shadow trees (CrushWrapper.cc:2681 device_class_clone)."""
    m = CrushMap(tunables=JEWEL)
    hosts = []
    for h in range(3):
        items = [h * 4 + i for i in range(4)]
        weights = [0x10000 + i * 0x4000 for i in range(4)]
        hosts.append(
            m.add_bucket(
                CRUSH_BUCKET_STRAW2, 1, items, weights,
                name=f"host{h}",
            )
        )
    hw = [m.buckets[b].weight for b in hosts]
    root = m.add_bucket(CRUSH_BUCKET_STRAW2, 3, hosts, hw, name="default")
    for dev in range(12):
        m.set_item_class(dev, "hdd" if dev % 2 == 0 else "ssd")
    return m, root


def test_device_class_shadow_trees():
    m, root = build_class_map()
    r_hdd = m.add_simple_rule("hdd_rule", "default", "host",
                              device_class="hdd")
    r_ssd = m.add_simple_rule("ssd_rule", "default", "host",
                              device_class="ssd", mode="indep")
    # shadow hierarchy exists with rolled-up weights
    sroot = m._name_to_item("default~hdd")
    assert sroot in m.buckets
    hdd_weight = sum(
        0x10000 + i * 0x4000 for i in range(0, 4, 2)
    ) * 3
    assert m.buckets[sroot].weight == hdd_weight
    # mappings stay inside the class
    for x in range(64):
        for rule, parity in ((r_hdd, 0), (r_ssd, 1)):
            out = m.do_rule(rule, x, 2)
            assert out, (rule, x)
            for dev in out:
                if dev >= 0:
                    assert dev % 2 == parity, (rule, x, out)


def test_device_class_rebuild_keeps_ids_and_tracks_weights():
    m, root = build_class_map()
    m.add_simple_rule("hdd_rule", "default", "host", device_class="hdd")
    sroot = m._name_to_item("default~hdd")
    before = dict(m.class_bucket)
    # reweight a device and rebuild: same shadow ids, new rollup
    h0 = m._name_to_item("host0")
    m.buckets[h0].item_weights[0] = 0x40000
    m.buckets[h0].weight = sum(m.buckets[h0].item_weights)
    m.touch()
    m.populate_classes()
    assert m.class_bucket == before
    sh0 = m.class_bucket[h0][m.get_class_id("hdd")]
    assert m.buckets[sh0].item_weights[0] == 0x40000


def test_device_class_on_device_kernel():
    """Shadow trees are plain straw2 buckets: the device kernel maps
    them with no special casing, oracle-equal."""
    import os

    import numpy as np

    from ceph_tpu.crush.jaxmap import batch_do_rule, compile_map

    m, root = build_class_map()
    r_hdd = m.add_simple_rule("hdd_rule", "default", "host",
                              device_class="hdd")
    cm = compile_map(m)
    xs = np.arange(128, dtype=np.int64)
    got, counts = batch_do_rule(cm, r_hdd, xs, 2)
    got, counts = np.asarray(got), np.asarray(counts)
    for x in range(128):
        expect = m.do_rule(r_hdd, x, 2)
        assert got[x, : counts[x]].tolist() == expect, x


def test_device_class_retag_never_aliases_clone_ids():
    """Retiring a class keeps its clone ids reserved (a rule may still
    TAKE them; the class may return) — a new class must never be
    handed a retired class's ids, and a returning class reclaims its
    own (the C's used_ids discipline, CrushWrapper.cc:2744-2752)."""
    m, root = build_class_map()
    m.populate_classes()
    ssd_root = m._name_to_item("default~ssd")
    for d in range(1, 12, 2):
        m.set_item_class(d, "nvme")
    m.populate_classes()
    nvme_root = m._name_to_item("default~nvme")
    assert nvme_root != ssd_root
    assert ssd_root not in m.buckets  # retired tree leaves the map
    m.set_item_class(1, "ssd")
    m.populate_classes()
    assert m._name_to_item("default~ssd") == ssd_root  # id reclaimed
    cid_s, cid_n = m.get_class_id("ssd"), m.get_class_id("nvme")
    h0 = m._name_to_item("host0")
    assert m.buckets[m.class_bucket[h0][cid_s]].items == [1]
    assert m.buckets[m.class_bucket[h0][cid_n]].items == [3]


def test_choose_args_empty_weight_set_falls_back():
    """ChooseArg(weight_set=[]) behaves like no weight replacement
    (the C's weight_set_positions == 0), on oracle and device."""
    import numpy as np

    from ceph_tpu.crush.jaxmap import batch_do_rule, compile_map
    from ceph_tpu.crush.types import ChooseArg

    m = _scenarios()[1]
    root = min(m.buckets)
    m.set_choose_args({root: ChooseArg(weight_set=[])})
    cm = compile_map(m)  # must not crash
    xs = np.arange(64, dtype=np.int64)
    got, counts = batch_do_rule(cm, 0, xs, 3)
    got, counts = np.asarray(got), np.asarray(counts)
    for x in range(64):
        expect = m.do_rule(0, x, 3)
        assert got[x, : counts[x]].tolist() == expect, x


def test_crush_ln_tables_survive_concurrent_first_calls():
    """Ten OSDs booting in one process all ask for the tables at once;
    ``base64.b85decode`` fills its decode table lazily and unlocked, so
    without the lock round the build one of them can read it half
    filled ("bad base85 character", seen once as a pool that never
    went active).  Many first calls, a short switch interval."""
    import base64
    import sys
    import threading

    from ceph_tpu.crush import ln

    want = int(crush_ln(0x7FFF))
    errors: list[BaseException] = []

    def first_call(go: threading.Event) -> None:
        go.wait(5)
        try:
            assert int(ln.crush_ln(0x7FFF)) == want
        except BaseException as e:  # noqa: BLE001 — collected, then raised
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(6):
            ln._tables.cache_clear()
            base64._b85dec = None  # as in a process that never decoded
            go = threading.Event()
            pool = [
                threading.Thread(target=first_call, args=(go,))
                for _ in range(16)
            ]
            for t in pool:
                t.start()
            go.set()
            for t in pool:
                t.join(30)
            assert not any(t.is_alive() for t in pool)
    finally:
        sys.setswitchinterval(interval)
        ln._tables.cache_clear()
    assert errors == []
