"""A remap a part at a time (ISSUE 37): ``jaxmap.map_parts`` hands a
part out with the parts after it already issued, and
``OSDMapMapping.update`` finishes each part — fallback lanes, widening,
fix-ups, its rows of the four tables — under their kernels.  Every
stage is row-local, so several parts give the bytes of one."""

from __future__ import annotations

import time

import numpy as np
import pytest

from ceph_tpu.crush import jaxmap
from ceph_tpu.crush.builder import CrushMap
from ceph_tpu.crush.types import (
    CRUSH_BUCKET_STRAW2,
    CRUSH_ITEM_NONE,
    PG_POOL_TYPE_ERASURE,
    PG_POOL_TYPE_REPLICATED,
    Tunables,
)
from ceph_tpu.ops.kernel_stats import kernel_stats
from ceph_tpu.osd import OSDMap, OSDMapMapping, PgPool
from ceph_tpu.osd.mapping import pool_pps_vec

JEWEL = Tunables(0, 0, 50, 1, 1, 1, 0)
PG_NUM = 1000  # 3 parts of 256 and a ragged one of 232
TABLES = ("up", "up_primary", "acting", "acting_primary")
# a PG of the first part, of a middle one, of the last (ragged) one
SPREAD = (3, 500, 999)


def _cluster(pool: str) -> OSDMap:
    """24 OSDs under 6 hosts with one pool of PG_NUM PGs — replicated
    (firstn: rows compact) or erasure (indep: holes stay) — and every
    stage of the pipeline given something to do in each of SPREAD's
    parts."""
    m = CrushMap(tunables=JEWEL)
    hosts = []
    for h in range(6):
        items = list(range(h * 4, h * 4 + 4))
        weights = [0x10000 + (i % 3) * 0x8000 for i in items]
        hosts.append(
            m.add_bucket(CRUSH_BUCKET_STRAW2, 1, items, weights, name=f"h{h}")
        )
    m.add_bucket(
        CRUSH_BUCKET_STRAW2, 3, hosts,
        [m.buckets[b].weight for b in hosts], name="default",
    )
    rep = m.add_simple_rule("rep", "default", "host", mode="firstn")
    ec = m.add_simple_rule("ecr", "default", "host", mode="indep")
    om = OSDMap.build(m, 24)
    if pool == "replicated":
        om.add_pool(
            PgPool(pool_id=1, type=PG_POOL_TYPE_REPLICATED, size=3,
                   pg_num=PG_NUM, crush_rule=rep)
        )
        full = [0, 4, 8]
    else:
        om.add_pool(
            PgPool(pool_id=1, type=PG_POOL_TYPE_ERASURE, size=5,
                   pg_num=PG_NUM, crush_rule=ec)
        )
        full = [0, 4, 8, 12, 16]
    om.mark_down(5)
    om.mark_down(13)
    om.osd_exists[17] = False
    om.mark_out(9)
    om.osd_weight[2] = 0x8000
    om.osd_primary_affinity = [0x10000] * 24
    om.osd_primary_affinity[0] = 0
    om.osd_primary_affinity[4] = 0x4000
    om.osd_primary_affinity[8] = 0x8000
    for ps in SPREAD:
        om.pg_upmap[(1, ps)] = list(full)
        om.pg_upmap_items[(1, ps + 1)] = [(0, 20), (4, 21)]
        om.pg_temp[(1, ps - 1)] = [10, 11, 12][: len(full)]
        om.primary_temp[(1, ps - 2)] = 15
    return om


def _tables(mapping: OSDMapMapping, pool_id: int = 1) -> dict:
    return {t: getattr(mapping, t)[pool_id] for t in TABLES}


def _norm(row) -> list:
    row = [int(o) for o in row]
    while row and row[-1] == CRUSH_ITEM_NONE:
        row.pop()
    return row


def _counter(name: str) -> int:
    return kernel_stats().dump().get(name, 0)


def _force_to_the_oracle(monkeypatch, values) -> None:
    """The kernel's answer spoiled and handed back (``ok`` False) for
    the lanes whose input is one of ``values``: only the oracle's
    re-mapping of them gives the right rows."""
    import jax.numpy as jnp

    real = jaxmap.batched_rule_call
    values = jnp.asarray(np.asarray(values).astype(np.int32))

    def forced(cm, ruleno, result_max, weights):
        fn, tables = real(cm, ruleno, result_max, weights)

        def spoiled(xs, wv, *packs):
            res, counts, ok = fn(xs, wv, *packs)
            hit = jnp.isin(xs, values)
            return jnp.where(hit[:, None], 0, res), counts, ok & ~hit

        return spoiled, tables

    monkeypatch.setattr(jaxmap, "batched_rule_call", forced)


@pytest.mark.parametrize("oracle_lanes", [False, True], ids=["kernel", "oracle_lanes"])
@pytest.mark.parametrize("pool", ["replicated", "erasure"])
def test_several_parts_give_the_rows_of_one(monkeypatch, pool, oracle_lanes):
    om = _cluster(pool)
    whole = OSDMapMapping()
    whole.update(om)  # CHUNK_LANES as shipped: one part
    want = _tables(whole)

    monkeypatch.setattr(jaxmap, "CHUNK_LANES", 256)
    if oracle_lanes:
        pps = pool_pps_vec(om.pools[1], np.asarray(SPREAD, dtype=np.int64))
        _force_to_the_oracle(monkeypatch, pps)
    lanes = _counter("l_tpu_crush_fallback_lanes")
    parts = OSDMapMapping()
    parts.update(om)
    got = _tables(parts)
    assert _counter("l_tpu_crush_fallback_lanes") - lanes == (
        len(SPREAD) if oracle_lanes else 0
    )
    for table in TABLES:
        assert got[table].dtype == want[table].dtype == np.int64
        assert got[table].shape == want[table].shape
        assert np.array_equal(got[table], want[table]), table
    for ps in range(PG_NUM):
        up, upp, acting, actp = om.pg_to_up_acting_osds(1, ps)
        gup, gupp, gact, gactp = parts.get(1, ps)
        assert (gup, gupp, gact, gactp) == (_norm(up), upp, _norm(acting), actp), ps


def test_the_host_maps_a_pool_in_one_part(monkeypatch):
    """Off the device (``use_device`` False, or a map outside the
    kernel's scope) the pool is one call of each stage, whatever
    CHUNK_LANES is."""
    om = _cluster("replicated")
    monkeypatch.setattr(jaxmap, "CHUNK_LANES", 256)
    seen = []
    real = OSDMapMapping._crush_stage

    def spy(self, osdmap, pool, pps, use_device):
        seen.append((len(pps), use_device))
        return real(self, osdmap, pool, pps, use_device)

    monkeypatch.setattr(OSDMapMapping, "_crush_stage", spy)
    host = OSDMapMapping()
    host.update(om, use_device=False)
    assert seen == [(PG_NUM, False)]
    device = OSDMapMapping()
    device.update(om)
    assert seen[1:] == [(256, True)] * 3 + [(232, True)]
    for table in TABLES:
        assert np.array_equal(_tables(host)[table], _tables(device)[table])
    # a map the kernel refuses: found before a part is cut
    del seen[:]

    def refuse(_crush_map):
        raise jaxmap.UnsupportedMap("a test's refusal")

    monkeypatch.setattr("ceph_tpu.osd.mapping._compiled", refuse)
    refused = OSDMapMapping()
    refused.update(om)
    assert seen == [(PG_NUM, False)]
    assert np.array_equal(refused.up[1], host.up[1])


def _events(n_parts: int, chunk: int = 8, work=None):
    """The order in which ``map_parts`` issues parts and hands them
    out over ``n_parts`` parts of ``chunk`` lanes (the last ragged)."""
    events = []

    def dispatch(part):
        events.append(("issue", int(part[0]) // chunk))
        res = np.repeat(np.asarray(part)[:, None], 3, axis=1)
        return res, np.full(len(part), 3), np.ones(len(part), dtype=bool)

    xs = np.arange(n_parts * chunk - 3, dtype=np.int32)
    for lo, res, counts, ok in jaxmap.map_parts(dispatch, xs, chunk):
        events.append(("got", lo // chunk))
        assert np.array_equal(res[:, 0], xs[lo : lo + chunk])
        assert len(res) == len(counts) == len(ok) == len(xs[lo : lo + chunk])
        if work:
            work()
    return events


@pytest.mark.parametrize("n_parts", [1, 2, 4, 9])
def test_parts_are_issued_ahead_and_never_beyond_the_depth(n_parts):
    events = _events(n_parts)
    assert [e for e in events if e[0] == "issue"] == [
        ("issue", i) for i in range(n_parts)
    ]
    assert [e for e in events if e[0] == "got"] == [
        ("got", i) for i in range(n_parts)
    ]
    issued = 0
    for kind, i in events:
        if kind == "issue":
            issued += 1
            continue
        # part i is handed out with every part up to i + PARTS_AHEAD
        # issued — the next one among them — and none beyond
        assert issued == min(i + 1 + jaxmap.PARTS_AHEAD, n_parts), events
    assert n_parts == 1 or events.index(("issue", 1)) < events.index(("got", 0))


def test_the_tail_repeats_its_last_input_and_one_part_is_not_padded():
    lanes = []

    def dispatch(part):
        lanes.append(np.asarray(part).tolist())
        n = len(part)
        return np.zeros((n, 3), np.int32), np.zeros(n, np.int32), np.ones(n, bool)

    res, counts, ok = jaxmap.map_chunked(dispatch, np.arange(11, dtype=np.int32), 4)
    assert lanes == [[0, 1, 2, 3], [4, 5, 6, 7], [8, 9, 10, 10]]
    assert res.shape == (11, 3) and counts.shape == ok.shape == (11,)
    del lanes[:]
    res, counts, ok = jaxmap.map_chunked(dispatch, np.arange(3, dtype=np.int32), 4)
    assert lanes == [[0, 1, 2]] and res.shape == (3, 3)


def test_host_time_is_overlapped_at_four_parts_and_not_at_one():
    def spent(n_parts):
        before = (
            _counter("l_tpu_crush_host_ns"),
            _counter("l_tpu_crush_host_overlapped_ns"),
        )
        _events(n_parts, work=lambda: time.sleep(0.002))
        return (
            _counter("l_tpu_crush_host_ns") - before[0],
            _counter("l_tpu_crush_host_overlapped_ns") - before[1],
        )

    host, overlapped = spent(1)
    assert host >= 2_000_000 and overlapped == 0
    host, overlapped = spent(4)
    # the last of four parts has nothing behind it: three of four sleeps
    assert host >= 8_000_000 and 6_000_000 <= overlapped < host
    assert overlapped == pytest.approx(0.75 * host, rel=0.2)


def test_a_remap_counts_its_overlapped_host_time(monkeypatch):
    """The same two counters through ``OSDMapMapping.update``: the
    fix-ups of every part but the last run with a part on the device;
    a caller that abandons the parts still closes the books."""
    om = _cluster("replicated")
    mapping = OSDMapMapping()

    def spent():
        before = (
            _counter("l_tpu_crush_host_ns"),
            _counter("l_tpu_crush_host_overlapped_ns"),
        )
        mapping.update(om)
        return (
            _counter("l_tpu_crush_host_ns") - before[0],
            _counter("l_tpu_crush_host_overlapped_ns") - before[1],
        )

    host, overlapped = spent()
    assert host > 0 and overlapped == 0
    monkeypatch.setattr(jaxmap, "CHUNK_LANES", 256)
    host, overlapped = spent()
    assert 0 < overlapped < host
    assert mapping._parts is None
    # a fix-up that raises leaves no pipeline behind
    monkeypatch.setattr(
        OSDMapMapping, "_upmap_stage",
        lambda *a: (_ for _ in ()).throw(RuntimeError("a test's fault")),
    )
    with pytest.raises(RuntimeError, match="a test's fault"):
        mapping.update(om)
    assert mapping._parts is None
