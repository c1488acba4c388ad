"""Erasure pools under the OSD daemon — ONE PG machinery for both
backends (the build_pg_backend split, src/osd/PGBackend.cc:571-607;
ECBackend under PrimaryLogPG, src/osd/ECBackend.cc:1502,2364).

The VERDICT round-2 acceptance walk: create an EC pool through the
monitor, write through librados, kill a shard OSD, watch the mon mark
it down, read degraded (reconstructing), write degraded, revive the
OSD and watch log-driven recovery hand it reconstructed shards — for
CLAY profiles via minimum (fractional-chunk) helper reads.
"""

from __future__ import annotations

import json
import time

import pytest

from ceph_tpu.crush.builder import CrushMap
from ceph_tpu.crush.types import CRUSH_BUCKET_STRAW2, Tunables
from ceph_tpu.mon.monitor import Monitor
from ceph_tpu.msg import Messenger
from ceph_tpu.osd.daemon import OBJ_PREFIX, OSD
from ceph_tpu.osd.ec_pg import ECCodec
from ceph_tpu.osd.osdmap import OSDMap
from ceph_tpu.rados import Rados
from ceph_tpu.store.ec_store import HINFO_KEY
import ceph_tpu.store.ec_store as ec_store_mod


def _base_map(n: int) -> OSDMap:
    cmap = CrushMap(tunables=Tunables())
    hosts = []
    for h in range(n):
        hosts.append(
            cmap.add_bucket(
                CRUSH_BUCKET_STRAW2, 1, [h], [0x10000],
                name=f"host{h}",
            )
        )
    cmap.add_bucket(
        CRUSH_BUCKET_STRAW2, 3, hosts,
        [cmap.buckets[b].weight for b in hosts], name="default",
    )
    cmap.add_simple_rule("rep", "default", "host", mode="firstn")
    return OSDMap.build(cmap, n)


class ECCluster:
    """Monitor + n OSD daemons + a librados client."""

    def __init__(self, n: int):
        self.n = n
        self.mon = Monitor(_base_map(n), min_reporters=2)
        self.mon_msgr = Messenger("mon")
        self.mon_msgr.add_dispatcher(self.mon)
        self.mon_addr = self.mon_msgr.bind()
        self.osds: dict[int, OSD] = {}
        self.stores: dict[int, object] = {}
        for i in range(n):
            self.start_osd(i)
        self.rados = Rados("ec-test").connect(*self.mon_addr)

    def start_osd(self, i: int):
        osd = OSD(
            i, store=self.stores.get(i), tick_interval=0.2,
            heartbeat_grace=1.0,
        )
        osd.boot(*self.mon_addr)
        self.osds[i] = osd
        self.stores[i] = osd.store
        return osd

    def kill_osd(self, i: int) -> None:
        osd = self.osds.pop(i)
        osd._stop.set()
        osd._workq.put(None)
        osd.messenger.shutdown()

    def wait_down(self, i: int, timeout=15.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if not self.rados.monc.osdmap.is_up(i):
                return
            time.sleep(0.1)
        raise AssertionError(f"mon never marked osd.{i} down")

    def shutdown(self):
        self.rados.shutdown()
        for i in list(self.osds):
            self.kill_osd(i)
        self.mon_msgr.shutdown()

    def create_ec_pool(
        self, name: str, profile: list[str], pg_num: int = 4,
        min_size: int | None = None,
    ) -> int:
        rc, _outb, outs = self.rados.mon_command(
            {
                "prefix": "osd erasure-code-profile set",
                "name": name + "_prof",
                "profile": profile,
            }
        )
        assert rc == 0, outs
        kwargs = dict(
            pool_type=3, pg_num=pg_num,
            erasure_code_profile=name + "_prof",
        )
        if min_size is not None:
            kwargs["min_size"] = min_size
        return self.rados.pool_create(name, **kwargs)


@pytest.fixture(scope="module")
def cluster():
    c = ECCluster(5)
    try:
        yield c
    finally:
        c.shutdown()


def _io(cluster, pool):
    return cluster.rados.open_ioctx(pool)


def test_ec_pool_create_and_io(cluster):
    pool_id = cluster.create_ec_pool(
        "ecpool", ["k=2", "m=2", "plugin=jerasure"]
    )
    pool = cluster.rados.monc.osdmap.pools[pool_id]
    assert pool.size == 4 and pool.min_size == 3  # k+m / k+1
    io = _io(cluster, "ecpool")
    payloads = {
        f"obj{i}": bytes([i]) * (1000 + 137 * i) for i in range(6)
    }
    for oid, data in payloads.items():
        io.write_full(oid, data)
    for oid, data in payloads.items():
        assert io.read(oid) == data
        assert io.stat(oid) == len(data)
    # partial read + offset read
    assert io.read("obj3", length=64, offset=10) == payloads["obj3"][10:74]
    # append + partial overwrite ride the RMW path
    io.append("obj0", b"TAIL")
    assert io.read("obj0") == payloads["obj0"] + b"TAIL"
    io.write("obj1", b"XYZ", offset=5)
    expect = bytearray(payloads["obj1"])
    expect[5:8] = b"XYZ"
    assert io.read("obj1") == bytes(expect)
    # xattrs replicate to every shard
    io.set_xattr("obj2", "color", b"teal")
    assert io.get_xattr("obj2", "color") == b"teal"
    # delete
    io.remove("obj5")
    with pytest.raises(Exception):
        io.read("obj5")


def test_ec_shards_land_positionally(cluster):
    """Every acting position holds exactly its encode_object shard."""
    io = _io(cluster, "ecpool")
    data = b"positional" * 321
    io.write_full("posobj", data)
    osdmap = cluster.rados.monc.osdmap
    pool_id = cluster.rados.pool_lookup("ecpool")
    prof = osdmap.erasure_code_profiles[
        osdmap.pools[pool_id].erasure_code_profile
    ]
    codec = ECCodec(prof)
    # find the pg
    primary_osd = None
    for ps in range(osdmap.pools[pool_id].pg_num):
        pgid = f"{pool_id}.{ps}"
        for osd in cluster.osds.values():
            pg = osd.pgs.get(pgid)
            if pg and osd.store.exists(pg.cid, OBJ_PREFIX + "posobj"):
                primary_osd = osd
                break
        if primary_osd:
            break
    assert primary_osd is not None
    pg = primary_osd.pgs[pgid]
    shards, meta = codec.encode_object(data)
    _u, _up, acting, _p = osdmap.pg_to_up_acting_osds(pool_id, ps)
    for pos, osd_id in enumerate(acting):
        store = cluster.stores[osd_id]
        assert store.read(pg.cid, OBJ_PREFIX + "posobj") == shards[pos]
        got_meta = json.loads(
            store.getattr(pg.cid, OBJ_PREFIX + "posobj", HINFO_KEY)
        )
        assert got_meta == meta


def test_ec_degraded_read_write_and_recovery(cluster):
    """Kill a shard OSD → mon marks it down → reads reconstruct,
    writes proceed at min_size → revived OSD recovers by log with
    reconstructed shard pushes."""
    io = _io(cluster, "ecpool")
    before = {f"deg{i}": bytes([64 + i]) * 2048 for i in range(4)}
    for oid, data in before.items():
        io.write_full(oid, data)
    # pick a victim that is NOT the primary of every pg: any osd works
    # for reads; choose one serving at least one shard
    osdmap = cluster.rados.monc.osdmap
    pool_id = cluster.rados.pool_lookup("ecpool")
    victim = None
    for ps in range(osdmap.pools[pool_id].pg_num):
        _u, _up, acting, primary = osdmap.pg_to_up_acting_osds(
            pool_id, ps
        )
        for o in acting:
            if o != primary and o in cluster.osds:
                victim = o
                break
        if victim is not None:
            break
    assert victim is not None
    victim_store = cluster.stores[victim]
    cluster.kill_osd(victim)
    cluster.wait_down(victim)
    # degraded reads reconstruct from surviving shards
    for oid, data in before.items():
        assert io.read(oid) == data
    # degraded writes proceed (k=2, m=2: 3 live shards >= min_size 3)
    during = {f"miss{i}": bytes([96 + i]) * 1536 for i in range(3)}
    for oid, data in during.items():
        io.write_full(oid, data)
    for oid, data in during.items():
        assert io.read(oid) == data
    # revive: log-driven recovery must hand the returning OSD
    # reconstructed shards for the objects written while it was gone
    cluster.start_osd(victim)
    deadline = time.monotonic() + 20.0
    pending = set(during)
    while pending and time.monotonic() < deadline:
        for oid in list(pending):
            for cid in victim_store.list_collections():
                if not cid.startswith("pg_"):
                    continue
                try:
                    if victim_store.exists(cid, OBJ_PREFIX + oid):
                        pending.discard(oid)
                        break
                except Exception:
                    pass
        time.sleep(0.2)
    # the revived osd may no longer be in the acting set of a pg
    # (crush remapped around the down interval); an object it still
    # serves MUST have arrived via a reconstructed-shard push
    osdmap = cluster.rados.monc.osdmap
    for oid in pending:
        ps = None
        for cand in range(osdmap.pools[pool_id].pg_num):
            pgid = f"{pool_id}.{cand}"
            for osd in cluster.osds.values():
                pg = osd.pgs.get(pgid)
                if pg is not None and osd.store.exists(
                    pg.cid, OBJ_PREFIX + oid
                ):
                    ps = cand
                    break
            if ps is not None:
                break
        assert ps is not None, f"{oid} vanished from the cluster"
        _u, _up, acting, _p = osdmap.pg_to_up_acting_osds(pool_id, ps)
        assert victim not in acting, (
            f"osd.{victim} serves {oid}'s pg but never recovered it"
        )
    # everything still reads back
    for oid, data in {**before, **during}.items():
        assert io.read(oid) == data


def test_clay_fractional_recovery_through_daemon():
    """A CLAY pool recovers a lost shard with FRACTIONAL helper reads
    travelling as real sub-op messages (the ECUtil::decode sub-chunk
    plumbing end-to-end, src/osd/ECUtil.cc:50-121)."""
    c = ECCluster(6)
    try:
        reads: list[int] = []
        orig = ec_store_mod.ECStore.reconstruct_shard

        def spy(self, name, shard, meta=None):
            data, read_bytes, meta = orig(self, name, shard, meta)
            reads.append(read_bytes)
            return data, read_bytes, meta

        ec_store_mod.ECStore.reconstruct_shard = spy
        try:
            c.create_ec_pool(
                "claypool",
                ["k=3", "m=2", "d=4", "plugin=clay"],
                pg_num=2,
                min_size=3,
            )
            io = c.rados.open_ioctx("claypool")
            io.write_full("seed", b"s" * 4096)  # warm the pool
            osdmap = c.rados.monc.osdmap
            pool_id = c.rados.pool_lookup("claypool")
            codec = ECCodec(
                osdmap.erasure_code_profiles[
                    osdmap.pools[pool_id].erasure_code_profile
                ]
            )
            victim = None
            for ps in range(osdmap.pools[pool_id].pg_num):
                _u, _up, acting, primary = osdmap.pg_to_up_acting_osds(
                    pool_id, ps
                )
                for o in acting:
                    if o != primary and o in c.osds:
                        victim = o
                        break
                if victim is not None:
                    break
            victim_store = c.stores[victim]
            c.kill_osd(victim)
            c.wait_down(victim)
            data = b"clay-fractional" * 1000
            io.write_full("frac", data)
            assert io.read("frac") == data
            reads.clear()
            c.start_osd(victim)
            deadline = time.monotonic() + 60.0  # 1-core suite load
            got = False
            while not got and time.monotonic() < deadline:
                for cid in victim_store.list_collections():
                    if cid.startswith("pg_"):
                        try:
                            if victim_store.exists(
                                cid, OBJ_PREFIX + "frac"
                            ):
                                got = True
                                break
                        except Exception:
                            pass
                time.sleep(0.2)
            assert got, "victim never received the recovered shard"
            assert reads, "recovery never went through reconstruct"
            # CLAY minimum repair: helpers send d sub-chunk fractions,
            # strictly less than reading k full shards of the object
            padded = codec.sinfo.logical_to_next_stripe_offset(
                len(data)
            )
            shard_len = padded // codec.k
            full_decode = codec.k * shard_len
            assert min(reads) < full_decode
            assert io.read("frac") == data
        finally:
            ec_store_mod.ECStore.reconstruct_shard = orig
    finally:
        c.shutdown()


def test_ec_partial_overwrite_ships_only_stripe_range(cluster):
    """A 4KB overwrite of a multi-hundred-KB EC object goes through
    the stripe-granular RMW pipeline (ECBackend.cc:1858 start_rmw):
    only the covered head/tail stripes are read, and each replica's
    MOSDRepOp carries ~one chunk of shard bytes, not the re-encoded
    object."""
    import ceph_tpu.osd.daemon as daemon_mod
    from ceph_tpu.osd import ec_pg

    cluster.create_ec_pool("rmwdaemon", ["k=3", "m=2"], pg_num=2)
    io = _io(cluster, "rmwdaemon")
    base = bytes(range(256)) * 3 * 1024  # 768KB = 64 whole stripes
    io.write_full("big", base)

    calls = []
    orig = ec_pg.rmw_write_txns

    def spy(codec, ecs, cid, oid, offset, data, positions, old_size):
        txns = orig(
            codec, ecs, cid, oid, offset, data, positions, old_size
        )
        shipped = {
            pos: sum(
                len(op[4]) for op in txn.ops if op[0] == "write"
            )
            for pos, txn in txns.items()
        }
        calls.append((oid, offset, len(data), shipped))
        return txns

    daemon_mod.rmw_write_txns = spy
    try:
        patch = b"Z" * 4096
        off = 2 * 12288 + 1000  # unaligned, inside the object
        io.write("big", patch, offset=off)
    finally:
        daemon_mod.rmw_write_txns = orig

    assert len(calls) == 1, "partial overwrite did not take the RMW path"
    _oid, _off, _len, shipped = calls[0]
    # 4KB at an unaligned offset spans at most 2 stripes of a
    # k=3/su=4KB pool: <= 2 chunks = 8KB per shard, vs the ~256KB a
    # whole-object re-encode would ship to every shard
    for pos, nbytes in shipped.items():
        assert 0 < nbytes <= 2 * 4096, (pos, nbytes)
    want = bytearray(base)
    want[off : off + len(patch)] = patch
    assert io.read("big") == bytes(want)
    # a second overwrite crossing a stripe boundary plus an append-ish
    # tail write keep content exact through the same pipeline
    patch2 = b"q" * 9000
    off2 = 5 * 12288 - 100
    io.write("big", patch2, offset=off2)
    want[off2 : off2 + len(patch2)] = patch2
    assert io.read("big") == bytes(want)
    # appends ride the same pipeline (RMW at old_size): the first
    # starts stripe-aligned (no read), the second lands mid-stripe so
    # the tail-stripe read+overlay path runs too
    daemon_mod.rmw_write_txns = spy
    try:
        calls.clear()
        io.append("big", b"tailbytes" * 100)
        io.append("big", b"more-tail" * 50)
    finally:
        daemon_mod.rmw_write_txns = orig
    assert len(calls) == 2, "appends did not take the RMW path"
    for call in calls:
        for pos, nbytes in call[3].items():
            assert 0 < nbytes <= 2 * 4096, (pos, nbytes)
    want += b"tailbytes" * 100 + b"more-tail" * 50
    assert io.read("big") == bytes(want)


def test_ec_write_one_shard_naks_the_others_are_still_waited_for():
    """k=2 m=2: three sub-ops go out together; the middle peer
    refuses.  The two others are still waited for and acked, the
    first try ends -EAGAIN (the objecter retries the reqid), and
    every position then holds exactly its own shard, logged once."""
    c = ECCluster(4)
    try:
        pool_id = c.create_ec_pool(
            "nakpool", ["k=2", "m=2", "plugin=jerasure"], pg_num=1
        )
        io = c.rados.open_ioctx("nakpool")
        io.write_full("warm", b"w" * 4096)
        pgid = f"{pool_id}.0"
        osdmap = c.rados.monc.osdmap
        _u, _up, acting, primary_id = osdmap.pg_to_up_acting_osds(pool_id, 0)
        primary = c.osds[primary_id]
        peers = [o for o in acting if o != primary_id]
        victim = peers[1]
        refused = []
        commit = primary._commit_and_replicate

        def spy(pg, *args, **kw):
            try:
                return commit(pg, *args, **kw)
            except Exception as e:
                refused.append((str(e), pg.repop_clean))
                raise

        primary._commit_and_replicate = spy
        c.osds[victim].pgs[pgid].activated_epoch = 0
        data = b"nak-one-shard" * 777
        io.write_full("nakobj", data)
        assert io.read("nakobj") == data
        ((error, clean),) = refused
        assert f"[{victim}]" in error and "EAGAIN" in error and clean is False
        (events,) = [
            [e["event"] for e in s["events"]]
            for s in primary.tracer.dump_traces()["spans"]
            if s["name"] == "sub_op_wait" and len(s["events"]) < 6
        ]
        assert events == [f"sub_op_sent osd.{o}" for o in peers] + [
            f"sub_op_commit_rec osd.{o}" for o in peers if o != victim
        ]
        shards, _meta = ECCodec(
            osdmap.erasure_code_profiles[
                osdmap.pools[pool_id].erasure_code_profile
            ]
        ).encode_object(data)
        reqid = next(
            e.reqid for e in primary.pgs[pgid].log.entries if e.oid == "nakobj"
        )

        def level():
            for pos, osd_id in enumerate(acting):
                pg = c.osds[osd_id].pgs[pgid]
                logged = [e for e in pg.log.entries if e.reqid == reqid]
                if len(logged) != 1:
                    return False
                store = c.stores[osd_id]
                if not store.exists(pg.cid, OBJ_PREFIX + "nakobj"):
                    return False
                if store.read(pg.cid, OBJ_PREFIX + "nakobj") != shards[pos]:
                    return False
            return True

        deadline = time.monotonic() + 20
        while not level():
            assert time.monotonic() < deadline, "the refused shard never landed"
            time.sleep(0.1)
    finally:
        c.shutdown()
