"""OSD daemon integration (OSD.cc / PeeringState.cc roles): a real
mini-cluster — monitor + 3 OSD daemons over the messenger — serving
replicated I/O with pg_log entries, surviving an OSD death (failure
reports → mon marks down → re-peer) and recovering the revived OSD
from the authoritative log (the qa/standalone tier analog)."""

from __future__ import annotations

import time

import pytest

from ceph_tpu.crush.builder import CrushMap
from ceph_tpu.crush.types import CRUSH_BUCKET_STRAW2, Tunables
from ceph_tpu.mon.monitor import Monitor
from ceph_tpu.msg import Messenger, MOSDOp, MOSDOpReply
from ceph_tpu.msg.message import (
    OSD_OP_DELETE,
    OSD_OP_READ,
    OSD_OP_WRITEFULL,
)
from ceph_tpu.mon.monitor import MonClient
from ceph_tpu.osd.daemon import OBJ_PREFIX, OSD
from ceph_tpu.osd.osdmap import OSDMap, PgPool

N = 3
POOL = 1
PG_NUM = 2


def _base_map() -> OSDMap:
    cmap = CrushMap(tunables=Tunables())
    hosts = []
    for h in range(N):
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
    om = OSDMap.build(cmap, N)
    om.add_pool(PgPool(pool_id=POOL, size=3, pg_num=PG_NUM, crush_rule=0))
    return om


class MiniCluster:
    def __init__(self):
        self.mon = Monitor(_base_map(), min_reporters=2)
        self.mon_msgr = Messenger("mon")
        self.mon_msgr.add_dispatcher(self.mon)
        self.mon_addr = self.mon_msgr.bind()
        self.osds: dict[int, OSD] = {}
        self.client_msgr = Messenger("client")
        self.monc = MonClient(self.client_msgr, whoami=-1)
        self.monc.connect(*self.mon_addr)

    def start_osd(self, i: int, store=None, **kw):
        osd = OSD(
            i, store=store, tick_interval=0.2, heartbeat_grace=1.0,
            **kw,
        )
        osd.boot(*self.mon_addr)
        self.osds[i] = osd
        return osd

    def kill_osd(self, i: int) -> None:
        osd = self.osds.pop(i)
        osd._stop.set()
        osd._workq.put(None)
        osd.messenger.shutdown()

    def shutdown(self):
        for i in list(self.osds):
            self.kill_osd(i)
        self.client_msgr.shutdown()
        self.mon_msgr.shutdown()

    # -- client ops --------------------------------------------------------
    def primary_of(self, pgid: str) -> int:
        ps = int(pgid.split(".")[1])
        _up, _upp, _acting, primary = self.monc.osdmap.pg_to_up_acting_osds(
            POOL, ps
        )
        return primary

    _op_seq = __import__("itertools").count(1)

    def op(self, pgid: str, oid: str, op, data=b"", timeout=10.0):
        deadline = time.monotonic() + timeout
        reqid = f"test.{next(MiniCluster._op_seq)}"  # stable across retries
        while time.monotonic() < deadline:
            primary = self.primary_of(pgid)
            osd = self.osds.get(primary)
            if osd is None:
                time.sleep(0.1)
                continue
            conn = self.client_msgr.connect(*osd.addr)
            reply = conn.call(
                MOSDOp(
                    pool=POOL, pgid=pgid, oid=oid, op=op,
                    data=data, length=-1, reqid=reqid,
                    epoch=self.monc.epoch,
                )
            )
            assert isinstance(reply, MOSDOpReply)
            if reply.ok:
                return reply
            time.sleep(0.15)  # not primary yet / still peering
        raise AssertionError(f"op on {pgid}/{oid} never succeeded")

    def wait_active(self, timeout=15.0):
        deadline = time.monotonic() + timeout
        pgids = [f"{POOL}.{ps}" for ps in range(PG_NUM)]
        while time.monotonic() < deadline:
            ok = True
            for pgid in pgids:
                primary = self.primary_of(pgid)
                osd = self.osds.get(primary)
                pg = osd.pgs.get(pgid) if osd else None
                if pg is None or pg.state != "active":
                    ok = False
                    break
            if ok:
                return
            time.sleep(0.1)
        raise AssertionError("PGs never went active")


@pytest.fixture
def cluster():
    c = MiniCluster()
    try:
        for i in range(N):
            c.start_osd(i)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and not all(
            c.monc.osdmap.is_up(i) for i in range(N)
        ):
            time.sleep(0.1)
        c.wait_active()
        yield c
    finally:
        c.shutdown()


def test_replicated_io_with_pg_log(cluster):
    c = cluster
    c.op("1.0", "alpha", OSD_OP_WRITEFULL, b"alpha-data" * 50)
    c.op("1.1", "beta", OSD_OP_WRITEFULL, b"beta-data" * 50)
    r = c.op("1.0", "alpha", OSD_OP_READ)
    assert r.data == b"alpha-data" * 50
    # every acting OSD holds the object AND the log entry
    for i, osd in c.osds.items():
        pg = osd.pgs["1.0"]
        assert osd.store.read(pg.cid, OBJ_PREFIX + "alpha") == (
            b"alpha-data" * 50
        )
        assert pg.log.head > (0, 0)
        assert pg.log.object_op("alpha") is not None


def test_osd_death_failover_and_log_recovery(cluster):
    c = cluster
    c.op("1.0", "before", OSD_OP_WRITEFULL, b"written-before-death")
    victim = c.primary_of("1.0")
    victim_store = c.osds[victim].store
    epoch0 = c.monc.epoch
    c.kill_osd(victim)
    # heartbeats from the two survivors report; mon marks down
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and c.monc.osdmap.is_up(victim):
        time.sleep(0.2)
    assert not c.monc.osdmap.is_up(victim), "mon never marked victim down"
    assert c.monc.epoch > epoch0
    # cluster still serves I/O on the surviving acting set
    c.op("1.0", "during", OSD_OP_WRITEFULL, b"written-while-down" * 10)
    c.op("1.0", "before", OSD_OP_DELETE)
    r = c.op("1.0", "during", OSD_OP_READ)
    assert r.data == b"written-while-down" * 10

    # revive with the SAME store: it must catch up from the log
    c.start_osd(victim, store=victim_store)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and not c.monc.osdmap.is_up(victim):
        time.sleep(0.2)
    assert c.monc.osdmap.is_up(victim)

    def caught_up():
        osd = c.osds[victim]
        pg = osd.pgs.get("1.0")
        if pg is None:
            return False
        try:
            got = osd.store.read(pg.cid, OBJ_PREFIX + "during")
        except Exception:
            return False
        if got != b"written-while-down" * 10:
            return False
        return not osd.store.exists(pg.cid, OBJ_PREFIX + "before")

    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and not caught_up():
        time.sleep(0.2)
    assert caught_up(), "revived OSD never recovered from the log"


def test_restarted_osd_reloads_pgs_from_store(cluster):
    c = cluster
    c.op("1.0", "persist", OSD_OP_WRITEFULL, b"persisted")
    some = c.primary_of("1.0")
    store = c.osds[some].store
    head_before = c.osds[some].pgs["1.0"].log.head
    c.kill_osd(some)
    # cold restart on the same store: log + info reload (load_pgs)
    osd = OSD(some + 100, store=store)  # fresh object, no boot needed
    osd.addr = ("", 0)
    osd._load_pgs()
    pg = osd.pgs["1.0"]
    assert pg.log.head == head_before
    assert pg.info.last_update == head_before
    assert pg.log.object_op("persist") is not None
    osd.messenger.shutdown()


def _bump_epoch(c):
    """Commit a no-op-ish incremental (reweight to same value) so every
    primary sees a new epoch."""
    c.monc.command({"prefix": "osd reweight", "id": 0, "weight": 1.0})


def test_xattrs_survive_recovery(cluster):
    """Recovery pushes carry xattrs (review finding: attrs were
    dropped, silently losing them on recovered copies)."""
    c = cluster
    c.op("1.0", "xobj", OSD_OP_WRITEFULL, b"data")
    from ceph_tpu.msg.message import OSD_OP_SETXATTR

    primary = c.primary_of("1.0")
    conn = c.client_msgr.connect(*c.osds[primary].addr)
    from ceph_tpu.msg import MOSDOp

    r = conn.call(MOSDOp(pool=POOL, pgid="1.0", oid="xobj",
                         op=OSD_OP_SETXATTR, attr="k", data=b"v",
                         length=-1))
    assert r.ok
    victim = next(i for i in c.osds if i != primary)
    store = c.osds[victim].store
    c.kill_osd(victim)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline and c.monc.osdmap.is_up(victim):
        time.sleep(0.2)
    c.start_osd(victim, store=store)
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        osd = c.osds[victim]
        pg = osd.pgs.get("1.0")
        try:
            if (
                pg is not None
                and osd.store.getattr(pg.cid, OBJ_PREFIX + "xobj", "u_k")
                == b"v"
            ):
                return
        except Exception:
            pass
        time.sleep(0.2)
    raise AssertionError("xattr lost through recovery")


def test_divergent_entry_rewound_on_peering(cluster):
    """A replica carrying a never-replicated (divergent) entry rewinds
    it at the next peering: phantom objects disappear, the log
    truncates to the shared prefix (rewind_divergent_log role)."""
    c = cluster
    c.op("1.0", "base", OSD_OP_WRITEFULL, b"shared-history")
    primary = c.primary_of("1.0")
    replica = next(i for i in c.osds if i != primary)
    osd = c.osds[replica]
    pg = osd.pgs["1.0"]
    # inject a divergent entry + phantom object directly, as if this
    # replica applied a write that never reached anyone else
    from ceph_tpu.osd.daemon import _encode_entry, _log_oid
    from ceph_tpu.osd.pg_log import EV_ZERO, MODIFY, LogEntry
    from ceph_tpu.store.objectstore import Transaction

    # divergent at the CURRENT epoch (the realistic shape: a write
    # the old primary applied locally but never fanned out)
    phantom = LogEntry(
        op=MODIFY, oid="ghost",
        version=(c.monc.epoch, pg.seq + 1),
        prior_version=EV_ZERO,
    )
    txn = Transaction()
    txn.touch(pg.cid, OBJ_PREFIX + "ghost")
    txn.write(pg.cid, OBJ_PREFIX + "ghost", 0, b"phantom")
    txn.touch(pg.cid, _log_oid(phantom.version))
    txn.write(pg.cid, _log_oid(phantom.version), 0, _encode_entry(phantom))
    osd.store.queue_transaction(txn)
    pg.log.append(phantom)
    pg.info.last_update = phantom.version
    # the cluster moves on: a newer epoch + a newer authoritative
    # write make the primary's log strictly newer than the phantom
    _bump_epoch(c)
    c.op("1.0", "after", OSD_OP_WRITEFULL, b"newer-history")
    # force a new peering round
    for o in c.osds.values():
        for p in o.pgs.values():
            p.peered_interval = None
    _bump_epoch(c)
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if (
            not osd.store.exists(pg.cid, OBJ_PREFIX + "ghost")
            and pg.log.object_op("ghost") is None
        ):
            return
        time.sleep(0.2)
    raise AssertionError("divergent entry was not rewound")


def test_append_is_atomic_and_log_trims(cluster):
    c = cluster
    from ceph_tpu.msg.message import OSD_OP_APPEND

    primary = c.primary_of("1.1")
    osd = c.osds[primary]
    osd.log_keep = 8
    for o in c.osds.values():
        o.log_keep = 8
    import concurrent.futures

    def one(i):
        return c.op("1.1", "alog", OSD_OP_APPEND, bytes([i]) * 3)

    with concurrent.futures.ThreadPoolExecutor(4) as ex:
        list(ex.map(one, range(12)))
    r = c.op("1.1", "alog", OSD_OP_READ)
    # every append landed exactly once, each 3 bytes
    assert len(r.data) == 36
    counts = sorted(r.data.count(bytes([i])) for i in range(12))
    assert counts == [3] * 12
    pg = osd.pgs["1.1"]
    assert len(pg.log.entries) <= 8
    assert pg.log.log_tail > (0, 0)
    assert pg.info.log_tail == pg.log.log_tail
    # trimmed entries' store objects are gone too
    logs = [o for o in osd.store.list_objects(pg.cid)
            if o.startswith("_log/")]
    assert len(logs) == len(pg.log.entries)


# -- the repop fan-out's failure path (issue_repop: send all, wait once) -----


def _try_once(c, pgid: str, oid: str, data: bytes, reqid: str):
    """ONE write_full to the primary and its reply, ok or not."""
    primary = c.osds[c.primary_of(pgid)]
    reply = c.client_msgr.connect(*primary.addr).call(
        MOSDOp(
            pool=POOL, pgid=pgid, oid=oid, op=OSD_OP_WRITEFULL, data=data,
            length=-1, reqid=reqid, epoch=c.monc.epoch,
        ),
        timeout=20.0,
    )
    assert isinstance(reply, MOSDOpReply)
    return reply


def _fan_out_spans(primary, reqid: str):
    """(the events of the ``sub_op_wait`` span, {peer: ok} of the
    ``sub_op_rtt`` spans) of the primary's first try under ``reqid``."""
    spans = primary.tracer.dump_traces(reqid)["spans"]
    wait = next(s for s in spans if s["name"] == "sub_op_wait")
    rtts = {
        s["tags"]["osd"]: s["tags"]["ok"]
        for s in spans
        if s["name"] == "sub_op_rtt" and s["end"] <= wait["end"] + 1e-6
    }
    return [e["event"] for e in wait["events"]], rtts


def _name_link(primary, peer) -> str:
    host, port = peer.addr
    primary.messenger.faults.alias(f"osd.{peer.whoami}", f"{host}:{port}")
    return f"osd.{peer.whoami}"


@pytest.mark.parametrize("fault", ["nak", "link_drop", "no_address"])
def test_a_failed_sub_op_fails_the_write_once_every_peer_is_resolved(
    cluster, fault
):
    """One of the two peers refuses, loses the frame, or cannot be
    dialed: the other's sub-op is still sent and still waited for
    (its ack counts though it is read after the deadline), the op
    ends -EAGAIN with the PG marked unclean and un-peered for the
    queued re-peer, and the client's retry of the same reqid finds
    the write committed exactly once on all three."""
    from ceph_tpu.store.objectstore import StoreError

    c = cluster
    pgid = "1.0"
    c.op(pgid, "pre", OSD_OP_WRITEFULL, b"pre")  # the sessions are dialed
    primary = c.osds[c.primary_of(pgid)]
    pg = primary.pgs[pgid]
    # the victim is sent to, and waited for, FIRST
    victim, good = [o for o in pg.acting if o != primary.whoami]
    primary.repop_timeout = 1.0
    addrs = primary.monc.osdmap.osd_addrs
    if fault == "nak":
        c.osds[victim].pgs[pgid].activated_epoch = 0
        heal = lambda: None  # noqa: E731 — the re-peer activates it again
    elif fault == "link_drop":
        primary.messenger.faults.add_rule(
            dst=_name_link(primary, c.osds[victim]), drop=1.0
        )
        heal = primary.messenger.faults.clear
    else:
        addr = addrs.pop(victim)
        heal = lambda: addrs.setdefault(victim, addr)  # noqa: E731
    at_failure = []
    commit = primary._commit_and_replicate

    def spy(pg_, *args, **kw):
        try:
            return commit(pg_, *args, **kw)
        except StoreError as e:
            at_failure.append((str(e), pg_.repop_clean, pg_.peered_interval))
            heal()  # the re-peer this queued finds the peer again
            raise

    primary._commit_and_replicate = spy
    reqid, data = f"fanout.{fault}", fault.encode() * 100
    reply = _try_once(c, pgid, "fan", data, reqid)
    assert not reply.ok and "EAGAIN" in reply.error, reply.error
    ((error, clean, interval),) = at_failure
    assert f"[{victim}]" in error and clean is False and interval is None
    events, rtts = _fan_out_spans(primary, reqid)
    assert events == [
        f"sub_op_sent osd.{victim}", f"sub_op_sent osd.{good}",
        f"sub_op_commit_rec osd.{good}",
    ]
    assert rtts == (
        {good: True} if fault == "no_address" else {victim: False, good: True}
    )
    # the retry: the reqid cache answers, and the re-peer has (or
    # will have) brought the victim level
    deadline = time.monotonic() + 20
    while not _try_once(c, pgid, "fan", data, reqid).ok:
        assert time.monotonic() < deadline, "the retry never succeeded"
        time.sleep(0.15)

    def level():
        for osd in c.osds.values():
            mine = [e for e in osd.pgs[pgid].log.entries if e.reqid == reqid]
            if len(mine) != 1:
                return False
            try:
                if osd.store.read(osd.pgs[pgid].cid, OBJ_PREFIX + "fan") != data:
                    return False
            except StoreError:
                return False
        return pg.repop_clean and pg.peered_interval is not None

    while not level():
        assert time.monotonic() < deadline, {
            o: [e.reqid for e in osd.pgs[pgid].log.entries]
            for o, osd in c.osds.items()
        }
        time.sleep(0.1)
    assert len(at_failure) == 1  # nothing was applied a second time


def test_the_client_is_answered_after_the_slowest_peers_ack(cluster):
    """The ack point did not move: with one link delayed the other
    peer commits at once (the sub-ops overlap) and the client's reply
    still waits for the delayed one."""
    c = cluster
    pgid = "1.0"
    c.op(pgid, "pre", OSD_OP_WRITEFULL, b"pre")
    primary = c.osds[c.primary_of(pgid)]
    slow, fast = [o for o in primary.pgs[pgid].acting if o != primary.whoami]
    committed: dict[int, float] = {}
    for o in (slow, fast):
        osd = c.osds[o]

        def handle(conn, msg, osd=osd, inner=osd._handle_rep_op):
            inner(conn, msg)
            committed[osd.whoami] = time.monotonic()

        osd._handle_rep_op = handle
    primary.messenger.faults.add_rule(
        dst=_name_link(primary, c.osds[slow]), delay=0.5
    )
    try:
        t0 = time.monotonic()
        reply = _try_once(c, pgid, "late", b"late" * 100, "fanout.delay")
        acked = time.monotonic()
    finally:
        primary.messenger.faults.clear()
    assert reply.ok, reply.error
    assert committed[fast] - t0 < 0.5 <= committed[slow] - t0
    assert committed[slow] <= acked
    events, rtts = _fan_out_spans(primary, "fanout.delay")
    assert events[:2] == [f"sub_op_sent osd.{slow}", f"sub_op_sent osd.{fast}"]
    assert sorted(events[2:]) == sorted(
        f"sub_op_commit_rec osd.{o}" for o in (slow, fast)
    )
    assert rtts == {slow: True, fast: True}
