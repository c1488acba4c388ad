"""Lossless-peer sessions: reconnect + replay + dedup
(src/msg/async/ProtocolV2.cc session reconnect, src/msg/Policy.h
lossless_peer), fault injection (ms_inject_socket_failures,
src/common/options.cc:1087), and the exactly-once write guarantee
across a mid-repop connection drop."""

from __future__ import annotations

import threading
import time

import pytest

from ceph_tpu.msg import Messenger, MPing, Message
from ceph_tpu.msg.message import MessageError, MOSDOpReply
from ceph_tpu.msg.messenger import Dispatcher, wait_for
from ceph_tpu.rados import Rados

from test_osd_daemon import MiniCluster


class EchoServer(Dispatcher):
    """Counts every (deduped) delivery; echoes pings."""

    def __init__(self):
        self.received: list[float] = []

    def ms_dispatch(self, conn, msg) -> bool:
        if isinstance(msg, MPing) and not msg.is_reply:
            self.received.append(msg.stamp)
            conn.send(
                MPing(
                    tid=msg.tid, from_osd=99, stamp=msg.stamp,
                    is_reply=True,
                )
            )
            return True
        return False


def test_session_survives_socket_kill_and_replays():
    srv_msgr = Messenger("sess-srv")
    srv = EchoServer()
    srv_msgr.add_dispatcher(srv)
    host, port = srv_msgr.bind()
    cli_msgr = Messenger("sess-cli")
    try:
        sc = cli_msgr.connect_session(host, port, "t1")
        r = sc.call(MPing(from_osd=1, stamp=1.0))
        assert isinstance(r, MPing) and r.is_reply
        # kill the underlying socket from the server side (hold the
        # OLD transport: the session proactively redials on reset,
        # so sc._conn may already be a fresh open connection by the
        # time we look)
        old_conn = sc._conn
        for conn in list(srv_msgr._conns):
            conn.close()
        assert wait_for(lambda: old_conn.is_closed, 5.0)
        # the session transparently reconnects and the call completes
        r = sc.call(MPing(from_osd=1, stamp=2.0))
        assert isinstance(r, MPing) and r.stamp == 2.0
        assert srv.received == [1.0, 2.0]
    finally:
        cli_msgr.shutdown()
        srv_msgr.shutdown()


def test_session_replays_unacked_after_drop_without_duplicates():
    srv_msgr = Messenger("sess-srv2")
    srv = EchoServer()
    srv_msgr.add_dispatcher(srv)
    host, port = srv_msgr.bind()
    cli_msgr = Messenger("sess-cli2")
    try:
        sc = cli_msgr.connect_session(host, port, "t2")
        # inject: every 3rd outbound frame from the CLIENT messenger
        # tears the connection down instead of transmitting
        cli_msgr.inject_socket_failures = 3
        for i in range(30):
            # generous per-call budget: every 3rd frame tears the
            # connection down, and the redial+replay cycles stack up
            # under CI load
            sc.call(MPing(from_osd=1, stamp=float(i)), timeout=30.0)
        cli_msgr.inject_socket_failures = 0
        # every ping delivered exactly once, in order
        assert srv.received == [float(i) for i in range(30)]
    finally:
        cli_msgr.shutdown()
        srv_msgr.shutdown()


class HoldingServer(Dispatcher):
    """Counts every (deduped) ping and answers it when told to:
    ``release`` replies, in arrival order, to what has come so far;
    stamps below zero are never answered."""

    def __init__(self):
        self.received: list[float] = []
        self._held: list = []
        self.hold = True

    def ms_dispatch(self, conn, msg) -> bool:
        if not isinstance(msg, MPing) or msg.is_reply:
            return False
        self.received.append(msg.stamp)
        self._held.append((conn, msg))
        if not self.hold:
            self.release()
        return True

    def release(self) -> None:
        held, self._held = self._held, []
        for conn, msg in held:
            if msg.stamp >= 0:
                conn.send(
                    MPing(
                        tid=msg.tid, from_osd=99, stamp=msg.stamp,
                        is_reply=True,
                    )
                )


@pytest.fixture
def held():
    """(server dispatcher, server messenger, a dialed session)."""
    srv_msgr = Messenger("sess-hold-srv")
    srv = HoldingServer()
    srv_msgr.add_dispatcher(srv)
    host, port = srv_msgr.bind()
    cli_msgr = Messenger("sess-hold-cli")
    try:
        yield srv, srv_msgr, cli_msgr.connect_session(host, port, "t3")
    finally:
        cli_msgr.shutdown()
        srv_msgr.shutdown()


def _call(sc, msg, timeout):
    return sc.call(msg, timeout=timeout)


def _submit_then_wait(sc, msg, timeout):
    return sc.submit(msg).wait(timeout)


@pytest.mark.parametrize("send", [_call, _submit_then_wait])
@pytest.mark.parametrize("outcome", ["reply", "timeout", "reset"])
def test_call_is_submit_then_wait(held, send, outcome):
    """One implementation: the same reply, the same timeout error with
    nothing left pending, the same fail-fast on a peer that is gone."""
    srv, srv_msgr, sc = held
    srv.hold = False
    first = send(sc, MPing(from_osd=1, stamp=1.0), 5.0)
    assert isinstance(first, MPing) and first.is_reply and first.stamp == 1.0
    if outcome == "timeout":
        t0 = time.monotonic()
        with pytest.raises(MessageError, match="timed out"):
            send(sc, MPing(from_osd=1, stamp=-1.0), 0.3)
        assert 0.3 <= time.monotonic() - t0 < 3.0
        assert srv.received == [1.0, -1.0]
    elif outcome == "reset":
        old_conn = sc._conn
        srv_msgr.shutdown()
        assert wait_for(lambda: old_conn.is_closed, 5.0)
        with pytest.raises((MessageError, OSError)):
            send(sc, MPing(from_osd=1, stamp=2.0), 5.0)
    assert sc._pending == {}


def test_two_submits_keep_fifo_order_and_resolve_apart(held):
    """Both requests are on the wire before either wait; they arrive
    in seq order and each wait gets its own reply, whichever is
    waited for first."""
    srv, _srv_msgr, sc = held
    a = sc.submit(MPing(from_osd=1, stamp=1.0))
    b = sc.submit(MPing(from_osd=1, stamp=2.0))
    assert [seq for seq, _frame in sc.state.unacked] == [1, 2]
    assert wait_for(lambda: srv.received == [1.0, 2.0], 5.0)
    assert len(sc._pending) == 2
    srv.release()
    assert b.wait(5.0).stamp == 2.0
    assert a.wait(5.0).stamp == 1.0
    assert sc._pending == {}


def test_concurrent_submitters_each_get_their_own_reply(held):
    """More threads than cores submit and wait on one session under a
    short switch interval: every request is delivered once, every wait
    returns the reply to its own request, nothing is left pending."""
    import sys

    srv, _srv_msgr, sc = held
    srv.hold = False
    threads, each = 16, 12
    wrong: list = []

    def worker(n: int) -> None:
        for i in range(each):
            stamp = float(n * 1000 + i)
            first = sc.submit(MPing(from_osd=1, stamp=stamp))
            second = sc.submit(MPing(from_osd=1, stamp=stamp + 0.5))
            got = (second.wait(30.0).stamp, first.wait(30.0).stamp)
            if got != (stamp + 0.5, stamp):
                wrong.append((stamp, got))

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        pool = [threading.Thread(target=worker, args=(n,)) for n in range(threads)]
        for t in pool:
            t.start()
        for t in pool:
            t.join(60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in pool)
    assert wrong == []
    assert len(srv.received) == len(set(srv.received)) == threads * each * 2
    assert sc._pending == {}


def test_a_drop_between_submit_and_wait_replays_the_request_once(held):
    """The transport dies with the request delivered and its reply
    not yet sent: the session redials at once (a reply is pending),
    the replayed request is deduplicated, and the reply finds the
    pending wait over the new socket."""
    srv, srv_msgr, sc = held
    pending = sc.submit(MPing(from_osd=1, stamp=7.0))
    assert wait_for(lambda: srv.received == [7.0], 5.0)
    old_conn = sc._conn
    for conn in list(srv_msgr._conns):
        conn.close()
    assert wait_for(lambda: old_conn.is_closed, 5.0)
    assert wait_for(
        lambda: sc._conn is not old_conn and not sc._conn.is_closed, 5.0
    )
    srv.release()
    assert pending.wait(10.0).stamp == 7.0
    assert srv.received == [7.0]
    assert sc._pending == {}


@pytest.fixture(scope="module")
def cluster():
    c = MiniCluster()
    for i in range(3):
        c.start_osd(i)
    c.wait_active()
    try:
        yield c
    finally:
        c.shutdown()


def test_write_commits_exactly_once_across_repop_drops(cluster):
    """Drop OSD↔OSD connections mid-repop (injected socket failures
    on every OSD messenger): writes succeed and each lands exactly
    once on every replica — session replay + seq dedup on the rep-op
    path, reqid dedup on the client path."""
    client = Rados("once").connect(*cluster.mon_addr)
    # a write may ride out several injected teardowns; the objecter's
    # internal retries reuse ONE reqid, so a long timeout preserves
    # the exactly-once property under test
    client.objecter.op_timeout = 60.0
    try:
        client.pool_create("oncepool", pg_num=2, size=3)
        io = client.open_ioctx("oncepool")
        io.write_full("warm", b"w")  # settle peering
        pool_id = client.pool_lookup("oncepool")

        def log_entries():
            """per-OSD list of (pgid, version, oid) client-op entries."""
            out = {}
            for o, osd in cluster.osds.items():
                entries = []
                for pg in osd.pgs.values():
                    if pg.pool_id != pool_id:
                        continue
                    entries.extend(
                        (pg.pgid, e.version, e.oid)
                        for e in pg.log.entries
                    )
                out[o] = sorted(entries)
            return out

        for osd in cluster.osds.values():
            osd.messenger.inject_socket_failures = 10
        try:
            payloads = {}
            for i in range(12):
                data = bytes([i]) * 512
                io.write_full(f"once{i}", data)
                payloads[f"once{i}"] = data
        finally:
            for osd in cluster.osds.values():
                osd.messenger.inject_socket_failures = 0
        # reads agree
        for oid, data in payloads.items():
            assert io.read(oid) == data
        # give straggler replication a moment, then compare logs:
        # every OSD holds each entry AT MOST once (dedup held), and
        # all three agree once the dust settles
        def logs_converged():
            logs = log_entries()
            for entries in logs.values():
                if len(entries) != len(set(entries)):
                    return False  # duplicate applied entry!
            vals = list(logs.values())
            return vals[0] == vals[1] == vals[2]

        assert wait_for(logs_converged, 20.0), log_entries()
        # and every logical write appears exactly once per OSD
        logs = log_entries()
        for o, entries in logs.items():
            oids = [e[2] for e in entries]
            for i in range(12):
                assert oids.count(f"once{i}") == 1, (o, oids)
    finally:
        client.shutdown()
