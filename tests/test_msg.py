"""Messenger layer tests: frame codec, dispatch, RPC pairing, resets
(SURVEY.md §2.4 Messenger row; src/msg/Messenger.h:89 contract)."""

from __future__ import annotations

import threading
import time

import pytest

from ceph_tpu.msg import (
    MECSubRead,
    MECSubWrite,
    MECSubWriteReply,
    MPing,
    Message,
    MessageError,
    Messenger,
)
from ceph_tpu.msg.message import (
    READ_DATA,
    decode_transaction,
    encode_transaction,
)
from ceph_tpu.common.encoding import Decoder, Encoder
from ceph_tpu.msg.messenger import Dispatcher, wait_for
from ceph_tpu.store.objectstore import Transaction


def test_frame_roundtrip():
    msg = MPing(tid=7, from_osd=3, stamp=1.5)
    frame = msg.to_frame()
    mtype, tid, plen = Message.parse_header(frame[: Message.HEADER_SIZE])
    assert (mtype, tid) == (MPing.TYPE, 7)
    payload = frame[Message.HEADER_SIZE : Message.HEADER_SIZE + plen]
    crc = int.from_bytes(frame[Message.HEADER_SIZE + plen :], "little")
    out = Message.from_payload(mtype, tid, payload, crc)
    assert isinstance(out, MPing)
    assert out.from_osd == 3 and out.stamp == 1.5


def test_frame_corruption_detected():
    frame = bytearray(MPing(tid=1, from_osd=2).to_frame())
    frame[5] ^= 0xFF
    with pytest.raises(MessageError):
        Message.parse_header(bytes(frame[: Message.HEADER_SIZE]))


def test_transaction_codec_roundtrip():
    txn = (
        Transaction()
        .create_collection("coll")
        .touch("coll", "obj")
        .write("coll", "obj", 16, b"hello")
        .truncate("coll", "obj", 8)
        .setattr("coll", "obj", "k", b"v")
        .rmattr("coll", "obj", "k")
        .remove("coll", "obj")
        .remove_collection("coll")
    )
    e = Encoder()
    encode_transaction(e, txn)
    out = decode_transaction(Decoder(e.getvalue()))
    assert out.ops == txn.ops


class _Echo(Dispatcher):
    def __init__(self):
        self.resets = 0

    def ms_dispatch(self, conn, msg):
        if isinstance(msg, MPing) and not msg.is_reply:
            conn.send(
                MPing(
                    tid=msg.tid, from_osd=99, stamp=msg.stamp,
                    is_reply=True,
                )
            )
            return True
        return False

    def ms_handle_reset(self, conn):
        self.resets += 1


def test_call_reply_pairing_and_reset():
    server = Messenger("server")
    echo = _Echo()
    server.add_dispatcher(echo)
    host, port = server.bind()
    client = Messenger("client")
    try:
        conn = client.connect(host, port)
        # concurrent calls pair replies by tid
        results = {}

        def call(i):
            results[i] = conn.call(MPing(from_osd=i, stamp=float(i)))

        threads = [
            threading.Thread(target=call, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for i in range(8):
            assert results[i].stamp == float(i)
            assert results[i].is_reply
        # server going away resets the client connection
        server.shutdown()
        assert wait_for(lambda: conn.is_closed, 5)
        with pytest.raises(MessageError):
            conn.call(MPing(from_osd=1), timeout=2)
    finally:
        client.shutdown()
        if server._loop is not None:
            server.shutdown()


def test_unclaimed_message_drops_silently():
    server = Messenger("server")
    server.add_dispatcher(_Echo())
    host, port = server.bind()
    client = Messenger("client")
    try:
        conn = client.connect(host, port)
        # MECSubWrite is not claimed by _Echo; connection must survive
        conn.send(MECSubWrite(tid=client.new_tid(), txn=Transaction()))
        time.sleep(0.1)
        assert conn.call(MPing(from_osd=1)).is_reply
    finally:
        client.shutdown()
        server.shutdown()


# -- fewer hand-overs of the interpreter on a frame's path (ISSUE 34) ---------


def test_a_short_crc_keeps_the_interpreter_and_a_payload_gives_it_up(monkeypatch):
    """Either side of ``_CRC_RELEASE_BYTES`` the checksum is the same
    number (the table walk is the reference); which handle computed
    it is what differs."""
    import os

    from ceph_tpu import native

    lib = native._lib()
    if lib is None:
        pytest.skip("no C compiler here: the table walk is the only path")
    calls = []
    held, released = lib.crc32c_held, lib.ceph_crc32c
    monkeypatch.setattr(
        lib, "crc32c_held",
        lambda c, d, n: calls.append(("held", n)) or held(c, d, n),
    )
    monkeypatch.setattr(
        lib, "ceph_crc32c",
        lambda c, d, n: calls.append(("released", n)) or released(c, d, n),
    )
    table = native._py_table()

    def walk(crc: int, data: bytes) -> int:
        for b in data:
            crc = (crc >> 8) ^ table[(crc ^ b) & 0xFF]
        return crc

    edge = native._CRC_RELEASE_BYTES
    data = os.urandom(edge + 1)
    for n in (0, 20, edge - 1, edge, edge + 1):
        assert native.ceph_crc32c(0xDEADBEEF, data[:n]) == walk(0xDEADBEEF, data[:n])
    assert calls == [
        ("held", 0), ("held", 20), ("held", edge - 1),
        ("released", edge), ("released", edge + 1),
    ]


def test_a_4_mib_frame_is_read_without_stopping_the_transport(monkeypatch):
    """Both ends open their streams with ``STREAM_LIMIT``: an op of
    4 MiB passes under the reader's high-water mark (twice the limit),
    so the transport is not paused and resumed every 128 KiB."""
    import asyncio
    import os

    from ceph_tpu.msg import messenger as messenger_mod

    paused = []
    pause = asyncio.selector_events._SelectorSocketTransport.pause_reading
    monkeypatch.setattr(
        asyncio.selector_events._SelectorSocketTransport, "pause_reading",
        lambda self: paused.append(1) or pause(self),
    )
    got = []

    class Sink(Dispatcher):
        def ms_dispatch(self, conn, msg) -> bool:
            if isinstance(msg, MECSubWrite):
                got.append(len(msg.txn.ops[0][4]))
                conn.send(MECSubWriteReply(tid=msg.tid, ok=True))
                return True
            return False

    server = Messenger("limit-srv")
    server.add_dispatcher(Sink())
    addr = server.bind()
    client = Messenger("limit-cli")
    try:
        conn = client.connect(*addr)
        assert conn._reader._limit == messenger_mod.STREAM_LIMIT
        txn = Transaction().write("c", "o", 0, os.urandom(4 << 20))
        for _ in range(3):
            assert conn.call(MECSubWrite(txn=txn), timeout=20).ok
        (accepted,) = list(server._conns)
        assert accepted._reader._limit == messenger_mod.STREAM_LIMIT
    finally:
        client.shutdown()
        server.shutdown()
    assert got == [4 << 20] * 3 and paused == []
