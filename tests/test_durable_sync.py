"""The durable deployment's product half (ISSUE 36): the cluster spec's
``sync`` key reaches the OSDs' BlockStores, a WAL-fronted BlockStore
opened ``sync=True`` keeps the order of fsyncs that "an acknowledged
write survives" rests on (and ``sync=False`` does not: the test that
can tell a skipped fsync, which the chip's process-loss check cannot),
and the store's four stage spans count from the WAL's own threads."""

import os
import pathlib
import threading

import pytest

from ceph_tpu.store import BlockStore
from ceph_tpu.store.objectstore import Transaction
from ceph_tpu.store.wal_store import WALStore

CID = "c"


class Disk:
    """What the loss of the host would leave of the files under
    ``root``: each as of its last ``os.fsync``, renames applied (a
    renamed file was fsynced under its old name; directory entries are
    taken as durable, which no configuration claims).  ``instants``
    keeps that image at chosen moments, each with the writes
    acknowledged by then."""

    def __init__(self, root: pathlib.Path, monkeypatch):
        self.root = root
        self.synced: dict[str, bytes] = {}
        self.acked: list[str] = []
        self.instants: list[tuple[str, dict, list]] = []
        # at each truncation of the write-ahead log: was every byte of
        # the block file and the KV log fsynced later than written?
        self.fresh_at_truncation: list[dict[str, bool]] = []
        self.lock = threading.Lock()
        real_fsync, real_replace = os.fsync, os.replace

        def fsync(fd):
            real_fsync(fd)
            path = pathlib.Path(os.readlink(f"/proc/self/fd/{fd}"))
            if root not in path.parents:
                return
            with self.lock:
                self.synced[self._rel(path)] = path.read_bytes()
                if path.name == "wal.log" and not path.stat().st_size:
                    self._log_truncated()

        def replace(src, dst):
            real_replace(src, dst)
            if root in pathlib.Path(dst).parents:
                with self.lock:
                    self.synced[self._rel(dst)] = self.synced.pop(self._rel(src), b"")

        monkeypatch.setattr(os, "fsync", fsync)
        monkeypatch.setattr(os, "replace", replace)

    def _rel(self, path) -> str:
        return str(pathlib.Path(path).relative_to(self.root))

    def _log_truncated(self) -> None:
        self.fresh_at_truncation.append({
            rel: self.synced.get(rel) == (self.root / rel).read_bytes()
            for rel in ("osd/block.dev", "osd/kv.log")
        })
        self.instants.append(("truncation", dict(self.synced), list(self.acked)))

    def ack(self, oid: str) -> None:
        with self.lock:
            self.acked.append(oid)
            self.instants.append(("ack", dict(self.synced), list(self.acked)))

    def lost(self, image: dict, acked: list, payloads: dict, where) -> list[str]:
        """Mount what ``image`` holds anew and read every write of
        ``acked`` back: the names that are absent or differ."""
        for rel, data in image.items():
            (where / rel).parent.mkdir(parents=True, exist_ok=True)
            (where / rel).write_bytes(data)
        try:
            store = WALStore(BlockStore(where / "osd", sync=False), where / "wal",
                             sync=False)
        except Exception:  # noqa: BLE001 — a store that does not mount holds nothing
            return list(acked)
        gone = []
        for oid in acked:
            try:
                if store.read(CID, oid) != payloads[oid]:
                    gone.append(oid)
            except Exception:  # noqa: BLE001 — an absent object is a lost one
                gone.append(oid)
        store.close()
        return gone


def _write_through_checkpoints(tmp_path, monkeypatch, sync):
    """Twelve acknowledged writes, small ones (acknowledged at the
    log's barrier, applied later) between large ones (acknowledged
    after the apply), through a log that checkpoints every 192 KiB."""
    live = tmp_path / "live"
    live.mkdir()
    disk = Disk(live, monkeypatch)
    store = WALStore(BlockStore(live / "osd", sync=sync), live / "wal", sync=True,
                     checkpoint_bytes=192 << 10)
    txn = Transaction()
    txn.create_collection(CID)
    store.queue_transaction(txn)
    payloads = {}
    for i in range(12):
        oid = f"obj-{i}"
        payloads[oid] = os.urandom((96 << 10) if i % 2 == 0 else 4096)
        txn = Transaction()
        txn.write(CID, oid, 0, payloads[oid])
        store.queue_transaction(txn)
        disk.ack(oid)
    assert store.flush()
    store.compact()  # one more truncation, with nothing pending
    checkpoints = store.wal_perf.dump()["l_os_wal_checkpoints"]
    store.close()
    assert checkpoints >= 3 and len(disk.fresh_at_truncation) >= checkpoints
    lost = []
    for n, (what, image, acked) in enumerate(disk.instants):
        where = tmp_path / f"after-{n}"
        lost.append((what, disk.lost(image, acked, payloads, where)))
    return disk, lost


def test_a_synced_store_acks_after_the_log_and_truncates_after_the_store(
        tmp_path, monkeypatch):
    disk, lost = _write_through_checkpoints(tmp_path, monkeypatch, sync=True)
    # no ack before the record's barrier has fsynced, no truncation
    # before the applies have: at every ack and at every truncation
    # the fsynced files alone give every acknowledged write back
    assert [(what, gone) for what, gone in lost if gone] == []
    assert {what for what, _ in lost} == {"ack", "truncation"}
    # the order itself: whenever the log was cut, the block file and
    # the KV log had been fsynced later than their last write
    assert all(all(fresh.values()) for fresh in disk.fresh_at_truncation)


def test_an_unsynced_store_under_the_log_has_the_hole(tmp_path, monkeypatch):
    """``BlockStore(sync=False)`` under a log that fsyncs: until the
    first checkpoint every acknowledged write is in the fsynced log;
    the checkpoint cuts the log on the word that the store persisted
    its applies, and the store never fsynced one."""
    disk, lost = _write_through_checkpoints(tmp_path, monkeypatch, sync=False)
    first_cut = [what for what, _ in lost].index("truncation")
    assert all(not gone for _, gone in lost[:first_cut])  # acks wait for the barrier
    assert lost[first_cut][1], "a checkpoint over an unsynced store lost nothing"
    assert not any(fresh["osd/block.dev"] for fresh in disk.fresh_at_truncation)


# -- the spec's ``sync`` reaches the OSDs' stores ---------------------------


@pytest.mark.parametrize("extra,sync", [({"sync": True}, True), ({}, False)])
def test_a_cluster_opens_its_blockstores_as_the_spec_says(tmp_path, extra, sync):
    from ceph_tpu.tools.cluster import Cluster

    cluster = Cluster({"dir": str(tmp_path), "osds": 2, "wal": True, **extra})
    cluster.start()
    try:
        for osd in cluster.osds:
            assert isinstance(osd.store, WALStore) and osd.store.sync is True
            assert isinstance(osd.store.inner, BlockStore)
            assert osd.store.inner.sync is sync and osd.store.inner.kv.sync is sync
    finally:
        cluster.stop()


@pytest.mark.parametrize("sync", [True, False, None])
def test_a_daemon_process_opens_its_blockstore_as_the_spec_says(
        tmp_path, monkeypatch, sync):
    """``proc/daemon._boot_osd`` with the OSD stood in for: the store it
    is handed.  ``None``: a ``spec.json`` written before the key was."""
    from ceph_tpu.osd import daemon as osd_daemon
    from ceph_tpu.proc import ClusterSpec
    from ceph_tpu.proc import daemon as proc_daemon

    handed = {}

    class StandIn:
        def __init__(self, whoami, store=None, wal_dir=None, **_kw):
            handed.update(store=store, wal_dir=wal_dir)
            self.store = store

        def boot(self, **_kw):
            pass

    monkeypatch.setattr(osd_daemon, "OSD", StandIn)
    monkeypatch.setattr(proc_daemon, "_publish_ready", lambda *a, **kw: None)
    spec = ClusterSpec.plan(tmp_path, mons=1, osds=1, wal=True, sync=bool(sync))
    assert spec.data["sync"] is bool(sync)
    if sync is None:
        del spec.data["sync"]
    proc_daemon._boot_osd(ClusterSpec.load(spec.save()), 0)
    try:
        assert isinstance(handed["store"], BlockStore)
        assert handed["store"].sync is bool(sync)
        assert handed["wal_dir"] == str(tmp_path / "osd.0-wal")
    finally:
        handed["store"].close()


# -- the four spans ---------------------------------------------------------


def test_the_stores_spans_count_from_the_logs_own_threads(tmp_path):
    """No daemon, no ambient span: the writer thread's ``wal_barrier``,
    the drain thread's ``wal_apply`` and ``wal_checkpoint`` and the
    block store's ``store_fsync`` under it feed ``l_stage_*``."""
    from ceph_tpu.ops.kernel_stats import kernel_stats

    names = ("wal_barrier", "wal_apply", "wal_checkpoint", "store_fsync")

    def counts():
        dump = kernel_stats().dump()
        return {n: (dump.get(f"l_stage_{n}_count", 0), dump.get(f"l_stage_{n}_ns", 0))
                for n in names}

    before = counts()
    store = WALStore(BlockStore(tmp_path / "osd", sync=True), tmp_path / "wal",
                     checkpoint_bytes=64 << 10)
    txn = Transaction()
    txn.create_collection(CID)
    store.queue_transaction(txn)
    for i in range(4):
        txn = Transaction()
        txn.write(CID, f"obj-{i}", 0, os.urandom(80 << 10))
        store.queue_transaction(txn)
    assert store.flush()
    store.compact()
    perf = store.wal_perf.dump()
    store.close()
    grew = {n: (counts()[n][0] - before[n][0], counts()[n][1] - before[n][1])
            for n in names}
    assert grew["wal_barrier"][0] == perf["l_os_wal_barriers"] == 5
    # the mount's own applies (the stamp's collection) are no wal_apply
    assert grew["wal_apply"][0] == perf["l_os_wal_applies"] == 5
    assert grew["wal_checkpoint"][0] == perf["l_os_wal_checkpoints"] >= 1
    # two a shard apply (block file, KV frame), one an apply without data
    assert grew["store_fsync"][0] >= 2 * 4 + 1
    assert all(ns > 0 for _count, ns in grew.values())


def test_an_unsynced_blockstore_opens_no_fsync_span(tmp_path):
    from ceph_tpu.ops.kernel_stats import kernel_stats

    before = kernel_stats().dump().get("l_stage_store_fsync_count", 0)
    store = BlockStore(tmp_path / "osd", sync=False)
    txn = Transaction()
    txn.create_collection(CID)
    txn.write(CID, "obj", 0, os.urandom(8192))
    store.queue_transaction(txn)
    store.compact()
    store.close()
    assert kernel_stats().dump().get("l_stage_store_fsync_count", 0) == before
