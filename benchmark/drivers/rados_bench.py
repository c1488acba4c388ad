"""``rados bench`` against one served erasure-coded pool: a closed loop
of full-object writes or reads from one librados client, the cluster
(mon, mgr, OSDs) thread-hosted in this process as ``chip_smoke.py``
phase C hosts it.

The traffic mix is data: ``mode`` (``write`` = ``rados bench write``,
``rand_read`` = ``rados bench rand``), ``object_bytes``, ``in_flight``,
``payload_pool``, ``prefill_objects``, ``warm_ops``, ``check_sample``.
As upstream's, a write window never reuses a name: op ``i`` creates
``obj-<i>`` with payload ``i`` of a seeded pool, rotated; a read window
draws seeded names from the objects that set-up wrote.  The stores
are the process's memory and go with it, so nothing is deleted at the
end.  ``in_flight`` ops are outstanding at the client's
``aio_write_full`` / ``aio_read``; how many of them the client has on
the wire is the product's business (``client_wire_p50_ms``).

``check`` compares, once the window has closed, every shard that every
write of the window left on each of the k+m OSDs with the plain
reference's shard, and a seeded sample of read answers with the payload
written.
"""

from __future__ import annotations

import concurrent.futures
import json
import random
import time

import numpy as np

OBJ_PREFIX = "o_"  # how the OSD names a head object in its store


class Driver:
    def __init__(self, config, traffic, seed, workdir, annotate, reference):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.workdir = workdir
        self.annotate = annotate
        self.reference = reference
        self.cluster = None
        self.client = None
        self.ioctx = None
        self.ops_done = 0
        self.next_op = 0  # op indices run on from the warm-up's
        self.written: dict[str, int] = {}  # name -> payload index, acked writes
        self.read_samples: list[tuple[str, bytes]] = []
        self.short_reads = 0
        self.timeout = float(traffic.get("op_timeout_s", 60.0))
        self.k = int(config["profile"]["k"])
        self.m = int(config["profile"]["m"])

    # -- set-up ----------------------------------------------------------
    def setup(self) -> None:
        from ceph_tpu.rados import Rados
        from ceph_tpu.tools.cluster import Cluster

        cfg, tr = self.config, self.traffic
        if cfg["objectstore"] != "memstore":
            raise ValueError("this driver hosts memstore OSDs only")
        self.cluster = Cluster(
            {"dir": str(self.workdir), "osds": int(cfg["osds"]), "memstore": True}
        )
        mon_addr = tuple(self.cluster.start()["mon_addr"])
        if not self.cluster.wait_healthy(self.timeout):
            raise RuntimeError("cluster never reported every OSD up")
        self.client = Rados("bench").connect(*mon_addr)
        self.client.objecter.op_timeout = self.timeout
        profile = [f"{key}={val}" for key, val in cfg["profile"].items()]
        self._mon({
            "prefix": "osd erasure-code-profile set",
            "name": "bench", "profile": profile,
        })
        self.pool_id = self.client.pool_create(
            "bench", pool_type=3, pg_num=int(cfg["pg_num"]),
            erasure_code_profile="bench",
        )
        self.ioctx = self.client.open_ioctx("bench")
        self._wait_active()

        rng = np.random.default_rng(self.seed)
        size = int(tr["object_bytes"])
        self.payloads = [
            rng.integers(0, 256, size, dtype=np.uint8).tobytes()
            for _ in range(int(tr["payload_pool"]))
        ]
        self.prefilled = [
            f"pre-{i:05d}" for i in range(int(tr.get("prefill_objects", 0)))
        ]
        if tr["mode"] not in ("write", "rand_read"):
            raise ValueError(f"mode {tr['mode']!r} is neither write nor rand_read")
        self.reads = tr["mode"] == "rand_read"
        if self.reads and not self.prefilled:
            raise ValueError("reads need prefill_objects")
        # a read window's own names, from the seed
        self.read_names = rng.integers(0, max(len(self.prefilled), 1), 1 << 16)
        # which read answers are kept for check: a reservoir of
        # check_sample, drawn from the seed over all reads of the window
        self.sample_rng = random.Random(self.seed)
        self.reads_seen = 0

        # prefill, then warm the window's own shapes through its own
        # calls; none of it is kept
        self._run_ops(
            [
                ("w", name, i % len(self.payloads))
                for i, name in enumerate(self.prefilled)
            ]
        )
        if not self.reads:
            self._warm_coalesced_encode()
        warm = int(tr.get("warm_ops", 0))
        self.window(seconds=None, max_ops=warm)
        self.ops_done = 0
        self.written.clear()
        self.read_samples.clear()
        self.reads_seen = 0
        self.spans()  # the warm-up's are not the window's

    def _warm_coalesced_encode(self) -> None:
        """An OSD that finds several writes queued encodes them in one
        coalesced dispatch, which is another program than a lone
        write's, and whether the warm-up's own writes ever queue up is
        a matter of timing.  So the pool's codec encodes batches of
        every size class here, before anything is timed."""
        from ceph_tpu.osd.ec_pg import ECCodec

        om = self.client.monc.osdmap
        pool = om.pools[self.pool_id]
        codec = ECCodec(dict(om.erasure_code_profiles[pool.erasure_code_profile]))
        batch_max = max(o.osd_tpu_batch_max for o in self.cluster.osds)
        sizes = {batch_max}
        n = 2
        while n < batch_max:  # 2, 3, 5, 9, ...: one of every power-of-two bucket
            sizes.add(n)
            n = 2 * n - 1
        for n in sorted(size for size in sizes if size >= 2):
            codec.encode_object_batch([self.payloads[0]] * n)

    def _mon(self, cmd: dict) -> dict:
        rc, outb, outs = self.client.mon_command(cmd)
        if rc != 0:
            raise RuntimeError(f"mon command {cmd} failed: {outs}")
        return json.loads(outb) if outb else {}

    def _wait_active(self) -> None:
        """Every PG of the pool active on its primary (read in-process:
        the daemons are hosted here)."""
        osds = {o.whoami: o for o in self.cluster.osds}
        deadline = time.monotonic() + self.timeout
        why = ""
        while time.monotonic() < deadline:
            om = self.client.monc.osdmap
            pool = om.pools.get(self.pool_id)
            why = "pool not in the client's map"
            if pool is not None:
                for ps in range(pool.pg_num):
                    primary = om.pg_to_up_acting_osds(self.pool_id, ps)[3]
                    pg = osds[primary].pgs.get(f"{self.pool_id}.{ps}")
                    if pg is None or pg.state != "active":
                        why = f"pg {self.pool_id}.{ps} on osd.{primary}"
                        break
                else:
                    return
            time.sleep(0.1)
        raise RuntimeError(f"pool never went active: {why}")

    # -- the loop ----------------------------------------------------------
    def _submit(self, kind: str, name: str, payload_idx: int):
        if kind == "w":
            return self.ioctx.aio_write_full(name, self.payloads[payload_idx])
        return self.ioctx.aio_read(name)

    def _run_ops(self, ops) -> None:
        """Set-up traffic: every op awaited, a failure raises."""
        in_flight = int(self.traffic["in_flight"])
        for lo in range(0, len(ops), in_flight):
            for fut in [self._submit(*op) for op in ops[lo : lo + in_flight]]:
                fut.result(timeout=self.timeout)

    def _op(self, i: int) -> tuple[str, str, int]:
        """The i-th op since set-up began: kind, name, payload index."""
        if self.reads:
            j = int(self.read_names[i % len(self.read_names)])
            return "r", self.prefilled[j], j % len(self.payloads)
        return "w", f"obj-{i:07d}", i % len(self.payloads)

    def window(self, seconds, max_ops=None) -> dict:
        """Keep ``in_flight`` ops outstanding until ``seconds`` have
        passed (or ``max_ops`` were submitted), then wait for the rest.
        Returns the ops as (t_submit, t_done, nbytes, ok, name)."""
        in_flight = int(self.traffic["in_flight"])
        size = int(self.traffic["object_bytes"])
        pending: dict = {}
        done_at: dict = {}
        ops = []
        submitted = 0
        t0 = time.perf_counter()

        def more() -> bool:
            if max_ops is not None and submitted >= max_ops:
                return False
            return seconds is None or time.perf_counter() - t0 < seconds

        def submit():
            nonlocal submitted
            kind, name, pidx = self._op(self.next_op)
            t = time.perf_counter()
            fut = self._submit(kind, name, pidx)
            fut.add_done_callback(
                lambda f: done_at.__setitem__(f, time.perf_counter())
            )
            pending[fut] = (t, kind, name, pidx)
            self.next_op += 1
            submitted += 1

        with self.annotate("bench:client_submit"):
            while len(pending) < in_flight and more():
                submit()
        while pending:
            with self.annotate("bench:client_wait"):
                done, _ = concurrent.futures.wait(
                    pending, timeout=self.timeout,
                    return_when=concurrent.futures.FIRST_COMPLETED,
                )
            if not done:
                raise RuntimeError(
                    f"no op completed in {self.timeout:.0f} s: "
                    f"{len(pending)} outstanding"
                )
            with self.annotate("bench:client_submit"):
                for fut in done:
                    t_sub, kind, name, pidx = pending.pop(fut)
                    t_done = done_at.pop(fut, None) or time.perf_counter()
                    ok = fut.exception() is None
                    if ok and kind == "w":
                        self.written[name] = pidx
                    elif ok:
                        data = fut.result()
                        if len(data) != size:
                            self.short_reads += 1
                        self._keep_sample(name, data)
                    ops.append((t_sub, t_done, size, ok, name))
                    self.ops_done += 1
                while len(pending) < in_flight and more():
                    submit()
        return {"ops": ops, "units": len(ops), "t0": t0}

    def _keep_sample(self, name: str, data: bytes) -> None:
        keep = int(self.traffic.get("check_sample", 32))
        self.reads_seen += 1
        if len(self.read_samples) < keep:
            self.read_samples.append((name, data))
        else:
            slot = self.sample_rng.randrange(self.reads_seen)
            if slot < keep:
                self.read_samples[slot] = (name, data)

    def counters(self) -> dict:
        return {"client.ops_done": self.ops_done}

    def spans(self) -> list[dict]:
        """The Objecter's root spans (``client_op``: target, send,
        retries, reply — the time an op is the Objecter's) finished
        since the last call, each with the object's name."""
        return [
            {"name": s["name"], "oid": s["tags"].get("oid"),
             "duration_s": s["duration"]}
            for s in self.client.objecter.tracer.drain(1 << 20)
            if s["name"] == "client_op"
        ]

    # -- correctness -------------------------------------------------------
    def check(self) -> dict:
        """Numbers compared, each with its limit (all exact: 0)."""
        from ceph_tpu.osdc.objecter import object_to_pg

        cfg = self.config
        stripe_unit = int(cfg["stripe_unit"])
        om = self.client.monc.osdmap
        pool = om.pools[self.pool_id]
        stores = {o.whoami: o for o in self.cluster.osds}
        expected: dict[int, list] = {}

        def shards_of(pidx: int):
            if pidx not in expected:
                expected[pidx] = self.reference.encode_shards(
                    self.payloads[pidx], self.k, self.m, stripe_unit
                )
            return expected[pidx]

        wrong_shards = 0
        shards_seen = 0
        written = dict(self.written)
        if not written:
            # a read-only window: the shards its answers came from
            for name, _data in self.read_samples:
                written[name] = int(name.split("-")[1]) % len(self.payloads)
        for name, pidx in sorted(written.items()):
            pgid = object_to_pg(pool, name)
            acting = om.pg_to_up_acting_osds(
                self.pool_id, int(pgid.split(".")[1])
            )[2]
            want = shards_of(pidx)
            if len(acting) != len(want):
                wrong_shards += len(want)
                continue
            for pos, osd_id in enumerate(acting):
                shards_seen += 1
                osd = stores.get(osd_id)
                pg = osd.pgs.get(pgid) if osd is not None else None
                try:
                    got = osd.store.read(pg.cid, OBJ_PREFIX + name)
                except Exception:  # noqa: BLE001 — an absent shard is a wrong one
                    got = None
                if got is None or bytes(got) != want[pos].tobytes():
                    wrong_shards += 1

        wrong_reads = self.short_reads
        for name, data in self.read_samples:
            pidx = int(name.split("-")[1]) % len(self.payloads)
            if data != self.payloads[pidx]:
                wrong_reads += 1
        # writes: read a seeded few back through the client as well
        rng = np.random.default_rng(self.seed + 1)
        names = sorted(self.written)
        back = int(self.traffic.get("check_sample", 32))
        for name in rng.permutation(names)[:back]:
            try:
                data = self.ioctx.read(str(name))
            except Exception:  # noqa: BLE001 — an answer that never comes is a wrong one
                data = None
            if data != self.payloads[self.written[str(name)]]:
                wrong_reads += 1
        return {
            "wrong_shards": (wrong_shards, 0),
            "wrong_reads": (wrong_reads, 0),
            "shards_unchecked": (0 if shards_seen else 1, 0),
        }

    # -- faults (control.py and the tests plant them; never a run) ---------
    def fault_control(self):
        """The control: the reference with its guarantee broken, put in
        the program's place — every object's last parity shard leaves
        the last data chunk out."""
        return self._patch_last_parity(
            lambda payload, _shard: self.reference.encode_shards(
                payload, self.k, self.m, int(self.config["stripe_unit"]),
                guarantee="broken",
            )[-1]
        )

    def fault_altered_answer(self):
        """One byte of every object's last parity shard altered where
        it is produced."""
        def alter(_payload, shard):
            out = np.array(shard, dtype=np.uint8)
            out[len(out) // 2] ^= 0x01
            return out

        return self._patch_last_parity(alter)

    def _patch_last_parity(self, replace):
        """``stripe.encode_batch`` with every object's last parity shard
        replaced by ``replace(payload, shard)``; returns the undo."""
        from ceph_tpu.ec import stripe

        original = stripe.encode_batch
        last = self.k + self.m - 1

        def broken(sinfo, ec, buffers):
            shard_sets = original(sinfo, ec, buffers)
            for buf, shards in zip(buffers, shard_sets):
                shards[last] = replace(bytes(buf), shards[last])
            return shard_sets

        stripe.encode_batch = broken
        return lambda: setattr(stripe, "encode_batch", original)

    def fault_state_unchanged(self):
        """Every shard write acknowledged and not applied: the stores
        keep what they had."""
        from ceph_tpu.osd import daemon
        from ceph_tpu.store.objectstore import Transaction

        original = daemon.shard_write_txn

        def unchanged(cid, oid, shard, meta, attrs=None):
            txn = Transaction()
            txn.touch(cid, oid)
            return txn

        daemon.shard_write_txn = unchanged
        return lambda: setattr(daemon, "shard_write_txn", original)

    def close(self) -> None:
        if self.client is not None:
            self.client.shutdown()
        if self.cluster is not None:
            self.cluster.stop()
