#!/usr/bin/env python3
"""Chip smoke: the main path, once, on a TPU, through the entry points
a user calls — and nothing on it able to carry on without the device.

    python chip_smoke.py [--seed N] [--chips 4]

Default (one chip), all in this one process (a chip belongs to one
process; the CLIs are imported and their ``main(argv)`` called):

- A. EC through the plugin boundary: ``ec_benchmark`` encode/decode
  with ``backend=jax``, then seeded payloads through the registry,
  jax vs numpy backend, shards and recovered bytes identical.
- B. CRUSH: ``crushtool --test`` on the 10,000-OSD map over 2^20
  inputs, a strided sample against the scalar oracle, then
  ``osdmaptool --test-map-pgs`` over 2^20 PGs.
- C. One served EC pool: the in-process cluster (mon + mgr + OSDs),
  a ``backend=jax`` erasure pool, 4 MiB objects written through
  librados from concurrent writers and read back, an OSD lost and a
  sample read degraded, recovery, a deep scrub, HEALTH_OK.

``--chips 4`` runs only the mesh-sharded EC encode and CRUSH paths and
what they are compared with.

One JSON line per phase; the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any exception in any phase exits non-zero.  The phases are plain
functions of their sizes so ``tests/test_chip_smoke.py`` runs them tiny
on the CPU; only ``main`` insists on a TPU and the real sizes.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import copy
import io
import json
import sys
import tempfile
import time

import numpy as np

KINDS = ("ec_encode", "ec_decode", "crc32c", "compare", "crush")
HOST_BACKENDS = ("cpu", "numpy")


# -- bookkeeping ------------------------------------------------------------


class CompileClock:
    """Seconds JAX spent compiling (and persistent-cache hits), from
    JAX's own monitoring events — so a phase's wall splits into
    compile and run, and a warm second run shows as cache hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, name, secs, **_kw):
        if name.endswith("backend_compile_duration"):
            self.compile_s += secs

    def _on_event(self, name, **_kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.cache_hits += 1


class Dispatches:
    """Flight-recorder entries by (kind, backend), harvested by seq
    so the bounded ring cannot wrap unnoticed between harvests."""

    def __init__(self):
        from ceph_tpu.ops.profiler import dispatch_profiler

        self.prof = dispatch_profiler()
        self.counts: dict[str, int] = {}
        self.seq = max(
            (e["seq"] for e in self.prof.history()["entries"]), default=0
        )

    def harvest(self) -> dict[str, int]:
        entries = [
            e for e in self.prof.history()["entries"] if e["seq"] > self.seq
        ]
        new: dict[str, int] = {}
        if entries:
            if entries[0]["seq"] != self.seq + 1:
                raise RuntimeError(
                    "dispatch ring wrapped between harvests: entries "
                    f"{self.seq + 1}..{entries[0]['seq'] - 1} lost"
                )
            self.seq = entries[-1]["seq"]
        for e in entries:
            key = f"{e['kind']}:{e['backend']}"
            new[key] = new.get(key, 0) + 1
            self.counts[key] = self.counts.get(key, 0) + 1
        return new


@contextlib.contextmanager
def phase(name: str, clock: CompileClock, disp: Dispatches):
    """Time one phase — the body fills the yielded dict — and print
    its JSON line.  No ``except``: a failure propagates and the script
    exits non-zero."""
    rec: dict = {}
    c0, h0 = clock.compile_s, clock.cache_hits
    t0 = time.perf_counter()
    yield rec
    wall = time.perf_counter() - t0
    compile_s = clock.compile_s - c0
    rec = {
        "phase": name,
        **rec,
        "seconds": round(wall, 3),
        "compile_seconds": round(compile_s, 3),
        "run_seconds": round(wall - compile_s, 3),
        "persistent_cache_hits": clock.cache_hits - h0,
        "dispatches": disp.harvest(),
    }
    print(json.dumps(rec), flush=True)


def _cli(main, argv) -> str:
    """Call a tool's ``main(argv)`` in this process; return its
    stdout (echoed, so the run's log still shows it)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(argv)
    out = buf.getvalue()
    sys.stdout.write(out)
    if rc != 0:
        raise RuntimeError(f"{main.__module__} {argv} exited {rc}")
    return out


def _on_platform(arr, platform: str, what: str) -> None:
    got = {d.platform for d in arr.devices()}
    if got != {platform}:
        raise RuntimeError(f"{what}: result sits on {got}, not {platform}")


# -- phase A: EC through the plugin boundary --------------------------------


def phase_ec(
    seed: int,
    platform: str,
    *,
    size: int = 1 << 20,
    batch: int = 64,
    encode_iters: int = 16,
    decode_iters: int = 8,
) -> dict:
    import jax.numpy as jnp

    from ceph_tpu import gf
    from ceph_tpu.ec import ErasureCodeProfile, registry_instance, stripe
    from ceph_tpu.ops import packed_gf
    from ceph_tpu.ops.gf_matmul import (
        gf_matrix_stripes,
        matrix_to_device_bitmatrix,
    )
    from ceph_tpu.tools import ec_benchmark

    common = [
        "-p", "jerasure", "-P", "technique=reed_sol_van", "-P", "k=8",
        "-P", "m=3", "-P", "w=8", "-P", "backend=jax", "-s", str(size),
    ]
    # both through the stripe seam (ec/stripe.encode, ec/stripe.decode:
    # one dispatch a call); the tool runs one untimed iteration first,
    # so its seconds hold no compile — this phase's compile_s does
    batched = ["--batch", str(batch)]
    enc = _cli(
        ec_benchmark.main,
        common + batched + ["--workload", "encode", "-i", str(encode_iters)],
    )
    dec = _cli(
        ec_benchmark.main,
        common + batched
        + ["--workload", "decode", "-e", "2", "-i", str(decode_iters)],
    )

    rng = np.random.default_rng(seed)
    compared = 0
    cases = []
    for plugin, k, m, erasure_sets in (
        ("jerasure", 8, 3, ((1, 6), (0, 4, 9))),
        ("isa", 4, 2, ((0, 3),)),
    ):
        prof = {"k": str(k), "m": str(m), "w": "8"}
        if plugin == "jerasure":
            prof["technique"] = "reed_sol_van"
        ec_jax = registry_instance().factory(
            plugin, ErasureCodeProfile(backend="jax", **prof)
        )
        ec_np = registry_instance().factory(
            plugin, ErasureCodeProfile(**prof)
        )
        assert ec_jax.backend.name == "jax", ec_jax.backend.name
        assert ec_np.backend.name == "numpy", ec_np.backend.name
        payload = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        want = set(range(k + m))
        got_j = ec_jax.encode(want, payload)
        got_n = ec_np.encode(want, payload)
        for i in sorted(want):
            if not np.array_equal(got_j[i], got_n[i]):
                raise RuntimeError(
                    f"{plugin} k={k} m={m}: shard {i} differs jax vs numpy"
                )
            compared += got_j[i].nbytes
        # the stripe seam at a pool's 4 KiB chunk, where the packed
        # kernel reads the caller's buffer in stripe form: 12 stripes,
        # so a whole block of them and an edge block
        sinfo = stripe.StripeInfo(k, k * 4096)
        stripes = rng.integers(0, 256, 12 * k * 4096, dtype=np.uint8)
        seam_j = stripe.encode(sinfo, ec_jax, stripes)
        per_stripe = [
            ec_np.encode(want, one.tobytes())
            for one in stripes.reshape(12, -1)
        ]
        for i in sorted(want):
            shard = np.concatenate([chunks[i] for chunks in per_stripe])
            if not np.array_equal(seam_j[i], shard):
                raise RuntimeError(
                    f"{plugin} k={k} m={m}: stripe.encode's shard {i} "
                    "differs jax vs numpy"
                )
            compared += seam_j[i].nbytes
        for lost in erasure_sets:
            avail = {i: c for i, c in got_j.items() if i not in lost}
            rec_j = ec_jax.decode(want, avail)
            rec_n = ec_np.decode(want, dict(avail))
            for i in lost:
                if not (
                    np.array_equal(rec_j[i], got_n[i])
                    and np.array_equal(rec_n[i], got_n[i])
                ):
                    raise RuntimeError(
                        f"{plugin} k={k} m={m}: chunk {i} recovered "
                        f"wrong after losing {lost}"
                    )
                compared += rec_j[i].nbytes
            data = ec_jax.decode_concat(avail).tobytes()[:size]
            if data != payload:
                raise RuntimeError(
                    f"{plugin} k={k} m={m}: payload differs after "
                    f"losing {lost}"
                )
            compared += size
        cases.append(f"{plugin} k={k} m={m} lost={list(erasure_sets)}")

    # a result of each EC kernel sits on the device
    ec = registry_instance().factory(
        "jerasure",
        ErasureCodeProfile(technique="reed_sol_van", k="4", m="2", w="8"),
    )
    mat = np.asarray(ec.matrix, dtype=np.int64)
    stripes = rng.integers(0, 256, (4, 4, 4096), dtype=np.uint8)
    out = gf_matrix_stripes(
        matrix_to_device_bitmatrix(mat, 8), jnp.asarray(stripes), w=8
    )
    _on_platform(out, platform, "bitplane stripes")
    packed_built = packed_gf._packed_call.cache_info().currsize
    if platform == "tpu":
        # on a TPU the w=8 plugin calls above must have taken the
        # packed-lane kernel, not the bitplane program
        if not packed_built:
            raise RuntimeError("packed-lane kernel never built on the TPU")
        words = packed_gf.packed_word_regions(
            gf.jerasure_bitmatrix(mat, 8),
            packed_gf.to_words(stripes.transpose(1, 0, 2).reshape(4, -1)),
        )
        _on_platform(words[0], platform, "packed-lane kernel")
    return {
        "ec_benchmark_encode": enc.strip(),
        "ec_benchmark_encode_bytes": (1 + encode_iters) * batch * size,
        "ec_benchmark_decode": dec.strip(),
        "ec_benchmark_decode_bytes": (1 + decode_iters) * batch * size,
        "registry_cases": cases,
        "bytes_compared": compared,
        "packed_kernels_built": packed_built,
    }


# -- phase B: batched CRUSH -------------------------------------------------


def phase_crush(
    seed: int,
    platform: str,
    *,
    build: str = "10000:40:25",
    max_x: int = 1 << 20,
    pg_num: int = 1 << 20,
    sample: int = 4096,
) -> dict:
    import jax.numpy as jnp

    from ceph_tpu.crush import jaxmap
    from ceph_tpu.ops.kernel_stats import kernel_stats
    from ceph_tpu.tools import crushtool, osdmaptool

    parts = [int(v) for v in build.split(":")]
    m = crushtool.build_hierarchy(*parts)
    # UnsupportedMap here is a failure: the CLIs would fall to the
    # oracle and there would be nothing of the device to check
    cm = jaxmap.compile_map(m)

    def fallback_lanes() -> int:
        return int(kernel_stats().dump().get("l_tpu_crush_fallback_lanes", 0))

    lanes0 = fallback_lanes()
    t0 = time.perf_counter()
    out = _cli(
        crushtool.main,
        ["--test", "--build", build, "--min-x", "0", "--max-x", str(max_x),
         "--num-rep", "3", "--backend", "jax", "--show-statistics"],
    )
    crushtool_s = time.perf_counter() - t0
    if "[jax]" not in out:
        raise RuntimeError(f"crushtool did not run the jax backend: {out!r}")
    cli_lanes = fallback_lanes() - lanes0

    # the same call the CLI makes (replays its compiled chunk
    # program), sampled against the scalar oracle
    xs = np.arange(0, max_x, dtype=np.int64)
    res, counts = jaxmap.batch_do_rule(cm, 0, xs, 3)
    # ~25 ms an input for the scalar oracle on this map: a strided
    # sample plus a few seeded picks, not the whole range
    stride = max(max_x // sample, 1)
    rng = np.random.default_rng(seed)
    picks = np.unique(
        np.concatenate(
            [np.arange(0, max_x, stride), rng.integers(0, max_x, 64)]
        )
    )
    weights = [0x10000] * m.max_devices
    for x in picks:
        row = m.do_rule(0, int(x), 3, weights)
        got = [int(v) for v in res[x, : counts[x]]]
        if got != row:
            raise RuntimeError(f"crush x={x}: device {got} != oracle {row}")

    t0 = time.perf_counter()
    out2 = _cli(
        osdmaptool.main,
        ["--test-map-pgs", "--build", build, "--pg-num", str(pg_num),
         "--backend", "jax"],
    )
    osdmaptool_s = time.perf_counter() - t0

    fn, tables = jaxmap.batched_rule_call(cm, 0, 3, None)
    r, _c, _ok = fn(
        jnp.arange(8, dtype=jnp.int32),
        jnp.full(cm.max_devices, 0x10000, dtype=jnp.int32),
        *tables,
    )
    _on_platform(r, platform, "crush kernel")
    return {
        "build": build,
        "crushtool": out.strip().splitlines(),
        "crushtool_seconds": round(crushtool_s, 3),
        "chunk_lanes": jaxmap.CHUNK_LANES,
        "oracle_inputs_compared": int(len(picks)),
        "oracle_fallback_lanes_cli": cli_lanes,
        "oracle_fallback_lanes_total": fallback_lanes() - lanes0,
        "osdmaptool": out2.strip().splitlines(),
        "osdmaptool_seconds": round(osdmaptool_s, 3),
    }


# -- phase C: one served EC pool --------------------------------------------


def _wait(pred, timeout: float, what: str, poll: float = 0.25):
    """Poll ``pred`` until it returns something truthy that is not a
    ``str``; a ``str`` is its reason for "not yet" and ends up in the
    timeout's message."""
    deadline = time.monotonic() + timeout
    why = ""
    while time.monotonic() < deadline:
        got = pred()
        if got and not isinstance(got, str):
            return got
        why = got or why
        time.sleep(poll)
    raise RuntimeError(
        f"timed out after {timeout:.0f}s waiting for {what}: {why}"
    )


def _mon(client, cmd: dict) -> dict:
    rc, outb, outs = client.mon_command(cmd)
    if rc != 0:
        raise RuntimeError(f"mon command {cmd} failed: {outs}")
    return json.loads(outb) if outb else {}


def phase_pool(
    seed: int,
    platform: str,
    workdir: str,
    *,
    osds: int = 10,
    k: int = 4,
    m: int = 2,
    pg_num: int = 8,
    objects: int = 64,
    obj_size: int = 4 << 20,
    writers: int = 4,
    degraded_sample: int = 8,
    timeout: float = 300.0,
) -> dict:
    from ceph_tpu.ops import scrub_kernels
    from ceph_tpu.osd.daemon import OSD
    from ceph_tpu.osdc.objecter import object_to_pg
    from ceph_tpu.rados import Rados
    from ceph_tpu.tools.cluster import Cluster

    assert osds > k + m, "recovery needs a spare OSD to rebuild onto"
    rec: dict = {"osds": osds, "profile": f"jerasure k={k} m={m} backend=jax"}
    t_boot = time.perf_counter()
    cluster = Cluster({"dir": workdir, "osds": osds, "memstore": True})
    mon_addr = tuple(cluster.start()["mon_addr"])
    client = None
    try:
        if not cluster.wait_healthy(timeout):
            raise RuntimeError("cluster never reported every OSD up")
        rec["boot_seconds"] = round(time.perf_counter() - t_boot, 3)
        client = Rados("chip-smoke").connect(*mon_addr)
        client.objecter.op_timeout = timeout
        _mon(client, {
            "prefix": "osd erasure-code-profile set",
            "name": "smoke",
            "profile": [
                "plugin=jerasure", "technique=reed_sol_van",
                f"k={k}", f"m={m}", "backend=jax",
            ],
        })
        pool_id = client.pool_create(
            "smoke", pool_type=3, pg_num=pg_num,
            erasure_code_profile="smoke",
        )
        ioctx = client.open_ioctx("smoke")
        pool = client.monc.osdmap.pools[pool_id]

        rng = np.random.default_rng(seed)
        payloads = {
            f"obj-{i:04d}": rng.integers(
                0, 256, obj_size, dtype=np.uint8
            ).tobytes()
            for i in range(objects)
        }

        def read_all(names) -> int:
            n = 0
            for oid in names:
                if ioctx.read(oid) != payloads[oid]:
                    raise RuntimeError(f"{oid}: read differs from write")
                n += len(payloads[oid])
            return n

        # writes: a few concurrent writers, every ack awaited
        t0 = time.perf_counter()
        with concurrent.futures.ThreadPoolExecutor(writers) as ex:
            for fut in [
                ex.submit(ioctx.write_full, oid, data)
                for oid, data in payloads.items()
            ]:
                fut.result()
        rec["write_seconds"] = round(time.perf_counter() - t0, 3)
        rec["bytes_written"] = objects * obj_size
        t0 = time.perf_counter()
        rec["bytes_read_back"] = read_all(payloads)
        rec["read_seconds"] = round(time.perf_counter() - t0, 3)

        # lose an OSD that holds data shards.  FINDING (PERF.md): CRUSH
        # indep may re-draw positions that collided with the lost item,
        # and EC recovery wedges when a surviving shard's position
        # shifts (stores key shards by name, not position) — so the
        # victim is one whose going out moves no other position
        osdmap = client.monc.osdmap
        pgs = range(pool.pg_num)
        acting_of_pg = {
            ps: osdmap.pg_to_up_acting_osds(pool_id, ps)[2] for ps in pgs
        }

        def stable_out(osd: int) -> bool:
            om = copy.deepcopy(osdmap)
            om.osd_weight[osd] = 0
            return all(
                a == osd or a == b
                for ps in pgs
                for a, b in zip(
                    acting_of_pg[ps],
                    om.pg_to_up_acting_osds(pool_id, ps)[2],
                )
            )

        acting_of = {
            oid: acting_of_pg[int(object_to_pg(pool, oid).split(".")[1])]
            for oid in payloads
        }

        def data_shards_on(osd: int) -> int:
            return sum(osd in a[:k] for a in acting_of.values())

        candidates = [
            o for o in range(osds) if data_shards_on(o) and stable_out(o)
        ]
        if not candidates:
            raise RuntimeError(
                "no OSD holding data shards can go out without CRUSH "
                "shifting another EC position (see PERF.md findings)"
            )
        victim = max(candidates, key=data_shards_on)
        rec["victim"] = victim
        rec["position_stable_candidates"] = candidates
        hit = [o for o, a in acting_of.items() if victim in a[:k]]
        dead = cluster.osds[victim]
        dead.shutdown()
        _mon(client, {"prefix": "osd down", "id": victim})
        _wait(
            lambda: not client.monc.osdmap.is_up(victim), timeout,
            f"osd.{victim} down in the client's map",
        )
        t0 = time.perf_counter()
        rec["degraded_objects_read"] = len(hit[:degraded_sample])
        rec["bytes_read_degraded"] = read_all(hit[:degraded_sample])
        rec["degraded_read_seconds"] = round(time.perf_counter() - t0, 3)

        # out -> CRUSH re-places its positions -> recovery rebuilds
        # the lost shards from the survivors
        _mon(client, {"prefix": "osd out", "id": victim})

        def clean():
            """Every PG of the pool active on a full acting set of
            live OSDs, no recovery or reservation pending on any OSD
            (read in-process: the daemons are hosted here), and the
            mon's digest back to zero degraded/misplaced."""
            om = client.monc.osdmap
            live = {o.whoami: o for o in cluster.osds if o is not dead}
            for ps in range(pool.pg_num):
                acting, primary = om.pg_to_up_acting_osds(pool_id, ps)[2:]
                if primary not in live or len(acting) != k + m or any(
                    o not in live for o in acting
                ):
                    return f"pg {pool_id}.{ps} acting {acting}"
                pg = live[primary].pgs.get(f"{pool_id}.{ps}")
                if (
                    pg is None
                    or pg.state != "active"
                    or pg.peered_interval is None
                ):
                    return (
                        f"pg {pool_id}.{ps} on osd.{primary}: "
                        f"{getattr(pg, 'state', None)}"
                    )
            busy = {
                o.whoami: (
                    len(o._recovering),
                    len(o._local_reservations),
                    len(o._remote_reservations),
                )
                for o in live.values()
                if o._recovering
                or o._local_reservations
                or o._remote_reservations
            }
            if busy:
                return f"recovering/reserved: {busy}"
            data = _mon(client, {"prefix": "status"}).get(
                "pgmap", {}
            ).get("data", {})
            if not data or int(data.get("degraded", 0)) or int(
                data.get("misplaced", 0)
            ):
                return f"pgmap digest: {data}"
            return True

        t0 = time.perf_counter()
        _wait(clean, timeout, "recovery to finish", poll=0.5)
        rec["recovery_seconds"] = round(time.perf_counter() - t0, 3)
        rec["bytes_read_after_recovery"] = read_all(payloads)

        # deep scrub of the PG holding the first object
        pgid = object_to_pg(pool, "obj-0000")
        before = deep_scrub_stamps(cluster, pgid)
        client.pg_scrub(pgid, deep=True)
        _wait(
            lambda: deep_scrub_stamps(cluster, pgid) != before
            and not client.list_inconsistent_obj(pgid),
            timeout, f"deep scrub of {pgid}",
        )
        rec["deep_scrubbed_pg"] = pgid
        if client.list_inconsistent_obj(pgid):
            raise RuntimeError(f"deep scrub of {pgid} found damage")

        # the failed OSD comes back with its store and is marked in:
        # only then can the cluster be HEALTH_OK again
        back = OSD(
            victim, store=dead.store,
            admin_socket_path=str(cluster.dir / f"osd.{victim}.asok"),
        )
        back.boot(*mon_addr)
        cluster.osds[victim] = back
        dead = None
        _mon(client, {"prefix": "osd in", "id": victim})

        def healthy():
            h = _mon(client, {"prefix": "health"})
            if h.get("status") != "HEALTH_OK":
                return f"health: {h.get('checks')}"
            settled = clean()
            return h if settled is True else settled

        health = _wait(healthy, timeout, "HEALTH_OK", poll=0.5)
        rec["health"] = health["status"]
        rec["bytes_read_at_end"] = read_all(payloads)

        r = scrub_kernels._crc_call(scrub_kernels._CHUNK, 1)(
            np.zeros((1, 1, scrub_kernels._CHUNK), dtype=np.uint8),
            scrub_kernels._device_chunk_matrix(scrub_kernels._CHUNK),
            scrub_kernels._device_combine_matrix(scrub_kernels._CHUNK, 1),
        )
        _on_platform(r, platform, "crc32c kernel")
    finally:
        if client is not None:
            client.shutdown()
        cluster.stop()
    return rec


def deep_scrub_stamps(cluster, pgid: str) -> dict:
    """When ``pgid`` last finished a deep scrub, by primary OSD (read
    in-process: the daemons are hosted here)."""
    out = {}
    for osd in cluster.osds:
        pg = osd.pgs.get(pgid)
        if pg is not None and pg.primary == osd.whoami:
            out[osd.whoami] = pg.last_deep_scrub
    return out


def check_dispatches(disp: Dispatches, platform: str) -> dict:
    """The flight recorder's verdict on the whole run: the device
    kinds ran on the jax backend, none of them on a host backend."""
    disp.harvest()
    counts = disp.counts
    host = {
        key: n for key, n in counts.items()
        if key.split(":")[0] in KINDS and key.split(":")[1] in HOST_BACKENDS
    }
    if host:
        raise RuntimeError(f"host-backend dispatches on the main path: {host}")
    # ec_decode: phase A's batched decode and every degraded read of
    # phase C are one recorded dispatch each (ec/stripe.decode)
    for kind in ("ec_encode", "ec_decode", "crc32c", "crush"):
        if not counts.get(f"{kind}:jax"):
            raise RuntimeError(f"no {kind} dispatch with backend jax recorded")
    return {"phase": "dispatches", "platform": platform, "by_kind": counts}


# -- --chips 4: the mesh paths ----------------------------------------------


def phase_mesh(
    seed: int,
    n_devices: int,
    *,
    batches: tuple = (64, 61),
    chunk: int = 131072,
    build: str = "10000:40:25",
    inputs: int = 1 << 18,
    sample: int = 1024,
) -> dict:
    import jax
    import jax.numpy as jnp

    from ceph_tpu.crush import jaxmap
    from ceph_tpu.ec import ErasureCodeProfile, registry_instance
    from ceph_tpu.ec.backend import get_backend
    from ceph_tpu.ops import mesh
    from ceph_tpu.ops.gf_matmul import (
        gf_matrix_stripes,
        matrix_to_device_bitmatrix,
    )
    from ceph_tpu.osd import sharded_mapping
    from ceph_tpu.tools.crushtool import build_hierarchy

    dmesh = mesh.default_mesh()
    if dmesh is None or dmesh.n != n_devices:
        raise RuntimeError(f"default mesh is {dmesh}, wanted {n_devices}")
    one = jax.devices()[0]

    def spread(arr, what):
        ids = {s.device.id for s in arr.addressable_shards}
        if len(ids) != n_devices:
            raise RuntimeError(f"{what}: shards sit on devices {ids}")

    rng = np.random.default_rng(seed)
    ec = registry_instance().factory(
        "jerasure",
        ErasureCodeProfile(technique="reed_sol_van", k="8", m="3", w="8"),
    )
    mat = np.asarray(ec.matrix, dtype=np.int64)
    bm = matrix_to_device_bitmatrix(mat, 8)
    compared = 0
    for b in batches:
        stripes = rng.integers(0, 256, (b, 8, chunk), dtype=np.uint8)
        got = mesh.sharded_matrix_stripes(bm, stripes, 8, dmesh)
        single = np.asarray(
            gf_matrix_stripes(bm, jax.device_put(stripes, one), w=8)
        )
        oracle = get_backend("numpy").matrix_stripes(mat, stripes, 8)
        if not (np.array_equal(got, single) and np.array_equal(got, oracle)):
            raise RuntimeError(f"sharded encode differs at batch {b}")
        compared += got.nbytes
        # where the shards sit: the same placement, kept on device
        padded, _ = mesh.pad_to_devices(stripes, dmesh.n)
        data = jax.device_put(padded, dmesh.batch_spec(3))
        spread(data, "encode input")
        spread(
            mesh._sharded_stripe_fn(dmesh, 8)(
                jax.device_put(bm, dmesh.replicated_spec()), data
            ),
            "encode output",
        )

    parts = [int(v) for v in build.split(":")]
    m = build_hierarchy(*parts)
    cm = jaxmap.compile_map(m)
    xs = np.arange(inputs, dtype=np.int32)
    res, counts = sharded_mapping.mesh_batch_do_rule(cm, 0, xs, 3)
    res1, counts1 = jaxmap.batch_do_rule(cm, 0, xs, 3)
    if not (np.array_equal(res, res1) and np.array_equal(counts, counts1)):
        raise RuntimeError("sharded CRUSH differs from one device")
    weights = [0x10000] * m.max_devices
    for x in np.arange(0, inputs, max(inputs // sample, 1)):
        row = m.do_rule(0, int(x), 3, weights)
        if [int(v) for v in res[x, : counts[x]]] != row:
            raise RuntimeError(f"sharded CRUSH x={x} differs from oracle")
    fn, tables = jaxmap.batched_rule_call(cm, 0, 3, None)
    xs_dev = jax.device_put(
        xs[: min(inputs, jaxmap.CHUNK_LANES * dmesh.n)], dmesh.batch_spec(1)
    )
    spread(xs_dev, "crush input")
    r, _c, _ok = fn(
        xs_dev, jnp.full(cm.max_devices, 0x10000, dtype=jnp.int32), *tables
    )
    spread(r, "crush output")
    return {
        "mesh_devices": dmesh.n,
        "encode_batches": list(batches),
        "encode_bytes_compared": compared,
        "crush_inputs": inputs,
        "crush_oracle_inputs_compared": sample,
    }


# -- entry ------------------------------------------------------------------


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = p.parse_args(argv)

    import jax

    devices = jax.devices()  # raises where no backend initialises
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {len(devices)} x "
            f"{dev.platform} ({dev.device_kind})",
            file=sys.stderr,
        )
        return 1
    if len(devices) != args.chips:
        print(
            f"chip_smoke: --chips {args.chips} but JAX found "
            f"{len(devices)} devices",
            file=sys.stderr,
        )
        return 1

    from ceph_tpu.common.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    clock = CompileClock()
    disp = Dispatches()
    print(json.dumps({
        "phase": "start", "seed": args.seed, "chips": args.chips,
        "jax": jax.__version__, "compile_cache": cache_dir,
    }), flush=True)

    if args.chips == 4:
        with phase("mesh", clock, disp) as rec:
            rec.update(phase_mesh(args.seed, 4))
    else:
        with phase("A:ec", clock, disp) as rec:
            rec.update(phase_ec(args.seed, dev.platform))
        with phase("B:crush", clock, disp) as rec:
            rec.update(phase_crush(args.seed, dev.platform))
        with tempfile.TemporaryDirectory(prefix="chip_smoke.") as work:
            with phase("C:pool", clock, disp) as rec:
                rec.update(phase_pool(args.seed, dev.platform, work))
        print(json.dumps(check_dispatches(disp, dev.platform)), flush=True)

    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
