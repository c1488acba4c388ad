"""Batched full-map PG→OSD computation (OSDMapMapping replacement).

The reference shards pgid ranges over a thread pool
(ParallelPGMapper, src/osd/OSDMapMapping.h:18-156).  Here one device
call per pool runs the CRUSH stage for every PG
(ceph_tpu.crush.jaxmap), and the cheap fix-up stages — nonexistent/down
filtering, upmap overrides, primary affinity, pg_temp — are vectorized
numpy on the host.  Falls back to the scalar oracle per-PG when the map
is outside the device kernel's scope (legacy bucket algs etc.).
"""

from __future__ import annotations

import numpy as np

from ..common import tracing
from ..crush.hashing import crush_hash32_2
from ..crush.types import CRUSH_ITEM_NONE
from .osdmap import (
    CEPH_OSD_DEFAULT_PRIMARY_AFFINITY,
    CEPH_OSD_MAX_PRIMARY_AFFINITY,
    OSDMap,
    PgPool,
)

_NONE = CRUSH_ITEM_NONE


def _stable_mod_vec(x: np.ndarray, b: int, bmask: int) -> np.ndarray:
    lo = x & bmask
    return np.where(lo < b, lo, x & (bmask >> 1))


def pool_pps_vec(pool: PgPool, ps: np.ndarray) -> np.ndarray:
    """Vectorized pg_pool_t::raw_pg_to_pps."""
    m = _stable_mod_vec(ps, pool.pgp_num, pool.pgp_num_mask)
    if pool.hashpspool:
        return crush_hash32_2(
            m.astype(np.uint32),
            np.uint32(pool.pool_id & 0xFFFFFFFF),
        )
    return (m + pool.pool_id).astype(np.uint32)


def _compact_rows(osds: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """Shift valid entries left per row (replicated-pool hole removal);
    invalid tail slots become CRUSH_ITEM_NONE."""
    order = np.argsort(~valid, axis=1, kind="stable")
    packed = np.take_along_axis(osds, order, axis=1)
    keep = np.take_along_axis(valid, order, axis=1)
    return np.where(keep, packed, _NONE)


class _PoolPps:
    """``pool_pps_vec`` of a pool's PGs, worked out as far as it has
    been asked for (``pps[lo:hi]``, nothing else).  The pipeline asks
    for a part as it issues it (jaxmap.map_parts), so every part's
    inputs but the first few are computed under the kernels of the
    parts before — a ``crush_inputs`` span each time — and a part of
    them stays in the cache."""

    def __init__(self, pool: PgPool, ps: np.ndarray):
        self._pool = pool
        self._ps = ps
        self._pps = np.empty(len(ps), dtype=np.int64)
        self._done = 0

    def __len__(self) -> int:
        return len(self._ps)

    def __getitem__(self, rows: slice) -> np.ndarray:
        stop = min(rows.stop, len(self._ps))
        if stop > self._done:
            todo = slice(self._done, stop)
            with tracing.span(
                "crush_inputs", tags={"pool": self._pool.pool_id}
            ):
                self._pps[todo] = pool_pps_vec(self._pool, self._ps[todo])
            self._done = stop
        return self._pps[rows]


class _OsdTables:
    """What the fix-ups look up by OSD id, as arrays built once a pool:
    ``exists``, ``up`` (exists and up) and ``affinity`` (None where the
    map sets none), each with one slot past ``max_osd`` — where ids out
    of range are clipped to — that reads False / 0."""

    def __init__(self, osdmap: OSDMap):
        self.exists = np.zeros(osdmap.max_osd + 1, dtype=bool)
        self.up = np.zeros(osdmap.max_osd + 1, dtype=bool)
        self.exists[:-1] = np.asarray(osdmap.osd_exists, dtype=bool)
        self.up[:-1] = self.exists[:-1] & np.asarray(
            osdmap.osd_up, dtype=bool
        )
        self.affinity = None
        if osdmap.osd_primary_affinity is not None:
            self.affinity = np.zeros(osdmap.max_osd + 1, dtype=np.int64)
            self.affinity[:-1] = np.asarray(
                osdmap.osd_primary_affinity, dtype=np.int64
            )


def _build_perf():
    from ..common import PerfCountersBuilder

    return (
        PerfCountersBuilder("osdmap_mapping")
        .add_u64_counter("updates", "full-map recomputes")
        .add_u64_counter("pgs_mapped", "PGs mapped across updates")
        .add_time_avg("crush_stage", "device/oracle CRUSH stage time")
        .add_time_avg("fixup_stages", "host fix-up stage time")
        .create_perf_counters()
    )


class OSDMapMapping:
    """Caches up/acting/primaries for every PG of every pool
    (the consumer API of src/osd/OSDMapMapping.h:173-340); exposes
    reference-style perf counters (the l_osd_* analog) via
    ``self.perf.dump()``, and traces every ``update`` as a ``remap``
    span with one child a stage.  A host that serves the spans
    (osdmaptool prints them) passes its ``tracer``; without one the
    spans feed the stage counters and the profiler mirror and nothing
    is kept."""

    def __init__(self, tracer: tracing.Tracer | None = None):
        self.up: dict[int, np.ndarray] = {}
        self.up_primary: dict[int, np.ndarray] = {}
        self.acting: dict[int, np.ndarray] = {}
        self.acting_primary: dict[int, np.ndarray] = {}
        self.epoch = 0
        self.perf = _build_perf()
        self.tracer = tracer or tracing.Tracer("mapping", buffered=False)
        # the pool's parts off the device while ``_update_pool`` runs
        self._parts = None

    # -- batch pipeline ----------------------------------------------------
    def update(self, osdmap: OSDMap, use_device: bool = True) -> None:
        """Recompute every pool's full PG mapping."""
        with self.tracer.start_span(
            "remap", tags={"epoch": osdmap.epoch}
        ):
            self.epoch = osdmap.epoch
            self.perf.inc("updates")
            for pool_id, pool in osdmap.pools.items():
                self._update_pool(osdmap, pool, use_device)
                self.perf.inc("pgs_mapped", pool.pg_num)

    def _update_pool(
        self, osdmap: OSDMap, pool: PgPool, use_device: bool
    ) -> None:
        """The pool's four tables, a part at a time: a part's raw rows
        come from one ``_crush_stage`` call and are fixed up and written
        into their rows while the device maps the parts after it
        (``_device_parts``).  Every stage is row-local, so the parts in
        order give the bytes of the whole; a pool of one part, or one
        the host maps, is one call of each stage."""
        from ..ops.kernel_stats import kernel_stats

        n = pool.pg_num
        ps = np.arange(n, dtype=np.int64)
        pps = _PoolPps(pool, ps)
        up = np.empty((n, pool.size), dtype=np.int64)
        acting = np.empty_like(up)
        up_primary = np.empty(n, dtype=np.int64)
        acting_primary = np.empty_like(up_primary)

        ks = kernel_stats()
        pgs_counter = ks.counter(
            "crush", "pgs", desc="PGs mapped through the CRUSH kernel"
        )
        osds = _OsdTables(osdmap)
        # what ``_crush_stage`` draws from for the length of this call
        self._parts, step = (
            self._device_parts(osdmap, pool, pps) if use_device
            else (None, n)
        )
        on_device = self._parts is not None
        try:
            for lo in range(0, n, step or 1):
                rows = slice(lo, lo + step)
                part_pps = pps[rows]
                with self.perf.time_it("crush_stage"), ks.timed(
                    "crush", bytes_in=part_pps.nbytes
                ) as kt:
                    raw = self._crush_stage(
                        osdmap, pool, part_pps, on_device
                    )
                    kt.bytes_out = raw.nbytes
                ks.perf.inc(pgs_counter, len(raw))
                with self.perf.time_it("fixup_stages"):
                    part_up, part_primary = self._fixup(
                        osdmap, pool, osds, ps[rows], part_pps, raw
                    )
                    with tracing.span("fixup_temp"):
                        up[rows] = acting[rows] = part_up
                        up_primary[rows] = part_primary
                        acting_primary[rows] = part_primary
                        self._temp_stage(
                            osdmap, pool, lo,
                            acting[rows], acting_primary[rows],
                        )
        finally:
            if on_device:
                self._parts.close()
            self._parts = None
        self.up[pool.pool_id] = up
        self.up_primary[pool.pool_id] = up_primary
        self.acting[pool.pool_id] = acting
        self.acting_primary[pool.pool_id] = acting_primary

    def _fixup(self, osdmap, pool, osds, ps, pps, raw):
        """(up, up_primary) of the rows ``raw`` holds."""
        with tracing.span("fixup_exists"):
            # _remove_nonexistent_osds + _raw_to_up_osds, fused: both
            # drop to NONE (EC) or compact (replicated)
            idx = np.clip(raw, 0, osdmap.max_osd)
            in_range = (raw >= 0) & (raw < osdmap.max_osd)
            raw_exists = in_range & osds.exists[idx]
            if pool.can_shift_osds():
                raw = _compact_rows(raw, raw_exists)
            else:
                raw = np.where(raw_exists | (raw == _NONE), raw, _NONE)

        with tracing.span("fixup_upmap"):
            raw = self._upmap_stage(osdmap, pool, ps, raw)

        with tracing.span("fixup_up"):
            idx = np.clip(raw, 0, osdmap.max_osd)
            in_range = (raw >= 0) & (raw < osdmap.max_osd)
            alive = in_range & osds.up[idx]
            if pool.can_shift_osds():
                up = _compact_rows(raw, alive)
            else:
                up = np.where(alive, raw, _NONE)

        with tracing.span("fixup_affinity"):
            up_primary = self._primary_vec(up)
            up, up_primary = self._affinity_stage(
                osdmap, pool, osds, pps, up, up_primary
            )
        return up, up_primary

    def _device_parts(self, osdmap: OSDMap, pool: PgPool, pps):
        """(the pool's raw mappings off the device as a generator of
        ``(lo, results, counts)`` parts, the rows a part) — or (None, all
        the rows) where the map is outside the kernel's scope and the
        host maps the pool.  Nothing is issued until the first part is
        drawn; from then on the parts after the one in hand are on the
        device (jaxmap.map_parts)."""
        from ..crush import jaxmap
        from ..ops import mesh as meshmod
        from ..ops.profiler import record_pad
        from ..ops.residency import bucket_pow2, note_shape
        from .sharded_mapping import mesh_rule_parts, part_lanes

        n = len(pps)
        ruleno = osdmap.crush.find_rule(pool.crush_rule, pool.type, pool.size)
        if ruleno < 0:
            return None, n
        # shards across the device mesh when >1 device exists
        # (ParallelPGMapper role); single-device unchanged
        dmesh = meshmod.default_mesh()
        step = part_lanes(dmesh)
        # a pool of one part is bucketed to a power of two (padded with
        # a repeat of lane 0 — a valid input — and the rows sliced
        # back), a larger one cut into parts of exactly ``step`` lanes,
        # so pools with ragged pg_num and remap sweeps replay ONE
        # compiled program per bucket; reuse lands in
        # l_tpu_compile_cache_{hit,miss}
        lanes = min(bucket_pow2(n), step)
        pad = max(lanes - n, 0)
        pad_bytes = 0
        if pad:
            pps = pps[0:n]
            pps = np.concatenate([pps, np.full(pad, pps[0], dtype=pps.dtype)])
            pad_bytes = pad * pps.itemsize
        try:
            parts = mesh_rule_parts(
                _compiled(osdmap.crush), ruleno, pps, pool.size,
                osdmap.osd_weight, dmesh,
            )
        except jaxmap.UnsupportedMap:
            return None, n

        def drawn():
            # under the first part's dispatch record
            record_pad(pad_bytes)
            note_shape("crush_batch", lanes, pool.size)
            yield from parts

        return drawn(), step

    def _crush_stage(
        self, osdmap: OSDMap, pool: PgPool, pps: np.ndarray, use_device: bool
    ) -> np.ndarray:
        """(len(pps), size) raw mappings of the part of the pool that
        ``_update_pool`` is at: the next part off the device kernel, or
        the oracle's where the map is outside its scope."""
        ruleno = osdmap.crush.find_rule(pool.crush_rule, pool.type, pool.size)
        n = len(pps)
        if ruleno < 0:
            return np.full((n, pool.size), _NONE, dtype=np.int64)
        from ..ops.profiler import dispatch_profiler

        if use_device:
            # one flight-recorder entry a part: its compute / sync
            # stages are bracketed where a part is issued and fetched
            # (jaxmap.map_parts) — the issue is of a part further on,
            # the fetch of this one — and what comes back is numpy
            with dispatch_profiler().dispatch(
                "crush", backend="jax"
            ) as dp:
                dp.set_ops(1)
                dp.set_stripes(n)
                dp.add_bytes_in(pps.nbytes)
                dp.add_upload(pps.nbytes)
                _lo, res, counts = next(self._parts)
                raw = res[:n].astype(np.int64)
                # positions beyond the returned count are absent,
                # not NONE
                cols = np.arange(pool.size)
                raw[cols[None, :] >= counts[:n, None]] = _NONE
                return raw

        with dispatch_profiler().dispatch(
            "crush", backend="cpu"
        ) as dp:
            dp.set_ops(1)
            dp.set_stripes(n)
            dp.add_bytes_in(pps.nbytes)
            raw = np.full((n, pool.size), _NONE, dtype=np.int64)
            for i in range(n):
                row = osdmap.crush.do_rule(
                    ruleno, int(pps[i]), pool.size, osdmap.osd_weight
                )
                raw[i, : len(row)] = row
            return raw

    def _upmap_stage(self, osdmap, pool, ps, raw):
        """Sparse dict overrides — handled per-affected-row."""
        if not osdmap.pg_upmap and not osdmap.pg_upmap_items:
            return raw
        seeds = _stable_mod_vec(ps, pool.pg_num, pool.pg_num_mask)
        affected = {}
        for (pid, seed), v in osdmap.pg_upmap.items():
            if pid == pool.pool_id:
                affected[seed] = True
        for (pid, seed), v in osdmap.pg_upmap_items.items():
            if pid == pool.pool_id:
                affected[seed] = True
        if not affected:
            return raw
        seed_to_rows: dict[int, list[int]] = {}
        for row, s in enumerate(seeds):
            if int(s) in affected:
                seed_to_rows.setdefault(int(s), []).append(row)
        for seed, rows in seed_to_rows.items():
            for row in rows:
                fixed = osdmap._apply_upmap(
                    pool, int(ps[row]), [int(o) for o in raw[row] if o != _NONE]
                    if pool.can_shift_osds()
                    else [int(o) for o in raw[row]],
                )
                out = np.full(raw.shape[1], _NONE, dtype=np.int64)
                out[: len(fixed)] = fixed
                raw[row] = out
        return raw

    @staticmethod
    def _primary_vec(up: np.ndarray) -> np.ndarray:
        """First non-NONE per row, -1 if none (OSDMap::_pick_primary)."""
        valid = up != _NONE
        first = np.argmax(valid, axis=1)
        has = valid.any(axis=1)
        return np.where(has, up[np.arange(len(up)), first], -1)

    def _affinity_stage(self, osdmap, pool, osds, pps, up, up_primary):
        """Vectorized _apply_primary_affinity (OSDMap.cc:2540-2590)."""
        affv = osds.affinity
        if affv is None:
            return up, up_primary
        idx = np.clip(up, 0, osdmap.max_osd)
        valid = (up != _NONE) & (up >= 0) & (up < osdmap.max_osd)
        a = np.where(valid, affv[idx], CEPH_OSD_DEFAULT_PRIMARY_AFFINITY)
        rows_any = (
            valid & (a != CEPH_OSD_DEFAULT_PRIMARY_AFFINITY)
        ).any(axis=1)
        if not rows_any.any():
            return up, up_primary
        draws = (
            crush_hash32_2(
                np.broadcast_to(
                    pps[:, None].astype(np.uint32), up.shape
                ).copy(),
                np.where(valid, up, 0).astype(np.uint32),
            ).astype(np.int64)
            >> 16
        )
        rejected = (a < CEPH_OSD_MAX_PRIMARY_AFFINITY) & (draws >= a)
        # accepted slot: first valid & ~rejected; fallback: first valid
        accept = valid & ~rejected
        pos_acc = np.argmax(accept, axis=1)
        has_acc = accept.any(axis=1)
        pos_fb = np.argmax(valid, axis=1)
        has_fb = valid.any(axis=1)
        pos = np.where(has_acc, pos_acc, pos_fb)
        has = has_acc | has_fb
        apply = rows_any & has
        rowix = np.arange(len(up))
        new_primary = np.where(apply, up[rowix, pos], up_primary)
        if pool.can_shift_osds():
            # rotate the chosen primary to the front of each applied row
            up = up.copy()
            for row in np.nonzero(apply & (pos > 0))[0]:
                p = pos[row]
                up[row, 1 : p + 1] = up[row, :p]
                up[row, 0] = new_primary[row]
        return up, new_primary

    def _temp_stage(self, osdmap, pool, lo, acting, acting_primary):
        """pg_temp / primary_temp sparse overrides (scalar per entry)
        on the rows of PGs ``lo`` onwards that the two arrays hold."""
        hi = lo + len(acting)
        for (pid, seed), temps in osdmap.pg_temp.items():
            if pid != pool.pool_id or not lo <= seed < hi:
                continue
            t, tp = osdmap._get_temp_osds(pool, seed)
            if t:
                row = np.full(acting.shape[1], _NONE, dtype=np.int64)
                row[: len(t)] = t
                acting[seed - lo] = row
                acting_primary[seed - lo] = tp
        for (pid, seed), tp in osdmap.primary_temp.items():
            if pid != pool.pool_id or not lo <= seed < hi:
                continue
            acting_primary[seed - lo] = tp

    # -- queries (OSDMapMapping consumer API) ------------------------------
    def get(self, pool_id: int, ps: int):
        """(up, up_primary, acting, acting_primary) for one PG."""
        up = [int(o) for o in self.up[pool_id][ps]]
        acting = [int(o) for o in self.acting[pool_id][ps]]
        while up and up[-1] == _NONE:
            up.pop()
        while acting and acting[-1] == _NONE:
            acting.pop()
        return (
            up,
            int(self.up_primary[pool_id][ps]),
            acting,
            int(self.acting_primary[pool_id][ps]),
        )


def _compiled(crush_map):
    """Per-CrushMap compiled-array cache, invalidated on mutation.

    Keyed on ``CrushMap.mutation`` (bumped by every builder mutator /
    ``touch()``) so editing the map after a batched mapping pass
    recompiles the dense arrays instead of silently reusing stale
    topology/weights."""
    gen = getattr(crush_map, "mutation", 0)
    cached = getattr(crush_map, "_jax_compiled", None)
    if cached is None or cached[0] != gen:
        from ..crush import jaxmap

        cached = (gen, jaxmap.compile_map(crush_map))
        crush_map._jax_compiled = cached
    return cached[1]
