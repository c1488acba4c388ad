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
        from ..ops.kernel_stats import kernel_stats

        n = pool.pg_num
        size = pool.size
        with tracing.span("crush_inputs", tags={"pool": pool.pool_id}):
            ps = np.arange(n, dtype=np.int64)
            pps = pool_pps_vec(pool, ps).astype(np.int64)

        ks = kernel_stats()
        pgs_counter = ks.counter(
            "crush", "pgs", desc="PGs mapped through the CRUSH kernel"
        )
        with self.perf.time_it("crush_stage"), ks.timed(
            "crush", bytes_in=pps.nbytes
        ) as kt:
            raw = self._crush_stage(osdmap, pool, pps, use_device)
            kt.bytes_out = raw.nbytes
        ks.perf.inc(pgs_counter, n)

        with self.perf.time_it("fixup_stages"):
            up, up_primary, acting, acting_primary = self._fixup(
                osdmap, pool, ps, pps, raw
            )
        self.up[pool.pool_id] = up
        self.up_primary[pool.pool_id] = up_primary
        self.acting[pool.pool_id] = acting
        self.acting_primary[pool.pool_id] = acting_primary

    def _fixup(self, osdmap, pool, ps, pps, raw):
        with tracing.span("fixup_exists"):
            # _remove_nonexistent_osds + _raw_to_up_osds, fused: both
            # drop to NONE (EC) or compact (replicated)
            exists = np.zeros(osdmap.max_osd + 1, dtype=bool)
            up_ok = np.zeros(osdmap.max_osd + 1, dtype=bool)
            exists[:-1] = np.asarray(osdmap.osd_exists, dtype=bool)
            up_ok[:-1] = exists[:-1] & np.asarray(
                osdmap.osd_up, dtype=bool
            )
            idx = np.clip(raw, 0, osdmap.max_osd)
            in_range = (raw >= 0) & (raw < osdmap.max_osd)
            raw_exists = in_range & exists[idx]
            if pool.can_shift_osds():
                raw = _compact_rows(raw, raw_exists)
            else:
                raw = np.where(raw_exists | (raw == _NONE), raw, _NONE)

        with tracing.span("fixup_upmap"):
            raw = self._upmap_stage(osdmap, pool, ps, raw)

        with tracing.span("fixup_up"):
            idx = np.clip(raw, 0, osdmap.max_osd)
            in_range = (raw >= 0) & (raw < osdmap.max_osd)
            alive = in_range & up_ok[idx]
            if pool.can_shift_osds():
                up = _compact_rows(raw, alive)
            else:
                up = np.where(alive, raw, _NONE)

        with tracing.span("fixup_affinity"):
            up_primary = self._primary_vec(up)
            up, up_primary = self._affinity_stage(
                osdmap, pool, pps, up, up_primary
            )

        with tracing.span("fixup_temp"):
            acting = up.copy()
            acting_primary = up_primary.copy()
            self._temp_stage(osdmap, pool, acting, acting_primary)

        return up, up_primary, acting, acting_primary

    def _crush_stage(
        self, osdmap: OSDMap, pool: PgPool, pps: np.ndarray, use_device: bool
    ) -> np.ndarray:
        """(npgs, size) raw mappings via the device kernel, oracle
        fallback outside its scope."""
        ruleno = osdmap.crush.find_rule(pool.crush_rule, pool.type, pool.size)
        n = len(pps)
        if ruleno < 0:
            return np.full((n, pool.size), _NONE, dtype=np.int64)
        if use_device:
            try:
                from ..crush import jaxmap
                from ..ops.profiler import dispatch_profiler
                from ..ops.residency import bucket_pow2, note_shape
                from .sharded_mapping import mesh_batch_do_rule

                cm = _compiled(osdmap.crush)
                # an UnsupportedMap raised anywhere in here discards
                # the flight-recorder entry (no commit on exception —
                # the oracle loop below records its own)
                with dispatch_profiler().dispatch(
                    "crush", backend="jax"
                ) as dp:
                    dp.set_ops(1)
                    dp.set_stripes(n)
                    dp.add_bytes_in(pps.nbytes)
                    dp.add_upload(pps.nbytes)
                    # bucket the PG batch to a power of two (pad with
                    # a repeat of lane 0 — a valid input — and slice
                    # the rows back) so pools with ragged pg_num and
                    # remap sweeps replay ONE compiled program per
                    # bucket; reuse lands in
                    # l_tpu_compile_cache_{hit,miss}
                    nb = bucket_pow2(n)
                    pps_in = pps
                    if nb != n:
                        pps_in = np.concatenate(
                            [pps, np.full(nb - n, pps[0], dtype=pps.dtype)]
                        )
                        dp.add_pad((nb - n) * pps.itemsize)
                    note_shape("crush_batch", nb, pool.size)
                    # shards across the device mesh when >1 device
                    # exists (ParallelPGMapper role); single-device
                    # unchanged.  The record's compute / sync stages
                    # are bracketed where the chunks are issued and
                    # fetched (jaxmap.map_chunked): what comes back
                    # here is numpy already
                    res, counts = mesh_batch_do_rule(
                        cm, ruleno, pps_in, pool.size,
                        osdmap.osd_weight,
                    )
                    raw = np.asarray(res, dtype=np.int64)[:n]
                    counts = np.asarray(counts)[:n]
                    # positions beyond the returned count are absent,
                    # not NONE
                    cols = np.arange(pool.size)
                    return np.where(
                        cols[None, :] < counts[:, None], raw, _NONE
                    )
            except jaxmap.UnsupportedMap:
                pass
        from ..ops.profiler import dispatch_profiler

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

    def _affinity_stage(self, osdmap, pool, pps, up, up_primary):
        """Vectorized _apply_primary_affinity (OSDMap.cc:2540-2590)."""
        aff = osdmap.osd_primary_affinity
        if aff is None:
            return up, up_primary
        affv = np.zeros(osdmap.max_osd + 1, dtype=np.int64)
        affv[:-1] = np.asarray(aff, dtype=np.int64)
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

    def _temp_stage(self, osdmap, pool, acting, acting_primary):
        """pg_temp / primary_temp sparse overrides (scalar per entry)."""
        for (pid, seed), temps in osdmap.pg_temp.items():
            if pid != pool.pool_id or seed >= pool.pg_num:
                continue
            t, tp = osdmap._get_temp_osds(pool, seed)
            if t:
                row = np.full(acting.shape[1], _NONE, dtype=np.int64)
                row[: len(t)] = t
                acting[seed] = row
                acting_primary[seed] = tp
        for (pid, seed), tp in osdmap.primary_temp.items():
            if pid != pool.pool_id or seed >= pool.pg_num:
                continue
            acting_primary[seed] = tp

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
