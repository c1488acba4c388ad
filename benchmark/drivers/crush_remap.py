"""Whole-map remaps: ``OSDMapMapping.update(osdmap)`` again and again on
one large map, each a full recompute of every PG of the pool through
the batched CRUSH kernel and the fix-up stages, as the mon/mgr does
once an epoch (``osdmaptool --test-map-pgs`` runs one).

The traffic mix is data: ``out_fraction`` and ``reweight_fraction``
(a seeded choice of OSDs marked out, or reweighted to a seeded weight,
before the window), ``flip_per_remap`` (so many seeded OSDs go out in
each epoch and come back in the next, so no two successive epochs map
alike and a remap that recomputes nothing is seen), ``check_sample``.

``check`` compares what the window's last remap left in
``up``/``acting``/``*_primary`` for a seeded sample of PGs with the
plain reference's mapping of that epoch's map.
"""

from __future__ import annotations

import time

import numpy as np


class Driver:
    def __init__(self, config, traffic, seed, workdir, annotate, reference):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.annotate = annotate
        self.reference = reference
        self.remaps = 0
        self.mapping = None

    # -- set-up ----------------------------------------------------------
    def setup(self) -> None:
        from ceph_tpu.osd.mapping import OSDMapMapping
        from ceph_tpu.osd.osdmap import OSDMap, PgPool
        from ceph_tpu.tools.crushtool import build_hierarchy

        cfg, tr = self.config, self.traffic
        self.build = [int(v) for v in str(cfg["build"]).split(":")]
        self.num_osds = self.build[0]
        pool = cfg["pool"]
        if pool["type"] != "replicated":
            raise ValueError("this driver maps one replicated pool")
        self.pool_id = int(pool["pool_id"])
        self.osdmap = OSDMap.build(build_hierarchy(*self.build), self.num_osds)
        self.osdmap.add_pool(
            PgPool(
                pool_id=self.pool_id, type=1, size=int(pool["size"]),
                pg_num=int(pool["pg_num"]), crush_rule=int(pool["crush_rule"]),
            )
        )
        rng = np.random.default_rng(self.seed)
        weight = np.full(self.num_osds, 0x10000, dtype=np.int64)
        n_out = int(round(float(tr.get("out_fraction", 0)) * self.num_osds))
        n_rw = int(round(float(tr.get("reweight_fraction", 0)) * self.num_osds))
        picked = rng.choice(self.num_osds, n_out + n_rw, replace=False)
        weight[picked[:n_out]] = 0
        weight[picked[n_out:]] = rng.integers(0x1000, 0x10000, n_rw)
        self.base_weight = weight
        self.churned = set(int(o) for o in picked)
        # each epoch's own OSDs to flip, drawn now so the window draws nothing
        flips = int(tr.get("flip_per_remap", 0))
        steady = np.array(
            [o for o in range(self.num_osds) if o not in self.churned]
        )
        self.flips = [
            rng.choice(steady, flips, replace=False) for _ in range(64)
        ]
        self.mapping = OSDMapMapping()
        # warm-up: the window's own call, twice (compile, then replay)
        self.window(seconds=None, max_units=2)
        self.remaps = 0

    def _epoch_weights(self, epoch: int) -> np.ndarray:
        weight = self.base_weight.copy()
        weight[self.flips[epoch % len(self.flips)]] = 0
        return weight

    # -- the loop ----------------------------------------------------------
    def window(self, seconds, max_units=None) -> dict:
        """Remap until ``seconds`` have passed; the remap in flight at
        that moment is finished and counted, with its time."""
        pg_num = self.osdmap.pools[self.pool_id].pg_num
        ops = []
        t0 = time.perf_counter()
        while True:
            if max_units is not None and len(ops) >= max_units:
                break
            t = time.perf_counter()
            if seconds is not None and t - t0 >= seconds:
                break
            self.osdmap.epoch += 1
            self.weights_now = self._epoch_weights(self.osdmap.epoch)
            self.osdmap.osd_weight = [int(w) for w in self.weights_now]
            with self.annotate("bench:remap"):
                self.mapping.update(self.osdmap)
            ops.append((t, time.perf_counter(), pg_num, True))
            self.remaps += 1
        return {"ops": ops, "units": len(ops), "t0": t0}

    def counters(self) -> dict:
        perf = self.mapping.perf.dump() if self.mapping is not None else {}
        out = {"remaps": self.remaps}
        for key in ("crush_stage", "fixup_stages"):
            val = perf.get(key)
            if isinstance(val, dict):
                out[f"mapping.{key}.sum"] = float(val.get("sum", 0.0))
                out[f"mapping.{key}.avgcount"] = float(val.get("avgcount", 0))
        return out

    # -- correctness -------------------------------------------------------
    def check(self) -> dict:
        cfg = self.config
        pool = cfg["pool"]
        pg_num = int(pool["pg_num"])
        rng = np.random.default_rng(self.seed + 1)
        sample = min(int(self.traffic.get("check_sample", 65536)), pg_num)
        ps = np.sort(rng.choice(pg_num, sample, replace=False))
        hierarchy = self.reference.Hierarchy(*self.build)
        up, primary = self.reference.replicated_up_acting(
            hierarchy, ps,
            pool_id=self.pool_id, pgp_num=pg_num, size=int(pool["size"]),
            domain=int(pool["failure_domain_type"]),
            osd_weight=self.weights_now,
            osd_up=np.ones(self.num_osds, dtype=bool),
        )
        m = self.mapping
        wrong = np.zeros(sample, dtype=bool)
        for got, want in (
            (m.up[self.pool_id], up),
            (m.acting[self.pool_id], up),
        ):
            got = np.asarray(got)
            if got.shape != (pg_num, up.shape[1]):
                return {"wrong_pgs": (sample, 0), "epoch_behind": (0, 0)}
            wrong |= (got[ps] != want).any(axis=1)
        for got in (m.up_primary[self.pool_id], m.acting_primary[self.pool_id]):
            wrong |= np.asarray(got)[ps] != primary
        return {
            "wrong_pgs": (int(wrong.sum()), 0),
            "epoch_behind": (int(self.osdmap.epoch - m.epoch), 0),
        }

    # -- faults (control.py and the tests plant them; never a run) ---------
    def _patch_crush_stage(self, make):
        from ceph_tpu.osd.mapping import OSDMapMapping

        original = OSDMapMapping._crush_stage
        OSDMapMapping._crush_stage = make(original)
        return lambda: setattr(OSDMapMapping, "_crush_stage", original)

    def fault_control(self):
        """The control: the reference put in the kernel's place with the
        straw2 logarithm taken in float64 instead of from upstream's
        tables — exactness is the guarantee it breaks."""
        hierarchy = self.reference.Hierarchy(*self.build)
        domain = int(self.config["pool"]["failure_domain_type"])

        def make(_original):
            def stage(mapping, osdmap, pool, pps, use_device):
                rows = []
                for lo in range(0, len(pps), 1 << 16):
                    raw, cnt = self.reference.chooseleaf_firstn(
                        hierarchy, pps[lo : lo + (1 << 16)], pool.size, domain,
                        np.asarray(osdmap.osd_weight), log="float",
                    )
                    rows.append(raw)
                return np.concatenate(rows)

            return stage

        return self._patch_crush_stage(make)

    def fault_state_unchanged(self):
        """A remap that recomputes nothing: the last epoch's mapping stays."""
        from ceph_tpu.osd.mapping import OSDMapMapping

        original = OSDMapMapping._update_pool
        OSDMapMapping._update_pool = lambda *a, **kw: None
        return lambda: setattr(OSDMapMapping, "_update_pool", original)

    def fault_half_batch(self):
        """The second half of the PGs left out of the remap: their rows
        stay the last epoch's."""
        stale = np.asarray(self.mapping.up[self.pool_id]).copy()

        def make(original):
            def stage(mapping, osdmap, pool, pps, use_device):
                half = len(pps) // 2
                raw = original(mapping, osdmap, pool, pps, use_device)
                return np.concatenate([raw[:half], stale[half:].astype(raw.dtype)])

            return stage

        return self._patch_crush_stage(make)

    def fault_altered_answer(self):
        """One OSD of every 64th PG altered where the kernel's answer is
        produced."""

        def make(original):
            def stage(mapping, osdmap, pool, pps, use_device):
                raw = np.array(original(mapping, osdmap, pool, pps, use_device))
                raw[::64, 0] = (raw[::64, 0] + 1) % osdmap.max_osd
                return raw

            return stage

        return self._patch_crush_stage(make)

    def close(self) -> None:
        self.mapping = None
