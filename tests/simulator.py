"""Production traffic simulator — the measured SLO harness (ROADMAP
open item 3: "handles heavy traffic from millions of users" as a
regression surface, not a claim).

An OPEN-LOOP workload generator over a live in-process cluster:

- arrivals are Poisson per QoS class (exponential inter-arrival at a
  configured rate) and do NOT wait for completions — when the cluster
  falls behind, latency grows instead of the offered load shrinking,
  exactly how overload looks to real users (closed-loop harnesses
  hide it);
- keys are zipfian over multi-tenant namespaces (a few hot tenants ×
  hot keys dominate, the long tail trickles) with a tunable
  read/write/list mix;
- traffic drives BOTH front doors: librados (IoCtx tagged with the
  class's QoS) and the RGW HTTP gateway (S3-flavored PUT/GET over a
  real socket);
- per-class mclock reservations come from the OSD's dmclock
  scheduler (osd/scheduler.py MClockQueue), so the reservation-floor
  claim is tested against the real queue, not a model;
- fault weather composes in from msg/faults.py: lossy links
  (delay+jitter+drop), an OSD kill mid-run, or a fill-to-nearfull
  capacity squeeze.

Per-op latency (arrival → completion, queue wait included) lands in
``common/histogram.py`` LogHistograms; scenarios report per-class
p50/p99 curves plus a reservation-floor verdict.
``python tests/simulator.py [scenario ...]`` runs standalone;
tests/test_slo.py drives the fast variants in tier-1.
"""

from __future__ import annotations

import json
import os
import random
import sys
import threading
import time
from dataclasses import dataclass, field

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from ceph_tpu.common.histogram import LogHistogram  # noqa: E402
from ceph_tpu.mgr import Manager  # noqa: E402
from ceph_tpu.mon.monitor import Monitor  # noqa: E402
from ceph_tpu.msg import Messenger  # noqa: E402
from ceph_tpu.msg.messenger import wait_for  # noqa: E402
from ceph_tpu.osd.daemon import OSD  # noqa: E402
from ceph_tpu.rados import Rados, RadosError  # noqa: E402

DEFAULT_SEED = 20260804

# dmclock profiles for the simulated tenant classes, in cost-units/s
# (cost_unit=4096: one ~3KB object op ≈ 1 unit).  gold holds a real
# reservation; bulk gets weight only — the overload scenario proves
# the floor by drowning gold's share in bulk arrivals.
DEFAULT_QOS_PROFILES = {
    "gold": (80.0, 20.0, 0.0),
    "bulk": (5.0, 80.0, 0.0),
}


# -- zipfian multi-tenant keyspace ------------------------------------------
class ZipfKeys:
    """Bounded zipf sampler: P(rank r) ∝ r^-s over [1, n].  Separate
    samplers for tenant and key pick hot tenants × hot keys."""

    def __init__(self, n: int, s: float, rng: random.Random):
        self._rng = rng
        weights = [r ** -s for r in range(1, n + 1)]
        total = sum(weights)
        acc, cdf = 0.0, []
        for w in weights:
            acc += w / total
            cdf.append(acc)
        self._cdf = cdf

    def sample(self) -> int:
        u = self._rng.random()
        lo, hi = 0, len(self._cdf) - 1
        while lo < hi:
            mid = (lo + hi) // 2
            if self._cdf[mid] < u:
                lo = mid + 1
            else:
                hi = mid
        return lo + 1


@dataclass
class ClassSpec:
    """One traffic class: its arrival rate, mix, and QoS identity."""

    name: str
    rate: float  # ops/sec (Poisson arrivals)
    read_frac: float = 0.55
    write_frac: float = 0.40  # remainder = list
    object_size: int = 3072  # +1024 op overhead ≈ 1 cost unit
    via: str = "rados"  # rados | rgw | mixed
    rgw_frac: float = 0.3  # of ops, when via == "mixed"
    workers: int = 12


@dataclass
class ClassStats:
    hist: LogHistogram = field(default_factory=LogHistogram)
    count: int = 0
    errors: int = 0
    read_misses: int = 0
    lock: threading.Lock = field(default_factory=threading.Lock)


class SimCluster:
    """mon + mgr + N OSDs (+ RGW gateway) hosted in-process — the
    vstart-shaped substrate every scenario runs on."""

    def __init__(
        self,
        n_osd: int = 3,
        pg_num: int = 8,
        size: int = 2,
        op_queue: str = "mclock",
        qos_profiles: dict | None = None,
        with_mgr: bool = True,
        with_rgw: bool = False,
        osd_kw: dict | None = None,
        slo_targets: str = "",
    ):
        sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
        from ceph_tpu.tools.cluster import _build_map

        self.qos_profiles = dict(
            qos_profiles
            if qos_profiles is not None
            else DEFAULT_QOS_PROFILES
        )
        self.mon = Monitor(_build_map(n_osd), min_reporters=2)
        self.mon_msgr = Messenger("mon")
        self.mon_msgr.add_dispatcher(self.mon)
        self.mon_addr = self.mon_msgr.bind()
        self.mgr = None
        if with_mgr:
            self.mgr = Manager(name="sim")
            if slo_targets:
                self.mgr.set_module_option(
                    "slo", "targets", slo_targets
                )
            self.mgr.start(self.mon_addr)
        self.osds: dict[int, OSD] = {}
        for i in range(n_osd):
            self.start_osd(i, op_queue=op_queue, **(osd_kw or {}))
        self.client = Rados("sim-admin").connect(*self.mon_addr)
        assert wait_for(
            lambda: all(
                self.client.monc.osdmap.is_up(i) for i in range(n_osd)
            ),
            15.0,
        ), "OSDs never booted"
        self.pool_id = self.client.pool_create(
            "sim", pg_num=pg_num, size=size
        )
        self._wait_active(pg_num)
        self.rgw = None
        self.rgw_port = 0
        if with_rgw:
            from ceph_tpu.rgw import RGW

            rgw_io = self.client.open_ioctx("sim")
            rgw_io.set_qos_class("bulk")  # gateway data rides bulk
            self.rgw = RGW(rgw_io)
            self.rgw_port = self.rgw.serve(0)

    def start_osd(self, i: int, op_queue: str = "mclock", **kw):
        osd = OSD(
            i,
            tick_interval=0.2,
            heartbeat_grace=2.0,
            op_queue=op_queue,
            qos_profiles=self.qos_profiles,
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

    def _wait_active(self, pg_num: int) -> None:
        def active():
            for ps in range(pg_num):
                pgid = f"{self.pool_id}.{ps}"
                _u, _upp, _a, primary = (
                    self.client.monc.osdmap.pg_to_up_acting_osds(
                        self.pool_id, ps
                    )
                )
                osd = self.osds.get(primary)
                pg = osd.pgs.get(pgid) if osd else None
                if pg is None or pg.state != "active":
                    return False
            return True

        assert wait_for(active, 20.0), "PGs never went active"

    def health(self) -> dict:
        reply = self.client.monc.command({"prefix": "health"})
        return json.loads(reply.outb) if reply.rc == 0 else {}

    def shutdown(self) -> None:
        try:
            if self.rgw is not None:
                self.rgw.shutdown()
        except Exception:  # noqa: BLE001
            pass
        if self.mgr is not None:
            self.mgr.shutdown()
        for i in list(self.osds):
            self.kill_osd(i)
        self.client.shutdown()
        self.mon_msgr.shutdown()


# -- fault weather ----------------------------------------------------------
def apply_weather(cluster: SimCluster, weather: str, seed: int) -> dict:
    """Install a named weather condition; returns its description.
    ``osd_kill`` arms a delayed kill the caller fires mid-run."""
    if weather in ("", "baseline"):
        return {"weather": "baseline"}
    if weather == "lossy":
        # delay+jitter on every OSD's outbound path + a thin drop on
        # the client's — retries and session NACKs do the rest
        for osd in cluster.osds.values():
            osd.messenger.faults.reseed(seed)
            osd.messenger.faults.add_rule(
                dst="*", delay=0.004, jitter=0.006
            )
        cluster.client.messenger.faults.reseed(seed)
        cluster.client.messenger.faults.add_rule(
            dst="*", delay=0.002, jitter=0.004, drop=0.01
        )
        return {
            "weather": "lossy",
            "detail": "4-10ms osd link delay, 1% client drop",
        }
    if weather == "osd_kill":
        return {
            "weather": "osd_kill",
            "detail": "one OSD killed mid-run (deferred)",
        }
    raise ValueError(f"unknown weather {weather!r}")


def clear_weather(cluster: SimCluster) -> None:
    for osd in cluster.osds.values():
        osd.messenger.faults.clear()
    cluster.client.messenger.faults.clear()


# -- the open-loop engine ---------------------------------------------------
class TrafficSim:
    def __init__(
        self,
        cluster: SimCluster,
        classes: list[ClassSpec],
        tenants: int = 16,
        keys_per_tenant: int = 256,
        zipf_s: float = 1.1,
        seed: int = DEFAULT_SEED,
    ):
        self.cluster = cluster
        self.classes = classes
        self.tenants = tenants
        self.rng = random.Random(seed)
        self.tenant_keys = ZipfKeys(tenants, zipf_s, self.rng)
        self.object_keys = ZipfKeys(keys_per_tenant, zipf_s, self.rng)
        self.stats: dict[str, ClassStats] = {
            c.name: ClassStats() for c in classes
        }
        self._stop = threading.Event()
        self._threads: list[threading.Thread] = []
        # per-class ioctx carrying the QoS tag
        self._ioctx = {}
        for spec in classes:
            rados = Rados(f"sim-{spec.name}").connect(
                *cluster.mon_addr
            )
            io = rados.open_ioctx("sim")
            io.set_qos_class(spec.name)
            self._ioctx[spec.name] = (rados, io)
        self._queues: dict[str, list] = {
            c.name: [] for c in classes
        }
        self._qcond: dict[str, threading.Condition] = {
            c.name: threading.Condition() for c in classes
        }

    # -- op execution ------------------------------------------------------
    def _pick_op(self, spec: ClassSpec) -> str:
        u = self.rng.random()
        if u < spec.read_frac:
            return "read"
        if u < spec.read_frac + spec.write_frac:
            return "write"
        return "list"

    def _key(self) -> tuple[str, str]:
        tenant = self.tenant_keys.sample()
        rank = self.object_keys.sample()
        return f"t{tenant}", f"o{rank}"

    def _run_rados(self, spec: ClassSpec, op: str, stats: ClassStats):
        _rados, io = self._ioctx[spec.name]
        tenant, key = self._key()
        oid = f"{tenant}/{key}"
        if op == "write":
            io.write_full(
                oid, self.rng.randbytes(spec.object_size)
            )
        elif op == "read":
            try:
                io.read(oid)
            except RadosError:
                with stats.lock:
                    stats.read_misses += 1
        else:
            # the pgls surface: real list ops through the scheduler
            io.list_objects()

    def _run_rgw(self, spec: ClassSpec, op: str, stats: ClassStats):
        import http.client

        tenant, key = self._key()
        conn = http.client.HTTPConnection(
            "127.0.0.1", self.cluster.rgw_port, timeout=10
        )
        try:
            if op == "write":
                conn.request(
                    "PUT",
                    f"/{tenant}/{key}",
                    body=self.rng.randbytes(spec.object_size),
                )
            elif op == "read":
                conn.request("GET", f"/{tenant}/{key}")
            else:
                conn.request("GET", f"/{tenant}?list-type=2")
            resp = conn.getresponse()
            resp.read()
            if op == "read" and resp.status == 404:
                with stats.lock:
                    stats.read_misses += 1
        finally:
            conn.close()

    def _worker(self, spec: ClassSpec) -> None:
        stats = self.stats[spec.name]
        cond = self._qcond[spec.name]
        q = self._queues[spec.name]
        while True:
            with cond:
                while not q and not self._stop.is_set():
                    cond.wait(0.1)
                if not q:
                    return
                arrival, op, via = q.pop(0)
            try:
                if via == "rgw":
                    self._run_rgw(spec, op, stats)
                else:
                    self._run_rados(spec, op, stats)
                ok = True
            except Exception:  # noqa: BLE001 — weather makes ops fail
                ok = False
            latency = time.monotonic() - arrival
            with stats.lock:
                stats.count += 1
                if not ok:
                    stats.errors += 1
            stats.hist.add(latency)

    def _arrival_loop(self, spec: ClassSpec) -> None:
        cond = self._qcond[spec.name]
        q = self._queues[spec.name]
        next_t = time.monotonic()
        while not self._stop.is_set():
            next_t += self.rng.expovariate(max(spec.rate, 1e-3))
            delay = next_t - time.monotonic()
            if delay > 0 and self._stop.wait(delay):
                break
            op = self._pick_op(spec)
            via = spec.via
            if via == "mixed":
                via = (
                    "rgw"
                    if self.rng.random() < spec.rgw_frac
                    else "rados"
                )
            if via == "rgw" and not self.cluster.rgw_port:
                via = "rados"
            with cond:
                # open loop: the arrival is stamped NOW — queue wait
                # behind saturated workers counts as latency
                q.append((time.monotonic(), op, via))
                cond.notify()

    def prefill(self, per_tenant: int = 8, hot_tenants: int = 4) -> None:
        """Seed hot keys so the read mix hits mostly-existing data;
        every tenant's RGW bucket is created (a PUT into a missing
        bucket would 404-noop instead of exercising the data path)."""
        _r, io = next(iter(self._ioctx.values()))
        for t in range(1, hot_tenants + 1):
            for k in range(1, per_tenant + 1):
                io.write_full(f"t{t}/o{k}", b"seed" * 256)
        if self.cluster.rgw is not None:
            for t in range(1, self.tenants + 1):
                try:
                    self.cluster.rgw.create_bucket(f"t{t}")
                except Exception:  # noqa: BLE001 — already there
                    pass

    def run(self, duration: float, on_midpoint=None) -> dict:
        """Drive the load for ``duration`` seconds; ``on_midpoint``
        fires once halfway (the osd-kill hook).  Returns per-class
        results."""
        t0 = time.monotonic()
        for spec in self.classes:
            for _ in range(spec.workers):
                t = threading.Thread(
                    target=self._worker, args=(spec,),
                    name=f"sim.{spec.name}.w", daemon=True,
                )
                t.start()
                self._threads.append(t)
            t = threading.Thread(
                target=self._arrival_loop, args=(spec,),
                name=f"sim.{spec.name}.arrivals", daemon=True,
            )
            t.start()
            self._threads.append(t)
        fired = False
        while time.monotonic() - t0 < duration:
            if (
                on_midpoint is not None
                and not fired
                and time.monotonic() - t0 >= duration / 2
            ):
                fired = True
                on_midpoint()
            time.sleep(0.05)
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10)
        elapsed = time.monotonic() - t0
        return self.results(elapsed)

    def results(self, elapsed: float) -> dict:
        out = {}
        for spec in self.classes:
            stats = self.stats[spec.name]
            with stats.lock:
                count, errors = stats.count, stats.errors
                misses = stats.read_misses
            out[spec.name] = {
                "offered_ops_s": round(spec.rate, 2),
                "achieved_ops_s": round(count / max(elapsed, 1e-9), 2),
                "count": count,
                "errors": errors,
                "read_misses": misses,
                "p50_ms": round(
                    1000 * stats.hist.percentile(50), 3
                ),
                "p99_ms": round(
                    1000 * stats.hist.percentile(99), 3
                ),
                "histogram": stats.hist.snapshot(),
            }
        return out

    def close(self) -> None:
        self._stop.set()
        for rados, _io in self._ioctx.values():
            rados.shutdown()


# -- scenarios --------------------------------------------------------------
def scenario_baseline(
    duration: float = 6.0,
    rate: float = 60.0,
    seed: int = DEFAULT_SEED,
    with_rgw: bool = True,
    slo_targets: str = "",
) -> dict:
    """Steady mixed load through librados AND the RGW front end."""
    cluster = SimCluster(with_rgw=with_rgw, slo_targets=slo_targets)
    try:
        sim = TrafficSim(
            cluster,
            [
                ClassSpec(
                    "gold", rate=rate * 0.3, via="rados", workers=8
                ),
                ClassSpec(
                    "bulk", rate=rate * 0.7,
                    via="mixed" if with_rgw else "rados",
                    workers=12,
                ),
            ],
            seed=seed,
        )
        sim.prefill()
        res = sim.run(duration)
        sim.close()
        return {"condition": "baseline", "classes": res}
    finally:
        cluster.shutdown()


def scenario_weather(
    weather: str = "lossy",
    duration: float = 6.0,
    rate: float = 60.0,
    seed: int = DEFAULT_SEED,
) -> dict:
    """The same mixed load under fault weather (lossy links or an
    OSD kill mid-run) — tails grow, the harness measures by how
    much, and the run still completes."""
    cluster = SimCluster(with_rgw=False)
    try:
        desc = apply_weather(cluster, weather, seed)
        sim = TrafficSim(
            cluster,
            [
                ClassSpec("gold", rate=rate * 0.3, workers=8),
                ClassSpec("bulk", rate=rate * 0.7, workers=12),
            ],
            seed=seed,
        )
        sim.prefill()
        on_mid = None
        if weather == "osd_kill":
            def on_mid():
                victim = max(cluster.osds)
                cluster.kill_osd(victim)

        res = sim.run(duration, on_midpoint=on_mid)
        sim.close()
        clear_weather(cluster)
        return {"condition": weather, **desc, "classes": res}
    finally:
        cluster.shutdown()


def scenario_overload_floor(
    duration: float = 8.0,
    gold_rate: float = 40.0,
    bulk_rate: float = 600.0,
    seed: int = DEFAULT_SEED,
    floor_frac: float = 0.7,
) -> dict:
    """Reservation floor under overload: bulk offers ~10x what the
    cluster serves; gold's mclock reservation (80 units/s across the
    cluster, gold offers 40 ops/s ≈ 40 units/s) must keep gold near
    its offered rate while bulk latency explodes.  The verdict is
    the artifact's pass/fail line."""
    cluster = SimCluster(with_rgw=False)
    try:
        sim = TrafficSim(
            cluster,
            [
                ClassSpec(
                    "gold", rate=gold_rate, read_frac=0.3,
                    write_frac=0.7, workers=16,
                ),
                ClassSpec(
                    "bulk", rate=bulk_rate, read_frac=0.3,
                    write_frac=0.7, workers=48,
                ),
            ],
            seed=seed,
        )
        sim.prefill()
        res = sim.run(duration)
        sim.close()
        gold = res["gold"]
        bulk = res["bulk"]
        floor = min(gold_rate, _cluster_reservation(cluster, "gold"))
        held = gold["achieved_ops_s"] >= floor_frac * floor
        return {
            "condition": "overload",
            "classes": res,
            "reservation_floor": {
                "class": "gold",
                "reserved_ops_s": floor,
                "achieved_ops_s": gold["achieved_ops_s"],
                "required_frac": floor_frac,
                "held": bool(held),
                "bulk_p99_over_gold_p99": round(
                    bulk["p99_ms"] / max(gold["p99_ms"], 1e-9), 2
                ),
            },
        }
    finally:
        cluster.shutdown()


def _cluster_reservation(cluster: SimCluster, klass: str) -> float:
    """Total reserved ops/s for a class across primaries (each OSD
    reserves independently; with balanced PGs the cluster floor is
    roughly the per-OSD reservation — report the conservative
    per-OSD figure)."""
    triple = cluster.qos_profiles.get(klass)
    return float(triple[0]) if triple else 0.0


SCENARIOS = {
    "baseline": scenario_baseline,
    "lossy": lambda **kw: scenario_weather("lossy", **kw),
    "osd_kill": lambda **kw: scenario_weather("osd_kill", **kw),
    "overload": scenario_overload_floor,
}


def main(argv: list[str]) -> int:
    names = argv or ["baseline", "lossy", "overload"]
    out = {}
    for name in names:
        fn = SCENARIOS.get(name)
        if fn is None:
            print(f"unknown scenario {name!r}", file=sys.stderr)
            return 2
        print(f"--- {name} ---", file=sys.stderr)
        out[name] = fn()
    json.dump(out, sys.stdout, indent=2)
    print()
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
