"""osdmaptool --test-map-pgs equivalent (src/tools/osdmaptool.cc:41-53,
147-218): bulk-map every PG of every pool, print distribution stats and
timing — the full-map-recompute benchmark (ParallelPGMapper's job, done
as one batched device call per pool).  The timed remap's stage spans
(``remap`` and its children, common/tracing.py) are printed beside the
rate: where a slow remap spends its time."""

from __future__ import annotations

import argparse
import sys
import time

import numpy as np

from ..common import tracing
from ..crush.types import (
    CRUSH_ITEM_NONE,
    PG_POOL_TYPE_ERASURE,
    PG_POOL_TYPE_REPLICATED,
)
from ..osd import OSDMap, OSDMapMapping, PgPool
from .crushtool import build_hierarchy


def parse_args(argv=None):
    p = argparse.ArgumentParser(prog="osdmaptool", description=__doc__)
    p.add_argument("--test-map-pgs", action="store_true", required=True)
    p.add_argument("--build", metavar="OSDS:PER_HOST[:HOSTS_PER_RACK]",
                   default="64:4")
    p.add_argument("--pg-num", type=int, default=1024)
    p.add_argument("--pool-type", default="replicated",
                   choices=["replicated", "erasure"])
    p.add_argument("--size", type=int, default=0,
                   help="pool size (default 3 replicated / 5 erasure)")
    p.add_argument("--backend", default="jax", choices=["jax", "oracle"])
    p.add_argument("--dump", action="store_true",
                   help="print per-osd pg counts")
    return p.parse_args(argv)


def stage_line(spans: list[dict]) -> str:
    """``name ms`` for each stage of the one ``remap`` in ``spans``
    (same-named children summed, in order of first start) and
    ``other`` for what no child covers."""
    root = next(s for s in spans if s["name"] == "remap")
    stages: dict[str, float] = {}
    for s in sorted(spans, key=lambda s: s["start"]):
        if s["parent_id"] == root["span_id"]:
            stages[s["name"]] = stages.get(s["name"], 0.0) + s["duration"]
    stages["other"] = root["duration"] - sum(stages.values())
    return ", ".join(f"{n} {1e3 * d:.1f}" for n, d in stages.items())


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.backend == "jax":
        from ..common.compile_cache import configure_compile_cache

        configure_compile_cache()
    parts = [int(v) for v in args.build.split(":")]
    num_osds, per_host = parts[0], parts[1]
    hpr = parts[2] if len(parts) > 2 else 0
    crush = build_hierarchy(num_osds, per_host, hpr)
    om = OSDMap.build(crush, num_osds)
    if args.pool_type == "replicated":
        pool = PgPool(
            pool_id=1, type=PG_POOL_TYPE_REPLICATED,
            size=args.size or 3, pg_num=args.pg_num, crush_rule=0,
        )
    else:
        pool = PgPool(
            pool_id=1, type=PG_POOL_TYPE_ERASURE,
            size=args.size or 5, pg_num=args.pg_num, crush_rule=1,
        )
    om.add_pool(pool)

    tracer = tracing.Tracer("osdmaptool", max_spans=1 << 16)
    mapping = OSDMapMapping(tracer=tracer)
    use_device = args.backend == "jax"
    mapping.update(om, use_device=use_device)  # warm-up incl. compile
    tracer.drain(1 << 16)
    t0 = time.perf_counter()
    mapping.update(om, use_device=use_device)
    elapsed = time.perf_counter() - t0

    up = mapping.up[1]
    valid = up != CRUSH_ITEM_NONE
    per_osd = np.bincount(up[valid].astype(np.int64), minlength=num_osds)
    total = int(valid.sum())
    print(
        f"pool 1 pg_num {pool.pg_num} size {pool.size} "
        f"({args.pool_type}): mapped {total} osd slots over "
        f"{num_osds} osds in {elapsed:.4f}s = "
        f"{pool.pg_num / elapsed:.0f} pg mappings/sec [{args.backend}]"
    )
    print(f"  remap stages (ms): {stage_line(tracer.drain(1 << 16))}")
    print(
        f"  per-osd pgs: min {per_osd.min()} max {per_osd.max()} "
        f"avg {per_osd.mean():.1f} stddev {per_osd.std():.1f}"
    )
    if args.dump:
        for osd, cnt in enumerate(per_osd):
            print(f"  osd.{osd}\t{cnt}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
