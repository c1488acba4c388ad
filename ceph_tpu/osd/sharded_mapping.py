"""Mesh-sharded batched CRUSH — the ParallelPGMapper analog at
pod scale.

``crush/jaxmap.py`` turned ``crush_do_rule`` into one vmapped device
call; this module splits that call's PG batch across every chip of a
``DeviceMesh`` (ops/mesh.py) the way the reference splits pgid ranges
across a thread pool (src/osd/OSDMapMapping.h:18-156).  The per-lane
kernel is untouched — the batch axis is simply sharded — so results
are byte-identical to the single-device path; the acting-set table
re-assembles host-side from the gathered shards (ragged PG counts pad
to a device multiple and slice back), and the same exact-oracle
fallback sweeps any speculation-overflow lanes afterwards.

``mesh_rule_parts`` is the product entry point: OSDMap full remaps
(osd/mapping.py, so the balancer's dry-runs and osdmaptool inherit it)
draw their parts from it, sharded whenever more than one device exists
and on the one device otherwise — one pipeline (jaxmap.map_parts)
either way.  ``mesh_batch_do_rule`` is the same choice for a caller
that wants the whole table at once.
"""

from __future__ import annotations

import time

from ..crush import jaxmap
from ..ops import mesh as meshmod


def part_lanes(dmesh: meshmod.DeviceMesh | None) -> int:
    """Lanes one issue of the CRUSH program maps: every device sees at
    most CHUNK_LANES lanes per program."""
    return jaxmap.CHUNK_LANES * (dmesh.n if dmesh is not None else 1)


def mesh_rule_parts(
    cm,
    ruleno: int,
    xs,
    result_max: int,
    weights=None,
    dmesh: meshmod.DeviceMesh | None = None,
):
    """``jaxmap.rule_parts`` with each part's lanes sharded across
    ``dmesh`` (None: the one device): set-up now, then a generator of
    finished parts ``(lo, results, counts)`` of ``part_lanes(dmesh)``
    rows — THE pipeline of a whole-map remap (osd/mapping.py), on one
    device or many."""
    if dmesh is None:
        return jaxmap.rule_parts(cm, ruleno, xs, result_max, weights)
    import jax

    spec = dmesh.batch_spec(1)

    def place(part):
        padded, _ = meshmod.pad_to_devices(part, dmesh.n)
        return jax.device_put(padded, spec)

    parts = jaxmap.rule_parts(
        cm, ruleno, xs, result_max, weights, place, part_lanes(dmesh)
    )

    def counted():
        # the mesh counters' latency runs from the first issue to the
        # last part handed back, the caller's work on the parts between
        t0 = time.perf_counter()
        yield from parts
        meshmod.record_shard_dispatch(
            dmesh, "crush", 4 * len(xs),  # the lanes go up as int32
            time.perf_counter() - t0,
        )

    return counted()


def sharded_batch_do_rule(
    cm,
    ruleno: int,
    xs,
    result_max: int,
    weights=None,
    dmesh: meshmod.DeviceMesh | None = None,
):
    """``jaxmap.batch_do_rule`` with the PG batch sharded across
    ``dmesh`` (default: the process mesh).  Same signature, same
    (results, counts) numpy contract, byte-identical output."""
    if dmesh is None:
        dmesh = meshmod.default_mesh()
    if dmesh is None:
        return jaxmap.batch_do_rule(cm, ruleno, xs, result_max, weights)
    # host-side re-assembly: every shard gathered, the pad lanes dropped
    return jaxmap.join_parts(
        mesh_rule_parts(cm, ruleno, xs, result_max, weights, dmesh)
    )


def mesh_batch_do_rule(cm, ruleno, xs, result_max, weights=None):
    """Product dispatch: shard across the default mesh when more than
    one device exists, else the single-device path unchanged."""
    return sharded_batch_do_rule(cm, ruleno, xs, result_max, weights)


class ShardedPGMapper:
    """Thin OO wrapper over one (map, mesh) pair — the shape the
    dryrun drives: compile once, map many PG ranges."""

    def __init__(self, crush_map, dmesh: meshmod.DeviceMesh):
        self.cm = jaxmap.compile_map(crush_map)
        self.dmesh = dmesh

    def map_pgs(self, ruleno: int, xs, result_max: int, weights=None):
        return sharded_batch_do_rule(
            self.cm, ruleno, xs, result_max, weights, self.dmesh
        )
