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

``mesh_batch_do_rule`` is the product entry point: OSDMap full remaps
(osd/mapping.py, so the balancer's dry-runs and osdmaptool inherit it)
route through it and shard automatically whenever more than one device
exists; single-device hosts keep the exact existing dispatch.
"""

from __future__ import annotations

import time

import numpy as np

from ..crush import jaxmap
from ..ops import mesh as meshmod


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
    import jax
    import jax.numpy as jnp

    if weights is None:
        weights = np.full(max(cm.max_devices, 1), 0x10000, np.int32)
    xs_np = np.asarray(xs, dtype=np.int32)
    t0 = time.perf_counter()
    wv = jnp.asarray(weights, dtype=jnp.int32)
    fn, tables = jaxmap.batched_rule_call(
        cm, ruleno, result_max, weights
    )
    spec = dmesh.batch_spec(1)

    def dispatch(part):
        padded, _ = meshmod.pad_to_devices(part, dmesh.n)
        return fn(jax.device_put(padded, spec), wv, *tables)

    # host-side re-assembly: gather every shard, drop the pad lanes;
    # every device sees at most CHUNK_LANES lanes per program
    res, counts, ok = jaxmap.map_chunked(
        dispatch, xs_np, jaxmap.CHUNK_LANES * dmesh.n
    )
    meshmod.record_shard_dispatch(
        dmesh, "crush", xs_np.nbytes, time.perf_counter() - t0
    )
    return jaxmap.apply_oracle_fallback(
        cm, ruleno, xs_np, res, counts, ok, result_max, weights
    )


def mesh_batch_do_rule(cm, ruleno, xs, result_max, weights=None):
    """Product dispatch: shard across the default mesh when more than
    one device exists, else the single-device path unchanged."""
    dmesh = meshmod.default_mesh()
    if dmesh is None:
        return jaxmap.batch_do_rule(cm, ruleno, xs, result_max, weights)
    return sharded_batch_do_rule(
        cm, ruleno, xs, result_max, weights, dmesh
    )


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
