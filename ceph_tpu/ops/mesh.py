"""Device-mesh execution plane: discovery, sharding, telemetry.

The single-device kernels (ops/gf_matmul.py, crush/jaxmap.py) batch a
whole workload into one device call; this module spreads that batch
across EVERY chip — real TPUs or the
``--xla_force_host_platform_device_count=8`` virtual CPU mesh the test
suite provisions.  The reference's
CPU analog shards pgid ranges over a thread pool
(ParallelPGMapper, src/osd/OSDMapMapping.h:18-156); here the pool is
the device mesh and the shard axis is the batch dimension of an
already-jitted kernel, so sharding never changes the per-lane math —
outputs are byte-identical to the single-device path (asserted in
tests/test_mesh.py, ragged batch sizes included).

Pieces:

- discovery: ``available_devices()`` lists JAX's devices (a backend
  that cannot initialise raises) and ``build_mesh(n)`` /
  ``default_mesh()`` construct 1-D meshes over them.  Everything is
  device-count-agnostic: callers ask for a mesh and get however many
  chips exist.
- sharding specs: ``DeviceMesh.batch_spec(ndim, axis)`` names the
  batch axis of an operand, ``replicated_spec()`` the broadcast
  tables; ragged batches pad to a device-count multiple on the host
  and slice back after gather (``pad_to_devices``).
- sharded EC encode: ``sharded_matrix_stripes`` runs the bitplane
  stripe kernel with the object batch sharded across the mesh.
- telemetry: every sharded dispatch records per-device counters
  (``l_tpu_mesh_dev<i>_calls/_bytes``) plus the usual group totals
  through ops/kernel_stats.py, so mesh behavior flows perf dump →
  MMgrReport → /metrics like every other kernel counter.
"""

from __future__ import annotations

import os
import threading

import numpy as np

from .kernel_stats import kernel_stats

_AXIS = "shard"


def available_devices() -> list:
    """``jax.devices()`` as a list.  A backend that cannot initialise
    raises: callers of this module asked for the device."""
    import jax

    return list(jax.devices())


def device_count() -> int:
    return len(available_devices())


class DeviceMesh:
    """A 1-D ``jax.sharding.Mesh`` over explicit devices, axis
    ``shard`` — the batch axis every sharded kernel splits on."""

    def __init__(self, devices, axis: str = _AXIS):
        from jax.sharding import Mesh

        self.devices = list(devices)
        if not self.devices:
            raise ValueError("DeviceMesh needs at least one device")
        self.axis = axis
        self.mesh = Mesh(np.asarray(self.devices), (axis,))

    @property
    def n(self) -> int:
        return len(self.devices)

    @property
    def platform(self) -> str:
        return self.devices[0].platform

    def batch_spec(self, ndim: int, axis: int = 0):
        """NamedSharding splitting dimension ``axis`` of an
        ``ndim``-dimensional operand across the mesh."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        spec = [None] * ndim
        spec[axis] = self.axis
        return NamedSharding(self.mesh, P(*spec))

    def replicated_spec(self):
        from jax.sharding import NamedSharding, PartitionSpec as P

        return NamedSharding(self.mesh, P())

    # skey-style cache identity: the same device set compiles once
    def cache_key(self) -> tuple:
        return tuple(d.id for d in self.devices)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"DeviceMesh({self.n}x{self.platform})"


def build_mesh(n: int | None = None, devices=None) -> DeviceMesh | None:
    """Mesh over the first ``n`` (default: all) devices; None for an
    empty explicit ``devices`` list."""
    devs = list(devices) if devices is not None else available_devices()
    if not devs:
        return None
    if n is not None:
        devs = devs[: max(int(n), 1)]
    return DeviceMesh(devs)


# -- the default product mesh ------------------------------------------------
# Probed once per process (like the one JAX runtime the kernels share).
# CEPH_TPU_MESH=0 disables sharding outright; CEPH_TPU_MESH_DEVICES=k
# caps the device count.  Single-device hosts get None so product
# paths keep their exact existing dispatch.

_default_lock = threading.Lock()
_default_probed = False
_default_mesh: DeviceMesh | None = None


def default_mesh() -> DeviceMesh | None:
    """The process mesh product paths shard over when >1 device
    exists; None on single-device (or disabled) hosts."""
    global _default_probed, _default_mesh
    if not _default_probed:
        with _default_lock:
            if not _default_probed:
                mesh = None
                if os.environ.get("CEPH_TPU_MESH", "1") != "0":
                    devs = available_devices()
                    try:
                        cap = int(
                            os.environ.get("CEPH_TPU_MESH_DEVICES", "0")
                        )
                    except ValueError:
                        cap = 0
                    if cap > 0:
                        devs = devs[:cap]
                    if len(devs) > 1:
                        mesh = DeviceMesh(devs)
                _default_mesh = mesh
                _default_probed = True
    return _default_mesh


def _reset_default_mesh_for_tests() -> None:
    global _default_probed, _default_mesh
    with _default_lock:
        _default_probed = False
        _default_mesh = None


# -- ragged-batch padding ----------------------------------------------------


def pad_to_devices(arr: np.ndarray, n_dev: int, axis: int = 0):
    """Pad ``axis`` up to a multiple of ``n_dev`` by repeating the
    last slice (any valid input works — padded lanes are discarded
    after gather).  Returns (padded, original_length)."""
    n = arr.shape[axis]
    pad = (-n) % max(n_dev, 1)
    if not pad:
        return arr, n
    tail = np.take(arr, [n - 1], axis=axis)
    reps = [1] * arr.ndim
    reps[axis] = pad
    return np.concatenate([arr, np.tile(tail, reps)], axis=axis), n


# -- telemetry ---------------------------------------------------------------


def record_shard_dispatch(
    dmesh: DeviceMesh, group: str, bytes_in: int, seconds: float
) -> None:
    """Per-device mesh counters: each device of the mesh saw one shard
    of ~bytes_in/n, plus the per-group rollup (``l_tpu_mesh_*``)."""
    ks = kernel_stats()
    ks.record(f"mesh_{group}", bytes_in=bytes_in, seconds=seconds)
    per_dev = bytes_in // max(dmesh.n, 1)
    for i in range(dmesh.n):
        ks.perf.inc(
            ks.counter("mesh", f"dev{i}_calls", desc="shards dispatched")
        )
        if per_dev:
            ks.perf.inc(
                ks.counter(
                    "mesh", f"dev{i}_bytes", desc="shard bytes in"
                ),
                per_dev,
            )


# -- sharded EC encode -------------------------------------------------------

_stripe_call_cache: dict[tuple, object] = {}
_stripe_call_lock = threading.Lock()


def _sharded_stripe_fn(dmesh: DeviceMesh, w: int):
    """Jitted ``gf_matrix_stripes`` with the (B, k, chunk) batch axis
    sharded across the mesh; compiled once per (device set, w)."""
    import jax

    from .gf_matmul import gf_matrix_stripes

    key = (dmesh.cache_key(), w)
    with _stripe_call_lock:
        fn = _stripe_call_cache.get(key)
        if fn is None:
            data_spec = dmesh.batch_spec(3)
            repl = dmesh.replicated_spec()
            fn = jax.jit(
                lambda bm, s: gf_matrix_stripes(bm, s, w=w),
                in_shardings=(repl, data_spec),
                out_shardings=data_spec,
            )
            _stripe_call_cache[key] = fn
    return fn


def sharded_matrix_stripes(
    bm, stripes: np.ndarray, w: int, dmesh: DeviceMesh
) -> np.ndarray:
    """Batched (B, k, chunk) → (B, m, chunk) encode with the object
    batch sharded across ``dmesh``.  Byte-identical to the
    single-device ``gf_matrix_stripes``: each stripe's math is
    lane-independent integer mod-2 arithmetic, so splitting B never
    changes a byte — ragged B pads on the host and slices back."""
    import time

    import jax
    import jax.numpy as jnp

    stripes = np.ascontiguousarray(stripes, dtype=np.uint8)
    padded, n = pad_to_devices(stripes, dmesh.n)
    t0 = time.perf_counter()
    data = jax.device_put(jnp.asarray(padded), dmesh.batch_spec(3))
    bm_d = jax.device_put(bm, dmesh.replicated_spec())
    out = np.asarray(_sharded_stripe_fn(dmesh, w)(bm_d, data))[:n]
    record_shard_dispatch(
        dmesh, "ec_encode", stripes.nbytes, time.perf_counter() - t0
    )
    return out
