"""Round benchmark: the two BASELINE.md headline configs.

1. EC encode throughput, ``ceph_erasure_code_benchmark --workload encode
   --parameter k=8 --parameter m=3`` with 1MB stripes
   (src/test/erasure-code/ceph_erasure_code_benchmark.cc:156-186):
   GB/s of *input* bytes encoded.
2. CRUSH mapping throughput, BASELINE config #5: 1M PGs mapped through a
   10k-OSD straw2 hierarchy (``crushtool --test`` /
   ``osdmaptool --test-map-pgs`` surface, src/crush/CrushTester.cc,
   src/tools/osdmaptool.cc:147-218): mappings/sec.

``vs_baseline`` is stated honestly: the reference publishes no absolute
numbers, and this host cannot run real jerasure/ISA-L, so the EC ratio
is computed against an ISA-L-class estimate (~7.5 GB/s for one SIMD CPU
core — real jerasure/ISA-L does roughly 5-10 GB/s/core on this config),
NOT against the repo's own single-threaded numpy oracle (which is
~40x slower than ISA-L and would overstate the win).  Both the
measured numpy-oracle rate and the estimate are reported alongside.

Prints exactly ONE JSON line on stdout; diagnostics go to stderr.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

K, M, W = 8, 3, 8
OBJECT_SIZE = 1 << 20  # 1MB stripe
CHUNK = OBJECT_SIZE // K


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


_BACKEND: str | None = None


def _backend() -> str:
    """The one backend this run measures: a TPU, or the CPU only when
    the caller set ``JAX_PLATFORMS=cpu`` (the line is then labelled
    ``backend: cpu`` and carries no device metric name).  A backend
    that cannot initialise, or any other platform, raises."""
    global _BACKEND
    if _BACKEND is None:
        import jax

        be = jax.default_backend()
        if be != "tpu" and os.environ.get("JAX_PLATFORMS") != "cpu":
            raise RuntimeError(
                f"bench.py needs a TPU (JAX found {be!r}); set "
                "JAX_PLATFORMS=cpu for a labelled CPU run"
            )
        _BACKEND = be
    return _BACKEND


def measure_device(matrix, batch: int, iters: int, kernel: str) -> float:
    """Marginal throughput: chained dependent encodes at two sizes so
    dispatch/link overhead subtracts out (naive timing of queued
    identical calls over-reports on remote-attached devices).

    ``kernel``: "packed" = the packed-lane VPU kernel
    (ops/packed_gf.py, the fast TPU path), "bitplane" = the mod-2
    matmul (ops/gf_matmul.py)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ops import packed_gf
    from ceph_tpu.ops.gf_matmul import (
        gf_matrix_stripes,
        matrix_to_device_bitmatrix,
    )

    bm = matrix_to_device_bitmatrix(matrix, W)
    bm_np = np.asarray(bm)
    rng = np.random.default_rng(1)

    if kernel == "packed":
        # word-form chain (the fast path's layout contract): every
        # iteration's input depends on the previous parity outputs, so
        # no encode can be elided
        assert packed_gf.supports(bm_np, W), (
            "benchmark config outside the packed kernel's carry bound"
        )
        call = packed_gf.prebuilt_word_call(bm_np)

        def chained(xs):
            for _ in range(iters):
                outs = call(*xs)
                xs = tuple(xs[j] ^ outs[j % M] for j in range(K))
            return sum(x.sum(dtype=jnp.int32) for x in xs)

        def make_data(b):
            from ceph_tpu.layout import fold_stripes

            stripes = rng.integers(
                0, 256, size=(b, K, CHUNK), dtype=np.uint8
            )
            return tuple(
                jax.device_put(w)
                for w in packed_gf.to_words(fold_stripes(stripes))
            )

    else:

        def chained(stripes):
            # consume the WHOLE output each iteration (a sum keeps
            # every byte live; slicing one element would let XLA DCE
            # the encode)
            acc = jnp.uint8(0)
            for _ in range(iters):
                out = gf_matrix_stripes(bm, stripes ^ acc, w=W)
                acc = out.sum(dtype=jnp.uint8)
            return acc

        def make_data(b):
            return jax.device_put(
                rng.integers(0, 256, size=(b, K, CHUNK), dtype=np.uint8)
            )

    small, big = batch, batch * 8
    fns = {}
    data = {}
    for b in (small, big):
        data[b] = make_data(b)
        fns[b] = jax.jit(chained)
        int(fns[b](data[b]))  # compile + warm
    # interleaved pairs; median delta resists the dispatch/link
    # jitter that dwarfs any single measurement
    deltas = []
    for trial in range(5):
        t_small = _timed(lambda: int(fns[small](data[small])))
        t_big = _timed(lambda: int(fns[big](data[big])))
        deltas.append(t_big - t_small)
        _log(
            f"device[{jax.devices()[0].platform}][{kernel}] trial "
            f"{trial}: {iters}x{small}x1MB {t_small * 1000:.1f}ms, "
            f"{iters}x{big}x1MB {t_big * 1000:.1f}ms"
        )
    delta = sorted(deltas)[len(deltas) // 2]
    extra_bytes = iters * (big - small) * K * CHUNK
    if delta <= 0:
        _log("warning: non-positive median delta; using total time")
        total = iters * big * K * CHUNK
        gbs = total / min(
            _timed(lambda: int(fns[big](data[big]))) for _ in range(3)
        ) / 2**30
    else:
        gbs = extra_bytes / delta / 2**30
    _log(f"device marginal [{kernel}]: {gbs:.3f} GB/s input")
    return gbs


def _timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def measure_e2e(matrix, batch: int = 64, rounds: int = 10):
    """Sustained STORAGE-PATH throughput: host bytes in → parity bytes
    back in host memory, the product path of ECStore.put at scale
    (SURVEY §7 Phase 5).

    Layout contract (measured, not assumed): the storage plane
    accumulates inbound chunks in per-position HOST region buffers
    and ships them as u32 views — a free numpy view, no copy.  Every
    alternative pays a full relayout pass on device: u8→u32 bitcast
    reshuffles the (32,128)→(8,128) tiling at ~20 GB/s, and a
    (B,K,chunk)→(K,B·chunk) u8 transpose is slower still, against a
    ~125 GB/s kernel.  So the pipeline here is device_put(u32 views)
    → packed kernel → fetch parity words → free u8 view back.

    Returns a dict of rates, or None off-TPU.  Two figures matter:
    ``e2e_storage_GBps`` (host round trip — capped by the measured
    host↔device link, reported alongside) and
    ``e2e_device_pipeline_GBps`` (the same pipeline with
    device-resident buffers, dispatch-floor amortized — what a
    colocated host would approach)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.gf import matrix_vector_mul_region
    from ceph_tpu.ops import packed_gf
    from ceph_tpu.ops.gf_matmul import matrix_to_device_bitmatrix

    bm_np = np.asarray(matrix_to_device_bitmatrix(matrix, W))
    if not packed_gf.supports(bm_np, W):
        return None
    call = packed_gf.prebuilt_word_call(bm_np)
    rng = np.random.default_rng(3)

    def host_words(regions_u8: np.ndarray):
        """(K, nbytes) u8 region buffers → K u32 views (free)."""
        return [
            np.ascontiguousarray(row).view(np.uint32).reshape(1, -1)
            for row in regions_u8
        ]

    # correctness gate: word-form round trip must match the oracle
    probe = rng.integers(0, 256, size=(K, 4096), dtype=np.uint8)
    outs = call(*[jax.device_put(w) for w in host_words(probe)])
    got = np.stack(
        [np.asarray(o).reshape(-1).view(np.uint8) for o in outs]
    )
    if not np.array_equal(got, matrix_vector_mul_region(matrix, probe, W)):
        _log("e2e path MISMATCH vs oracle — not reporting e2e")
        return None

    # raw link probe: the host↔device link caps any host↔device
    # figure — measure it so the report says what it was
    link_mb = 8 << 20
    blob = rng.integers(0, 256, size=(link_mb,), dtype=np.uint8)
    d = jax.device_put(blob)
    d.block_until_ready()
    t0 = time.perf_counter()
    d = jax.device_put(blob)
    d.block_until_ready()
    link_gbs = link_mb / (time.perf_counter() - t0) / 2**30
    _log(f"host↔device link: {link_gbs:.3f} GB/s")
    if link_gbs < 1.0:
        batch, rounds = 8, 3  # keep a slow link from eating the run

    data = [
        rng.integers(
            0, 256, size=(K, batch * CHUNK), dtype=np.uint8
        )
        for _ in range(2)
    ]
    jall = jax.jit(lambda *xs: call(*xs))
    [np.asarray(o) for o in jall(*host_words(data[0]))]  # warm
    rates = []
    # per-round op latencies (dispatch→sync) so the section reports
    # TAILS alongside the throughput mean (p50/p99, not just GB/s)
    op_lats: list[float] = []
    for trial in range(2):
        t0 = time.perf_counter()
        pending = None
        for i in range(rounds):
            r0 = time.perf_counter()
            dev = [jax.device_put(w) for w in host_words(data[i % 2])]
            outs = jall(*dev)
            if pending is not None:
                [np.asarray(o) for o in pending]
            pending = outs
            op_lats.append(time.perf_counter() - r0)
        [np.asarray(o) for o in pending]
        dt = time.perf_counter() - t0
        total_in = rounds * batch * K * CHUNK
        rates.append(total_in / dt / 2**30)
        _log(
            f"e2e trial {trial}: {rounds}x{batch}x1MB in {dt:.3f}s = "
            f"{rates[-1]:.2f} GB/s host→device→host"
        )
    e2e = sorted(rates)[len(rates) // 2]
    lat_sorted = sorted(op_lats)
    e2e_p50 = lat_sorted[len(lat_sorted) // 2]
    e2e_p99 = lat_sorted[
        min(len(lat_sorted) - 1, int(len(lat_sorted) * 0.99))
    ]

    # device-resident pipeline: XOR-chained so every iteration's
    # output stays live with no per-iteration (1, N) reduction (those
    # run far below HBM rate and would mask the kernel); enough
    # iterations to amortize the per-dispatch floor
    big_b = 256
    words = tuple(
        jax.device_put(w)
        for w in host_words(
            rng.integers(
                0, 256, size=(K, big_b * CHUNK), dtype=np.uint8
            )
        )
    )
    iters = 40

    @jax.jit
    def pipeline(xs):
        def body(_i, xs):
            outs = call(*xs)
            # dependency through ONE lane: keeps the pallas call live
            # every iteration while adding only ~chunk-sized extra
            # HBM traffic (chaining all K inputs would TRIPLE the
            # traffic and measure the chain, not the kernel)
            return (xs[0] ^ outs[0],) + xs[1:]

        xs = jax.lax.fori_loop(0, iters, body, xs)
        return sum(x.sum(dtype=jnp.int32) for x in xs)

    int(pipeline(words))  # compile + warm
    t = min(_timed(lambda: int(pipeline(words))) for _ in range(3))
    pipe_gbs = iters * big_b * K * CHUNK / t / 2**30
    _log(f"device-resident pipeline: {pipe_gbs:.2f} GB/s")
    _log(
        "e2e note: host→device→host sustained rate; "
        + (
            "on this mount the host↔device link (e2e_link_GBps) is "
            "the cap, not the encode pipeline "
            "(e2e_device_pipeline_GBps)"
            if link_gbs < 1.0
            else "double-buffered"
        )
    )
    return {
        "e2e_storage_GBps": round(e2e, 3),
        "e2e_storage_p50_ms": round(e2e_p50 * 1000, 3),
        "e2e_storage_p99_ms": round(e2e_p99 * 1000, 3),
        "e2e_link_GBps": round(link_gbs, 3),
        "e2e_device_pipeline_GBps": round(pipe_gbs, 2),
    }


def measure_e2e_batched(on_tpu: bool) -> dict:
    """Batch-size → throughput sweep through the PRODUCT coalesced
    write path (``ECCodec.encode_object_batch`` → the pipelined
    device pass with async double-buffered transfers): host payload
    in → every k+m shard's bytes + HashInfo back in host memory, the
    full storage-side cost of one coalesced dispatch.  batch=1 is the
    per-op path every write paid before (``encode_object``).

    Also measures payload residency across EC encode → deep scrub:
    ``ECStore.put`` registers each shard device-resident, and
    ``scrub_batch`` digests the same upload
    (``residency_reuse_ratio``).

    Batched-vs-per-op outputs are gated byte-identical here AND in
    tests/test_residency.py.
    """
    from ceph_tpu.ops.profiler import breakdown, dispatch_profiler
    from ceph_tpu.ops.residency import residency_cache
    from ceph_tpu.osd.ec_pg import ECCodec
    from ceph_tpu.store.ec_store import ECStore

    # the PRODUCT backend for this platform: the device kernels on
    # TPU; the host backend (C region-MAC, native/gf8.c, with numpy
    # fallback) on a deviceless mount — what a pool with no explicit
    # backend= actually runs
    profile = {
        "plugin": "jerasure", "technique": "reed_sol_van",
        "k": str(K), "m": str(M), "w": str(W),
    }
    if on_tpu:
        profile["backend"] = "jax"
    codec = ECCodec(profile)
    obj_size = OBJECT_SIZE if on_tpu else 256 << 10
    rng = np.random.default_rng(17)

    # identity gate: the batched dispatch must reproduce the per-op
    # encode byte-for-byte on a ragged probe set before any number
    # is reported (mirrors the e2e section's oracle gate)
    probe = [
        rng.integers(0, 256, size=sz, dtype=np.uint8).tobytes()
        for sz in (1, 4096, 70000, obj_size)
    ]
    for data, got in zip(probe, codec.encode_object_batch(probe)):
        if got != codec.encode_object(data):
            raise AssertionError(
                "batched encode disagrees with per-op encode"
            )

    batch_sizes = [1, 2, 4, 8, 16, 32]
    rounds = 3
    sweep = []
    # flight-recorder attribution for everything measured below (the
    # warm-up/probe dispatches above are excluded on purpose)
    disp_before = dispatch_profiler().totals()
    best = (0.0, 1)
    per_op_lats: dict[int, list[float]] = {}
    for b in batch_sizes:
        objs = [
            rng.integers(0, 256, size=obj_size, dtype=np.uint8)
            .tobytes()
            for _ in range(b)
        ]
        encode = (
            (lambda: [codec.encode_object(o) for o in objs])
            if b == 1
            else (lambda: codec.encode_object_batch(objs))
        )
        encode()  # warm/compile
        lats = per_op_lats[b] = []
        t0 = time.perf_counter()
        for _ in range(rounds):
            r0 = time.perf_counter()
            encode()
            # every op in the dispatch completes when the dispatch
            # commits: the per-op completion latency IS the dispatch
            lats.append(time.perf_counter() - r0)
        dt = time.perf_counter() - t0
        gbs = rounds * b * obj_size / dt / 2**30
        sweep.append({"batch": b, "GBps": round(gbs, 3)})
        if gbs > best[0]:
            best = (gbs, b)
        _log(
            f"e2e batched[b={b}]: {rounds}x{b}x{obj_size >> 10}KB in "
            f"{dt:.3f}s = {gbs:.3f} GB/s"
        )
    lat_sorted = sorted(per_op_lats[best[1]])
    p50 = lat_sorted[len(lat_sorted) // 2]
    p99 = lat_sorted[min(len(lat_sorted) - 1, int(len(lat_sorted) * 0.99))]

    # residency reuse: EC encode → deep scrub share one upload
    # (ECStore.put registers each shard; scrub_batch digests the
    # registered payloads without re-reading or re-uploading)
    ecs = ECStore(profile=profile, stripe_width=K * 4096)
    names = [f"res{i}" for i in range(8)]
    for name in names:
        ecs.put(name, rng.integers(
            0, 256, size=obj_size // 4, dtype=np.uint8
        ).tobytes())
    rc = residency_cache()
    before = rc.stats()
    findings = ecs.scrub_batch(names)
    after = rc.stats()
    assert not any(
        f.missing or f.corrupt or f.inconsistent
        for f in findings.values()
    ), "clean freshly-written objects must scrub clean"
    hits = after["hits"] - before["hits"]
    misses = after["misses"] - before["misses"]
    reuse = round(hits / max(hits + misses, 1), 4)
    per_op = sweep[0]["GBps"] if sweep else 0.0
    # where the device time of the measured work went: the breakdown
    # keys are contractual — they emit on a JAX_PLATFORMS=cpu run
    # too (backend=cpu), never regressing to missing keys
    disp = breakdown(
        disp_before, dispatch_profiler().totals(),
        backend="jax-tpu" if on_tpu else "cpu",
    )
    _log(
        f"e2e batched: best {best[0]:.3f} GB/s at batch={best[1]} "
        f"({best[0] / max(per_op, 1e-9):.1f}x the per-op rate), "
        f"scrub residency reuse {reuse:.2%}, dispatch split "
        f"T/C/S {disp['transfer_ms']}/{disp['compute_ms']}/"
        f"{disp['sync_ms']} ms"
    )
    return {
        "e2e_batched": {
            "dispatch": disp,
            "sweep": sweep,
            "object_bytes": obj_size,
            "rounds": rounds,
            "profile": f"k{K}m{M}",
            "per_op_GBps": per_op,
            "best_batch": best[1],
            "per_op_p50_ms": round(p50 * 1000, 3),
            "per_op_p99_ms": round(p99 * 1000, 3),
            "note": (
                "batch amortizes device dispatch + link; on a "
                "deviceless mount the host backend has no dispatch "
                "cost, so the curve is flat-to-declining"
                if not on_tpu
                else "device path: transfers double-buffered, sync "
                "at commit"
            ),
        },
        "e2e_batched_GBps": round(best[0], 3),
        "residency_reuse_ratio": reuse,
    }


def measure_cpu(matrix, iters: int) -> float:
    from ceph_tpu.gf import matrix_vector_mul_region

    rng = np.random.default_rng(2)
    data = rng.integers(0, 256, size=(K, CHUNK), dtype=np.uint8)
    matrix_vector_mul_region(matrix, data, W)  # warm table caches
    t0 = time.perf_counter()
    for _ in range(iters):
        matrix_vector_mul_region(matrix, data, W)
    dt = time.perf_counter() - t0
    total = K * CHUNK * iters
    _log(f"cpu oracle: {total / dt / 2**30:.3f} GB/s ({iters} stripes, {dt:.3f}s)")
    return total / dt / 2**30


# ISA-L-class single-core RS encode rate for k=8,m=3 @1MB: real SIMD
# implementations land in the 5-10 GB/s range; use the midpoint as the
# honest denominator (the numpy oracle is ~40x slower than that and
# would be a strawman).
ISAL_CLASS_GBPS = 7.5

# BASELINE.json configs 1-4: every code family the reference's
# ceph_erasure_code_benchmark sweeps, with the decode workload
# (random + exhaustive erasures, content-verified — the
# ceph_erasure_code_benchmark.cc:202-317 contract).
EC_FAMILY_CONFIGS = [
    # (tag, plugin, profile, object_size, erasures, exhaustive_e)
    ("jerasure_rs_k4m2_4KB", "jerasure",
     {"technique": "reed_sol_van", "k": "4", "m": "2", "w": "8"},
     4096, 2, 2),
    ("isa_rs_k8m3_1MB", "isa",
     {"technique": "reed_sol_van", "k": "8", "m": "3"},
     1 << 20, 2, 2),
    ("isa_cauchy_k10m4_1MB", "isa",
     {"technique": "cauchy", "k": "10", "m": "4"},
     1 << 20, 2, 2),
    # BASELINE says l=4, but k=8,m=4,l=4 fails the reference's own
    # parser (ErasureCodeLrc.cc: k must be a multiple of (k+m)/l);
    # l=6 is the valid proportional config (2 groups of 6)
    ("lrc_k8m4_l6_1MB", "lrc",
     {"k": "8", "m": "4", "l": "6"},
     1 << 20, 2, 1),
    ("shec_k8m4_c2_1MB", "shec",
     {"k": "8", "m": "4", "c": "2"},
     1 << 20, 2, 1),
    ("clay_k8m4_d11_1MB", "clay",
     {"k": "8", "m": "4", "d": "11"},
     1 << 20, 1, 1),
]


def _record_matrix_ops(fn):
    """Run fn() recording every NumpyBackend.matrix_regions call —
    the seam every family's region math goes through (layered codes
    recurse into jerasure/isa sub-plugins which land here too).
    Returns (result, ops) with ops = [(matrix, n_in, chunk_bytes, w)].
    """
    from ceph_tpu.ec import backend as eb

    ops = []
    orig = eb.NumpyBackend.matrix_regions

    def rec(self, matrix, regions, w):
        regions = np.asarray(regions)
        ops.append(
            (
                np.array(matrix, dtype=np.int64),
                regions.shape[0],
                int(regions.shape[1]),
                int(w),
            )
        )
        return orig(self, matrix, regions, w)

    eb.NumpyBackend.matrix_regions = rec
    try:
        out = fn()
    finally:
        eb.NumpyBackend.matrix_regions = orig
    return out, ops


def _family_device_rate(ops, object_size, force_bitplane=False):
    """Device GB/s for one family workload: ONE jitted program applies
    the family's recorded matrix-op chain per stripe per iteration
    (outputs folded into the next round's inputs so nothing is
    elided), batched over enough stripes to amortize dispatch.  Rate =
    logical object bytes decoded/encoded per second (the reference
    bench's KB accounting).

    Each distinct matrix routes through the packed-lane kernel
    (ops/packed_gf.py) when its carry bound admits it — the fast path
    the product ECStore uses — falling back to the mod-2 bitplane
    matmul otherwise.  The packed path also sidesteps the lane-
    misalignment penalty on chunk sizes that are not multiples of 128
    (k=10 splits 1MB into 104864B chunks; the bitplane kernel's
    (batch, k, chunk) layout tiles that badly, which is why round 4's
    cauchy entry ran 6x below its rs sibling).  Repeated identical
    ops (CLAY records hundreds of tiny pairwise transforms) dedupe
    into one data buffer applied count times serially.

    Returns (rate_GBps, kernel_name)."""
    import jax
    import jax.numpy as jnp

    from ceph_tpu.ops import packed_gf
    from ceph_tpu.ops.gf_matmul import (
        gf_matrix_stripes,
        matrix_to_device_bitmatrix,
    )

    if not ops:
        return None
    groups: dict[tuple, list] = {}
    order = []
    for m, n, c, w in ops:
        key = (m.tobytes(), m.shape, n, c, w)
        if key not in groups:
            groups[key] = [m, n, c, w, 0]
            order.append(key)
        groups[key][4] += 1
    glist = [groups[k] for k in order]

    max_bytes = max(n * c for _m, n, c, _w, _cnt in glist)
    batch = max(1, min(4096, (32 << 20) // max_bytes))
    rng = np.random.default_rng(7)

    specs = []  # ("packed", call, n, m_out, cnt) | ("bitplane", ...)
    datas = []
    kernels = set()
    for m, n, c, w, cnt in glist:
        bm = matrix_to_device_bitmatrix(m, w)
        bm_np = np.asarray(bm)
        if (
            not force_bitplane
            and c % 4 == 0
            and packed_gf.supports(bm_np, w)
        ):
            kernels.add("packed")
            call = packed_gf.prebuilt_word_call(bm_np)
            specs.append(("packed", call, n, bm_np.shape[0] // 8, cnt))
            datas.append(tuple(
                jax.device_put(rng.integers(
                    0, 1 << 32, size=(1, batch * c // 4),
                    dtype=np.uint32,
                ))
                for _ in range(n)
            ))
        else:
            kernels.add("bitplane")
            specs.append(("bitplane", bm, n, w, cnt))
            datas.append(jax.device_put(rng.integers(
                0, 256, size=(batch, n, c), dtype=np.uint8
            )))
    datas = tuple(datas)

    @jax.jit
    def chain(it, datas):
        def one(spec, d):
            if spec[0] == "packed":
                _, call, n, mo, cnt = spec

                def step(xs):
                    outs = call(*xs)
                    return tuple(
                        xs[j] ^ outs[j % mo] for j in range(n)
                    )

                if cnt > 4:
                    return jax.lax.fori_loop(
                        0, cnt, lambda _j, xs: step(xs), d
                    )
                for _ in range(cnt):
                    d = step(d)
                return d
            _, bm, n, w, cnt = spec

            def bstep(x):
                out = gf_matrix_stripes(bm, x, w=w)
                mi = out.shape[1]
                return x ^ out[:, jnp.arange(n) % mi, :]

            if cnt > 4:
                return jax.lax.fori_loop(
                    0, cnt, lambda _j, x: bstep(x), d
                )
            for _ in range(cnt):
                d = bstep(d)
            return d

        def body(_i, datas):
            return tuple(
                one(spec, d) for spec, d in zip(specs, datas)
            )

        datas = jax.lax.fori_loop(0, it, body, datas)
        total = jnp.int32(0)
        for d in datas:
            if isinstance(d, tuple):
                for x in d:
                    total = total + x.sum(dtype=jnp.int32)
            else:
                total = total + d.sum(dtype=jnp.int32)
        return total

    kernel_name = "+".join(sorted(kernels))
    # marginal method: the iteration count is a traced argument (one
    # compile), and the small/big delta cancels the per-dispatch
    # link overhead that dwarfs the compute at these sizes
    small, big = 4, 24
    int(chain(small, datas))  # compile + warm
    int(chain(big, datas))
    return _family_rate_timed(
        chain, datas, small, big, batch, object_size, kernel_name
    )


def _family_rate_timed(
    chain, datas, small, big, batch, object_size, kernel_name
):
    deltas = []
    for _trial in range(3):
        t_small = _timed(lambda: int(chain(small, datas)))
        t_big = _timed(lambda: int(chain(big, datas)))
        deltas.append(t_big - t_small)
    delta = sorted(deltas)[len(deltas) // 2]
    if delta <= 0:
        t = min(_timed(lambda: int(chain(big, datas))) for _ in range(3))
        return big * batch * object_size / t / 2**30, kernel_name
    rate = (big - small) * batch * object_size / delta / 2**30
    return rate, kernel_name


def measure_ec_families(fast: bool = False) -> dict:
    """BASELINE configs 1-4: encode AND decode per code family.

    ``fast`` (the no-TPU fallback): cap object sizes and the
    exhaustive-erasure depth so the correctness sweep still runs on
    CPU in seconds instead of minutes; device rates are skipped
    off-TPU regardless.

    Correctness first: for each config one random-erasure decode and a
    full exhaustive-erasure sweep (every C(n,e) pattern) run through
    the PLUGIN with content verification — then the recorded matrix
    work of that family's encode/decode is measured on device.  The
    clay entry also proves the d=11 minimum-bandwidth repair contract
    (fractional sub-chunk reads)."""
    import random as _random

    from ceph_tpu.ec import ErasureCodeProfile, registry_instance
    from ceph_tpu.ops.profiler import breakdown, dispatch_profiler
    from ceph_tpu.tools.ec_benchmark import _decode_exhaustive

    disp_before = dispatch_profiler().totals()
    out = {}
    for tag, plugin, prof, size, erasures, ex_e in EC_FAMILY_CONFIGS:
        if fast:
            size = min(size, 1 << 15)
            ex_e = min(ex_e, 1)
        profile = ErasureCodeProfile()
        for kk, vv in prof.items():
            profile[kk] = vv
        ec = registry_instance().factory(plugin, profile)
        data = bytes(
            np.random.default_rng(11).integers(
                0, 256, size=size, dtype=np.uint8
            )
        )
        n = ec.get_chunk_count()
        want = set(range(n))
        encoded, enc_ops = _record_matrix_ops(
            lambda: ec.encode(want, data)
        )

        # random-erasure decode, content-verified, ops recorded.
        # Locally-repairable codes are not MDS: reroll patterns the
        # code itself declares unrecoverable (the caller would never
        # ask it to decode those).
        from ceph_tpu.ec.interface import ErasureCodeError

        rng = _random.Random(5)
        for _attempt in range(64):
            chunks = dict(encoded)
            for _ in range(erasures):
                while True:
                    e = rng.randrange(n)
                    if e in chunks:
                        break
                chunks.pop(e)
            try:
                decoded, dec_ops = _record_matrix_ops(
                    lambda: ec.decode(want, chunks)
                )
                break
            except ErasureCodeError:
                continue
        else:
            raise SystemExit(f"{tag}: no decodable {erasures}-pattern")
        for c in want:
            assert np.array_equal(
                np.asarray(decoded[c]), np.asarray(encoded[c])
            ), f"{tag}: chunk {c} decode mismatch"

        # exhaustive sweep (every erasure pattern), content-verified
        t0 = time.perf_counter()
        _decode_exhaustive(ec, encoded, dict(encoded), 0, ex_e, False)
        ex_s = time.perf_counter() - t0

        # verification details go to stderr — the final JSON line must
        # stay compact enough for the driver's tail capture (round-4
        # artifact lost its headline to an oversized line)
        _log(
            f"ec family {tag}: config {plugin} {prof} object={size}B; "
            f"{erasures}-erasure decode content-verified; exhaustive "
            f"{ex_e}-erasure sweep content-verified in {ex_s:.2f}s cpu"
        )
        entry = {}

        def rate(ops):
            """The packed path first; if the remote Mosaic compile
            service hiccups (it degrades after many large compiles in
            one session), retry once, then fall back to the bitplane
            program rather than losing the family entry."""
            try:
                return _family_device_rate(ops, size)
            except Exception as e1:  # noqa: BLE001
                _log(f"{tag}: packed compile failed ({e1}); retrying")
                try:
                    return _family_device_rate(ops, size)
                except Exception as e2:  # noqa: BLE001
                    _log(f"{tag}: retry failed ({e2}); bitplane fallback")
                    return _family_device_rate(
                        ops, size, force_bitplane=True
                    )

        if _backend() == "tpu":
            enc = rate(enc_ops)
            dec = rate(dec_ops)
            kern = set()
            if enc:
                entry["encode_GBps"] = round(enc[0], 2)
                kern.add(enc[1])
            if dec:
                entry["decode_GBps"] = round(dec[0], 2)
                kern.add(dec[1])
            if kern:
                entry["kernel"] = "+".join(sorted(kern))
            if enc:
                entry["vs_core"] = round(enc[0] / ISAL_CLASS_GBPS, 2)
        if plugin == "clay":
            # d=11 minimum-bandwidth repair: fractional sub-chunk reads
            avail = set(range(n)) - {0}
            spec = ec.minimum_to_decode({0}, avail)
            sub_no = ec.get_sub_chunk_count()
            read_sub = sum(
                ln for runs in spec.values() for _off, ln in runs
            )
            entry["repair_read_fraction"] = round(
                read_sub / (sub_no * n), 4
            )
            entry["repair_helpers"] = len(spec)
        _log(f"ec family {tag}: {entry}")
        out[tag] = entry
    out["dispatch"] = breakdown(
        disp_before, dispatch_profiler().totals(),
        backend="jax-tpu" if _backend() == "tpu" else "cpu",
    )
    return out

CRUSH_OSDS = 10_000
CRUSH_PER_HOST = 40
CRUSH_HOSTS_PER_RACK = 25
CRUSH_PGS = 1 << 20
CRUSH_REP = 3
CRUSH_DEVICE_BATCH = 1 << 16  # jaxmap.CHUNK_LANES: scratch is ~25 kB a lane


def measure_crush() -> dict:
    """BASELINE #5: 1M-PG remap over a 10k-OSD straw2 hierarchy.

    Two figures, mirroring the EC bench's split:

    * ``crush_mappings_per_sec`` (headline): device-resident rate —
      one jitted program maps 8 consecutive ranges back-to-back,
      each round's results consumed into a checksum feeding the next
      round (jaxmap.make_chained_runner), so nothing is elided and
      no result crosses the device→host link.
    * ``crush_e2e_mappings_per_sec``: the osdmaptool-comparable
      end-to-end pass — dispatch every chunk, then materialize ALL
      results into host numpy (int16-packed wire form) including the
      oracle-fallback sweep.

    The denominator is this repo's scalar Python oracle
    (``crush_vs_oracle``); the reference's compiled C is not in the
    tree.
    """
    from ceph_tpu.crush import jaxmap
    from ceph_tpu.tools.crushtool import build_hierarchy

    m = build_hierarchy(CRUSH_OSDS, CRUSH_PER_HOST, CRUSH_HOSTS_PER_RACK)
    rule = 0  # replicated firstn over hosts
    cm = jaxmap.compile_map(m)

    t0 = time.perf_counter()
    res, counts, ok = jaxmap.batch_do_rule_range(
        cm, rule, 0, CRUSH_DEVICE_BATCH, CRUSH_REP, packed=True
    )
    np.asarray(res)
    compile_s = time.perf_counter() - t0
    _log(f"crush compile+first batch: {compile_s:.1f}s")

    # weights-only recompile honesty: a new CompiledMap of the same
    # topology (the per-epoch reweight pattern) must reuse the kernel
    t0 = time.perf_counter()
    cm2 = jaxmap.compile_map(m)
    r2 = jaxmap.batch_do_rule_range(
        cm2, rule, 0, CRUSH_DEVICE_BATCH, CRUSH_REP, packed=True
    )
    np.asarray(r2[0])
    recompile_s = time.perf_counter() - t0
    _log(f"crush same-topology re-map (cached kernel): {recompile_s:.2f}s")

    def one_pass():
        # dispatch chunk j+1, then materialize chunk j: device compute
        # and host copies overlap (the ParallelPGMapper pipelining
        # role) with two chunk programs' scratch in flight, not all
        # of them; per-chunk oracle fallback for speculation overflow
        # is part of the timed path (a handful of lanes per million)
        def finish(lo, rck):
            return jaxmap.apply_oracle_fallback(
                cm, rule,
                np.arange(lo, lo + CRUSH_DEVICE_BATCH),
                *rck, CRUSH_REP,
            )

        done = []
        prev = None
        for lo in range(0, CRUSH_PGS, CRUSH_DEVICE_BATCH):
            cur = (lo, jaxmap.batch_do_rule_range(
                cm, rule, lo, CRUSH_DEVICE_BATCH, CRUSH_REP,
                packed=True,
            ))
            if prev is not None:
                done.append(finish(*prev))
            prev = cur
        done.append(finish(*prev))
        return done

    one_pass()  # warm every dispatch path
    times = [_timed(one_pass) for _ in range(3)]
    dt = sorted(times)[len(times) // 2]
    e2e_rate = CRUSH_PGS / dt
    _log(
        f"crush e2e (host materialization): "
        f"{CRUSH_PGS} mappings in {dt:.3f}s = {e2e_rate:,.0f}/s"
    )

    # device-resident chained rate (the kernel itself); off-TPU the
    # chain shrinks so the CPU emulation finishes in seconds
    chain_n = 1 << 17 if _backend() == "tpu" else 1 << 12
    chain_iters = 8 if _backend() == "tpu" else 2
    runner = jaxmap.make_chained_runner(
        cm, rule, CRUSH_REP, chain_n, chain_iters
    )
    runner(0)  # compile + warm
    ctimes = []
    for trial in range(3):
        t0 = time.perf_counter()
        runner(1 + trial)
        ctimes.append(time.perf_counter() - t0)
    cdt = sorted(ctimes)[len(ctimes) // 2]
    dev_rate = chain_iters * chain_n / cdt
    _log(
        f"crush device-resident: {chain_iters * chain_n} mappings in "
        f"{cdt:.3f}s = {dev_rate:,.0f}/s"
    )

    # measure the device→host link so the e2e cap is stated, not
    # implied (fresh buffer each time: jax caches a fetched host copy)
    import jax as _jax
    import jax.numpy as _jnp

    blob = np.zeros(4 << 20, np.uint8)
    d = _jax.device_put(blob)
    rates = []
    for i in range(2):
        d2 = (d + np.uint8(i + 1)).block_until_ready()
        t0 = time.perf_counter()
        np.asarray(d2)
        rates.append(blob.size / (time.perf_counter() - t0) / 2**20)
    link_mbs = max(rates)
    _log(f"device->host link: {link_mbs:.0f} MB/s")

    sample = 2048
    t0 = time.perf_counter()
    for x in range(sample):
        m.do_rule(rule, x, CRUSH_REP)
    oracle_rate = sample / (time.perf_counter() - t0)
    _log(f"crush cpu oracle: {oracle_rate:,.0f} mappings/s ({sample} sample)")
    # context goes to stderr; the JSON line carries numbers only
    _log(
        f"crush config: {CRUSH_OSDS} osds straw2 (hosts of "
        f"{CRUSH_PER_HOST}, racks of {CRUSH_HOSTS_PER_RACK}), "
        f"{CRUSH_PGS} PGs, firstn num_rep={CRUSH_REP}"
    )
    _log(
        f"crush link note: headline is the device-resident chained "
        f"rate (results consumed on device); e2e materializes "
        f"~{7 * CRUSH_PGS // 2**20}MB to host over a "
        f"{link_mbs:.0f} MB/s device->host link"
    )
    # mapping-plane attribution: one PRODUCT OSDMapMapping pass over
    # this same hierarchy (the flight recorder's "crush" kind —
    # jaxmap calls above bypass it by design; _crush_stage is the
    # instrumented seam).  Non-pow2 pg_num so the lane-0 pad shows.
    from ceph_tpu.ops.profiler import breakdown, dispatch_profiler
    from ceph_tpu.osd import OSDMap, OSDMapMapping, PgPool

    om = OSDMap.build(m, CRUSH_OSDS)
    om.add_pool(PgPool(
        pool_id=1, size=CRUSH_REP, pg_num=3000, crush_rule=rule
    ))
    disp_before = dispatch_profiler().totals()
    OSDMapMapping().update(om, use_device=True)
    crush_disp = breakdown(
        disp_before, dispatch_profiler().totals(),
        backend="jax-tpu" if _backend() == "tpu" else "cpu",
    )
    _log(
        f"crush mapping-plane dispatch split T/C/S "
        f"{crush_disp['transfer_ms']}/{crush_disp['compute_ms']}/"
        f"{crush_disp['sync_ms']} ms, pad waste "
        f"{crush_disp['pad_waste_ratio']:.2%}"
    )
    out = {
        "crush_mappings_per_sec": round(dev_rate),
        "crush_e2e_mappings_per_sec": round(e2e_rate),
        "crush_compile_sec": round(compile_s, 1),
        "crush_remap_cached_sec": round(recompile_s, 2),
        "crush_oracle_mappings_per_sec": round(oracle_rate),
        "crush_dispatch": crush_disp,
    }
    out["crush_vs_oracle"] = round(dev_rate / oracle_rate, 2)
    return out


def measure_cpu_kernel(matrix, stripes=8, chunk=4096, iters=5) -> float:
    """The jax-on-CPU bitplane kernel at a size the host finishes in
    seconds — the fallback compute plane's own rate, distinct from
    the numpy oracle."""
    import jax.numpy as jnp

    from ceph_tpu.ops.gf_matmul import (
        gf_matrix_stripes,
        matrix_to_device_bitmatrix,
    )

    bm = matrix_to_device_bitmatrix(matrix, W)
    rng = np.random.default_rng(7)
    data = jnp.asarray(
        rng.integers(0, 256, size=(stripes, K, chunk), dtype=np.uint8)
    )
    np.asarray(gf_matrix_stripes(bm, data, w=W))  # compile + warm
    t0 = time.perf_counter()
    for _ in range(iters):
        np.asarray(gf_matrix_stripes(bm, data, w=W))
    dt = time.perf_counter() - t0
    gbs = stripes * K * chunk * iters / dt / 2**30
    _log(f"cpu bitplane kernel: {gbs:.3f} GB/s ({stripes}x{chunk}B)")
    return gbs


def measure_scrub() -> dict:
    """Deep-scrub checksum plane: GB/s of object bytes crc32c'd by
    the batched device kernel (ops/scrub_kernels.py — one mod-2
    matmul per PG chunk) vs the native slicing-by-8 C oracle, with a
    findings-parity check on a subsample (the batched path must see
    exactly what the per-object loop sees)."""
    from ceph_tpu.ops.scrub_kernels import batch_crc32c

    on_tpu = _backend() == "tpu"
    nobj = 64 if on_tpu else 16
    size = (1 << 20) if on_tpu else (256 << 10)
    rng = np.random.default_rng(11)
    objs = [rng.integers(0, 256, size, np.uint8).tobytes() for _ in range(nobj)]
    total = nobj * size
    # a failure here propagates to the section's try/except, is
    # recorded as scrub_error and fails the run
    batch_crc32c(objs[:2], 0xFFFFFFFF, backend="device")  # warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        dev = batch_crc32c(objs, 0xFFFFFFFF, backend="device")
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[len(times) // 2]
    dev_gbs = total / dt / 2**30
    t0 = time.perf_counter()
    ora = batch_crc32c(objs, 0xFFFFFFFF, backend="oracle")
    ora_gbs = total / (time.perf_counter() - t0) / 2**30
    if not (dev == ora).all():
        raise AssertionError("batched scrub crc disagrees with oracle")
    _log(
        f"deep-scrub crc32c: device {dev_gbs:.3f} GB/s vs native C "
        f"oracle {ora_gbs:.3f} GB/s ({nobj}x{size >> 10}KB, "
        "findings identical)"
    )
    return {
        "scrub_crc32c_GBps": round(dev_gbs, 3),
        "scrub_oracle_GBps": round(ora_gbs, 3),
        "scrub_objects": nobj,
        "scrub_object_bytes": size,
    }


def measure_msgr() -> dict:
    """Messenger plane on the shared network stack (ISSUE 14):
    messages/s and dispatch p50/p99 at 3, 16, and 100 in-process
    daemons, with the process thread count at each rung — the curve
    that shows thread cost stays flat while daemon count grows.
    Entirely CPU-side (no device kernels anywhere near the path)."""
    import threading as _threading

    from ceph_tpu.msg import Messenger, MPing
    from ceph_tpu.msg.messenger import Dispatcher
    from ceph_tpu.msg.stack import NetworkStack

    class _Echo(Dispatcher):
        def ms_dispatch(self, conn, msg):
            if isinstance(msg, MPing) and not msg.is_reply:
                conn.send(
                    MPing(
                        tid=msg.tid, from_osd=0, stamp=msg.stamp,
                        is_reply=True,
                    )
                )
                return True
            return False

    def rung(n_daemons: int, duration: float = 2.0) -> dict:
        msgrs = []
        clients = []
        try:
            for i in range(n_daemons):
                m = Messenger(f"bench-d{i}")
                m.add_dispatcher(_Echo())
                m.bind()
                msgrs.append(m)
            n_cli = 4
            lats: list[float] = []
            lock = _threading.Lock()
            stop = _threading.Event()

            def drive(widx: int):
                cli = Messenger(f"bench-c{widx}")
                clients.append(cli)
                conns = [
                    cli.connect(*m.bound_addr)
                    for m in msgrs[widx::n_cli] or msgrs[:1]
                ]
                mine: list[float] = []
                k = 0
                while not stop.is_set():
                    t0 = time.perf_counter()
                    conns[k % len(conns)].call(
                        MPing(stamp=1.0), timeout=10.0
                    )
                    mine.append(time.perf_counter() - t0)
                    k += 1
                with lock:
                    lats.extend(mine)

            threads = [
                _threading.Thread(target=drive, args=(w,), daemon=True)
                for w in range(n_cli)
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            time.sleep(duration)
            stop.set()
            for t in threads:
                t.join(timeout=10)
            dt = time.perf_counter() - t0
            stack = NetworkStack.live()
            s = sorted(lats) or [0.0]
            return {
                "daemons": n_daemons,
                "msgs_per_s": round(len(lats) / dt, 1),
                "dispatch_p50_ms": round(
                    s[len(s) // 2] * 1000, 3
                ),
                "dispatch_p99_ms": round(
                    s[min(len(s) - 1, int(len(s) * 0.99))] * 1000, 3
                ),
                "threads": _threading.active_count(),
                "stack_workers": (
                    len(stack.workers) if stack else 0
                ),
                "stack_offload": (
                    stack.offload.size if stack else 0
                ),
            }
        finally:
            for m in clients + msgrs:
                try:
                    m.shutdown()
                except Exception:  # noqa: BLE001 — teardown
                    pass

    curve = [rung(n) for n in (3, 16, 100)]
    for row in curve:
        _log(
            f"msgr @{row['daemons']:>3} daemons: "
            f"{row['msgs_per_s']:.0f} msg/s, dispatch p50 "
            f"{row['dispatch_p50_ms']}ms p99 "
            f"{row['dispatch_p99_ms']}ms, {row['threads']} threads "
            f"({row['stack_workers']} workers)"
        )
    return {"msgr": curve}


def measure_rgw_index() -> dict:
    """Sharded bucket-index plane (ROADMAP open item 4): index write
    ops/s and listing p99 on one bucket at 1 vs N shards under
    concurrent writers, then an ONLINE 1→N reshard under live load —
    duration plus the client-visible write stall (the worst single
    put latency across the reshard window), with a zero-lost /
    zero-phantom verdict.  Entirely CPU-side (omap traffic over the
    in-process cluster)."""
    import pathlib
    import sys as _sys
    import threading as _threading

    _sys.path.insert(0, str(pathlib.Path(__file__).parent / "tests"))
    from test_osd_daemon import MiniCluster

    from ceph_tpu.rados import Rados
    from ceph_tpu.rgw import RGW

    n_threads = 4
    n_objs = 480
    shards_hi = 8
    c = MiniCluster()
    r = gw = None
    try:
        for i in range(3):
            c.start_osd(i)
        c.wait_active()
        r = Rados("bench-rgw").connect(*c.mon_addr)
        r.pool_create("rgwbench", pg_num=8, size=2)
        # threshold checks off: the curve measures the index write
        # path, not the fill probe
        gw = RGW(r.open_ioctx("rgwbench"), max_objs_per_shard=0)

        def fill_rate(bucket: str, shards: int) -> tuple[float, float]:
            """Index-PLANE ops/s: concurrent ``set_entry`` mutations
            (sharded omap write + layout validation read — exactly
            the path a PUT's index transaction rides, without the
            data write/ACL/datalog overhead that buries the shard
            spread), then listing p99 over paged merged walks of the
            same index.  NOTE this whole in-process mount shares one
            GIL, so the shard spread shows up as reduced hot-object
            serialization, not core scaling — the raw-omap ceiling
            here is ~1.4x."""
            gw.create_bucket(bucket, shards=shards)
            rec = gw._bucket_rec(bucket)
            ent = {
                "size": 64, "etag": "0" * 32, "mtime": 0.0,
                "owner": None, "acl": {"owner": None, "grants": []},
            }

            def put_range(t: int):
                for i in range(t, n_objs, n_threads):
                    gw.index.set_entry(
                        bucket, f"o{i:05d}", ent, rec=rec
                    )

            threads = [
                _threading.Thread(target=put_range, args=(t,))
                for t in range(n_threads)
            ]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            ops_per_s = n_objs / (time.perf_counter() - t0)
            # listing p99 over paged walks of the full bucket
            pages: list[float] = []
            for _round in range(3):
                marker = ""
                while True:
                    t0 = time.perf_counter()
                    entries, trunc = gw.list_objects(
                        bucket, marker=marker, max_keys=100
                    )
                    pages.append(time.perf_counter() - t0)
                    if not trunc:
                        break
                    marker = entries[-1]["key"]
            s = sorted(pages)
            p99 = s[min(len(s) - 1, int(len(s) * 0.99))] * 1000
            return ops_per_s, p99

        # 1 shard vs N shards: the hot single omap object vs the
        # hash-spread shard set.  Interleaved best-of-trials (the
        # measure_mesh idiom): single-core CI noise swings one trial
        # by ±20%, which would randomly invert a one-shot curve
        ops_1 = ops_n = 0.0
        list_p99_1 = list_p99_n = float("inf")
        for trial in range(3):
            o1, l1 = fill_rate(f"b1_{trial}", 1)
            on, ln = fill_rate(f"bN_{trial}", shards_hi)
            ops_1, list_p99_1 = max(ops_1, o1), min(list_p99_1, l1)
            ops_n, list_p99_n = max(ops_n, on), min(list_p99_n, ln)
        _log(
            f"rgw_index: {ops_1:.0f} index ops/s @1 shard → "
            f"{ops_n:.0f} @{shards_hi} shards ({n_threads} writers, "
            "best of 3, GIL-shared mount); listing p99 "
            f"{list_p99_1:.1f} → {list_p99_n:.1f} ms"
        )

        # online reshard under load: writers keep hammering while
        # the bucket reshards 1→4; stall = worst put latency seen
        gw.create_bucket("live")
        for i in range(240):
            gw.put_object("live", f"seed{i:04d}", b"y" * 64)
        stop = _threading.Event()
        lats: list[float] = []
        lock = _threading.Lock()
        oracle: dict[int, dict] = {}
        errors: list[str] = []

        def hammer(t: int):
            mine: dict = {}
            i = 0
            try:
                while not stop.is_set():
                    key = f"w{t}-{i % 40:02d}"
                    t0 = time.perf_counter()
                    if i % 6 == 5 and key in mine:
                        gw.delete_object("live", key)
                        mine.pop(key)
                    else:
                        gw.put_object("live", key, b"z" * 64)
                        mine[key] = True
                    dt = time.perf_counter() - t0
                    with lock:
                        lats.append(dt)
                    i += 1
            except Exception as e:  # noqa: BLE001 — verdict below
                errors.append(f"{type(e).__name__}: {e}")
            oracle[t] = mine

        threads = [
            _threading.Thread(target=hammer, args=(t,), daemon=True)
            for t in range(n_threads)
        ]
        for th in threads:
            th.start()
        time.sleep(0.5)
        st = gw.bucket_reshard("live", 4)
        time.sleep(0.5)
        stop.set()
        for th in threads:
            th.join(timeout=30)
        expect = {f"seed{i:04d}" for i in range(240)}
        for mine in oracle.values():
            expect.update(mine)
        listed, marker = set(), ""
        while True:
            entries, trunc = gw.list_objects(
                "live", marker=marker, max_keys=500
            )
            listed.update(e["key"] for e in entries)
            if not trunc:
                break
            marker = entries[-1]["key"]
        stall_ms = max(lats) * 1000 if lats else 0.0
        _log(
            f"rgw_reshard: 1→4 shards in {st['duration_s']}s over "
            f"{st['entries']} entries, worst client write stall "
            f"{stall_ms:.0f}ms, lost={len(expect - listed)} "
            f"phantom={len(listed - expect)} errors={len(errors)}"
        )
        out = {
            "rgw_index": {
                "writers": n_threads,
                "objects": n_objs,
                "curve": [
                    {
                        "shards": 1,
                        "ops_per_s": round(ops_1, 1),
                        "list_p99_ms": round(list_p99_1, 2),
                    },
                    {
                        "shards": shards_hi,
                        "ops_per_s": round(ops_n, 1),
                        "list_p99_ms": round(list_p99_n, 2),
                    },
                ],
                "reshard": {
                    "from_shards": 1,
                    "to_shards": 4,
                    "entries": st["entries"],
                    "passes": st["passes"],
                    "duration_s": st["duration_s"],
                    "stall_ms": round(stall_ms, 1),
                    "ops_during": len(lats),
                    "lost": len(expect - listed),
                    "phantom": len(listed - expect),
                    "writer_errors": errors,
                },
            },
            # flat regression surfaces (the trajectory keys)
            "rgw_index_ops_per_s": {
                "1": round(ops_1, 1),
                str(shards_hi): round(ops_n, 1),
            },
            "rgw_reshard_stall_ms": round(stall_ms, 1),
        }
        return out
    finally:
        # teardown on EVERY path: a section failure must not leak
        # the gateway workers / client connections into the bench
        # sections that follow
        if gw is not None:
            gw.shutdown()
        if r is not None:
            r.shutdown()
        c.shutdown()


# the bench's own crash writer: a real child process storming 4k
# writes through WALStore(BlockStore) with a throttled drain, printing
# each oid AFTER its ack — the oracle the post-SIGKILL remount must
# reproduce byte-for-byte
_WAL_KILL_WRITER = """
import sys
from ceph_tpu.store import BlockStore, Transaction, WALStore
w = WALStore(BlockStore(sys.argv[1], sync=False), sys.argv[2],
             drain_delay=0.2)
w.queue_transaction(Transaction().create_collection("c"))
print("ready", flush=True)
i = 0
while True:
    oid = f"o{i}"
    w.queue_transaction(Transaction().write(
        "c", oid, 0, (i % 256).to_bytes(1, "little") * 4096))
    print(oid, flush=True)
    i += 1
"""


def measure_wal() -> dict:
    """WAL-fronted object store (ROADMAP open item 5): 4k small-write
    IOPS and p99 commit latency for the synchronous store (every
    commit pays its own fsync) vs the WAL front (commit = group
    log append, one fsync per barrier, apply deferred), the measured
    group-commit occupancy, and a SIGKILL-mid-storm kill-replay
    verdict (acked oracle vs remount, byte-identical).  Entirely
    CPU-side."""
    import shutil as _shutil
    import signal as _signal
    import subprocess as _subprocess
    import tempfile as _tempfile
    import threading as _threading

    from ceph_tpu.store import BlockStore, Transaction, WALStore

    n_threads = 4
    n_each = 120
    obj = 4096
    workdir = _tempfile.mkdtemp(prefix="bench-wal-")

    def storm(store) -> tuple[float, float]:
        """IOPS + p99 commit latency for n_threads × n_each 4k
        writes of unique objects through ``queue_transaction``."""
        store.queue_transaction(
            Transaction().create_collection("c")
        )
        lats: list[float] = []
        lock = _threading.Lock()

        def writer(t: int):
            mine = []
            for i in range(n_each):
                txn = Transaction().write(
                    "c", f"o{t}_{i}", 0, bytes([1 + t]) * obj
                )
                t0 = time.perf_counter()
                store.queue_transaction(txn)
                mine.append(time.perf_counter() - t0)
            with lock:
                lats.extend(mine)

        threads = [
            _threading.Thread(target=writer, args=(t,))
            for t in range(n_threads)
        ]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        s = sorted(lats)
        p99 = s[min(len(s) - 1, int(len(s) * 0.99))] * 1000
        return len(lats) / wall, p99

    try:
        # interleaved best-of-trials (the measure_mesh idiom): CI
        # noise swings one fsync-bound trial enough to invert a
        # one-shot comparison
        sync_iops = wal_iops = 0.0
        sync_p99 = wal_p99 = float("inf")
        occupancy = 1.0
        for trial in range(3):
            sync_store = BlockStore(
                os.path.join(workdir, f"sync{trial}"), sync=True
            )
            try:
                i1, p1 = storm(sync_store)
            finally:
                sync_store.close()
            w = WALStore(
                BlockStore(
                    os.path.join(workdir, f"walb{trial}"),
                    sync=False,
                ),
                os.path.join(workdir, f"wal{trial}"),
            )
            try:
                i2, p2 = storm(w)
                w.flush()
                d = w.wal_perf.dump()
                g = d["l_os_wal_group_records"]
                if i2 > wal_iops and g["avgcount"]:
                    occupancy = g["sum"] / g["avgcount"]
            finally:
                w.close()
            sync_iops, sync_p99 = max(sync_iops, i1), min(sync_p99, p1)
            wal_iops, wal_p99 = max(wal_iops, i2), min(wal_p99, p2)
        _log(
            f"wal: 4k small writes {sync_iops:.0f} IOPS sync → "
            f"{wal_iops:.0f} IOPS WAL ({n_threads} writers, best of "
            f"3); commit p99 {sync_p99:.2f} → {wal_p99:.2f} ms; "
            f"group occupancy {occupancy:.1f} records/barrier"
        )

        # kill-replay verdict: SIGKILL a child mid-storm, remount its
        # dirs, and require every acked oid byte-identical
        bs = os.path.join(workdir, "kill-bs")
        wd = os.path.join(workdir, "kill-wal")
        pr = _subprocess.Popen(
            [sys.executable, "-c", _WAL_KILL_WRITER, bs, wd],
            stdout=_subprocess.PIPE, text=True,
        )
        try:
            assert pr.stdout.readline().strip() == "ready"
            acked = [
                pr.stdout.readline().strip() for _ in range(40)
            ]
        finally:
            pr.send_signal(_signal.SIGKILL)
            pr.wait(10)
        w = WALStore(BlockStore(bs, sync=False), wd)
        try:
            lost = sum(
                1
                for oid in acked
                if w.read("c", oid)
                != (int(oid[1:]) % 256).to_bytes(1, "little") * obj
            )
            replayed = w.replayed_records
        finally:
            w.close()
        verdict = {
            "acked": len(acked),
            "replayed": replayed,
            "lost": lost,
            "byte_identical": lost == 0,
        }
        _log(
            f"wal_kill_replay: {len(acked)} acked, {replayed} "
            f"records replayed at remount, lost={lost}"
        )
        return {
            "wal": {
                "writers": n_threads,
                "writes": n_threads * n_each,
                "object_bytes": obj,
                "sync_iops": round(sync_iops, 1),
                "wal_iops": round(wal_iops, 1),
                "sync_commit_p99_ms": round(sync_p99, 3),
                "wal_commit_p99_ms": round(wal_p99, 3),
                "group_occupancy": round(occupancy, 2),
                "kill_replay": verdict,
            },
            # flat regression surfaces (the trajectory keys)
            "wal_small_write_iops": round(wal_iops, 1),
            "wal_commit_p99_ms": round(wal_p99, 3),
            "wal_replay_records": replayed,
        }
    finally:
        _shutil.rmtree(workdir, ignore_errors=True)


# worker child for measure_procs: one self-contained workload copy
# (or `copies` thread-copies for the in-process GIL baseline) behind
# a ready/go stdin barrier, so every worker's measurement window
# overlaps.  Prints "ready", blocks on stdin, measures `duration`
# seconds, prints "count <ops>".
_PROC_WORKER = r"""
import sys, threading, time

mode, copies, duration = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
counts = [0] * copies

if mode == "msgr":
    from ceph_tpu.msg import Messenger, MPing
    from ceph_tpu.msg.messenger import Dispatcher

    class Echo(Dispatcher):
        def ms_dispatch(self, conn, msg):
            if isinstance(msg, MPing) and not msg.is_reply:
                conn.send(MPing(tid=msg.tid, from_osd=0,
                                stamp=msg.stamp, is_reply=True))
                return True
            return False

    srv = Messenger("w-srv")
    srv.add_dispatcher(Echo())
    srv.bind()
    cli = Messenger("w-cli")
    conns = [cli.connect(*srv.bound_addr) for _ in range(copies)]

    def run(i):
        end = time.perf_counter() + duration
        n = 0
        while time.perf_counter() < end:
            conns[i].call(MPing(stamp=1.0), timeout=10.0)
            n += 1
        counts[i] = n
elif mode == "index":
    from test_osd_daemon import MiniCluster
    from ceph_tpu.rados import Rados
    from ceph_tpu.rgw import RGW

    c = MiniCluster()
    for i in range(3):
        c.start_osd(i)
    c.wait_active()
    r = Rados("w-idx").connect(*c.mon_addr)
    r.pool_create("pb", pg_num=8, size=2)
    gw = RGW(r.open_ioctx("pb"), max_objs_per_shard=0)
    recs = []
    for i in range(copies):
        gw.create_bucket(f"b{i}", shards=8)
        recs.append(gw._bucket_rec(f"b{i}"))
    ent = {"size": 64, "etag": "0" * 32, "mtime": 0.0, "owner": None,
           "acl": {"owner": None, "grants": []}}

    def run(i):
        end = time.perf_counter() + duration
        n = 0
        while time.perf_counter() < end:
            gw.index.set_entry(f"b{i}", f"o{n % 500:05d}", ent,
                               rec=recs[i])
            n += 1
        counts[i] = n
else:
    raise SystemExit(f"unknown mode {mode!r}")

print("ready", flush=True)
sys.stdin.readline()
threads = [threading.Thread(target=run, args=(i,)) for i in range(copies)]
for t in threads:
    t.start()
for t in threads:
    t.join()
print("count", sum(counts), flush=True)
# skip interpreter teardown: a loaded 1-core box can take >30s to
# join a mini-cluster's threads, and the parent only needs the count
import os
os._exit(0)
"""


def measure_procs() -> dict:
    """Multi-process scaling plane (ISSUE 19): aggregate messenger
    messages/s and sharded-index ops/s at 1/2/4/8 worker PROCESSES,
    against an in-process baseline running the same four workload
    copies as THREADS — the honest GIL comparison the in-process
    curves (measure_msgr, measure_rgw_index) cannot make.  Entirely
    CPU-side; every child pins JAX_PLATFORMS=cpu."""
    import os as _os
    import pathlib
    import subprocess as _subprocess
    import sys as _sys

    try:
        cores = len(_os.sched_getaffinity(0))
    except AttributeError:
        cores = _os.cpu_count() or 1
    root = pathlib.Path(__file__).parent
    env = dict(_os.environ)
    env["PYTHONPATH"] = _os.pathsep.join(
        [str(root), str(root / "tests"),
         env.get("PYTHONPATH", "")]
    ).rstrip(_os.pathsep)
    env["JAX_PLATFORMS"] = "cpu"

    def rung(mode: str, n_procs: int, copies: int = 1,
             duration: float = 1.5) -> float:
        """Aggregate ops/s across n_procs workers whose measurement
        windows overlap (ready/go barrier)."""
        procs = [
            _subprocess.Popen(
                [_sys.executable, "-c", _PROC_WORKER, mode,
                 str(copies), str(duration)],
                stdin=_subprocess.PIPE, stdout=_subprocess.PIPE,
                env=env, text=True,
            )
            for _ in range(n_procs)
        ]
        try:
            for p in procs:
                line = p.stdout.readline().strip()
                if line != "ready":
                    raise RuntimeError(
                        f"procs worker died during boot: {line!r}"
                    )
            for p in procs:
                p.stdin.write("go\n")
                p.stdin.flush()
            total = 0
            for p in procs:
                parts = p.stdout.readline().split()
                if parts[:1] != ["count"]:
                    raise RuntimeError(
                        f"procs worker died mid-run: {parts!r}"
                    )
                total += int(parts[1])
            for p in procs:
                p.wait(timeout=30)
            return total / duration
        finally:
            for p in procs:
                if p.poll() is None:
                    p.kill()
                    p.wait(timeout=10)

    rungs = (1, 2, 4, 8)
    msgr_curve = []
    index_curve = []
    for n in rungs:
        msgr_curve.append(
            {"procs": n, "msgs_per_s": round(rung("msgr", n), 1)}
        )
        index_curve.append(
            {"procs": n, "ops_per_s": round(rung("index", n), 1)}
        )
    # in-process baseline: the SAME four workload copies as threads
    # in one interpreter — what 4 processes must beat to prove the
    # scaling is real and not workload slack
    msgr_inproc = rung("msgr", 1, copies=4)
    index_inproc = rung("index", 1, copies=4)
    msgr_4 = msgr_curve[2]["msgs_per_s"]
    index_4 = index_curve[2]["ops_per_s"]
    msgr_speedup = round(msgr_4 / max(msgr_inproc, 1e-9), 2)
    index_speedup = round(index_4 / max(index_inproc, 1e-9), 2)
    for row in msgr_curve:
        _log(
            f"procs msgr @{row['procs']} processes: "
            f"{row['msgs_per_s']:.0f} msg/s aggregate"
        )
    for row in index_curve:
        _log(
            f"procs index @{row['procs']} processes: "
            f"{row['ops_per_s']:.0f} ops/s aggregate"
        )
    _log(
        f"procs speedup @4 processes vs 4 threads in-process: msgr "
        f"{msgr_speedup}x ({msgr_inproc:.0f} → {msgr_4:.0f}), index "
        f"{index_speedup}x ({index_inproc:.0f} → {index_4:.0f}) "
        f"on {cores} core(s)"
    )
    if cores < 4:
        # the honest caveat the artifact must carry: with fewer
        # cores than workers, multi-process CANNOT beat the GIL
        # baseline — the curve measures scheduler overhead, not the
        # runtime.  On a >=4-core host the same section shows the
        # real scaling.
        _log(
            f"procs: only {cores} core(s) visible — speedup is "
            "core-limited, not a runtime verdict"
        )
    return {
        "procs": {
            "cores": cores,
            "msgr": msgr_curve,
            "index": index_curve,
            "msgr_inproc_4t_msgs_per_s": round(msgr_inproc, 1),
            "index_inproc_4t_ops_per_s": round(index_inproc, 1),
        },
        # flat regression surfaces (the trajectory keys):
        # the 4-process rung is the acceptance point
        "procs_cores": cores,
        "procs_msgr_msgs_per_s": msgr_4,
        "procs_index_ops_per_s": index_4,
        "procs_msgr_speedup": msgr_speedup,
        "procs_index_speedup": index_speedup,
    }


def measure_thrash() -> dict:
    """qa thrasher section (ISSUE 20): one short fixed-seed composed-
    fault schedule against a live in-process 3-OSD cluster under the
    consistency oracle — the artifact carries the weather survived
    (events applied, client ops checked, violations: must be 0) and
    the wall cost of the run.  Entirely CPU-side."""
    import time as _time

    from ceph_tpu.qa import Schedule
    from ceph_tpu.qa.thrasher import Thrasher

    seed = 20260807
    sched = Schedule.from_seed(seed, duration=12.0, osds=3)
    t0 = _time.monotonic()
    thr = Thrasher(sched, convergence_timeout=45.0)
    report = thr.run()
    wall = _time.monotonic() - t0
    _log(
        f"thrash seed={seed}: {report['events_applied']}/"
        f"{report['events']} events, {report['ops']} client ops, "
        f"{len(report['violations'])} violations, "
        f"converged={report['converged']}, {wall:.1f}s wall"
    )
    return {
        "thrash_seed": seed,
        "thrash_events": report["events"],
        "thrash_events_applied": report["events_applied"],
        "thrash_ops": report["ops"],
        "thrash_op_errors": report["op_errors"],
        "thrash_violations": len(report["violations"]),
        "thrash_converged": report["converged"],
        "thrash_wall_s": round(wall, 1),
    }


def measure_recovery(on_tpu: bool) -> dict:
    """Recovery-storm plane (ROADMAP open item 2): decode-from-
    survivors rebuild throughput before/after the coalesced batched
    dispatch, recovery-read fan-in before/after LRC locality
    (MEASURED from minimum_to_decode-driven survivor reads, not
    claimed), and — through tests/chaos.py's kill-OSD-at-80%-full
    scenario — the client p99 + gold-class mclock floor verdict
    while a live rebuild storms."""
    from ceph_tpu.store.ec_store import ECStore

    profile = {
        "plugin": "jerasure", "technique": "reed_sol_van",
        "k": str(K), "m": str(M), "w": str(W),
    }
    if on_tpu:
        profile["backend"] = "jax"
    obj_size = OBJECT_SIZE if on_tpu else 256 << 10
    nobj = 32 if on_tpu else 12
    rng = np.random.default_rng(23)
    dead = 2  # the rebuilt position (a data shard: the worst case)

    def build(prof, plugin="jerasure", n=nobj):
        ecs = ECStore(plugin=plugin, profile=prof)
        datas = {}
        for i in range(n):
            d = rng.integers(
                0, 256, size=obj_size, dtype=np.uint8
            ).tobytes()
            datas[f"rec{i}"] = d
            ecs.put(f"rec{i}", d)
        return ecs, datas

    ecs, datas = build({k: v for k, v in profile.items() if k != "plugin"})
    names = list(datas)

    # identity gate: the batched rebuild must land byte-identical
    # shards to the per-op path before any number is reported
    probe = names[:3]
    for nm in probe:
        ecs.lose_shard(nm, dead)
    per_op_shards = {}
    for nm in probe:
        # reconstruct WITHOUT writing: the shard stays lost, so the
        # batched pass below rebuilds the very same objects
        data, _reads, meta = ecs.reconstruct_shard(nm, dead)
        per_op_shards[nm] = data
    results, fb, _stats = ecs.reconstruct_shards_batch(probe, dead)
    if fb:
        raise AssertionError(f"batched rebuild fell back: {fb}")
    for nm in probe:
        payload, _meta = results[nm]
        got = payload.host() if hasattr(payload, "host") else bytes(payload)
        if got != per_op_shards[nm]:
            raise AssertionError(
                "batched rebuild disagrees with per-op rebuild"
            )
    for nm in probe:
        ecs.recover_shard(nm, dead)

    def lose_all():
        for nm in names:
            ecs.lose_shard(nm, dead)

    # flight-recorder attribution for the measured rebuilds below
    # (the identity-gate probe above is excluded on purpose)
    from ceph_tpu.ops.profiler import breakdown, dispatch_profiler

    disp_before = dispatch_profiler().totals()

    # per-op rebuild (the pre-batching regime: one decode per object)
    lose_all()
    t0 = time.perf_counter()
    for nm in names:
        ecs.recover_shard(nm, dead)
    per_op_dt = time.perf_counter() - t0
    per_op_gbs = nobj * obj_size / per_op_dt / 2**30

    # batched rebuild: ONE coalesced decode-from-survivors dispatch
    lose_all()
    t0 = time.perf_counter()
    stats = ecs.recover_objects_batch(names, dead)
    batched_dt = time.perf_counter() - t0
    batched_gbs = nobj * obj_size / batched_dt / 2**30
    k8_fanin = stats["survivor_shards"] / max(stats["objects"], 1)
    for nm, d in datas.items():
        if ecs.get(nm) != d:
            raise AssertionError(f"{nm} corrupted by batched rebuild")
    _log(
        f"recovery[k{K}m{M}]: per-op {per_op_gbs:.3f} GB/s, batched "
        f"{batched_gbs:.3f} GB/s ({nobj}x{obj_size >> 10}KB, fan-in "
        f"{k8_fanin:.1f} shards/object)"
    )

    # LRC locality: the SAME rebuild reads k_local << k survivors
    lrc_prof = {"k": "6", "m": "3", "l": "3"}
    if on_tpu:
        lrc_prof["backend"] = "jax"
    lecs, ldatas = build(lrc_prof, plugin="lrc", n=nobj // 2)
    lnames = list(ldatas)
    for nm in lnames:
        lecs.lose_shard(nm, 0)
    t0 = time.perf_counter()
    lstats = lecs.recover_objects_batch(lnames, 0)
    lrc_dt = time.perf_counter() - t0
    lrc_fanin = lstats["survivor_shards"] / max(lstats["objects"], 1)
    for nm, d in ldatas.items():
        if lecs.get(nm) != d:
            raise AssertionError(f"lrc {nm} corrupted by rebuild")
    _log(
        f"recovery[lrc k6m3 l3]: fan-in {lrc_fanin:.1f} "
        f"shards/object vs {k8_fanin:.1f} without locality, "
        f"{len(lnames) * obj_size / lrc_dt / 2**30:.3f} GB/s"
    )

    # where the rebuilds' device time went (contractual keys — emit
    # as backend=cpu zeros/host walls on a JAX_PLATFORMS=cpu run too)
    disp = breakdown(
        disp_before, dispatch_profiler().totals(),
        backend="jax-tpu" if on_tpu else "cpu",
    )
    out = {
        "recovery": {
            "dispatch": disp,
            "profile": f"k{K}m{M}",
            "objects": nobj,
            "object_bytes": obj_size,
            "per_op_GBps": round(per_op_gbs, 3),
            "batched_GBps": round(batched_gbs, 3),
            "fanin_shards_per_object": round(k8_fanin, 2),
            "lrc": {
                "profile": "k6 m3 l3",
                "fanin_shards_per_object": round(lrc_fanin, 2),
                "read_bytes": lstats["read_bytes"],
                "GBps": round(
                    len(lnames) * obj_size / lrc_dt / 2**30, 3
                ),
            },
        },
        "recovery_batched_GBps": round(batched_gbs, 3),
        "recovery_lrc_fanin": round(lrc_fanin, 2),
    }

    # live storm: client p99 + the gold-class mclock floor while a
    # kill-OSD-at-80%-full rebuild drains (tests/chaos.py scenario —
    # CPU-side, in-process cluster; its own failure degrades to an
    # error marker instead of eating the section)
    try:
        import pathlib
        import sys as _sys

        _sys.path.insert(
            0, str(pathlib.Path(__file__).parent / "tests")
        )
        import chaos

        storm = chaos.scenario_kill_osd_at_fill()
        out["recovery"]["storm"] = storm
        out["recovery_client_p99_ms"] = storm["slo"]["storm_p99_ms"]
        out["recovery_floor_held"] = storm["slo"]["held"]
        # observability verdict (ISSUE 16): the storm was watchable —
        # the rebalance bar never regressed and the degraded count the
        # pgmap digest surfaced actually peaked nonzero
        out["recovery_progress_monotone"] = storm["progress_monotone"]
        out["recovery_observed_degraded_peak"] = storm["degraded_peak"]
    except Exception as e:  # noqa: BLE001 — the micro numbers above
        # still ship when the live-cluster storm dies under CI load
        import traceback

        traceback.print_exc()
        out["recovery"]["storm"] = {"error": f"{type(e).__name__}: {e}"}
        # top-level *_error: the run's exit code must say so too
        out["recovery_storm_error"] = out["recovery"]["storm"]["error"]
    return out


def measure_mesh(
    device_counts=None,
    pgs: int | None = None,
    batch: int | None = None,
    chunk: int | None = None,
    trials: int = 2,
) -> dict:
    """Multi-chip scaling, MEASURED: mappings/s and encode GB/s at
    1..N devices through the sharded execution plane (ops/mesh.py +
    osd/sharded_mapping.py), replacing the 8-core ParallelPGMapper
    extrapolation with a per-device curve.

    Two curves land in the JSON: ``curve`` is the raw best-of-trials
    aggregate throughput at exactly n devices, and ``envelope`` is its
    running max — the best aggregate observed at <= n devices, which
    is the monotone non-decreasing scaling headline (raw entries keep
    every measured dip; on shared-core virtual CPU meshes the raw
    curve is noisy by construction).

    Runs on whatever devices exist — real chips, or a
    ``--xla_force_host_platform_device_count`` virtual CPU mesh on a
    ``JAX_PLATFORMS=cpu`` run.  Workload knobs come from
    CEPH_TPU_BENCH_MESH_{COUNTS,PGS,BATCH,CHUNK} so the tier-1 CPU
    run finishes in seconds."""
    from ceph_tpu import gf
    from ceph_tpu.crush import jaxmap
    from ceph_tpu.ops import mesh as meshmod
    from ceph_tpu.ops.gf_matmul import matrix_to_device_bitmatrix
    from ceph_tpu.osd.sharded_mapping import sharded_batch_do_rule
    from ceph_tpu.tools.crushtool import build_hierarchy

    devs = meshmod.available_devices()
    out: dict = {"device_count": len(devs)}
    if not devs:
        out["error"] = "no devices initialize"
        return out
    out["platform"] = devs[0].platform
    on_tpu = devs[0].platform == "tpu"
    N = len(devs)

    def _env_int(name, default):
        try:
            return int(os.environ.get(name, "")) or default
        except ValueError:
            return default

    if device_counts is None:
        env = os.environ.get("CEPH_TPU_BENCH_MESH_COUNTS", "")
        if env:
            device_counts = [int(x) for x in env.split(",") if x]
        else:
            device_counts = list(range(1, N + 1))
    device_counts = sorted({min(max(int(c), 1), N) for c in device_counts})
    pgs = pgs or _env_int(
        "CEPH_TPU_BENCH_MESH_PGS", 1 << 17 if on_tpu else 1 << 11
    )
    batch = batch or _env_int(
        "CEPH_TPU_BENCH_MESH_BATCH", 64 if on_tpu else 16
    )
    chunk = chunk or _env_int(
        "CEPH_TPU_BENCH_MESH_CHUNK", 128 << 10 if on_tpu else 8 << 10
    )

    if on_tpu:
        m = build_hierarchy(CRUSH_OSDS, CRUSH_PER_HOST, CRUSH_HOSTS_PER_RACK)
    else:
        # CPU hierarchy, overridable ("osds:per_host[:hosts_per_rack]")
        # so the tier-1 CPU run compiles in seconds
        spec = os.environ.get("CEPH_TPU_BENCH_MESH_OSDS", "64:8:4")
        try:
            parts = [int(v) for v in spec.split(":")]
            m = build_hierarchy(
                parts[0],
                parts[1] if len(parts) > 1 else 8,
                parts[2] if len(parts) > 2 else 0,
            )
        except (ValueError, IndexError):
            m = build_hierarchy(64, 8, 4)
    cm = jaxmap.compile_map(m)
    matrix = gf.reed_sol_vandermonde_coding_matrix(K, M, W)
    bm = matrix_to_device_bitmatrix(matrix, W)
    rng = np.random.default_rng(13)
    stripes = rng.integers(0, 256, size=(batch, K, chunk), dtype=np.uint8)
    xs = np.arange(pgs, dtype=np.int64)
    enc_bytes = batch * K * chunk

    curve = []
    for n in device_counts:
        dmesh = meshmod.build_mesh(n)
        # warm: first call per device count compiles the sharded
        # programs; only replays are timed
        sharded_batch_do_rule(cm, 0, xs, CRUSH_REP, dmesh=dmesh)
        best_map = 0.0
        for _ in range(trials):
            t = _timed(
                lambda: sharded_batch_do_rule(
                    cm, 0, xs, CRUSH_REP, dmesh=dmesh
                )
            )
            best_map = max(best_map, pgs / t)
        meshmod.sharded_matrix_stripes(bm, stripes, W, dmesh)
        best_enc = 0.0
        for _ in range(trials):
            t = _timed(
                lambda: meshmod.sharded_matrix_stripes(
                    bm, stripes, W, dmesh
                )
            )
            best_enc = max(best_enc, enc_bytes / t / 2**30)
        curve.append(
            {
                "devices": n,
                "crush_mappings_per_sec": round(best_map),
                "ec_encode_GBps": round(best_enc, 3),
            }
        )
        _log(
            f"mesh[{n} dev]: {best_map:,.0f} mappings/s, "
            f"{best_enc:.3f} GB/s encode"
        )
    out["curve"] = curve
    out["workload"] = {"pgs": pgs, "ec_batch": batch, "ec_chunk": chunk}
    env_map, env_enc, envelope = 0.0, 0.0, []
    for c in curve:
        env_map = max(env_map, c["crush_mappings_per_sec"])
        env_enc = max(env_enc, c["ec_encode_GBps"])
        envelope.append(
            {
                "devices": c["devices"],
                "crush_mappings_per_sec": env_map,
                "ec_encode_GBps": env_enc,
            }
        )
    out["envelope"] = envelope
    return out


def _downscale_for_cpu() -> None:
    """Shrink the CRUSH config so the CPU emulation of the device
    kernel completes in seconds (the 10k-osd/1M-PG config is a TPU
    workload).  Only for a run whose caller set JAX_PLATFORMS=cpu;
    main() says so in the line (``config: cpu_downscaled``)."""
    global CRUSH_OSDS, CRUSH_PER_HOST, CRUSH_HOSTS_PER_RACK
    global CRUSH_PGS, CRUSH_DEVICE_BATCH
    CRUSH_OSDS = 400
    CRUSH_PER_HOST = 20
    CRUSH_HOSTS_PER_RACK = 5
    CRUSH_PGS = 1 << 13
    CRUSH_DEVICE_BATCH = 1 << 12


def main(argv=None) -> int:
    """One parseable JSON line on stdout.  The backend is a TPU, or
    the CPU when the caller set JAX_PLATFORMS=cpu (every measured key
    then sits under ``cpu_backend`` and no device metric name is
    written).  A section that fails records ``<section>_error`` and
    the others still run; the exit code is non-zero if any did.

    ``--mesh`` runs ONLY the multi-chip scaling section
    (measure_mesh) and emits its curve as the line — the MULTICHIP /
    BENCH weak-#5 artifact; the full run also embeds the mesh section
    whenever more than one device exists."""
    import pathlib

    argv = sys.argv[1:] if argv is None else argv
    mesh_only = "--mesh" in argv
    slo_only = "--slo" in argv

    if slo_only:
        # SLO traffic-simulator run (tests/simulator.py): per-class
        # p50/p99 latency under baseline + fault weather + overload,
        # with the mclock reservation-floor verdict.  Entirely
        # CPU-side (live in-process cluster, MemStore, no device
        # kernels on the hot path); the line ships even when a
        # scenario dies, and the exit code says that it did.
        out = {
            "metric": "slo_worst_class_p99_ms",  # worst per-class
            # baseline p99 — the headline regression surface; the
            # per-class curves live in out["slo"]
            "value": None,
            "unit": "ms",
        }
        try:
            sys.path.insert(
                0,
                str(pathlib.Path(__file__).parent / "tests"),
            )
            import simulator

            suite = simulator.run_suite(fast="--fast" in argv)
            out["slo"] = suite
            baseline = next(
                (
                    c
                    for c in suite["conditions"]
                    if c.get("condition") == "baseline"
                ),
                None,
            )
            if baseline:
                worst = max(
                    (
                        row.get("p99_ms", 0.0)
                        for row in baseline["classes"].values()
                    ),
                    default=None,
                )
                out["value"] = worst
            out["reservation_floor_held"] = bool(
                suite.get("reservation_floor", {}).get("held")
            )
        except Exception as e:  # noqa: BLE001 — the line is the
            # contract even when the simulator dies
            import traceback

            traceback.print_exc()
            out["error"] = f"{type(e).__name__}: {e}"
        return _emit(out)

    out = {
        "metric": (
            "mesh_scaling" if mesh_only else "ec_encode_k8m3_1M_GBps"
        ),
        "value": None,
        "unit": "GB/s",
    }
    try:
        from ceph_tpu.common.compile_cache import (
            configure_compile_cache,
        )

        configure_compile_cache()

        from ceph_tpu import gf

        matrix = gf.reed_sol_vandermonde_coding_matrix(K, M, W)
        be = _backend()  # raises unless TPU, or CPU by request
        out["backend"] = be
        on_tpu = be == "tpu"
        if not on_tpu:
            # a CPU run measures the CPU: no value under a device
            # metric's name (_emit moves the rest under cpu_backend)
            out.update(
                metric="cpu_backend_run", unit=None,
                config="cpu_downscaled",
            )
            _downscale_for_cpu()

        if mesh_only:
            try:
                out["mesh"] = measure_mesh()
                curve = out["mesh"].get("envelope") or []
                if curve and on_tpu:
                    out["value"] = curve[-1]["ec_encode_GBps"]
            except Exception as e:  # noqa: BLE001 — the line is the
                # contract even when the mesh section dies
                import traceback

                traceback.print_exc()
                out["error"] = f"{type(e).__name__}: {e}"
            return _emit(out)

        cpu = measure_cpu(matrix, iters=8)
        out["cpu_oracle_GBps"] = round(cpu, 3)
        if on_tpu:
            rates = {
                kern: measure_device(
                    matrix, batch=32, iters=10, kernel=kern
                )
                for kern in ("packed", "bitplane")
            }
            kern, gbs = max(rates.items(), key=lambda kv: kv[1])
            out["kernel_rates"] = {
                k: round(v, 2) for k, v in rates.items()
            }
            e2e = measure_e2e(matrix)
            if e2e is not None:
                out.update(e2e)
            out.update(
                value=round(gbs, 3),
                vs_baseline=round(gbs / ISAL_CLASS_GBPS, 2),
                kernel=kern,
            )
        else:
            out["cpu_bitplane_kernel_GBps"] = round(
                measure_cpu_kernel(matrix), 3
            )
        # messenger-plane curve: entirely CPU-side
        try:
            out.update(measure_msgr())
        except Exception as e:  # noqa: BLE001 — one section must not
            # eat the artifact (own key: this section is CPU-side, a
            # failure here says nothing about the device backend)
            import traceback

            traceback.print_exc()
            out["msgr_error"] = f"{type(e).__name__}: {e}"
        # sharded bucket-index curve + reshard-under-load verdict:
        # CPU-side like msgr — always attempted, never eats the line
        try:
            out.update(measure_rgw_index())
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            out["rgw_index_error"] = f"{type(e).__name__}: {e}"
        # WAL small-write curve + kill-replay verdict: CPU-side like
        # msgr — always attempted, never eats the artifact line
        try:
            out.update(measure_wal())
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            out["wal_error"] = f"{type(e).__name__}: {e}"
        # multi-process scaling curves (ISSUE 19): the first numbers
        # that can exceed one core — CPU-side, section-isolated
        try:
            out.update(measure_procs())
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            out["procs_error"] = f"{type(e).__name__}: {e}"
        # chaos thrash under the consistency oracle (ISSUE 20): one
        # short fixed-seed schedule — violations must stay 0
        try:
            out.update(measure_thrash())
        except Exception as e:  # noqa: BLE001
            import traceback

            traceback.print_exc()
            out["thrash_error"] = f"{type(e).__name__}: {e}"
        # families BEFORE the big crush compiles (the family entries
        # are a BASELINE deliverable).  Each section fails alone: its
        # error is recorded, the others still run, the exit code
        # says one failed
        from ceph_tpu.ops.mesh import device_count as _mesh_devices

        sections = [
            (
                "e2e_batched",
                lambda: measure_e2e_batched(on_tpu),
            ),
            (
                "ec_families",
                lambda: measure_ec_families(fast=not on_tpu),
            ),
            ("crush", measure_crush),
            ("scrub", measure_scrub),
            (
                "recovery",
                lambda: measure_recovery(on_tpu),
            ),
        ]
        if _mesh_devices() > 1:
            # multi-chip host (or virtual mesh): the scaling curve
            # is part of the standard artifact
            sections.append(("mesh", measure_mesh))
        for section, fn in sections:
            try:
                result = fn()
                if section == "ec_families":
                    out["ec_families"] = result
                elif section == "mesh":
                    out["mesh"] = result
                else:
                    out.update(result)
            except Exception as e:  # noqa: BLE001
                import traceback

                traceback.print_exc()
                out[f"{section}_error"] = f"{type(e).__name__}: {e}"
        _log(
            f"baseline note: vs ISA-L-class ~{ISAL_CLASS_GBPS} "
            "GB/s/core estimate (real jerasure/ISA-L: ~5-10 "
            "GB/s/core; reference publishes no numbers); measured "
            f"numpy oracle {cpu:.3f} GB/s"
        )
    except Exception as e:  # noqa: BLE001 — the result line is the
        # contract; a crash becomes a parseable error entry
        import traceback

        traceback.print_exc()
        out["error"] = f"{type(e).__name__}: {e}"
    return _emit(out)


_LINE_KEYS = ("metric", "value", "unit", "backend", "config")


def _emit(out: dict) -> int:
    """Print the line; return the exit code (1 if any section
    recorded an error).  A CPU run's measurements move under
    ``cpu_backend`` so no top-level key carries a device metric's
    name."""
    failed = sorted(
        k for k in out if k == "error" or k.endswith("_error")
    )
    if out.get("backend") == "cpu":
        out = {
            **{k: out[k] for k in _LINE_KEYS if k in out},
            **{k: out[k] for k in failed},
            "cpu_backend": {
                k: v for k, v in out.items()
                if k not in _LINE_KEYS and k not in failed
            },
        }
    # kernel-behavior snapshot (compile-cache hit ratio, per-group
    # call/byte totals): HOW the kernels ran, not just the headline
    from ceph_tpu.ops.kernel_stats import kernel_stats

    out["kernel_stats"] = kernel_stats().snapshot()
    print(json.dumps(out))
    if failed:
        _log(f"bench: sections failed: {failed}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
