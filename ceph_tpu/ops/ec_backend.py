"""The ``jax`` erasure-code backend: device dispatch of region math.

Slots under every code family through the same seam the reference uses
for gf-complete/isa-l (ceph_tpu.ec.backend); numpy in, numpy out, with
jit-compiled mod-2 matmuls in between.  The first call for a given
(shape, matrix-shape, w) pair compiles; later calls replay the cached
executable — the analog of the reference's one-time ec_init_tables SIMD
table expansion (src/erasure-code/isa/ErasureCodeIsa.cc:402).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from ..common import tracing
from ..ec.backend import _host_row as _row_u8
from ..ec.backend import register_backend
from ..ec.interface import ErasureCodeError
from ..layout import fold_stripes
from . import mesh, packed_gf
from .gf_matmul import (
    bitmatrix_packet_regions,
    gf_matrix_regions,
    gf_matrix_stripes,
    matrix_to_device_bitmatrix,
)
from .kernel_stats import DECODE_PACKED_CALLS, FOLD_OVERLAPPED_NS, kernel_stats
from .profiler import dispatch_profiler, record_pad

# the largest r·s the packed decode kernel takes: its work is r·s·8
# masked XORs a word on the VPU, where a larger matrix (CLAY's 64 x 176
# repair) is MXU work for the bitplane program
PACKED_DECODE_TERMS = 64


def _on_tpu() -> bool:
    # a backend that cannot initialise raises here: the caller asked
    # for the device (backend=jax), so that is an error, not "no TPU"
    import jax

    return jax.default_backend() == "tpu"


def _shard_rows(stripes: np.ndarray) -> list[np.ndarray]:
    """(B, n, chunk) host stripes → the n shards, 1-D and contiguous:
    one copy when B > 1; one stripe is its own fold, so its rows are
    views."""
    return list(np.ascontiguousarray(fold_stripes(stripes)))


def _fold_buffer(stripes: np.ndarray, k: int, n: int) -> np.ndarray:
    """An empty (k, n) uint8 array for the fold of ``stripes``, started
    half a page off the input within a page.  Where a copy's
    destination lies 1 to 31 bytes above its source modulo 4096 (16
    is what two neighbouring heap blocks give) the chip host's memcpy
    runs at a quarter of its speed — the 64 MiB fold 24 ms for 6:
    PERF.md section 6, PR 30 — and exactly 0 costs a third more on
    another CPU.  Chunks and rows of whole pages keep the half page
    for every piece of the fold; one page of slack buys it."""
    raw = np.empty(k * n + 4096, dtype=np.uint8)
    lead = (stripes.ctypes.data + 2048 - raw.ctypes.data) % 4096
    return raw[lead : lead + k * n].reshape(k, n)


@functools.lru_cache(maxsize=512)
def _host_bitmatrix(key: bytes, shape: tuple, w: int):
    """Host-side bitmatrix + packed-kernel eligibility, cached per
    matrix (no device upload, no per-call supports() recompute)."""
    from .. import gf

    mat = np.frombuffer(key, dtype=np.int64).reshape(shape)
    bm = gf.jerasure_bitmatrix(mat, w)
    return bm, packed_gf.supports(bm, w)


def _packed_bm(matrix: np.ndarray, w: int, region_bytes: int):
    """The host bitmatrix the packed-lane kernel unrolls, or None where
    the bitplane program runs instead: off the TPU, at another word
    size, for regions that are not whole 32-bit words, or for a matrix
    the kernel does not support."""
    if not (w == 8 and _on_tpu() and region_bytes % 4 == 0):
        return None
    mat = np.ascontiguousarray(matrix, dtype=np.int64)
    bm_np, ok = kernel_stats().counted_cache_call(
        _host_bitmatrix, mat.tobytes(), mat.shape, w
    )
    return bm_np if ok else None


class JaxBackend:
    name = "jax"

    def _dispatch(self, kind: str, **totals):
        """THE instrument of a dispatch site: one flight-recorder
        entry of ``kind`` (``totals``: its ``ops``, ``stripes`` and
        ``bytes_in``) whose commit also feeds the ``gf_matmul`` kernel
        counters (ops/profiler.py)."""
        return dispatch_profiler().dispatch(
            kind, backend=self.name, group="gf_matmul", **totals
        )

    def matrix_regions(
        self, matrix: np.ndarray, regions: np.ndarray, w: int
    ) -> np.ndarray:
        # np.asarray inside the timer forces the device sync, so the
        # recorded latency is the kernel, not the dispatch
        with kernel_stats().timed(
            "gf_matmul", bytes_in=regions.nbytes
        ) as kt:
            bm_np = _packed_bm(matrix, w, regions.shape[1])
            if bm_np is not None:
                out = packed_gf.packed_bitmatrix_regions(bm_np, regions)
            else:
                out = gf_matrix_regions(
                    matrix_to_device_bitmatrix(matrix, w),
                    jnp.asarray(regions),
                    w=w,
                )
            out = np.asarray(out)
            kt.bytes_out = out.nbytes
            return out

    def bitmatrix_regions(
        self,
        bm: np.ndarray,
        regions: np.ndarray,
        w: int,
        packetsize: int,
    ) -> np.ndarray:
        with kernel_stats().timed(
            "gf_bitmatrix", bytes_in=regions.nbytes
        ) as kt:
            out = np.asarray(
                bitmatrix_packet_regions(
                    jnp.asarray(bm, dtype=jnp.int8),
                    jnp.asarray(regions),
                    w=w,
                    packetsize=packetsize,
                )
            )
            kt.bytes_out = out.nbytes
            return out

    def matrix_stripe_shards(
        self, matrix: np.ndarray, stripes, w: int
    ) -> tuple[list[np.ndarray], list[np.ndarray]]:
        """One object's encode in ONE device dispatch, in shard form,
        what ``stripe.encode`` hands out: (B, k, chunk) → the k data
        shards and the m coding shards as host arrays, each 1-D and
        contiguous, B*chunk long (shard i is chunk i of every stripe,
        concatenated: the folded region layout).  One flight-recorder
        entry of kind ``ec_encode`` (ops 1, stripes B).  The packed
        path folds the input once and hands that copy out
        (:meth:`_packed_stripes`); the mesh and bitplane paths compute
        in stripe form and fold both sides at their edge.

        Device-array pipelines that want to keep results on-chip call
        ``ops.gf_matmul.gf_matrix_stripes`` (or
        ``ops.packed_gf.prebuilt_word_call``) directly instead."""
        stripes = np.ascontiguousarray(stripes, dtype=np.uint8)
        b, _k, chunk = stripes.shape
        with self._dispatch(
            "ec_encode", ops=1, stripes=b, bytes_in=stripes.nbytes
        ) as dp:
            # batch axis sharded across the device mesh when >1 device
            # exists and the batch is worth splitting — byte-identical
            # per-stripe math, just spread over chips (ops/mesh.py).
            # Checked BEFORE the packed fast path: a mesh batch takes
            # the bitplane program because the packed kernel folds the
            # batch into its byte axis, so there is no batch axis left
            # to shard.  Which of the two is faster is not measured
            # (ROADMAP S7)
            dmesh = mesh.default_mesh()
            on_mesh = dmesh is not None and b >= dmesh.n
            bm_np = None if on_mesh else _packed_bm(matrix, w, b * chunk)
            if bm_np is not None:
                data, coding = self._packed_stripes(dp, bm_np, stripes)
            else:
                bm = matrix_to_device_bitmatrix(matrix, w)
                dp.add_upload(stripes.nbytes)
                if on_mesh:
                    # upload/compute/sync all live inside the sharded
                    # helper; attribute its wall to compute
                    with dp.stage("compute"):
                        out = mesh.sharded_matrix_stripes(
                            bm, stripes, w, dmesh
                        )
                else:
                    with dp.stage("upload"):
                        dev = jnp.asarray(stripes)
                    with dp.stage("compute"):
                        odev = self._bitplane_dispatch(bm, dev, w)
                    with dp.stage("sync"):
                        out = np.asarray(odev)[:b]
                data, coding = _shard_rows(stripes), _shard_rows(out)
            dp.set_bytes_out(sum(c.nbytes for c in coding))
            return data, coding

    @staticmethod
    def _packed_stripes(dp, bm_np: np.ndarray, stripes: np.ndarray):
        """The packed-lane path of :meth:`matrix_stripe_shards`, the
        one stripes form of the packed kernel.  An encode makes ONE
        host copy of its input, the fold: row i of the fold is data
        shard i, and fetched row j is coding shard k+j as it arrives —
        nothing is stacked or laid out again.

        The upload's source is the caller's own buffer wherever the
        chunk is whole (8, 128) u32 tiles (``packed_gf.
        STRIPE_FORM_BYTES``: every ``stripe_unit`` a pool can have):
        ONE ``device_put`` of its stripe-form view, the kernel — which
        reads stripe form through its ``BlockSpec``s, so nothing is
        laid out again on the device either — and the fetch are issued
        first, and the fold runs under the link, for the answer alone.
        Any other chunk is folded first and goes up a row at a time as
        it is folded (shard form), the transfer draining under the
        rest of the fold.  ``l_tpu_ec_fold_overlapped_ns`` counts the
        fold's nanoseconds spent with this call's whole upload issued.

        Each stage is bracketed where it happens, the repeated ones
        accumulating: the ``device_put``s (``upload``), the kernel's
        issue (``compute``), the row copies (span ``ec_fold``, k a
        call), the fetch's start, the wait for and copy of the m
        result rows (``sync``), their views as bytes (span
        ``ec_unfold``)."""
        import jax

        b, k, chunk = stripes.shape
        # one stripe is its own fold: its rows are views of the input
        # and there is nothing to copy
        folded = (
            stripes.reshape(k, chunk)
            if b == 1
            else _fold_buffer(stripes, k, b * chunk)
        )
        dp.add_upload(stripes.nbytes)

        def put(words):
            with dp.stage("upload"):
                return jax.device_put(words)

        def issue(dev):
            with dp.stage("compute"):
                outs = packed_gf.prebuilt_word_call(bm_np)(*dev)
            with dp.stage("sync"):
                for o in outs:
                    o.copy_to_host_async()
            return outs

        def fold_row(i, row) -> int:
            with tracing.stage("ec_fold") as span:
                if b > 1:
                    np.copyto(row.reshape(b, chunk), stripes[:, i, :])
            return int(span.duration * 1e9)  # as l_stage_ec_fold_ns counts it

        if chunk % packed_gf.STRIPE_FORM_BYTES == 0:
            outs = issue([put(packed_gf.stripe_words(stripes))])
            kernel_stats().perf.inc(
                FOLD_OVERLAPPED_NS,
                sum(fold_row(i, row) for i, row in enumerate(folded)),
            )
        else:
            dev = []
            for i, row in enumerate(folded):
                fold_row(i, row)
                dev.append(put(row.view(np.uint32).reshape(1, -1)))
            outs = issue(dev)
        with dp.stage("sync"):
            host = [np.asarray(o) for o in outs]
        with tracing.stage("ec_unfold"):
            coding = [h.reshape(-1).view(np.uint8) for h in host]
        return list(folded), coding

    def matrix_shards(
        self,
        matrix: np.ndarray,
        shards,
        w: int,
        stripes: int,
        sub_rows: tuple[int, int] = (1, 1),
    ) -> list[np.ndarray]:
        """One object's reconstruction in ONE device dispatch, in shard
        form: ``shards`` are the s survivor shards named by the plan
        (equal-length 1-D payloads of whole 32-bit words: chunk i of
        every stripe, concatenated — the folded region layout as
        stored, so nothing is transposed on the host), ``matrix`` the
        (r, s) reconstruction rows; returns the r rebuilt shards as
        host arrays.  Recorded as kind ``ec_decode`` (ops 1,
        ``stripes``), upload / issue / fetch bracketed as their stages.

        The matrix is an OPERAND of either program, never unrolled: a
        reconstruction matrix is one of C(k+m, e) a pool may meet, so
        one program a shape serves every pattern.  Which program runs
        is read from the operands.  A whole-shard rebuild (``sub_rows``
        (1, 1)) on the TPU at w=8, of whole 128-word rows after the
        bucket's pad and a small matrix (r·s at most
        ``PACKED_DECODE_TERMS``: VPU work), takes the packed-lane
        kernel (``packed_gf.prebuilt_decode_call``): each survivor
        goes up as a free ``(nwords/128, 128)`` view, the coefficients
        are a cached device array, and the call counts in
        ``l_tpu_ec_decode_packed_calls``.  Everything else — another
        word size, a ragged row, a large matrix (CLAY's 64 x 176: MXU
        work), the CPU — takes the bitplane program
        (``gf_matrix_words``).  The length buckets to a power of two
        of stripes, as ``_bitplane_dispatch`` buckets batches.

        ``sub_rows`` = (rows in, rows out) is the row shape of a
        fractional repair (``stripe.repair``): a stripe of a payload
        holds so many rows of the matrix going in (a CLAY helper's 16
        sub-chunks) and so many coming out (the lost chunk's 64), each
        row a sub-chunk of every stripe, strided in what is stored.
        The fragments cross the link as they are stored and the same
        program folds them to rows and lays the result back as a
        shard (``gf_matrix_words``' ``tile``), so the fetch is the
        shard."""
        import jax

        from .gf_matmul import gf_matrix_words
        from .residency import bucket_pow2, note_shape

        rows_in, rows_out = sub_rows
        rows = [_row_u8(s).view(np.uint32) for s in shards]
        n4 = len(rows[0])
        out4 = n4 // rows_in * rows_out
        total = 4 * n4 * len(rows)
        bucket = bucket_pow2(stripes)
        pad = (bucket - stripes) * (n4 // stripes)
        lanes = packed_gf.LANES
        packed = (
            w == 8
            and sub_rows == (1, 1)
            and n4 % lanes == 0
            and pad % lanes == 0
            and matrix.size <= PACKED_DECODE_TERMS
            and _on_tpu()
        )
        with self._dispatch(
            "ec_decode", ops=1, stripes=stripes, bytes_in=total
        ) as dp:
            if packed:
                coef = packed_gf.device_coefficients(matrix)
                rows = [r.reshape(-1, lanes) for r in rows]
            else:
                bm = matrix_to_device_bitmatrix(matrix, w)
            dp.add_upload(total)
            with dp.stage("upload"):
                dev = [jax.device_put(r) for r in rows]
            with dp.stage("compute"):
                if pad:
                    edge = ((0, pad // lanes), (0, 0)) if packed else (0, pad)
                    dev = [jnp.pad(d, edge) for d in dev]
                    record_pad(4 * pad * len(dev))
                note_shape(
                    "ec_shards", n4 + pad, len(dev), len(matrix), w, sub_rows
                )
                if packed:
                    odev = packed_gf.prebuilt_decode_call(
                        len(matrix), len(dev)
                    )(coef, *dev)
                    kernel_stats().perf.inc(DECODE_PACKED_CALLS)
                else:
                    tile = (
                        None
                        if sub_rows == (1, 1)
                        else (bucket, rows_in, rows_out)
                    )
                    odev = gf_matrix_words(bm, tuple(dev), w=w, tile=tile)
            with dp.stage("sync"):
                out = np.asarray(odev)
            dp.set_bytes_out(4 * out4 * len(out))
        return [row.reshape(-1)[:out4].view(np.uint8) for row in out]

    def matrix_stripes_batch(
        self,
        matrix: np.ndarray,
        stripe_batches,
        w: int,
        group_stripes: int = 256,
    ) -> list[np.ndarray]:
        """Coalesced encode of MANY stripe batches (one per queued
        object) through :meth:`_coalesced`, fetched at its one sync —
        the commit point.  Byte-identical to a per-batch
        :meth:`matrix_stripe_shards` (same per-stripe math; padding is
        sliced away).  Returns one (Bi, m, chunk) host array per input
        batch."""
        batches = [
            np.ascontiguousarray(s, dtype=np.uint8)
            for s in stripe_batches
        ]
        if not batches:
            return []
        with self._dispatch(
            "ec_encode",
            ops=len(batches),
            stripes=sum(s.shape[0] for s in batches),
            bytes_in=sum(s.nbytes for s in batches),
        ) as dp:
            bm = matrix_to_device_bitmatrix(matrix, w)
            outs = self._coalesced(
                dp, bm, batches, w, group_stripes, fetch=True
            )
            dp.set_bytes_out(sum(o.nbytes for o in outs))
        return outs

    def _coalesced(
        self, dp, bm, arrays, w: int, group_stripes: int, fetch: bool
    ) -> list:
        """THE coalesced pipeline, the write path's and the repair
        path's alike: ``arrays`` — one (Bi, k, chunk) uint8 host array
        an object, in order — pack greedily into ~``group_stripes``-
        stripe groups, group j+1's ``jax.device_put`` is issued while
        group j computes (both are async dispatches), and each group's
        batch axis buckets to a power of two so ragged coalesced
        batches replay compiled programs (:meth:`_bitplane_dispatch`).
        Returns each object's (Bi, r, chunk) slice of its group's
        output, in order: with ``fetch`` the group outputs come to the
        host first, under the one ``sync`` bracket (the commit: every
        dispatched transfer and encode drains together), and the
        slices are host views; without it nothing waits and the slices
        stay DEVICE arrays for the caller to sync."""
        import jax

        if len({a.shape[1:] for a in arrays}) > 1:
            raise ErasureCodeError(
                "the objects of one coalesced dispatch must share "
                "their stripe geometry (rows, chunk)"
            )
        groups: list[list[np.ndarray]] = []
        cur: list[np.ndarray] = []
        cur_b = 0
        for a in arrays:
            if cur and cur_b + a.shape[0] > group_stripes:
                groups.append(cur)
                cur, cur_b = [], 0
            cur.append(a)
            cur_b += a.shape[0]
        if cur:
            groups.append(cur)

        def upload(group):
            arr = np.concatenate(group) if len(group) > 1 else group[0]
            # device_put is async: the transfer overlaps whatever
            # compute is already dispatched
            with dp.stage("upload"):
                dev = jax.device_put(arr)
            dp.add_upload(arr.nbytes)
            return dev

        mats = []
        dev = upload(groups[0]) if groups else None
        for j in range(len(groups)):
            with dp.stage("compute"):
                mats.append(self._bitplane_dispatch(bm, dev, w))
            if j + 1 < len(groups):
                # next group's transfer overlaps this group's
                # compute — the double buffer
                dev = upload(groups[j + 1])
        if fetch:
            with dp.stage("sync"):
                mats = [np.asarray(o) for o in mats]
        outs = []
        for group, mat in zip(groups, mats):
            off = 0
            for a in group:
                outs.append(mat[off : off + a.shape[0]])
                off += a.shape[0]
        return outs

    def decode_stripes_batch(
        self,
        matrix: np.ndarray,
        row_sets,
        w: int,
        chunk: int,
        group_stripes: int = 256,
    ) -> list:
        """Coalesced decode-from-survivors: the repair-side twin of
        :meth:`matrix_stripes_batch`.  ``row_sets`` is one list per
        object of equal-length 1-D survivor shard payloads — numpy
        arrays or resident DeviceBuf tokens.  Resident survivors ride
        the dispatch with ZERO re-upload (their link cost was paid at
        registration); host-only objects go through
        :meth:`_coalesced`, exactly like the write path.  The ONLY
        sync is the final block_until_ready, and the outputs stay
        DEVICE arrays — reconstructed shards leave device-born (the
        caller wraps them in DeviceBufs; host bytes are fetched at
        most once by whoever pushes/writes them)."""
        import jax

        from .residency import is_device_buf

        with self._dispatch(
            "ec_decode",
            ops=len(row_sets),
            bytes_in=sum(len(r) for rows in row_sets for r in rows),
        ) as dp:
            bm = matrix_to_device_bitmatrix(matrix, w)
            outs: list = [None] * len(row_sets)
            host_idx: list[int] = []
            for i, rows in enumerate(row_sets):
                if any(is_device_buf(r) for r in rows):
                    # already-resident survivors ride with zero link
                    # cost; a lazy (unregistered-yet) DeviceBuf's
                    # device() upload is a real transfer
                    for r in rows:
                        if is_device_buf(r):
                            (
                                dp.add_resident
                                if r.resident
                                else dp.add_upload
                            )(len(r))
                    # ONE device_put for the object's host rows (a
                    # single resident survivor must not force the
                    # rest row-by-row — the PR 10 _gather_rows
                    # lesson), then a device-side stack interleaves
                    # them with the already-resident rows
                    host_js = [
                        j
                        for j, r in enumerate(rows)
                        if not is_device_buf(r)
                    ]
                    stacked = (
                        np.stack(
                            [
                                _row_u8(rows[j]).reshape(-1, chunk)
                                for j in host_js
                            ]
                        )
                        if host_js
                        else None
                    )
                    if stacked is not None:
                        dp.add_upload(stacked.nbytes)
                    with dp.stage("upload"):
                        blk = (
                            jax.device_put(stacked)
                            if stacked is not None
                            else None
                        )
                        hi = 0
                        devs = []
                        for j, r in enumerate(rows):
                            if is_device_buf(r):
                                devs.append(
                                    r.device().reshape(-1, chunk)
                                )
                            else:
                                devs.append(blk[hi])
                                hi += 1
                        dev = jnp.stack(devs, axis=1)
                    with dp.stage("compute"):
                        out = self._bitplane_dispatch(bm, dev, w)
                    outs[i] = out[: dev.shape[0]]
                else:
                    host_idx.append(i)
            arrays = [
                np.stack(
                    [_row_u8(r).reshape(-1, chunk) for r in row_sets[i]],
                    axis=1,
                )
                for i in host_idx
            ]
            for i, out in zip(
                host_idx,
                self._coalesced(
                    dp, bm, arrays, w, group_stripes, fetch=False
                ),
            ):
                outs[i] = out
            dp.set_stripes(sum(o.shape[0] for o in outs))
            # sync ONLY here (the commit point); results STAY on
            # device for device-born registration downstream
            with dp.stage("sync"):
                outs = [jax.block_until_ready(o) for o in outs]
            dp.set_bytes_out(sum(int(np.prod(o.shape)) for o in outs))
        return outs

    @staticmethod
    def _bitplane_dispatch(bm, dev, w: int):
        """Bucketed dispatch for an ALREADY-uploaded (B, k, chunk)
        device array: the batch axis pads ON DEVICE to a power of two
        (the link carried exact bytes; only the compiled program sees
        the bucketed shape), so ragged object sizes and coalesced
        write batches replay compiled programs — reuse lands in the
        l_tpu_compile_cache_{hit,miss} counters
        (ops/residency.note_shape)."""
        from .residency import bucket_pow2, note_shape

        b, k, chunk = dev.shape
        bb = bucket_pow2(b)
        if bb != b:
            dev = jnp.pad(dev, ((0, bb - b), (0, 0), (0, 0)))
            record_pad((bb - b) * k * chunk)
        note_shape("ec_stripes", bb, k, chunk, w)
        return gf_matrix_stripes(bm, dev, w=w)


_backend = JaxBackend()
register_backend("jax", _backend)


def get_jax_backend() -> JaxBackend:
    return _backend
