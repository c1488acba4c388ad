"""Compute backends for erasure-code region math.

The reference dispatches its GF region kernels to CPU SIMD libraries
(gf-complete / isa-l asm); here the same seam dispatches to either the
numpy oracle or the TPU kernels in ``ceph_tpu.ops`` (registered lazily on
first use of ``backend=jax``).  Both implement six entries, one job
each, and the stripe seam (``ec/stripe.py``) asks for none of them by
name:

- ``matrix_regions(matrix, regions, w)``      — GF(2^w) matrix x chunk
  regions (the jerasure_matrix_encode / ec_encode_data contract).
- ``bitmatrix_regions(bm, regions, w, packetsize)`` — GF(2) bitmatrix over
  packet-interleaved regions (the jerasure_bitmatrix_dotprod contract:
  each chunk is blocks of w packets of ``packetsize`` bytes; output packet
  (i) of a block = XOR of input packets (j) where bm[i, j] == 1).
- ``matrix_stripe_shards`` / ``matrix_shards`` — one object's encode /
  rebuild, every stripe in one dispatch, shards in and out.
- ``matrix_stripes_batch`` / ``decode_stripes_batch`` — many objects'
  encode / rebuild in one coalesced dispatch.
"""

from __future__ import annotations

import warnings

import numpy as np

from ..common import allocator
from ..gf import matrix_vector_mul_region
from ..layout import fold_stripes, unfold_stripes


def _host_row(r) -> np.ndarray:
    """1-D uint8 view of a survivor payload: DeviceBuf tokens fetch
    host-side, bytes-likes go through frombuffer (ascontiguousarray
    would parse bytes as a scalar literal)."""
    if hasattr(r, "host"):
        r = r.host()
    if isinstance(r, (bytes, bytearray, memoryview)):
        return np.frombuffer(bytes(r), dtype=np.uint8)
    return np.ascontiguousarray(r, dtype=np.uint8).ravel()


class NumpyBackend:
    name = "numpy"

    def matrix_regions(
        self, matrix: np.ndarray, regions: np.ndarray, w: int
    ) -> np.ndarray:
        if w == 8:
            # C region-MAC fast path (native/gf8.c, the
            # jerasure/ISA-L pshufb hot loop): bit-exact with the
            # numpy fallback below; None when no compiler exists
            from ..native import gf8_matrix_regions

            out = gf8_matrix_regions(matrix, regions)
            if out is not None:
                return out
        return matrix_vector_mul_region(matrix, regions, w)

    def matrix_stripes(
        self, matrix: np.ndarray, stripes: np.ndarray, w: int
    ) -> np.ndarray:
        """Batched (B, k, chunk) → (B, m, chunk): stripes fold into the
        region byte dimension (same layout as the jax backend).  The
        stripe-form reference the batched seams of this oracle, the
        tests and ``chip_smoke.py`` compare with; the jax backend has
        no such entry."""
        stripes = np.ascontiguousarray(stripes, dtype=np.uint8)
        b, _k, chunk = stripes.shape
        out = self.matrix_regions(matrix, fold_stripes(stripes), w)
        return unfold_stripes(out, b, chunk)

    def _host_entry(self, kind: str, **totals):
        """A host flight-recorder entry (``totals``: its ``ops``,
        ``stripes`` and ``bytes_in``), what every batched seam of this
        oracle leaves so the dispatch plane stays populated
        deviceless.  Lazy: ceph_tpu.ops registers the jax backend
        through this module."""
        from ..ops.profiler import dispatch_profiler

        return dispatch_profiler().dispatch(
            kind, backend=self.name, **totals
        )

    def matrix_stripe_shards(
        self, matrix: np.ndarray, stripes: np.ndarray, w: int
    ) -> tuple[list[np.ndarray], list[np.ndarray]]:
        """``matrix_stripes`` in shard form, what ``stripe.encode``
        hands out: (B, k, chunk) → the k data shards and the m coding
        shards, each 1-D and contiguous, B*chunk long.  The fold is
        the one copy of the input: its rows are the regions the math
        reads and the data shards (views of the input when B is 1)."""
        stripes = np.ascontiguousarray(stripes, dtype=np.uint8)
        with self._host_entry(
            "ec_encode",
            ops=1,
            stripes=stripes.shape[0],
            bytes_in=stripes.nbytes,
        ):
            regions = np.ascontiguousarray(fold_stripes(stripes))
            return list(regions), list(
                self.matrix_regions(matrix, regions, w)
            )

    def matrix_stripes_batch(
        self, matrix: np.ndarray, stripe_batches, w: int
    ) -> list[np.ndarray]:
        """Coalesced-encode seam (the jax backend double-buffers
        device transfers here); the oracle just loops — coalescing is
        a dispatch-cost optimization, and the oracle has no dispatch
        cost to amortize."""
        batches = list(stripe_batches)
        with self._host_entry(
            "ec_encode",
            ops=len(batches),
            stripes=sum(s.shape[0] for s in batches),
            bytes_in=sum(s.nbytes for s in batches),
        ):
            return [
                self.matrix_stripes(matrix, s, w) for s in batches
            ]

    def decode_stripes_batch(
        self, matrix: np.ndarray, row_sets, w: int, chunk: int
    ) -> list[np.ndarray]:
        """Batched decode-from-survivors seam (the jax backend
        double-buffers uploads and keeps outputs device-born here).
        ``row_sets`` is one list per object of equal-length 1-D
        survivor payloads (ndarray or DeviceBuf — resident tokens
        fetch host-side on this oracle path); each reshapes to
        (nstripes, s, chunk) and multiplies by the reconstruction
        matrix.  The oracle loops — it has no dispatch cost to
        amortize — through the same C region-MAC fast path the
        encode side uses."""
        with self._host_entry(
            "ec_decode",
            ops=len(row_sets),
            bytes_in=sum(len(r) for rows in row_sets for r in rows),
        ) as dp:
            outs: list[np.ndarray] = []
            for rows in row_sets:
                arr = np.stack(
                    [_host_row(r).reshape(-1, chunk) for r in rows],
                    axis=1,
                )
                outs.append(self.matrix_stripes(matrix, arr, w))
            dp.set_stripes(sum(o.shape[0] for o in outs))
            return outs

    def matrix_shards(
        self,
        matrix: np.ndarray,
        shards,
        w: int,
        stripes: int,
        sub_rows: tuple[int, int] = (1, 1),
    ) -> list[np.ndarray]:
        """One object's reconstruction in shard form (the jax backend
        makes it one device dispatch): the s survivor shards are the
        regions as they are stored, the (r, s) reconstruction rows
        give the r rebuilt shards.  ``sub_rows`` = (rows in, rows
        out): a stripe of a payload holds so many rows of the matrix
        (a fractional repair's sub-chunks), folded to regions here and
        the result laid back as stored."""
        rows_in, rows_out = sub_rows
        payloads = [_host_row(s) for s in shards]
        with self._host_entry(
            "ec_decode",
            ops=1,
            stripes=stripes,
            bytes_in=sum(p.nbytes for p in payloads),
        ):
            regions = np.concatenate(
                [
                    fold_stripes(p.reshape(stripes, rows_in, -1))
                    for p in payloads
                ]
            )
            out = self.matrix_regions(matrix, regions, w)
            return [
                unfold_stripes(rows, stripes, -1).reshape(-1)
                for rows in out.reshape(-1, rows_out, out.shape[1])
            ]

    def bitmatrix_regions(
        self,
        bm: np.ndarray,
        regions: np.ndarray,
        w: int,
        packetsize: int,
    ) -> np.ndarray:
        n, size = regions.shape
        out_rows = bm.shape[0] // w
        block = w * packetsize
        assert size % block == 0, (size, block)
        nblocks = size // block
        # (n, nblocks, w, p) -> (nblocks, n*w, p)
        planes = (
            regions.reshape(n, nblocks, w, packetsize)
            .transpose(1, 0, 2, 3)
            .reshape(nblocks, n * w, packetsize)
        )
        bits = np.unpackbits(planes, axis=2)
        out_bits = (
            bm.astype(np.int32) @ bits.astype(np.int32)
        ) & 1
        out = np.packbits(out_bits.astype(np.uint8), axis=2)
        return (
            out.reshape(nblocks, out_rows, w, packetsize)
            .transpose(1, 0, 2, 3)
            .reshape(out_rows, size)
        )


_backends: dict[str, object] = {"numpy": NumpyBackend()}


def register_backend(name: str, backend) -> None:
    _backends[name] = backend


def get_backend(name: str):
    if name == "jax":
        if "jax" not in _backends:
            from .. import ops  # self-registers the jax backend

            assert "jax" in _backends
        # a codec on the device moves whole objects through fresh host
        # buffers call after call: the process that makes one keeps
        # its freed blocks -- daemon, tool and benchmark alike, and
        # nobody else sets the policy
        if not allocator.keep_large_blocks():
            warnings.warn(
                "the C library took no allocator policy: large EC "
                "buffers will fault fresh pages on every call",
                RuntimeWarning,
                stacklevel=2,
            )
    if name not in _backends:
        raise ValueError(f"unknown EC backend {name!r} (have {sorted(_backends)})")
    return _backends[name]
