"""GF(2^w) region math as mod-2 matmuls (the MXU formulation).

The reference computes ``coding[i] = Σ_j M[i,j] ⊗ data[j]`` with per-
coefficient table-lookup region passes (jerasure_matrix_encode /
ec_encode_data, SURVEY.md §3.1).  Multiplication by a constant in
GF(2^w) is linear over GF(2), so the whole matrix lifts to a
(m·w, k·w) bitmatrix B and the kernel is

    bits_out = (B @ bits_in) & 1

one int8 matmul with int32 accumulation — dense, static-shaped, and
tiled straight onto the systolic array.  Decode is the same kernel with
the inverted-survivor-submatrix rows (built host-side, tiny).

Two bit layouts share the primitive:

- word layout (matrix techniques, w ∈ {8,16,32}): bit x of each
  little-endian w-bit word → ``gf_matrix_regions``.
- packet layout (bitmatrix techniques: cauchy/liberation XOR schedules):
  regions are blocks of w packets of ``packetsize`` bytes; B works on
  whole packets, bytes are opaque → ``bitmatrix_packet_regions``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..layout import fold_stripes, unfold_stripes
from .bitops import (
    pack_byte_bits,
    pack_word_bits,
    unpack_byte_bits,
    unpack_word_bits,
)


def mod2_matmul(bm: jnp.ndarray, bits: jnp.ndarray) -> jnp.ndarray:
    """(R, C) 0/1 @ (C, N) 0/1 → (R, N) 0/1 via int8 matmul, int32 acc."""
    acc = jax.lax.dot_general(
        bm.astype(jnp.int8),
        bits,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    return (acc & 1).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("w",))
def gf_matrix_regions(
    bm: jnp.ndarray, regions: jnp.ndarray, *, w: int
) -> jnp.ndarray:
    """Apply a GF(2^w) coding matrix, given as its (m·w, k·w) bitmatrix,
    to (k, nbytes) uint8 regions → (m, nbytes) uint8."""
    with jax.named_scope("ec_bitplane_matmul"):
        bits = unpack_word_bits(regions, w)
        out = mod2_matmul(bm, bits)
        return pack_word_bits(out, w)


@functools.partial(jax.jit, static_argnames=("w", "packetsize"))
def bitmatrix_packet_regions(
    bm: jnp.ndarray, regions: jnp.ndarray, *, w: int, packetsize: int
) -> jnp.ndarray:
    """jerasure_bitmatrix_dotprod contract: each region is blocks of w
    packets of ``packetsize`` bytes; output packet i of each block is the
    XOR of input packets j where bm[i, j] == 1."""
    n, size = regions.shape
    out_rows = bm.shape[0] // w
    block = w * packetsize
    assert size % block == 0, (size, block)
    nblocks = size // block
    # (n, size) → packet planes (n*w, nblocks*packetsize): row j*w+p is
    # packet p of region j, blocks laid out contiguously per row.
    planes = (
        regions.reshape(n, nblocks, w, packetsize)
        .transpose(0, 2, 1, 3)
        .reshape(n * w, nblocks * packetsize)
    )
    bits = unpack_byte_bits(planes)
    out = pack_byte_bits(mod2_matmul(bm, bits))
    return (
        out.reshape(out_rows, w, nblocks, packetsize)
        .transpose(0, 2, 1, 3)
        .reshape(out_rows, size)
    )


@functools.partial(jax.jit, static_argnames=("w",))
def gf_matrix_stripes(
    bm: jnp.ndarray, stripes: jnp.ndarray, *, w: int
) -> jnp.ndarray:
    """Batched encode: (B, k, chunk_bytes) → (B, m, chunk_bytes).

    The ECUtil::encode per-stripe loop (src/osd/ECUtil.cc:123-162) hoisted
    into one device call: stripes fold into the matmul N dimension, so
    arbitrarily many stripes ride a single kernel launch."""
    b, _k, chunk = stripes.shape
    # the batched encode AND the decode-from-survivors entry (the
    # matrix decides which): fold, multiply, unfold under one name
    with jax.named_scope("ec_bitplane_stripes"):
        out = gf_matrix_regions(bm, fold_stripes(stripes), w=w)
        return unfold_stripes(out, b, chunk)


@functools.partial(jax.jit, static_argnames=("w", "tile"))
def gf_matrix_words(
    bm: jnp.ndarray, words, *, w: int, tile: tuple | None = None
) -> jnp.ndarray:
    """Decode-from-survivors in shard form.  ``words`` is a tuple of
    s equal-length 1-D uint32 arrays — survivor shards as they are
    stored (chunk i of every stripe concatenated, which IS the folded
    region layout) viewed as little-endian words, the form that
    crosses the link at its full rate (a uint8 array of the same
    bytes is re-tiled on the way: PERF.md section 6, PR 28) — and
    ``bm`` the reconstruction matrix's bitmatrix, an OPERAND: one
    program per (rows out, rows in, length) serves every erasure
    pattern.  → (r, nwords) uint32, row j the j-th rebuilt shard.

    ``tile`` = (stripes, rows in, rows out) is the sub-chunk form (a
    CLAY repair): a payload then holds ``rows in`` rows of the matrix
    a stripe — sub-chunk i of every stripe is one row, strided in what
    is stored — and a result ``rows out`` of them.  The fold to rows
    and the unfold of the result back to stored form happen here, on
    the device.

    A uint32 holds 32 // w code words; sub-word q's bit x is bit
    ``w*q + x``.  Each q is one mod-2 matmul over its bit planes, and
    the planes of the result are shifted back where they came from —
    no uint8 array exists on the device."""
    per = 32 // w
    with jax.named_scope("ec_bitplane_decode"):
        if tile is None:
            x = jnp.stack(words)
        else:
            stripes, rows_in, rows_out = tile
            x = jnp.concatenate(
                [
                    fold_stripes(p.reshape(stripes, rows_in, -1))
                    for p in words
                ]
            )
        s, n = x.shape
        r = bm.shape[0] // w
        acc = jnp.zeros((r, n), dtype=jnp.uint32)
        for q in range(per):
            shift = jnp.arange(w * q, w * q + w, dtype=jnp.uint32)
            bits = (x[:, None, :] >> shift[None, :, None]) & jnp.uint32(1)
            out = mod2_matmul(bm, bits.astype(jnp.int8).reshape(s * w, n))
            planes = out.reshape(r, w, n).astype(jnp.uint32)
            # the w planes of a sub-word touch disjoint bits: sum is OR
            acc = acc | (planes << shift[None, :, None]).sum(
                axis=1, dtype=jnp.uint32
            )
        if tile is not None:
            acc = jnp.stack(
                [
                    unfold_stripes(rows, stripes, -1).reshape(-1)
                    for rows in acc.reshape(-1, rows_out, n)
                ]
            )
        return acc


@functools.lru_cache(maxsize=512)
def _bitmatrix_cache(key: bytes, shape: tuple, w: int, dtype) -> jnp.ndarray:
    from .. import gf

    mat = np.frombuffer(key, dtype=np.int64).reshape(shape)
    return jnp.asarray(gf.jerasure_bitmatrix(mat, w), dtype=dtype)


def matrix_to_device_bitmatrix(
    matrix: np.ndarray, w: int, dtype=jnp.int8
) -> jnp.ndarray:
    """Lift a GF(2^w) matrix to its device-resident bitmatrix, cached by
    value — bitmatrix expansion AND host→device transfer happen once per
    distinct (matrix, dtype) (the analog of ErasureCodeIsaTableCache's
    one-time per-erasure-signature table preparation).  dtype jnp.int8
    for the XLA int-matmul path, jnp.bfloat16 for the pallas kernel."""
    from .kernel_stats import kernel_stats

    mat = np.ascontiguousarray(matrix, dtype=np.int64)
    return kernel_stats().counted_cache_call(
        _bitmatrix_cache, mat.tobytes(), mat.shape, w, dtype
    )
