"""Packed-lane GF(2^8) region kernel — the fast TPU encode/decode path.

The bitplane formulation (ops.gf_matmul) pays for an 8x unpack on the
VPU and a tiny (m·8, k·8) matmul that uses a few percent of the MXU.
This kernel keeps bytes PACKED four-per-u32 lane end to end:

- bit b of the four bytes in a lane extract together:
  ``(x >> b) & 0x01010101`` — one shift+and yields FOUR bitplane
  values, each in its own byte field;
- a GF(2) bitmatrix row is a fixed XOR-subset of input bit planes.
  Integer ADDs of the extracted fields accumulate each field
  independently (sums are bounded by the row's popcount <= 255, so
  carries never cross byte fields) and the low bit of each field is
  the mod-2 result;
- ``(acc & LSB) << b`` deposits output bit b of four output bytes at
  once, so the OR-accumulated result IS the byte-packed output lane.

Per input byte this costs ~15 single VPU ops (after the pair-CSE
schedule below) with NO 8x blowup and no MXU dependence; on a v5e
the k=8,m=3 encode of 64 MiB takes 0.28 ms of device time (0.0042 ns
a byte of input, 40% of the HBM roofline; PERF.md section 5, PR 28:
the benchmark's traced ``ec_plugin_k8m3.encode_1m``).  The ENCODE's
add-chain is unrolled per bitmatrix at trace time — its kernels cache
per matrix exactly like the reference's per-signature table expansion
(ErasureCodeIsa.cc:402 ec_init_tables); a pool has one coding matrix.

The REBUILD (``prebuilt_decode_call``) meets one of C(k+m, e)
reconstruction matrices, so it takes its (r, s) GF(2^8) coefficients
as an operand, through scalar prefetch into SMEM, and compiles once a
shape: a survivor word's four bytes double together by the field's
polynomial, ``((x << 1) & 0xFEFEFEFE) ^ (((x >> 7) & 0x01010101) *
0x1D)``, and output i XORs in doubling b of survivor j under a mask
of all ones where bit b of c[i][j] is set — some 70 VPU ops a word
whatever the matrix.  Its survivors are ``(nwords/128, 128)`` u32
views (a minor dimension of exactly 128 words: the link carries them
as they lie, the program is the one custom call), its result one
``(r, nwords/128, 128)`` array.  On a v5e the ``decode_2e_1m`` call's
2 of 8 from 64 MiB takes 0.25-0.27 ms of device time where the
bitplane program with its bitmatrix as an operand takes 1.73 ms
(PERF.md section 6, PR 43).

LAYOUT CONTRACT — "word form".  Region bytes enter as little-endian
u32 words, one region per (1, nwords) array (byte 4w+q of the region
is field q of word w — exactly ``numpy.view(uint32)``).  Rows travel
as SEPARATE arrays because XLA assigns a pathological 16x-padded
layout to a stacked (k, nwords) u32 operand and materializes u8⇄u32
bitcasts of big arrays at ~2 GB/s; per-row 1D-ish arrays sidestep
both (measured >60x difference).  Host callers get the conversion for
free via numpy views (``to_words``/``from_words``); device-resident
pipelines should carry word form between calls.

"Stripe form" is the other operand the same kernel body takes: ONE
u32 ``(B, k, chunk/512, 128)`` array, a free view of (B, k, chunk)
stripe bytes (``stripe_words``), where region j is chunk j of every
stripe.  The body is elementwise over its blocks, so input j's
``BlockSpec`` alone — ``(TB, None, TR, 128)`` at ``(bi, j, ri, 0)`` —
reads the region where it lies and no fold precedes the upload; with
a minor dimension of exactly 128 words the array's (8, 128) tiling is
row-major, so it needs no padding and neither direction of the
transfer re-tiles anything (the compiled program is the one custom
call: tests/test_chip_compile.py).  On a v5e the 64 MiB k=8,m=3
encode takes 0.272 ms so (PERF.md section 5, PR 40).

w=8 only (the jerasure/isa default and the BASELINE.md configs);
other word sizes use the bitplane path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import gf

TILE_WORDS = 8192  # u32 lanes per grid step (measured best 4096-8192)
STRIPE_FORM_BYTES = 4096  # a chunk of whole (8, 128) u32 tiles
LANES = 128  # u32 words a row of a (8, 128) tile
_LSB = 0x01010101


def _rows_of(bm: np.ndarray) -> tuple[tuple[int, ...], ...]:
    return tuple(
        tuple(np.nonzero(bm[r])[0].tolist()) for r in range(bm.shape[0])
    )


def supports(bm: np.ndarray, w: int) -> bool:
    """Eligibility: w=8 and every output row's popcount fits a byte
    field (no carry into the neighbouring packed byte)."""
    return (
        w == 8
        and bm.shape[0] % 8 == 0
        and bm.shape[1] % 8 == 0
        and int(bm.sum(axis=1).max(initial=0)) <= 255
    )


def to_words(regions: np.ndarray) -> list[np.ndarray]:
    """(k, nbytes) u8 → k arrays of (1, nbytes//4) u32 — a free view."""
    regions = np.ascontiguousarray(regions, dtype=np.uint8)
    assert regions.shape[1] % 4 == 0, regions.shape
    return [
        row.view(np.uint32).reshape(1, -1) for row in regions
    ]


def from_words(words: list[np.ndarray]) -> np.ndarray:
    """k arrays of (1, nwords) u32 → (k, nwords*4) u8 — a free view."""
    return np.stack(
        [np.asarray(w).reshape(-1).view(np.uint8) for w in words]
    )


@functools.lru_cache(maxsize=512)
def _schedule(rows: tuple[tuple[int, ...], ...]):
    """Greedy pair-CSE over the add-chains (the packed-lane analog of
    jerasure's smart XOR schedules): the most frequent column pair
    across all rows becomes a shared node, repeatedly.  Safe for the
    carry bound: a shared node's field sum never exceeds the largest
    row popcount it appears in.

    Returns (pair_nodes, row_exprs): pair_nodes[t] = (a, b) defines
    node ``base+t`` as a+b; row_exprs[r] lists the node ids summed."""
    exprs = [list(t) for t in rows]
    base = 1 + max((c for t in rows for c in t), default=0)
    pairs: list[tuple[int, int]] = []
    while True:
        counts: dict[tuple[int, int], int] = {}
        for e in exprs:
            seen = sorted(set(e))
            for ai in range(len(seen)):
                for bi in range(ai + 1, len(seen)):
                    p = (seen[ai], seen[bi])
                    counts[p] = counts.get(p, 0) + 1
        if not counts:
            break
        (a, b), cnt = max(counts.items(), key=lambda kv: kv[1])
        if cnt < 2:
            break
        node = base + len(pairs)
        pairs.append((a, b))
        for e in exprs:
            if a in e and b in e:
                e.remove(a)
                e.remove(b)
                e.append(node)
    return tuple(pairs), tuple(tuple(e) for e in exprs)


def _make_kernel(rows: tuple[tuple[int, ...], ...], n_in: int, m_out: int):
    pair_nodes, row_exprs = _schedule(rows)
    base = 1 + max((c for t in rows for c in t), default=0)

    def kernel(*refs):
        ins, outs = refs[:n_in], refs[n_in:]
        lsb = jnp.uint32(_LSB)
        nodes: dict[int, jnp.ndarray] = {}

        def node(c):
            if c not in nodes:
                if c >= base:
                    a, b = pair_nodes[c - base]
                    nodes[c] = node(a) + node(b)
                else:
                    j, b = divmod(c, 8)
                    x = ins[j][:]
                    nodes[c] = (x >> b) & lsb if b else x & lsb
            return nodes[c]

        for i in range(m_out):
            ob = None
            for b in range(8):
                expr = row_exprs[i * 8 + b]
                if not expr:
                    continue
                acc = node(expr[0])
                for c in expr[1:]:
                    acc = acc + node(c)
                t = (acc & lsb) << b if b else acc & lsb
                ob = t if ob is None else ob | t
            outs[i][:] = (
                ob if ob is not None else jnp.zeros_like(ins[0][:])
            )

    return kernel


def _make_decode_kernel(r: int, s: int):
    """The rebuild's body: r outputs from s survivors, the (r, s)
    GF(2^8) coefficients read from SMEM, so the compiled text is the
    same for every matrix of the shape.  A survivor word's four bytes
    double together (``xtime`` by the field's polynomial 0x11D, a
    byte a field), and output i takes doubling b of survivor j where
    bit b of c[i][j] is set: ``acc ^= xt_b & mask``, the mask a
    scalar of all ones or none."""

    def kernel(coef, *refs):
        ins, out = refs[:s], refs[s]
        hi = jnp.uint32(0xFEFEFEFE)
        poly = jnp.uint32(gf.PRIM_POLY[8] & 0xFF)
        lsb = jnp.uint32(_LSB)
        accs = [None] * r
        for j in range(s):
            x = ins[j][:]
            for b in range(8):
                if b:
                    x = ((x << 1) & hi) ^ (((x >> 7) & lsb) * poly)
                for i in range(r):
                    bit = (coef[i * s + j] >> b) & 1
                    t = x & (jnp.uint32(0) - bit.astype(jnp.uint32))
                    accs[i] = t if accs[i] is None else accs[i] ^ t
        for i in range(r):
            out[i] = accs[i]

    return kernel


@functools.lru_cache(maxsize=64)
def prebuilt_decode_call(r: int, s: int, *, interpret: bool = False):
    """The rebuild's kernel for every (r, s) matrix over GF(2^8):
    ``call(coef, *s_rows) -> (r, R, 128)`` u32, ``coef`` the matrix
    as a flat ``(r*s,)`` int32 device array
    (:func:`device_coefficients`), each survivor a ``(R, 128)`` u32
    array (a free view of its bytes, ``nwords/128`` rows: the link
    carries it as it lies)."""
    kernel = _make_decode_kernel(r, s)


    @jax.jit
    def run(coef, *xs):  # (r*s,) i32 and s arrays of (R, LANES) u32
        rows = xs[0].shape[0]
        tr = min(rows, TILE_WORDS // LANES)

        def in_index(g, _coef):
            return g, jnp.int32(0)

        def out_index(g, _coef):
            return jnp.int32(0), g, jnp.int32(0)

        # a stable name on the device op (else ``%run.N``)
        with jax.named_scope("ec_packed_decode"):
            return pl.pallas_call(
                kernel,
                grid_spec=pltpu.PrefetchScalarGridSpec(
                    num_scalar_prefetch=1,
                    grid=(pl.cdiv(rows, tr),),
                    in_specs=[
                        pl.BlockSpec((tr, LANES), in_index) for _ in range(s)
                    ],
                    out_specs=pl.BlockSpec((r, tr, LANES), out_index),
                ),
                out_shape=jax.ShapeDtypeStruct((r, rows, LANES), jnp.uint32),
                interpret=interpret,
            )(coef, *xs)

    return run


@functools.lru_cache(maxsize=512)
def _coefficients(key: bytes, shape: tuple) -> jnp.ndarray:
    mat = np.frombuffer(key, dtype=np.int64).reshape(shape)
    return jnp.asarray(mat.reshape(-1), dtype=jnp.int32)


def device_coefficients(matrix: np.ndarray) -> jnp.ndarray:
    """A GF(2^8) matrix as the decode kernel's flat int32 operand on
    the device, cached by value as ``matrix_to_device_bitmatrix``
    caches a bitmatrix: a ``device_put`` costs its issue whatever its
    size, so a pattern pays it once."""
    from .kernel_stats import kernel_stats

    mat = np.ascontiguousarray(matrix, dtype=np.int64)
    return kernel_stats().counted_cache_call(
        _coefficients, mat.tobytes(), mat.shape
    )


def stripe_words(stripes: np.ndarray) -> np.ndarray:
    """(B, k, chunk) u8 stripes → their ``(B, k, chunk/512, 128)`` u32
    stripe form — a free view of the caller's buffer.  A chunk of
    whole (8, 128) u32 tiles (4096 bytes) only: with a minor dimension
    of exactly 128 words the device's tiling of the array is
    row-major, so the link carries the bytes as they lie."""
    b, k, chunk = stripes.shape
    assert chunk % STRIPE_FORM_BYTES == 0, stripes.shape
    return stripes.view(np.uint32).reshape(b, k, chunk // 512, 128)


def _stripe_block(b: int, r: int) -> tuple[int, int]:
    """(stripes, rows of 128 words) a grid step takes of a stripe-form
    operand of ``b`` stripes of ``r`` rows a chunk: some TILE_WORDS
    words, whole chunks of several stripes where a chunk is short."""
    tr = min(r, TILE_WORDS // 128)
    return min(b, max(1, TILE_WORDS // (tr * 128))), tr


@functools.lru_cache(maxsize=512)
def _packed_call(
    rows: tuple[tuple[int, ...], ...],
    n_in: int,
    m_out: int,
    interpret: bool,
):
    kernel = _make_kernel(rows, n_in, m_out)

    # index maps return int32 on purpose: crush/jaxmap.py turns
    # jax_enable_x64 on for the whole process, a bare ``0`` then traces
    # as i64 and Mosaic refuses the index map ('func.return' (i64, i32))
    def block_index(i):
        return jnp.int32(0), i

    def shard_form(xs):  # n_in arrays of (1, nwords) u32
        n4 = xs[0].shape[1]
        tile = min(TILE_WORDS, n4)
        pad = (-n4) % tile
        if pad:
            z = jnp.zeros((1, pad), dtype=jnp.uint32)
            xs = tuple(jnp.concatenate([x, z], axis=1) for x in xs)
            n4 += pad
        # a stable name on the device op (else ``%run.N``)
        with jax.named_scope("ec_packed_encode"):
            outs = pl.pallas_call(
                kernel,
                grid=(n4 // tile,),
                in_specs=[
                    pl.BlockSpec((1, tile), block_index)
                    for _ in range(n_in)
                ],
                out_specs=[
                    pl.BlockSpec((1, tile), block_index)
                    for _ in range(m_out)
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((1, n4), jnp.uint32)
                    for _ in range(m_out)
                ],
                interpret=interpret,
            )(*xs)
        if pad:
            outs = [o[:, : n4 - pad] for o in outs]
        return outs

    def stripe_form(x):  # ONE (B, n_in, R, 128) u32 array
        b, k, r, lanes = x.shape
        assert (k, lanes) == (n_in, 128), x.shape
        tb, tr = _stripe_block(b, r)

        def chunk_of(j):
            # input j is chunk j of every stripe, read where it lies:
            # the body is elementwise over its blocks, so the specs
            # alone do the fold (an edge block's padding is harmless)
            return lambda bi, ri: (bi, jnp.int32(j), ri, jnp.int32(0))

        def out_index(bi, ri):
            return bi, ri, jnp.int32(0)

        with jax.named_scope("ec_packed_encode"):
            return pl.pallas_call(
                kernel,
                grid=(pl.cdiv(b, tb), pl.cdiv(r, tr)),
                in_specs=[
                    pl.BlockSpec((tb, None, tr, 128), chunk_of(j))
                    for j in range(n_in)
                ],
                out_specs=[
                    pl.BlockSpec((tb, tr, 128), out_index)
                    for _ in range(m_out)
                ],
                out_shape=[
                    jax.ShapeDtypeStruct((b, r, 128), jnp.uint32)
                    for _ in range(m_out)
                ],
                interpret=interpret,
            )(*[x] * n_in)

    @jax.jit
    def run(*xs):
        """The operand's form picks the specs: shard form, n_in
        regions that already are separate arrays, or stripe form, the
        ONE array of :func:`stripe_words` — coding output i then comes
        back ``(B, R, 128)``, which flattened IS the folded shard."""
        if len(xs) == 1 and xs[0].ndim == 4:
            return stripe_form(xs[0])
        return shard_form(xs)

    return run


def prebuilt_word_call(bm: np.ndarray, w: int = 8, *, interpret: bool = False):
    """Public constructor of the cached word-form kernel for one
    bitmatrix: returns ``call(*k_word_arrays) -> m_word_arrays``.
    For callers (benchmarks, device-resident pipelines) that apply
    the same matrix repeatedly and want to hold the compiled callable
    rather than re-entering packed_word_regions' conversion layer."""
    bm = np.asarray(bm)
    assert supports(bm, w), "packed kernel needs w=8, row popcount <= 255"
    return _packed_call(
        _rows_of(bm), bm.shape[1] // 8, bm.shape[0] // 8, interpret
    )


def packed_word_regions(
    bm: np.ndarray, words, *, interpret: bool = False
):
    """Apply a (m·8, k·8) GF(2) bitmatrix (word layout, w=8) to k
    word-form regions → m word-form regions (each (1, nwords) u32)."""
    bm = np.asarray(bm)
    assert supports(bm, 8), "packed kernel needs w=8, row popcount <= 255"
    words = [jnp.asarray(x) for x in words]
    return _packed_call(
        _rows_of(bm), len(words), bm.shape[0] // 8, interpret
    )(*words)


def packed_bitmatrix_regions(
    bm: np.ndarray, regions: np.ndarray, *, interpret: bool = False
) -> np.ndarray:
    """numpy-in/numpy-out convenience: (k, nbytes) u8 → (m, nbytes)
    u8, converting at the host boundary where views are free."""
    outs = packed_word_regions(
        bm, to_words(np.asarray(regions)), interpret=interpret
    )
    return from_words([np.asarray(o) for o in outs])
