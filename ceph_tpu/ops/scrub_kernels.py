"""Device-batched deep-scrub kernels — crc32c over a whole PG's
objects in one vectorized call, plus the re-encode compare reduce.

The reference deep scrub checksums every object with a per-object
CPU crc pass (``build_scrub_map_chunk`` → ``ceph_crc32c``,
src/osd/PGBackend.cc:1175); here the whole chunk of objects rides ONE
device call by lifting crc32c to GF(2) linear algebra over the
existing bit-plane matmul contract (ops/bitops.py conventions,
ops/gf_matmul.py mod-2 matmul idiom):

- The crc32c register update for one byte, ``crc' = (crc >> 8) ^
  T0[(crc ^ b) & 0xff]``, is linear over GF(2) in (crc, byte):
  ``crc' = L(crc ⊕ b)`` with L a fixed 32×32 bit matrix derived from
  the Castagnoli table (the SAME table ``native/crc32c.c`` builds).
- Four bytes at a time: with the little-endian u32 word w,
  ``crc' = F(crc ⊕ w)`` where ``F = L⁴`` (the slicing-by-4 identity
  the reference's slicing-by-8 loop is built on).
- So over m words, ``crc = F^m(init) ⊕ Σ_i F^(m-i)(w_i)`` — the data
  term is ONE (n, m·32) @ (m·32, 32) mod-2 matmul over the objects'
  word bits.  LSB-first byte unpacking IS the LE-u32 bit order, so no
  relayout is needed.
- Lengths vary per object: buffers are RIGHT-aligned (leading zero
  words contribute nothing to the data term, exactly like leading
  zeros keep a zero register at zero), and the per-object init term
  ``L^len(init)`` folds in host-side via 32×32 matrix powers.
- The matmul is two-level so the device matrix stays small: a cached
  per-chunk matrix (``_CHUNK`` bytes) computes chunk-local terms, and
  a cached combine matrix advances each chunk by ``F^(words/chunk)``
  to its distance from the end — both matrices compile/transfer once
  per shape (the ErasureCodeIsaTableCache idiom, counted in the
  ``l_tpu_compile_cache_*`` kernel stats).

Golden-checked against the reference crc32c test vectors
(src/test/common/test_crc32c.cc) and the native slicing-by-8 C
implementation.  ``batch_compare`` is the deep-scrub re-encode
verifier: stored shard bytes vs re-encoded shard bytes in one
device-side any-mismatch reduce.

``backend="oracle"`` selects the native-C loop; otherwise the JAX
backend runs them, and a backend, compile or runtime error propagates —
nothing here hands the work to the host behind the caller's back.
"""

from __future__ import annotations

import functools

import numpy as np

from ..native import ceph_crc32c

# reference test vectors (src/test/common/test_crc32c.cc): (init,
# payload, crc) — the parity tests AND the import-time self-check of
# the matrix construction both anchor on these
GOLDEN_VECTORS = (
    (0, b"foo bar baz", 4119623852),
    (4294967295, b"", 4294967295),
    (0, b"", 0),
    (1, b"", 1),
)

_CHUNK = 4096  # bytes per device chunk row (multiple of 4)


# -- host-side GF(2) matrix algebra (32x32, entries 0/1) --------------------


@functools.lru_cache(maxsize=1)
def _crc_table() -> list[int]:
    """T0 of the Castagnoli table — shared derivation with
    native/crc32c.c (reflected, poly 0x1EDC6F41)."""
    from ..native import _py_table

    return _py_table()


def _byte_step(x: int) -> int:
    """One crc32c register step with a zero input byte: L(x)."""
    return ((x >> 8) ^ _crc_table()[x & 0xFF]) & 0xFFFFFFFF


def _to_bits(x: int) -> np.ndarray:
    return np.array(
        [(x >> c) & 1 for c in range(32)], dtype=np.uint8
    )


def _from_bits(v: np.ndarray) -> int:
    return int(sum(int(b) << c for c, b in enumerate(v)))


@functools.lru_cache(maxsize=1)
def _L() -> np.ndarray:
    """The per-byte transition as a (32, 32) GF(2) matrix: column c is
    L(e_c)."""
    m = np.zeros((32, 32), dtype=np.uint8)
    for c in range(32):
        m[:, c] = _to_bits(_byte_step(1 << c))
    return m


def _matmul2(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    # 32-term dot products of 0/1 values: uint8 cannot overflow... it
    # can (max 32 < 256) — keep uint8, mask mod 2
    return (a.astype(np.uint16) @ b.astype(np.uint16) % 2).astype(
        np.uint8
    )


@functools.lru_cache(maxsize=1)
def _F() -> np.ndarray:
    """F = L⁴ — the one-u32-word transition."""
    l2 = _matmul2(_L(), _L())
    return _matmul2(l2, l2)


@functools.lru_cache(maxsize=256)
def _L_pow(n: int) -> np.ndarray:
    """L^n by square-and-multiply (init-term fold for a length-n
    buffer)."""
    if n == 0:
        return np.eye(32, dtype=np.uint8)
    half = _L_pow(n // 2)
    sq = _matmul2(half, half)
    return _matmul2(_L(), sq) if n % 2 else sq


def _apply(mat: np.ndarray, x: int) -> int:
    return _from_bits(mat @ _to_bits(x) % 2)


@functools.lru_cache(maxsize=8)
def _chunk_matrix(chunk_bytes: int) -> np.ndarray:
    """(chunk_bytes*8, 32) int8: rows 32i+b map bit b of word i to the
    chunk-local crc contribution F^(mc-i)(e_b)."""
    mc = chunk_bytes // 4
    f = _F()
    rows = np.empty((mc, 32, 32), dtype=np.int8)
    p = f  # F^1 belongs to the LAST word (i = mc-1)
    for i in range(mc - 1, -1, -1):
        rows[i] = p.T
        if i:
            p = _matmul2(p, f)
    return rows.reshape(chunk_bytes * 8, 32)


@functools.lru_cache(maxsize=64)
def _combine_matrix(chunk_bytes: int, nchunks: int) -> np.ndarray:
    """(nchunks*32, 32) int8: block j advances chunk j's local crc by
    Fc^(nchunks-1-j), Fc = F^(words per chunk)."""
    fc = np.eye(32, dtype=np.uint8)
    f = _F()
    for _ in range(chunk_bytes // 4):
        fc = _matmul2(fc, f)
    blocks = np.empty((nchunks, 32, 32), dtype=np.int8)
    p = np.eye(32, dtype=np.uint8)
    for j in range(nchunks - 1, -1, -1):
        blocks[j] = p.T
        if j:
            p = _matmul2(p, fc)
    return blocks.reshape(nchunks * 32, 32)


def _self_check() -> None:
    """The matrix construction must reproduce the reference vectors
    through the PURE-HOST path before any device math is trusted."""
    for init, payload, want in GOLDEN_VECTORS:
        got = _apply(_L_pow(len(payload)), init)
        m = np.zeros(32, dtype=np.uint8)
        for i, byte in enumerate(payload):
            adv = _L_pow(len(payload) - i)
            contrib = adv @ _to_bits(byte) % 2
            m = (m + contrib) % 2
        got ^= _from_bits(m)
        if got != want:
            raise AssertionError(
                f"crc32c matrix self-check failed: "
                f"crc({init:#x}, {payload!r}) = {got} != {want}"
            )


# -- device plane -----------------------------------------------------------


@functools.lru_cache(maxsize=8)
def _device_chunk_matrix(chunk_bytes: int):
    import jax.numpy as jnp

    return jnp.asarray(_chunk_matrix(chunk_bytes))


@functools.lru_cache(maxsize=64)
def _device_combine_matrix(chunk_bytes: int, nchunks: int):
    import jax.numpy as jnp

    return jnp.asarray(_combine_matrix(chunk_bytes, nchunks))


@functools.lru_cache(maxsize=8)
def _crc_call(chunk_bytes: int, nchunks: int):
    """The jitted two-matmul crc kernel for a padded shape."""
    import jax
    import jax.numpy as jnp

    def crc_bits(rows: jnp.ndarray, gc, hc) -> jnp.ndarray:
        n = rows.shape[0]
        flat = rows.reshape(n * nchunks, chunk_bytes)
        # LSB-first byte unpack == LE-u32 word-bit order (bitops.py
        # layout contract)
        bits = (
            jnp.right_shift(
                flat[:, :, None],
                jnp.arange(8, dtype=jnp.uint8)[None, None, :],
            )
            & 1
        ).astype(jnp.int8)
        x = bits.reshape(n * nchunks, chunk_bytes * 8)
        local = (
            jax.lax.dot_general(
                x, gc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.int8)
        folded = (
            jax.lax.dot_general(
                local.reshape(n, nchunks * 32), hc,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32,
            )
            & 1
        ).astype(jnp.uint32)
        weights = jnp.left_shift(
            jnp.uint32(1), jnp.arange(32, dtype=jnp.uint32)
        )
        return (folded * weights[None, :]).sum(
            axis=1, dtype=jnp.uint32
        )

    return jax.jit(crc_bits)


def _kstats():
    from .kernel_stats import kernel_stats

    return kernel_stats()


def _gather_rows(entries, width: int, *, align_right: bool, fillers: int = 0):
    """Build an (len(entries) + fillers, width) uint8 DEVICE matrix
    from mixed host-bytes / DeviceBuf entries — the ONE pad/stack/
    permute implementation both device kernels share: every host row
    (plus the zero filler rows) rides a single bulk ``device_put``,
    resident rows pad device-side (no second transfer), and one
    permutation gather restores entry order (fillers land after the
    real rows).  All-host batches skip the gather entirely."""
    import jax
    import jax.numpy as jnp

    from .residency import DeviceBuf

    n = len(entries)
    host_idx = [
        i for i, e in enumerate(entries)
        if not isinstance(e, DeviceBuf)
    ]
    res_idx = [
        i for i, e in enumerate(entries) if isinstance(e, DeviceBuf)
    ]
    # flight-recorder byte attribution: host rows cross the link this
    # dispatch; registered-resident tokens are served where they live
    # (a lazy unregistered DeviceBuf's device() upload is a transfer)
    from .profiler import record_resident, record_upload

    record_upload(sum(len(entries[i]) for i in host_idx))
    for i in res_idx:
        (
            record_resident
            if entries[i].resident
            else record_upload
        )(len(entries[i]))
    block = np.zeros((len(host_idx) + fillers, width), dtype=np.uint8)
    for r, i in enumerate(host_idx):
        raw = bytes(entries[i])
        if raw:
            if align_right:
                block[r, width - len(raw):] = np.frombuffer(
                    raw, dtype=np.uint8
                )
            else:
                block[r, : len(raw)] = np.frombuffer(
                    raw, dtype=np.uint8
                )
    dev_block = jax.device_put(block)
    if not res_idx:
        return dev_block  # already in entry order, fillers trailing
    res_rows = jnp.stack(
        [
            jnp.pad(
                entries[i].device(),
                (width - len(entries[i]), 0)
                if align_right
                else (0, width - len(entries[i])),
            )
            for i in res_idx
        ]
    )
    perm = np.empty(n + fillers, dtype=np.int32)
    for r, i in enumerate(host_idx):
        perm[i] = r
    for f in range(fillers):
        perm[n + f] = len(host_idx) + f
    base = len(host_idx) + fillers
    for r, i in enumerate(res_idx):
        perm[i] = base + r
    return jnp.concatenate([dev_block, res_rows])[jnp.asarray(perm)]


def _oracle(buffers, inits) -> np.ndarray:
    from .profiler import dispatch_profiler
    from .residency import as_host_bytes

    with dispatch_profiler().dispatch(
        "crc32c", backend="cpu"
    ) as dp:
        dp.set_ops(len(buffers))
        dp.add_bytes_in(sum(len(b) for b in buffers))
        return np.array(
            [
                ceph_crc32c(init, as_host_bytes(buf))
                for buf, init in zip(buffers, inits)
            ],
            dtype=np.uint32,
        )


def batch_crc32c(
    buffers, inits=0, *, backend: str | None = None
) -> np.ndarray:
    """crc32c of every buffer in one device call (uint32 array).

    ``inits`` is a scalar seed or a per-buffer sequence (ceph_crc32c
    running-crc semantics; the EC HashInfo convention seeds with
    0xffffffff).  ``backend``: "oracle" = the native C loop, anything
    else the device (errors propagate).

    Entries may be host bytes OR ``ops.residency.DeviceBuf`` tokens —
    a resident buffer (e.g. a shard the EC write path just encoded)
    is consumed where it already lives instead of paying a second
    host→device transfer per stage.
    """
    buffers = list(buffers)
    if not buffers:
        return np.zeros(0, dtype=np.uint32)
    if isinstance(inits, int):
        inits = [inits] * len(buffers)
    inits = [int(x) & 0xFFFFFFFF for x in inits]
    if backend == "oracle":
        return _oracle(buffers, inits)
    return _device_crc32c(buffers, inits)


def _device_crc32c(buffers, inits) -> np.ndarray:
    from .profiler import dispatch_profiler
    from .residency import bucket_pow2, note_shape

    _self_check()
    lens = [len(b) for b in buffers]
    n = len(buffers)
    padded = _CHUNK * bucket_pow2(-(-max(max(lens), 1) // _CHUNK))
    nchunks = padded // _CHUNK
    nrows = bucket_pow2(n)
    ks = _kstats()
    with ks.timed(
        "scrub_crc32c", bytes_in=sum(lens)
    ) as kt, dispatch_profiler().dispatch(
        "crc32c", backend="jax"
    ) as dp:
        dp.set_ops(n)
        dp.add_bytes_in(sum(lens))
        # right-align zeros + pow2 filler rows: device-visible bytes
        # the shape bucket padded in
        dp.add_pad(padded * nrows - sum(lens))
        gc = ks.counted_cache_call(_device_chunk_matrix, _CHUNK)
        hc = ks.counted_cache_call(
            _device_combine_matrix, _CHUNK, nchunks
        )
        call = _crc_call(_CHUNK, nchunks)
        note_shape("scrub_crc32c", nrows, nchunks)
        # resident payloads right-align ON DEVICE (no second
        # host→device transfer); host payloads + the pow2 filler rows
        # (which crc to 0 and slice away) ride ONE bulk device_put
        with dp.stage("upload"):
            rows = _gather_rows(
                buffers, padded, align_right=True, fillers=nrows - n
            ).reshape(nrows, nchunks, _CHUNK)
        with dp.stage("compute"):
            res = call(rows, gc, hc)
        with dp.stage("sync"):
            out = np.asarray(res).astype(np.uint32)[:n]
        kt.bytes_out = out.nbytes
    # per-object init fold: crc = data_term ⊕ L^len(init)
    for i, (ln, init) in enumerate(zip(lens, inits)):
        if init:
            out[i] ^= _apply(_L_pow(ln), init)
    return out


@functools.lru_cache(maxsize=8)
def _compare_call(ncols: int):
    import jax
    import jax.numpy as jnp

    def mismatch(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
        return jnp.any(a != b, axis=1)

    return jax.jit(mismatch)


def batch_compare(stored, expected, *, backend: str | None = None):
    """Per-pair any-byte-differs verdict (bool array) — the device
    side of re-encode verification: ``stored[i]`` is the shard bytes
    on disk, ``expected[i]`` the re-encoded truth.  Length mismatches
    are verdicts on their own (no device trip needed for them).

    Entries in either list may be host bytes or
    ``ops.residency.DeviceBuf`` tokens — resident shard payloads are
    compared where they already live (no second ``device_put`` of
    bytes the EC path just uploaded); the compare width buckets to a
    power of two so ragged verify chunks replay compiled programs."""
    from .residency import as_host_bytes, bucket_pow2, note_shape

    stored = list(stored)
    expected = list(expected)
    assert len(stored) == len(expected)
    if not stored:
        return np.zeros(0, dtype=bool)
    out = np.zeros(len(stored), dtype=bool)
    same_len = [
        i for i in range(len(stored))
        if len(stored[i]) == len(expected[i])
    ]
    for i in range(len(stored)):
        if len(stored[i]) != len(expected[i]):
            out[i] = True
    if not same_len:
        return out
    width = max(len(stored[i]) for i in same_len)
    if width == 0:
        return out
    bwidth = bucket_pow2(width)

    def _host_rows(seq) -> np.ndarray:
        rows = np.zeros((len(same_len), bwidth), dtype=np.uint8)
        for row, i in enumerate(same_len):
            raw = as_host_bytes(seq[i])
            rows[row, : len(raw)] = np.frombuffer(raw, dtype=np.uint8)
        return rows

    from .profiler import dispatch_profiler

    total = sum(
        len(stored[i]) + len(expected[i]) for i in same_len
    )
    if backend != "oracle":
        ks = _kstats()
        with ks.timed(
            "scrub_verify", bytes_in=total
        ) as kt, dispatch_profiler().dispatch(
            "compare", backend="jax"
        ) as dp:
            dp.set_ops(len(same_len))
            dp.add_bytes_in(total)
            dp.add_pad(2 * bwidth * len(same_len) - total)
            with dp.stage("upload"):
                a_dev = _gather_rows(
                    [stored[i] for i in same_len], bwidth,
                    align_right=False,
                )
                b_dev = _gather_rows(
                    [expected[i] for i in same_len], bwidth,
                    align_right=False,
                )
            note_shape("scrub_verify", len(same_len), bwidth)
            with dp.stage("compute"):
                vdev = _compare_call(bwidth)(a_dev, b_dev)
            with dp.stage("sync"):
                verdict = np.asarray(vdev)
            kt.bytes_out = verdict.nbytes
        out[same_len] = verdict
        return out
    with dispatch_profiler().dispatch(
        "compare", backend="cpu"
    ) as dp:
        dp.set_ops(len(same_len))
        dp.add_bytes_in(total)
        a = _host_rows(stored)
        b = _host_rows(expected)
        out[same_len] = (a != b).any(axis=1)
    return out
