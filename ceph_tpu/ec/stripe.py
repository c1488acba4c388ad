"""Stripe layer — the batching seam (src/osd/ECUtil.{h,cc}).

``StripeInfo`` is the stripe_width/chunk_size offset algebra
(ECUtil.h:27-100).  ``encode``/``decode`` replace the reference's
per-stripe plugin-call loop (ECUtil.cc:123-162, :12-48) with ONE
batched device call across all stripes for matrix code families — the
hoisted seam SURVEY.md §3.1 identifies — falling back to the per-stripe
loop for layered codes.  ``HashInfo`` keeps the cumulative per-shard
crc32c persisted as the hinfo xattr (ECUtil.cc:164-248).

What an encode copies: its input ONCE.  A shard is chunk i of every
stripe, concatenated — the folded region layout — so the backend's
fold (``matrix_stripe_shards``; span ``ec_fold`` round the row copies
on the packed device path) is the k data shards, and the m rows that
come back are the coding shards as they arrive (``ec_unfold`` brackets
their views as bytes, ``ec_assemble`` the ``{position: shard}`` dict
here: no payload byte moves in either).  On the packed path the fold
runs under the link wherever the chunk is whole 4096-byte tiles: the
upload's source is then the caller's buffer itself, which the kernel
reads in stripe form.  The coalesced ``encode_batch`` gets stripe-form
results and lays them out with ``_assemble_shards``; ``decode`` takes
and returns shards as stored and copies nothing but the fetch;
``repair`` is its fractional twin (CLAY's minimum-bandwidth repair:
helpers' sub-chunk fragments in, the lost shard out, one dispatch).
"""

from __future__ import annotations

import numpy as np

from ..common import tracing
from ..native import ceph_crc32c
from .backend import _host_row
from .interface import ErasureCodeError


class StripeInfo:
    """stripe_width = k * chunk_size; logical↔chunk offset algebra."""

    def __init__(self, k: int, stripe_width: int):
        if stripe_width % k:
            raise ErasureCodeError(
                f"stripe_width {stripe_width} not divisible by k={k}"
            )
        self.stripe_width = stripe_width
        self.chunk_size = stripe_width // k

    def logical_aligned(self, offset: int) -> bool:
        return offset % self.stripe_width == 0

    def logical_to_prev_chunk_offset(self, offset: int) -> int:
        return (offset // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, offset: int) -> int:
        return (
            (offset + self.stripe_width - 1) // self.stripe_width
        ) * self.chunk_size

    def logical_to_prev_stripe_offset(self, offset: int) -> int:
        return offset - (offset % self.stripe_width)

    def logical_to_next_stripe_offset(self, offset: int) -> int:
        rem = offset % self.stripe_width
        return offset + (self.stripe_width - rem) if rem else offset

    def aligned_logical_offset_to_chunk_offset(self, offset: int) -> int:
        assert offset % self.stripe_width == 0
        return (offset // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, offset: int) -> int:
        assert offset % self.chunk_size == 0
        return (offset // self.chunk_size) * self.stripe_width

    def offset_len_to_stripe_bounds(
        self, offset: int, length: int
    ) -> tuple[int, int]:
        start = self.logical_to_prev_stripe_offset(offset)
        end = self.logical_to_next_stripe_offset(offset + length)
        return start, end - start


def _kstats():
    """Lazy: ceph_tpu.ops pulls in the device runtime and registers
    the jax backend through ceph_tpu.ec — importing it at module
    scope here would be circular."""
    from ..ops.kernel_stats import kernel_stats

    return kernel_stats()


def _matrix_fast_path(ec):
    """The ONE eligibility gate for the batched matrix device path
    (shared by encode and encode_batch so the two can never drift):
    returns (matrix, backend, ok) where ok means the code family's
    whole-word matrix math is safe to batch.  Bitmatrix techniques
    (cauchy/liberation/blaum_roth) carry a .matrix too, but encode
    through XOR schedules over packet planes — the word-wise matrix
    path would corrupt them; chunk remapping likewise bails."""
    matrix = getattr(ec, "matrix", None)
    backend = getattr(ec, "backend", None)
    ok = (
        matrix is not None
        and getattr(ec, "bitmatrix", None) is None
        and backend is not None
        and not ec.get_chunk_mapping()
    )
    return matrix, backend, ok


def _host_loop(kind: str, ec, ops: int, stripes: int, bytes_in: int):
    """THE bracket of the per-stripe fallbacks (layered and bitmatrix
    codes, shards that are not whole words): one host-path
    flight-recorder entry of ``kind`` for the whole loop, under the
    codec's backend name — the inner ``ec.encode`` / ``ec._decode``
    calls record nothing themselves."""
    from ..ops.profiler import dispatch_profiler

    return dispatch_profiler().dispatch(
        kind,
        backend=getattr(getattr(ec, "backend", None), "name", None)
        or "cpu",
        ops=ops,
        stripes=stripes,
        bytes_in=bytes_in,
    )


def _logical_buffer(sinfo: StripeInfo, data) -> np.ndarray:
    """An encode's input as a 1-D uint8 array (the one coercion of
    ec/backend._host_row); whole stripes or an error."""
    buf = _host_row(data)
    if len(buf) % sinfo.stripe_width:
        raise ErasureCodeError(
            f"logical size {len(buf)} not stripe aligned"
        )
    return buf


def _assemble_shards(
    stripes: np.ndarray, coding: np.ndarray, k: int, n: int, want=None
) -> dict[int, np.ndarray]:
    """(B, k, chunk) data stripes + (B, m, chunk) coding → the
    per-shard concatenated-chunk dict — the ONE assembly of
    stripe-form results, ``encode_batch``'s (``encode`` gets its
    shards whole from the backend; tests/test_residency.py holds the
    two byte-identical)."""
    out: dict[int, np.ndarray] = {}
    for i in range(k):
        if want is None or i in want:
            out[i] = np.ascontiguousarray(
                stripes[:, i, :]
            ).reshape(-1)
    for j in range(n - k):
        if want is None or k + j in want:
            out[k + j] = np.ascontiguousarray(
                coding[:, j, :]
            ).reshape(-1)
    return out


def encode(
    sinfo: StripeInfo, ec, data: bytes | np.ndarray, want=None
) -> dict[int, np.ndarray]:
    """All stripes of ``data`` → per-shard concatenated chunks.

    Matrix code families take the batched path: (B, k, chunk) in one
    device call (``matrix_stripe_shards``), whose k folded data rows
    and m coding rows ARE the shards — the dict is built of them with
    no further copy, so the data shards of one call are rows of one
    buffer (for one stripe: views of ``data``) and nobody writes into
    them; others run the reference's per-stripe loop.  Either way the
    call lands in the ``l_tpu_ec_encode_*`` kernel counters (calls,
    bytes in/out, sync-bounded latency)."""
    buf = _logical_buffer(sinfo, data)
    n = ec.get_chunk_count()
    k = ec.get_data_chunk_count()
    if want is None:
        want = set(range(n))
    nstripes = len(buf) // sinfo.stripe_width
    if nstripes == 0:
        return {}

    with _kstats().timed("ec_encode", bytes_in=buf.nbytes) as kt:
        matrix, backend, ok = _matrix_fast_path(ec)
        if ok:
            stripes = buf.reshape(nstripes, k, sinfo.chunk_size)
            data_rows, coding_rows = backend.matrix_stripe_shards(
                matrix, stripes, ec.w
            )
            with tracing.stage("ec_assemble"):
                out = {
                    p: row
                    for p, row in enumerate(data_rows + coding_rows)
                    if p in want
                }
        else:
            with _host_loop("ec_encode", ec, 1, nstripes, buf.nbytes):
                parts = {i: [] for i in range(n)}
                for s in range(nstripes):
                    stripe = buf[
                        s * sinfo.stripe_width : (s + 1) * sinfo.stripe_width
                    ]
                    encoded = ec.encode(set(range(n)), stripe)
                    for i, chunk in encoded.items():
                        parts[i].append(chunk)
                out = {
                    i: np.concatenate(p)
                    for i, p in parts.items()
                    if i in want
                }
        kt.bytes_out = sum(v.nbytes for v in out.values())
        return out


def encode_batch(
    sinfo: StripeInfo, ec, buffers
) -> list[dict[int, np.ndarray]]:
    """Coalesced multi-object encode: every buffer's stripes ride ONE
    pipelined device pass (``matrix_stripes_batch`` — async
    double-buffered transfers, sync at the end) instead of one
    dispatch per object.  Byte-identical to per-buffer :func:`encode`
    by construction (same per-stripe math), proven in
    tests/test_residency.py.  Falls back to the per-buffer loop for
    layered/bitmatrix codes or single-object batches.

    Each coalesced dispatch counts in
    ``l_tpu_batch_encode_{dispatches,ops_per_dispatch}``.
    """
    bufs = [_logical_buffer(sinfo, b) for b in buffers]
    n = ec.get_chunk_count()
    k = ec.get_data_chunk_count()
    matrix, backend, ok = _matrix_fast_path(ec)
    if not ok or len(bufs) < 2:
        return [encode(sinfo, ec, buf) for buf in bufs]

    stripe_arrays = [
        buf.reshape(
            len(buf) // sinfo.stripe_width, k, sinfo.chunk_size
        )
        for buf in bufs
    ]
    ks = _kstats()
    from ..ops.residency import ensure_counters

    ensure_counters(ks)
    total = sum(buf.nbytes for buf in bufs)
    with ks.timed("ec_encode", bytes_in=total) as kt:
        codings = backend.matrix_stripes_batch(
            matrix, stripe_arrays, ec.w
        )
        ks.perf.inc("l_tpu_batch_encode_dispatches")
        ks.perf.inc("l_tpu_batch_encode_ops_per_dispatch", len(bufs))
        out: list[dict[int, np.ndarray]] = []
        for stripes, coding in zip(stripe_arrays, codings):
            if stripes.shape[0] == 0:
                out.append({})
                continue
            out.append(_assemble_shards(stripes, coding, k, n))
        kt.bytes_out = sum(
            v.nbytes for shards in out for v in shards.values()
        )
    return out


def survivor_basis(
    matrix: np.ndarray, erasures, k: int, w: int
) -> tuple[np.ndarray, list[int]]:
    """The survivor basis B⁻¹ (k × k over GF(2^w)) and the k survivor
    ids it spans: B⁻¹ @ survivor_chunks = data_chunks.  A thin
    error-translating wrapper over :func:`gf.survivor_basis` — the
    SAME implementation the per-op decode's make_decoding_matrix
    builds on, so the batched and per-op paths can never pick
    different systems."""
    from .. import gf

    try:
        return gf.survivor_basis(matrix, erasures, k, w)
    except (ValueError, np.linalg.LinAlgError) as e:
        raise ErasureCodeError(f"{e} (-EIO)")


def reconstruction_rows(
    matrix: np.ndarray, want, available, k: int, w: int
) -> tuple[np.ndarray, list[int]]:
    """ONE GF(2^w) matrix that rebuilds every wanted chunk (data or
    coding) straight from the k chosen survivors — the whole-PG repair
    collapses to a single matrix × survivor-regions dispatch.  Wanted
    data chunks take their B⁻¹ row; wanted coding chunks compose the
    generator row with B⁻¹ (exact field algebra, so the result is
    byte-identical to decode-data-then-re-encode).  Returns
    (rows[len(want), k], survivors)."""
    from .. import gf

    n = k + matrix.shape[0]
    erasures = sorted(set(range(n)) - set(available))
    binv, survivors = survivor_basis(matrix, erasures, k, w)
    rows = []
    for p in sorted(want):
        if p < k:
            rows.append(binv[p])
        else:
            rows.append(
                gf.matrix_multiply(
                    matrix[p - k : p - k + 1], binv, w
                )[0]
            )
    return np.array(rows, dtype=np.int64).reshape(len(rows), k), survivors


def decode_reconstruction(ec, want, available):
    """The decode analog of :func:`_matrix_fast_path`: a
    (rows, survivors, w, backend) plan that rebuilds ``want`` from
    ``available`` in one batched device dispatch, or None when the
    code family cannot express its repair as whole-word matrix math
    (bitmatrix/layered codes without a ``decode_matrix`` hook, chunk
    remapping, unsolvable systems)."""
    hook = getattr(ec, "decode_matrix", None)
    try:
        if hook is not None:
            return hook(set(want), set(available))
        matrix, backend, ok = _matrix_fast_path(ec)
        if not ok:
            return None
        rows, survivors = reconstruction_rows(
            matrix, want, available, ec.get_data_chunk_count(), ec.w
        )
    except ErasureCodeError:
        return None
    return rows, survivors, ec.w, backend


def _decode_one(ec, shards: dict[int, np.ndarray], want) -> dict:
    """Per-object decode-from-survivors — the reference per-op repair
    path (ErasureCode::_decode) and the oracle the batched dispatch
    must match byte for byte; one ``l_tpu_ec_decode_*`` call an
    object."""
    with _kstats().timed(
        "ec_decode", bytes_in=sum(len(v) for v in shards.values())
    ) as kt:
        chunks = {i: _host_row(v) for i, v in shards.items()}
        decoded = ec._decode(set(want), chunks)
        out = {
            p: np.ascontiguousarray(decoded[p], dtype=np.uint8)
            for p in sorted(want)
        }
        kt.bytes_out = sum(len(v) for v in out.values())
    return out


def decode_batch(
    sinfo: StripeInfo, ec, shard_sets, want
) -> list[dict]:
    """Coalesced decode-from-survivors: rebuild the SAME missing
    positions (``want`` — the dead OSD's shards) for MANY objects in
    one pipelined device pass, the repair-side twin of
    :func:`encode_batch` (ROADMAP open item 2).

    ``shard_sets`` is one dict per object of survivor shard payloads
    ({position: bytes | ndarray | DeviceBuf}); resident DeviceBufs
    ride the dispatch without re-uploading (the residency cache paid
    the link already), host payloads upload once, double-buffered
    against compute.  Returns one {position: reconstructed} dict per
    object — DeviceBuf tokens (device-born, zero extra transfer to
    register resident) when the device backend ran, numpy arrays on
    the host fallback.  Byte-identical to the per-object
    ``ec._decode`` repair by construction; a group whose survivors
    are ragged or unaligned degrades to it (device errors propagate).

    Each coalesced dispatch counts in
    ``l_tpu_batch_decode_{dispatches,ops_per_dispatch}``.
    """
    want = sorted(set(want))
    out: list[dict | None] = [None] * len(shard_sets)
    groups: dict[frozenset, list[int]] = {}
    for i, shards in enumerate(shard_sets):
        groups.setdefault(frozenset(shards), []).append(i)
    ks = _kstats()
    from ..ops.residency import ensure_counters

    ensure_counters(ks)
    cs = sinfo.chunk_size
    for key, idxs in groups.items():
        plan = (
            decode_reconstruction(ec, want, key)
            if len(idxs) >= 2 and not (set(want) & key)
            else None
        )
        batched = False
        if plan is not None:
            rows, survivors, w, backend = plan
            try:
                row_sets = []
                total = 0
                for i in idxs:
                    rows_i = [shard_sets[i][s] for s in survivors]
                    lengths = {len(r) for r in rows_i}
                    if len(lengths) != 1:
                        raise ErasureCodeError(
                            "survivor shards must be equal length"
                        )
                    (length,) = lengths
                    if length % cs or length == 0:
                        raise ErasureCodeError(
                            "shard length not chunk aligned"
                        )
                    total += length * len(rows_i)
                    row_sets.append(rows_i)
                with ks.timed("ec_decode", bytes_in=total) as kt:
                    outs = backend.decode_stripes_batch(
                        rows, row_sets, w, cs
                    )
                    kt.bytes_out = sum(
                        int(np.prod(o.shape)) for o in outs
                    )
                ks.perf.inc("l_tpu_batch_decode_dispatches")
                ks.perf.inc(
                    "l_tpu_batch_decode_ops_per_dispatch", len(idxs)
                )
                for i, rec in zip(idxs, outs):
                    out[i] = _wrap_decoded(rec, want)
                batched = True
            except ErasureCodeError:
                # mixed geometry (ragged or unaligned survivors): this
                # group takes the per-object repair path.  A backend,
                # compile or runtime error is not caught — the caller
                # asked for the device and must hear that it failed
                batched = False
        if not batched:
            nbytes = sum(
                len(v) for i in idxs for v in shard_sets[i].values()
            )
            with _host_loop("ec_decode", ec, len(idxs), 0, nbytes):
                for i in idxs:
                    out[i] = _decode_one(ec, shard_sets[i], want)
    return out


def _wrap_decoded(rec, want) -> dict:
    """One object's (nstripes, len(want), chunk) reconstruction →
    {position: payload}.  Device arrays wrap as device-born
    DeviceBufs (the push/write path fetches host bytes at most once;
    registering them resident costs zero extra transfer); numpy
    results stay numpy."""
    if isinstance(rec, np.ndarray):
        return {
            p: np.ascontiguousarray(rec[:, j, :]).reshape(-1)
            for j, p in enumerate(want)
        }
    from ..ops.residency import DeviceBuf

    return {
        p: DeviceBuf(dev=rec[:, j, :].reshape(-1))
        for j, p in enumerate(want)
    }


def _shard_views(
    sinfo: StripeInfo, shards
) -> tuple[dict[int, np.ndarray], int]:
    """{position: 1-D uint8 view} of one object's shards and the
    number of stripes they hold; ragged or unaligned shards are an
    error."""
    lengths = {len(v) for v in shards.values()}
    if len(lengths) != 1:
        raise ErasureCodeError("shards must be equal length")
    (shard_len,) = lengths
    if shard_len % sinfo.chunk_size:
        raise ErasureCodeError("shard length not chunk aligned")
    views = {
        i: np.frombuffer(bytes(v), dtype=np.uint8)
        if isinstance(v, (bytes, bytearray, memoryview))
        else np.ascontiguousarray(v, dtype=np.uint8)
        for i, v in shards.items()
    }
    return views, shard_len // sinfo.chunk_size


def _rebuild(
    sinfo: StripeInfo, ec, views: dict, nstripes: int, missing
) -> dict[int, np.ndarray]:
    """The ``missing`` shards of one object from the shards in
    ``views``, every stripe at once.  A code whose repair is
    whole-word matrix math (:func:`decode_reconstruction`) takes ONE
    dispatch: a shard is chunk i of every stripe, which is the folded
    region layout already, so the survivors go to the backend as they
    are stored and the rebuilt shards come back whole
    (``matrix_shards``).  Bitmatrix and layered codes run the
    reference's per-stripe ``ec._decode`` loop inside one flight-
    recorder entry, as :func:`encode`'s fallback does.  Byte-identical
    either way."""
    with tracing.stage("ec_plan"):
        plan = decode_reconstruction(ec, missing, views)
    # shards travel to the device as 32-bit words
    if plan is not None and sinfo.chunk_size % 4 == 0:
        rows, survivors, w, backend = plan
        rebuilt = backend.matrix_shards(
            rows, [views[s] for s in survivors], w, nstripes
        )
        return dict(zip(sorted(missing), rebuilt))
    cs = sinfo.chunk_size
    with _host_loop(
        "ec_decode", ec, 1, nstripes, sum(v.nbytes for v in views.values())
    ):
        parts: dict[int, list] = {p: [] for p in missing}
        for s in range(nstripes):
            chunks = {
                i: v[s * cs : (s + 1) * cs] for i, v in views.items()
            }
            decoded = ec._decode(set(missing), chunks)
            for p in missing:
                parts[p].append(decoded[p])
        return {p: np.concatenate(c) for p, c in parts.items()}


def decode(
    sinfo: StripeInfo, ec, shards: dict, want
) -> dict[int, np.ndarray]:
    """The B-stripe form of ``ErasureCode.decode(want, chunks)``
    (ECUtil::decode's per-stripe loop hoisted, the twin of
    :func:`encode`): the ``want`` shards of one object from the
    shards at hand, as host arrays.  A wanted shard that is at hand
    comes back as it was given; the others are rebuilt, for a matrix
    code in one ``ec_decode`` dispatch (:func:`_rebuild`)."""
    views, nstripes = _shard_views(sinfo, shards)
    want = sorted(set(want))
    out = {p: views[p] for p in want if p in views}
    missing = [p for p in want if p not in views]
    if not missing or nstripes == 0:
        return out
    with _kstats().timed(
        "ec_decode", bytes_in=sum(v.nbytes for v in views.values())
    ) as kt:
        rebuilt = _rebuild(sinfo, ec, views, nstripes, missing)
        kt.bytes_out = sum(v.nbytes for v in rebuilt.values())
    out.update(rebuilt)
    return out


def repair_reconstruction(ec, lost: int, helpers):
    """The fractional-repair analog of :func:`decode_reconstruction`,
    and the ONE gate of :func:`repair`'s device path: a (matrix,
    order, w, backend) plan that rebuilds chunk ``lost`` from the
    sub-chunks ``minimum_to_decode`` has ``helpers`` read, or None for
    a code without a ``repair_matrix`` hook (every family but CLAY) or
    a profile whose hook declines.  The span ``ec_repair_plan`` is
    round the hook: the matrix's build at first sight of a (lost,
    helpers), a dictionary hit after."""
    hook = getattr(ec, "repair_matrix", None)
    if hook is None:
        return None
    with tracing.stage("ec_repair_plan"):
        return hook(lost, set(helpers))


def repair(
    sinfo: StripeInfo, ec, fragments: dict, lost: int
) -> np.ndarray:
    """The B-stripe form of ``ec.decode({lost}, partial, chunk_size)``
    (ECUtil::decode's sub-chunk loop, src/osd/ECUtil.cc:82-116,
    hoisted; the twin of :func:`decode`): shard ``lost`` of one
    object, as stored, from ``fragments`` — ``{helper: payload}``, a
    stripe after another, each stripe the sub-chunk runs that
    ``minimum_to_decode({lost}, helpers)`` names, concatenated.  A
    code whose repair is one matrix (:func:`repair_reconstruction`)
    takes ONE ``ec_decode`` dispatch on its backend, the fragments
    going up as they are stored (``matrix_shards`` with sub-chunk
    rows); any other runs the per-stripe loop inside one
    flight-recorder entry, as :func:`_rebuild`'s fallback does.
    Byte-identical either way.  Counted in
    ``l_tpu_ec_repair_{calls,helper_bytes,rebuilt_bytes}``."""
    views = {h: _host_row(v) for h, v in fragments.items()}
    sub = ec.get_sub_chunk_count()
    cs = sinfo.chunk_size
    if cs % sub:
        raise ErasureCodeError(
            f"chunk size {cs} is not {sub} whole sub-chunks"
        )
    sc = cs // sub
    minimum = ec.minimum_to_decode({lost}, set(views))
    if set(minimum) != set(views):
        raise ErasureCodeError(
            f"fragments of {sorted(views)} given, the repair of "
            f"{lost} reads {sorted(minimum)}"
        )
    per_stripe = {
        h: sc * sum(count for _off, count in runs)
        for h, runs in minimum.items()
    }
    counts = {
        divmod(len(views[h]), per_stripe[h]) for h in views
    }
    if len(counts) != 1 or next(iter(counts))[1]:
        raise ErasureCodeError(
            "fragments must hold the same whole number of stripes"
        )
    ((nstripes, _),) = counts
    if nstripes == 0:
        return np.zeros(0, dtype=np.uint8)
    ks = _kstats()
    nbytes = sum(v.nbytes for v in views.values())
    plan = repair_reconstruction(ec, lost, views)
    # fragments travel to the device as 32-bit words
    if plan is not None and sc % 4 == 0:
        matrix, order, w, backend = plan
        (rebuilt,) = backend.matrix_shards(
            matrix,
            [views[h] for h in order],
            w,
            nstripes,
            sub_rows=(matrix.shape[1] // len(order), sub),
        )
    else:
        with _host_loop("ec_decode", ec, 1, nstripes, nbytes):
            rebuilt = np.concatenate(
                [
                    ec.decode(
                        {lost},
                        {
                            h: v[s * per_stripe[h] : (s + 1) * per_stripe[h]]
                            for h, v in views.items()
                        },
                        cs,
                    )[lost]
                    for s in range(nstripes)
                ]
            )
    for suffix, amount, desc in (
        ("calls", 1, "fractional repairs through ec/stripe.repair"),
        ("helper_bytes", nbytes, "fragment bytes the repairs were handed"),
        ("rebuilt_bytes", rebuilt.nbytes, "shard bytes the repairs rebuilt"),
    ):
        ks.perf.inc(ks.counter("ec_repair", suffix, desc=desc), amount)
    return rebuilt


def decode_concat(
    sinfo: StripeInfo, ec, shards: dict[int, np.ndarray]
) -> np.ndarray:
    """Concat-decode every stripe back to logical bytes
    (ECUtil.cc:12-48): the data shards, the missing ones rebuilt by
    :func:`decode`'s one dispatch, interleaved a chunk a stripe."""
    views, nstripes = _shard_views(sinfo, shards)
    if nstripes == 0:
        return np.zeros(0, dtype=np.uint8)
    data = [ec.chunk_index(i) for i in range(ec.get_data_chunk_count())]
    with _kstats().timed(
        "ec_decode", bytes_in=sum(v.nbytes for v in views.values())
    ) as kt:
        missing = [p for p in data if p not in views]
        if missing:
            views = {
                **views,
                **_rebuild(sinfo, ec, views, nstripes, missing),
            }
        with tracing.stage("ec_assemble"):
            res = np.stack(
                [views[p].reshape(nstripes, -1) for p in data], axis=1
            ).reshape(-1)
        kt.bytes_out = res.nbytes
        return res


class HashInfo:
    """Cumulative per-shard crc32c, persisted as the hinfo_key xattr
    (ECUtil.cc:164-248); seeds start at -1 like the reference."""

    def __init__(self, num_chunks: int):
        self.cumulative_shard_hashes = [0xFFFFFFFF] * num_chunks
        self.total_chunk_size = 0

    def append(self, old_size: int, to_append: dict[int, np.ndarray]):
        assert old_size == self.total_chunk_size
        size = len(next(iter(to_append.values())))
        for i, chunk in to_append.items():
            assert len(chunk) == size
            self.cumulative_shard_hashes[i] = ceph_crc32c(
                self.cumulative_shard_hashes[i], bytes(chunk)
            )
        self.total_chunk_size += size

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

    def clear(self):
        self.total_chunk_size = 0
        self.cumulative_shard_hashes = [
            0xFFFFFFFF for _ in self.cumulative_shard_hashes
        ]


def rmw_range(
    sinfo: StripeInfo, offset: int, length: int, old_size: int
) -> tuple[int, int, set[int]]:
    """The WritePlan head/tail analysis (ECBackend.cc:1858 start_rmw):
    for a partial overwrite of [offset, offset+length), returns
    (first_stripe, end_stripe, stripes_to_read) — only the partially
    covered head/tail stripes that hold pre-existing bytes need
    reading; fully-covered and beyond-EOF stripes encode fresh."""
    sw = sinfo.stripe_width
    start, span = sinfo.offset_len_to_stripe_bounds(offset, length)
    first, end = start // sw, (start + span) // sw
    old_stripes = sinfo.logical_to_next_stripe_offset(old_size) // sw
    need: set[int] = set()
    if offset % sw and first < old_stripes:
        need.add(first)
    if (offset + length) % sw and end - 1 < old_stripes:
        need.add(end - 1)
    return first, end, need


def rmw_encode(
    sinfo: StripeInfo,
    ec,
    offset: int,
    data: bytes,
    old_size: int,
    read_stripes,
) -> tuple[int, int, np.ndarray, dict[int, np.ndarray]]:
    """Shared stripe-granular RMW assembly used by BOTH the store
    pipeline (ECStore.write) and the daemon's EC write path
    (osd/ec_pg.rmw_write_txns): read the needed stripes through the
    caller's ``read_stripes(sorted_stripe_list) -> {stripe: bytes}``
    (extent-cache-aware in the store, sub-op reads in the daemon),
    overlay the new bytes, and re-encode just the covered range.
    Returns (first_stripe, end_stripe, range_buffer, shards)."""
    data = bytes(data)
    sw = sinfo.stripe_width
    first, end, need = rmw_range(sinfo, offset, len(data), old_size)
    existing = read_stripes(sorted(need))
    buf = np.zeros((end - first) * sw, dtype=np.uint8)
    for s, stripe in existing.items():
        buf[(s - first) * sw : (s - first + 1) * sw] = np.frombuffer(
            bytes(stripe), dtype=np.uint8
        )
    lo = offset - first * sw
    buf[lo : lo + len(data)] = np.frombuffer(data, dtype=np.uint8)
    shards = encode(sinfo, ec, buf)
    return first, end, buf, shards
