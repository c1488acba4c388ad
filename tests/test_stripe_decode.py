"""The B-stripe decode of the stripe seam (ec/stripe.decode, and
decode_concat on it): one recorded dispatch an object for matrix
codes, the per-stripe loop — recorded too — for the others, which
program a rebuild takes on the chip, and the packed encode path's
stage brackets."""

from __future__ import annotations

import itertools

import numpy as np
import pytest

import ceph_tpu.ops  # noqa: F401  registers the jax backend
from ceph_tpu.common import tracing
from ceph_tpu.ec import ErasureCodeProfile, registry_instance
from ceph_tpu.ec.stripe import (
    StripeInfo, decode, decode_batch, decode_concat, encode)
from ceph_tpu.ops import ec_backend, packed_gf
from ceph_tpu.ops.kernel_stats import kernel_stats
from ceph_tpu.ops.profiler import dispatch_profiler


def _code(plugin="jerasure", **profile):
    return registry_instance().factory(plugin, ErasureCodeProfile(**profile))


def _records(kind):
    return dispatch_profiler().history(kind)["entries"]


def _stage_counts(*names):
    dump = kernel_stats().dump()
    return [dump.get(f"l_stage_{name}_count", 0) for name in names]


@pytest.mark.parametrize("backend", ["jax", "numpy"])
def test_one_dispatch_rebuilds_every_pair_as_the_per_stripe_decode_does(backend):
    """All 55 pairs of k=8 m=3: stripe.decode's shards are the
    per-stripe ``ec.decode``'s, a chunk a stripe, and each call is one
    ``ec_decode`` record of B stripes."""
    ec = _code(technique="reed_sol_van", k="8", m="3", w="8", backend=backend)
    chunk, nstripes = 256, 5
    sinfo = StripeInfo(8, 8 * chunk)
    data = np.random.default_rng(11).integers(
        0, 256, nstripes * 8 * chunk, dtype=np.uint8)
    shards = encode(sinfo, ec, data)
    for lost in itertools.combinations(range(11), 2):
        have = {p: s for p, s in shards.items() if p not in lost}
        seen = len(_records("ec_decode"))
        plans = _stage_counts("ec_plan")
        got = decode(sinfo, ec, have, lost)
        new = _records("ec_decode")[seen:]
        assert len(new) == 1, lost
        rec = new[0]
        assert (rec["backend"], rec["ops"], rec["stripes"]) == (backend, 1, nstripes)
        assert rec["bytes_in"] == 8 * nstripes * chunk
        if backend == "jax":
            assert rec["bytes_uploaded"] == rec["bytes_in"]
            assert rec["transfer_s"] > 0 and rec["compute_s"] > 0 and rec["sync_s"] > 0
        assert _stage_counts("ec_plan") == [plans[0] + 1]
        assert sorted(got) == list(lost)
        for p in lost:
            per_stripe = np.concatenate([
                ec.decode({p}, {q: v[s * chunk:(s + 1) * chunk] for q, v in have.items()})[p]
                for s in range(nstripes)])
            assert np.array_equal(got[p], per_stripe), (lost, p)
            assert np.array_equal(got[p], shards[p])
        assert np.array_equal(decode_concat(sinfo, ec, have), data), lost


@pytest.mark.parametrize("plugin,profile", [
    ("jerasure", dict(technique="reed_sol_van", k="4", m="2", w="16")),
    ("jerasure", dict(technique="reed_sol_van", k="4", m="2", w="32")),
    ("jerasure", dict(technique="reed_sol_r6_op", k="4", m="2", w="8")),
    ("isa", dict(k="4", m="2")),
    ("isa", dict(k="4", m="2", technique="cauchy")),
])
def test_every_word_size_and_matrix_family_takes_the_one_dispatch(plugin, profile):
    ec = _code(plugin, backend="jax", **profile)
    chunk = ec.get_chunk_size(4 * 256)
    sinfo, nstripes = StripeInfo(4, 4 * chunk), 3
    data = np.random.default_rng(9).integers(
        0, 256, nstripes * 4 * chunk, dtype=np.uint8)
    shards = encode(sinfo, ec, data)
    for s in range(nstripes):  # the seam's encode is the plugin's, a stripe at a time
        per = ec.encode(set(range(6)), data[s * 4 * chunk:(s + 1) * 4 * chunk])
        assert all(np.array_equal(shards[p][s * chunk:(s + 1) * chunk], per[p])
                   for p in range(6))
    for lost in itertools.combinations(range(6), 2):
        have = {p: s for p, s in shards.items() if p not in lost}
        seen = len(_records("ec_decode"))
        got = decode(sinfo, ec, have, lost)
        assert all(np.array_equal(got[p], shards[p]) for p in lost), lost
        (rec,) = _records("ec_decode")[seen:]
        assert (rec["backend"], rec["stripes"]) == ("jax", nstripes) and rec["transfer_s"] > 0


def test_a_chunk_that_is_not_whole_words_takes_the_loop():
    ec = _code(technique="reed_sol_van", k="2", m="1", w="8", backend="numpy")
    sinfo = StripeInfo(2, 2 * 6)
    data = np.arange(3 * 12, dtype=np.uint8)
    shards = encode(sinfo, ec, data)
    seen = len(_records("ec_decode"))
    assert np.array_equal(decode_concat(sinfo, ec, {1: shards[1], 2: shards[2]}), data)
    (rec,) = _records("ec_decode")[seen:]
    assert (rec["backend"], rec["stripes"], rec["transfer_s"]) == ("numpy", 3, 0.0)


HOST_LOOP_CODES = {
    # a layered code has no backend of its own: its loop is "cpu";
    # losing 1 and 7 leaves no single layer that rebuilds both
    "lrc": ("lrc", dict(k="4", m="2", l="3", backend="jax"), (1, 7), "cpu"),
    "cauchy_good:jax": (
        "jerasure",
        dict(technique="cauchy_good", k="4", m="2", w="8", packetsize="8", backend="jax"),
        (0, 4), "jax"),
    "cauchy_good:numpy": (
        "jerasure",
        dict(technique="cauchy_good", k="4", m="2", w="8", packetsize="8"),
        (0, 4), "numpy"),
}


@pytest.mark.parametrize("seam", ["encode", "decode", "decode_batch"])
@pytest.mark.parametrize("code", sorted(HOST_LOOP_CODES))
def test_the_per_stripe_fallbacks_share_one_bracket(code, seam):
    """A layered code and a bitmatrix technique have no whole-word
    matrix path: each seam runs the reference's loop inside ONE host
    recorder entry for the whole call, under the codec's backend
    name, whole wall booked as compute — and the bytes are the
    per-stripe plugin's."""
    plugin, profile, lost, name = HOST_LOOP_CODES[code]
    ec = _code(plugin, **profile)
    k, nstripes = ec.get_data_chunk_count(), 3
    chunk = ec.get_chunk_size(k * 256)
    sinfo = StripeInfo(k, k * chunk)
    data = np.random.default_rng(8).integers(
        0, 256, nstripes * k * chunk, dtype=np.uint8)
    kind = "ec_encode" if seam == "encode" else "ec_decode"
    shards = encode(sinfo, ec, data)
    have = {p: s for p, s in shards.items() if p not in lost}
    have_bytes = sum(v.nbytes for v in have.values())
    seen = len(_records(kind))
    if seam == "encode":
        got, positions = [encode(sinfo, ec, data)], sorted(shards)
        expect = (name, 1, nstripes, data.nbytes)
        per_stripe = [
            ec.encode(set(shards), data[s * k * chunk:(s + 1) * k * chunk])
            for s in range(nstripes)]
    else:
        positions = lost
        per_stripe = [
            ec.decode(set(lost), {q: v[s * chunk:(s + 1) * chunk] for q, v in have.items()})
            for s in range(nstripes)]
        if seam == "decode":
            got = [decode(sinfo, ec, have, lost)]
            expect = (name, 1, nstripes, have_bytes)
        else:
            got = decode_batch(sinfo, ec, [have, have], lost)
            # the group's loop never counted stripes
            expect = (name, 2, 0, 2 * have_bytes)
    (rec,) = _records(kind)[seen:]
    assert (rec["backend"], rec["ops"], rec["stripes"], rec["bytes_in"]) == expect
    assert rec["bytes_uploaded"] == 0 and rec["compute_s"] == rec["wall_s"]
    for one in got:
        for p in positions:
            assert np.array_equal(
                np.asarray(one[p]), np.concatenate([c[p] for c in per_stripe])), p


def test_decode_returns_what_is_at_hand_without_a_dispatch():
    ec = _code(technique="reed_sol_van", k="4", m="2", w="8", backend="jax")
    sinfo = StripeInfo(4, 4 * 128)
    data = np.arange(3 * 4 * 128, dtype=np.uint8)
    shards = encode(sinfo, ec, data)
    seen = len(_records("ec_decode"))
    got = decode(sinfo, ec, shards, {0, 5})
    assert np.array_equal(got[0], shards[0]) and np.array_equal(got[5], shards[5])
    assert np.array_equal(decode_concat(sinfo, ec, shards), data)
    assert len(_records("ec_decode")) == seen
    assert decode_concat(sinfo, ec, {i: b"" for i in range(4)}).size == 0
    # a wanted shard at hand rides along with a rebuilt one
    have = {p: s for p, s in shards.items() if p != 1}
    got = decode(sinfo, ec, have, {0, 1})
    assert np.array_equal(got[1], shards[1]) and got[0] is not None
    assert len(_records("ec_decode")) == seen + 1


def test_ragged_lengths_bucket_to_one_program_a_power_of_two():
    """3, 5 and 6 stripes share the 4- and 8-stripe programs; the pad
    is counted and sliced away."""
    ec = _code(technique="reed_sol_van", k="4", m="2", w="8", backend="jax")
    chunk = 128
    sinfo = StripeInfo(4, 4 * chunk)
    for nstripes, padded in ((3, 1), (5, 3), (6, 2), (8, 0)):
        data = np.random.default_rng(nstripes).integers(
            0, 256, nstripes * 4 * chunk, dtype=np.uint8)
        shards = encode(sinfo, ec, data)
        have = {p: s for p, s in shards.items() if p not in (0, 2)}
        assert np.array_equal(decode_concat(sinfo, ec, have), data)
        rec = _records("ec_decode")[-1]
        assert rec["stripes"] == nstripes
        assert rec["bytes_padded"] == padded * chunk * 4


@pytest.mark.parametrize("profile", [
    dict(technique="cauchy_good", k="4", m="2", w="8", packetsize="32"),
    dict(technique="liberation", k="4", m="2", w="7", packetsize="32"),
])
def test_a_bitmatrix_technique_still_takes_the_loop_and_still_records(profile):
    ec = _code(backend="jax", **profile)
    chunk = ec.get_chunk_size(4 * 7 * 32 * 4) if profile["w"] == "7" else 1024
    sinfo = StripeInfo(4, 4 * chunk)
    nstripes = 3
    data = np.random.default_rng(2).integers(
        0, 256, nstripes * 4 * chunk, dtype=np.uint8)
    shards = encode(sinfo, ec, data)
    have = {p: s for p, s in shards.items() if p not in (1, 4)}
    seen = len(_records("ec_decode"))
    calls = []
    original = ec._decode
    ec._decode = lambda want, chunks: calls.append(1) or original(want, chunks)
    assert np.array_equal(decode_concat(sinfo, ec, have), data)
    assert len(calls) == nstripes  # the per-stripe loop
    new = _records("ec_decode")[seen:]
    assert len(new) == 1
    assert (new[0]["backend"], new[0]["ops"], new[0]["stripes"]) == ("jax", 1, nstripes)
    got = decode(sinfo, ec, have, {1, 4})
    assert np.array_equal(got[1], shards[1]) and np.array_equal(got[4], shards[4])


def test_more_erasures_than_m_is_an_error_not_a_wrong_answer():
    from ceph_tpu.ec.interface import ErasureCodeError

    ec = _code(technique="reed_sol_van", k="4", m="2", w="8", backend="jax")
    sinfo = StripeInfo(4, 4 * 128)
    shards = encode(sinfo, ec, np.arange(2 * 4 * 128, dtype=np.uint8))
    have = {p: shards[p] for p in (0, 1, 5)}
    with pytest.raises(ErasureCodeError):
        decode(sinfo, ec, have, {2, 3})
    with pytest.raises(ErasureCodeError):
        decode_concat(sinfo, ec, {0: shards[0], 1: shards[1][:-1]})


def test_the_stage_helper_counts_with_and_without_an_ambient_tracer():
    before = _stage_counts("probe_stage")
    with tracing.stage("probe_stage"):
        assert tracing.current_span().name == "probe_stage"
    tracer = tracing.Tracer("osd.test")
    with tracer.start_span("osd_op"), tracing.stage("probe_stage") as child:
        assert child.parent_id and child.daemon == "osd.test"
    assert _stage_counts("probe_stage") == [before[0] + 2]
    assert [s["name"] for s in tracer.dump_traces()["spans"]] == ["probe_stage", "osd_op"]


def _route_through_the_interpreter(monkeypatch):
    """The chip-only choice of ``matrix_shards``, made here: the
    backend thinks it is on the TPU and the packed decode kernel runs
    in its interpreter.  Returns the lists the two programs' calls
    are recorded in: (packed (r, s), bitplane)."""
    from ceph_tpu.ops import gf_matmul

    monkeypatch.setattr(ec_backend, "_on_tpu", lambda: True)
    packed, bitplane = [], []
    built = packed_gf.prebuilt_decode_call
    monkeypatch.setattr(
        packed_gf, "prebuilt_decode_call",
        lambda r, s: packed.append((r, s)) or built(r, s, interpret=True))
    words = gf_matmul.gf_matrix_words
    monkeypatch.setattr(
        gf_matmul, "gf_matrix_words",
        lambda *a, **kw: bitplane.append(1) or words(*a, **kw))
    return packed, bitplane


def _packed_count():
    return kernel_stats().dump()["l_tpu_ec_decode_packed_calls"]


@pytest.mark.parametrize("plugin,profile,lost,nstripes", [
    ("jerasure", dict(technique="reed_sol_van", k="8", m="3", w="8"), (2, 9), 4),
    ("jerasure", dict(technique="reed_sol_van", k="8", m="3", w="8"), (0, 7), 3),
    ("isa", dict(k="4", m="2"), (1,), 5),
])
def test_a_whole_shard_rebuild_takes_the_packed_kernel(
        monkeypatch, plugin, profile, lost, nstripes):
    """Whole 128-word rows at w=8 on the TPU: ``stripe.decode`` runs
    the packed decode kernel (3 and 5 stripes through the bucket's
    pad), byte for byte the per-stripe ``ec._decode``, and counts it."""
    ec = _code(plugin, backend="jax", **profile)
    k = ec.get_data_chunk_count()
    chunk = 512
    sinfo = StripeInfo(k, k * chunk)
    data = np.random.default_rng(nstripes).integers(
        0, 256, nstripes * k * chunk, dtype=np.uint8)
    shards = encode(sinfo, ec, data)
    have = {p: s for p, s in shards.items() if p not in lost}
    per_stripe = {  # the oracle, taken before the routing is patched
        p: np.concatenate([
            ec._decode({p}, {q: v[s * chunk:(s + 1) * chunk] for q, v in have.items()})[p]
            for s in range(nstripes)])
        for p in lost}
    packed, bitplane = _route_through_the_interpreter(monkeypatch)
    before = _packed_count()
    seen = len(_records("ec_decode"))
    got = decode(sinfo, ec, have, lost)
    assert packed == [(len(lost), k)] and bitplane == []
    assert _packed_count() == before + 1
    (rec,) = _records("ec_decode")[seen:]
    assert (rec["backend"], rec["ops"], rec["stripes"]) == ("jax", 1, nstripes)
    assert rec["bytes_uploaded"] == rec["bytes_in"] == k * nstripes * chunk
    for p in lost:
        np.testing.assert_array_equal(got[p], per_stripe[p])
        np.testing.assert_array_equal(got[p], shards[p])


@pytest.mark.parametrize("why", ["clay_repair", "w16", "ragged_rows"])
def test_other_shapes_keep_the_bitplane_program(monkeypatch, why):
    """A CLAY sub-chunk repair (a 64 x 176 matrix: MXU work), w=16, and
    a payload that is not whole 128-word rows take ``gf_matrix_words``
    on the TPU too, and do not count as packed."""
    if why == "clay_repair":
        from ceph_tpu.ec.stripe import repair

        ec = registry_instance().factory(
            "clay", ErasureCodeProfile(backend="jax", k="8", m="4", d="11"))
        cs = ec.get_sub_chunk_count() * 32
        sinfo = StripeInfo(8, 8 * cs)
        data = np.random.default_rng(5).integers(0, 256, 2 * 8 * cs, dtype=np.uint8)
        shards = encode(sinfo, ec, data)
        sc = cs // ec.get_sub_chunk_count()
        minimum = ec.minimum_to_decode({3}, set(range(12)) - {3})
        fragments = {
            h: np.concatenate([
                shards[h][s * cs + off * sc: s * cs + (off + cnt) * sc]
                for s in range(2) for off, cnt in runs])
            for h, runs in minimum.items()}
        ec.repair_matrix(3, set(fragments))  # the probe, on the host
        want, lost = {3: shards[3]}, 3

        def rebuild():
            return {3: repair(sinfo, ec, fragments, 3)}
    else:
        w, chunk = ("16", 512) if why == "w16" else ("8", 256)
        ec = _code(technique="reed_sol_van", k="4", m="2", w=w, backend="jax")
        sinfo = StripeInfo(4, 4 * chunk)
        data = np.random.default_rng(6).integers(0, 256, 3 * 4 * chunk, dtype=np.uint8)
        shards = encode(sinfo, ec, data)
        have = {p: s for p, s in shards.items() if p not in (0, 4)}
        want = {p: shards[p] for p in (0, 4)}

        def rebuild():
            return decode(sinfo, ec, have, (0, 4))
    packed, bitplane = _route_through_the_interpreter(monkeypatch)
    before = _packed_count()
    got = rebuild()
    assert sorted(got) == sorted(want)
    for p in want:
        np.testing.assert_array_equal(got[p], want[p])
    assert packed == [] and bitplane == [1]
    assert _packed_count() == before


def test_the_packed_encode_path_brackets_its_stages(monkeypatch):
    """The chip-only path of matrix_stripe_shards, run here through the
    kernel's interpreter: the same bytes as the bitplane path, the
    recorder's upload / compute / sync each bracketing its own part,
    the host fold (a span a row, each row going up as it is folded)
    and unfold in spans."""
    ec = _code(technique="reed_sol_van", k="4", m="2", w="8", backend="jax")
    sinfo = StripeInfo(4, 4 * 512)
    data = np.random.default_rng(4).integers(0, 256, 4 * 4 * 512, dtype=np.uint8)
    plain = encode(sinfo, ec, data)
    monkeypatch.setattr(ec_backend, "_on_tpu", lambda: True)
    monkeypatch.setattr(ec_backend.mesh, "default_mesh", lambda: None)
    built = packed_gf.prebuilt_word_call
    monkeypatch.setattr(
        packed_gf, "prebuilt_word_call", lambda bm, w=8: built(bm, w, interpret=True))
    seen = len(_records("ec_encode"))
    spans = _stage_counts("ec_fold", "ec_unfold", "ec_assemble")
    packed = encode(sinfo, ec, data)
    assert all(np.array_equal(packed[p], plain[p]) for p in range(6))
    (rec,) = _records("ec_encode")[seen:]
    assert (rec["kind"], rec["backend"], rec["ops"], rec["stripes"]) == (
        "ec_encode", "jax", 1, 4)
    assert rec["bytes_uploaded"] == data.nbytes
    assert rec["transfer_s"] > 0 and rec["compute_s"] > 0 and rec["sync_s"] > 0
    assert rec["transfer_s"] + rec["compute_s"] + rec["sync_s"] <= rec["wall_s"]
    assert _stage_counts("ec_fold", "ec_unfold", "ec_assemble") == [
        spans[0] + 4, spans[1] + 1, spans[2] + 1]
