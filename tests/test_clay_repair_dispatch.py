"""``ec/stripe.repair`` (ISSUE 38): the B-stripe form of a fractional
repair.  A CLAY codec's ``repair_matrix`` hook makes it ONE
``ec_decode`` dispatch on the codec's backend — the fragments as they
are stored, sub-chunk rows folded on the backend's side — and it is
byte-identical to the per-stripe ``ec.decode({lost}, partial,
chunk_size)`` loop it replaces; a code without the hook keeps the
loop."""

import numpy as np
import pytest

import ceph_tpu.ops  # noqa: F401  registers the jax backend
from ceph_tpu.ec import ErasureCodeProfile, registry_instance, stripe
from ceph_tpu.ec.interface import ErasureCodeError
from ceph_tpu.ops.kernel_stats import kernel_stats
from ceph_tpu.ops.profiler import dispatch_profiler
from ceph_tpu.store.ec_store import ECStore

PROFILES = {
    "k8m4d11": dict(k="8", m="4", d="11"),
    "k4m2d5": dict(k="4", m="2", d="5"),
    "k5m2d6_nu1": dict(k="5", m="2", d="6"),
    "k8m4d10_aloof": dict(k="8", m="4", d="10"),
}


def make(backend="numpy", **profile):
    return registry_instance().factory(
        "clay", ErasureCodeProfile(backend=backend, **profile)
    )


def object_of(ec, nstripes, seed=0, sub_bytes=None):
    """(sinfo, the shards of a seeded object of ``nstripes`` stripes)."""
    width = ec.get_chunk_size(1) * ec.k
    if sub_bytes is not None:
        width = ec.get_sub_chunk_count() * sub_bytes * ec.k
    sinfo = stripe.StripeInfo(ec.k, width)
    data = np.random.default_rng(seed).integers(
        0, 256, width * nstripes, dtype=np.uint8
    )
    return sinfo, stripe.encode(sinfo, ec, data)


def fragments_of(ec, sinfo, shards, lost, nstripes):
    """What ``ECStore._repair_minimum`` reads: a stripe after another,
    each the runs of ``minimum_to_decode`` concatenated."""
    n = ec.get_chunk_count()
    cs = sinfo.chunk_size
    sc = cs // ec.get_sub_chunk_count()
    minimum = ec.minimum_to_decode({lost}, set(range(n)) - {lost})
    return {
        h: np.concatenate([
            shards[h][s * cs + off * sc : s * cs + (off + cnt) * sc]
            for s in range(nstripes)
            for off, cnt in runs
        ])
        for h, runs in minimum.items()
    }


def decode_loop(ec, sinfo, fragments, lost, nstripes):
    """The loop ``_repair_minimum`` ran before this seam existed."""
    parts = []
    for s in range(nstripes):
        partial = {
            h: f.reshape(nstripes, -1)[s] for h, f in fragments.items()
        }
        parts.append(ec.decode({lost}, partial, sinfo.chunk_size)[lost])
    return np.concatenate(parts)


def entries_since(seq):
    return [
        e for e in dispatch_profiler().history("ec_decode")["entries"]
        if e["seq"] > seq
    ]


def last_seq():
    return max(
        (e["seq"] for e in dispatch_profiler().history()["entries"]), default=0
    )


@pytest.mark.parametrize("nstripes", [1, 3])
@pytest.mark.parametrize("name", PROFILES)
def test_repair_is_the_per_stripe_decode_loop_byte_for_byte(name, nstripes):
    ec = make(**PROFILES[name])
    sinfo, shards = object_of(ec, nstripes, seed=nstripes)
    for lost in range(ec.get_chunk_count()):
        fragments = fragments_of(ec, sinfo, shards, lost, nstripes)
        assert len(fragments) == ec.d
        got = stripe.repair(sinfo, ec, fragments, lost)
        np.testing.assert_array_equal(got, shards[lost], str(lost))
        np.testing.assert_array_equal(
            got, decode_loop(ec, sinfo, fragments, lost, nstripes), str(lost)
        )


@pytest.mark.parametrize("name", PROFILES)
def test_one_device_dispatch_a_call_and_no_host_entry(name):
    """On the jax backend a call is exactly one ``ec_decode`` entry
    under that backend's name — the plane-by-plane codec calls are
    gone — and 3 stripes ride the 4-stripe program (the pad)."""
    ec = make(backend="jax", **PROFILES[name])
    assert ec.backend.name == "jax"
    sinfo, shards = object_of(ec, 3, seed=7)
    per_helper = ec.get_sub_chunk_count() // ec.q
    for lost in range(ec.get_chunk_count()):
        fragments = fragments_of(ec, sinfo, shards, lost, 3)
        seq = last_seq()
        got = stripe.repair(sinfo, ec, fragments, lost)
        np.testing.assert_array_equal(got, shards[lost], str(lost))
        (entry,) = entries_since(seq)
        assert (entry["backend"], entry["ops"], entry["stripes"]) == ("jax", 1, 3)
        assert entry["bytes_in"] == entry["bytes_uploaded"] == sum(
            len(f) for f in fragments.values()
        )
        assert entry["bytes_in"] == 3 * ec.d * per_helper * (
            sinfo.chunk_size // ec.get_sub_chunk_count()
        )
        assert entry["bytes_padded"] == entry["bytes_in"] // 3
        assert entry["transfer_s"] > 0 and entry["sync_s"] > 0


def test_a_host_backend_codec_records_its_own_name():
    ec = make(**PROFILES["k4m2d5"])
    sinfo, shards = object_of(ec, 2)
    seq = last_seq()
    stripe.repair(sinfo, ec, fragments_of(ec, sinfo, shards, 1, 2), 1)
    (entry,) = entries_since(seq)
    assert entry["backend"] == "numpy" and entry["stripes"] == 2


@pytest.mark.parametrize("why", ["no_hook", "bitmatrix_inner", "odd_sub_chunk"])
def test_a_codec_without_the_plan_takes_the_loop(why, monkeypatch):
    """One gate: no hook, a hook that declines (packet-wise inner
    codes), or sub-chunks that are not whole words keep the per-stripe
    loop, inside one flight-recorder entry, and the bytes are the same."""
    profile = dict(PROFILES["k4m2d5"])
    sub_bytes = None
    if why == "bitmatrix_inner":
        profile["technique"] = "cauchy_good"
    ec = make(**profile)
    if why == "no_hook":
        monkeypatch.setattr(ec, "repair_matrix", None, raising=True)
    elif why == "bitmatrix_inner":
        assert ec.repair_matrix(0, set(range(1, 6))) is None
    else:
        sub_bytes = 32 + 2
        monkeypatch.setattr(ec, "get_chunk_size", lambda size: size // ec.k)
    if why == "bitmatrix_inner":
        width = ec.get_chunk_size(4096) * ec.k
        sinfo = stripe.StripeInfo(ec.k, width)
        data = np.random.default_rng(3).integers(0, 256, width * 2, dtype=np.uint8)
        shards = stripe.encode(sinfo, ec, data)
    else:
        sinfo, shards = object_of(ec, 2, seed=3, sub_bytes=sub_bytes)
    calls = []
    original = ec.decode
    monkeypatch.setattr(
        ec, "decode", lambda *a, **kw: calls.append(1) or original(*a, **kw)
    )
    for lost in (0, 5):
        fragments = fragments_of(ec, sinfo, shards, lost, 2)
        seq = last_seq()
        calls.clear()
        got = stripe.repair(sinfo, ec, fragments, lost)
        np.testing.assert_array_equal(got, shards[lost])
        assert len(calls) == 2  # a stripe at a time
        (entry,) = entries_since(seq)
        assert (entry["backend"], entry["stripes"]) == ("numpy", 2)


def test_the_hook_builds_a_matrix_once_and_says_when_it_declines():
    ec = make(**PROFILES["k8m4d11"])
    helpers = set(range(12)) - {5}
    matrix, order, w, backend = ec.repair_matrix(5, helpers)
    assert matrix.shape == (64, 11 * 16) and order == sorted(helpers)
    assert (w, backend) == (8, ec.backend)
    assert 0.05 < (matrix != 0).mean() < 0.12  # sparse: 8.4%
    assert ec.repair_matrix(5, helpers)[0] is matrix  # kept on the codec
    # not a repair set: a helper short, or the lost chunk among them
    assert ec.repair_matrix(5, helpers - {0}) is None
    assert ec.repair_matrix(5, helpers | {5}) is None
    # aloof nodes: d of the 11 others, the lost node's row among them
    aloof = make(**PROFILES["k8m4d10_aloof"])
    minimum = aloof.minimum_to_decode({0}, set(range(1, 12)))
    assert aloof.repair_matrix(0, set(minimum))[0].shape == (81, 10 * 27)
    assert aloof.repair_matrix(0, set(range(2, 12))) is None  # row mate 1 missing
    # a chunk mapping keeps the loop
    mapped = registry_instance().factory(
        "clay", ErasureCodeProfile(k="4", m="2", d="5", mapping="DD_DD_")
    )
    assert mapped.repair_matrix(0, set(range(1, 6))) is None


def test_a_jax_codec_probes_its_matrix_on_the_host():
    """Building the matrix is a few hundred odd-length region calls:
    they never reach the device, whatever the codec's backend."""
    ec = make(backend="jax", **PROFILES["k4m2d5"])
    seq = last_seq()
    matrix = ec.repair_matrix(2, {0, 1, 3, 4, 5})[0]
    assert not [
        e for e in dispatch_profiler().history()["entries"] if e["seq"] > seq
    ]
    np.testing.assert_array_equal(
        matrix, make(**PROFILES["k4m2d5"]).repair_matrix(2, {0, 1, 3, 4, 5})[0]
    )


def test_a_whole_row_aloof_leaves_no_plane_of_score_one():
    """k=8 m=4 d=9: q = 2 and the two aloof nodes are one whole row, so
    every repair plane has intersection score 2; the traversal starts
    at the lowest score there is."""
    ec = make(k="8", m="4", d="9")
    sinfo, shards = object_of(ec, 2, seed=9)
    for lost in range(12):
        fragments = fragments_of(ec, sinfo, shards, lost, 2)
        assert len(fragments) == 9
        np.testing.assert_array_equal(
            stripe.repair(sinfo, ec, fragments, lost), shards[lost], str(lost)
        )


def test_fragments_that_are_not_the_repairs_reads_are_refused():
    ec = make(**PROFILES["k4m2d5"])
    sinfo, shards = object_of(ec, 2)
    fragments = fragments_of(ec, sinfo, shards, 0, 2)
    with pytest.raises(ErasureCodeError, match="same whole number"):
        stripe.repair(sinfo, ec, {**fragments, 1: fragments[1][:-4]}, 0)
    # more than the repair reads: the seam names what it wanted
    with pytest.raises(ErasureCodeError, match="reads"):
        stripe.repair(sinfo, ec, {**fragments, 0: fragments[1]}, 0)
    assert len(stripe.repair(
        sinfo, ec, {h: f[:0] for h, f in fragments.items()}, 0)) == 0


def test_repair_counts_calls_and_bytes_and_spans_its_plan():
    ec = make(**PROFILES["k4m2d5"])
    sinfo, shards = object_of(ec, 2)
    fragments = fragments_of(ec, sinfo, shards, 4, 2)
    stripe.repair(sinfo, ec, fragments, 4)
    before = kernel_stats().dump()
    got = stripe.repair(sinfo, ec, fragments, 4)
    after = kernel_stats().dump()

    def grew(name):
        return after[name] - before[name]

    assert grew("l_tpu_ec_repair_calls") == 1
    assert grew("l_tpu_ec_repair_helper_bytes") == sum(
        len(f) for f in fragments.values())
    assert grew("l_tpu_ec_repair_rebuilt_bytes") == len(got) == len(shards[4])
    # 5 helpers x 1/2 of a chunk: 5/8 of the object where k chunks are 1
    assert grew("l_tpu_ec_repair_helper_bytes") / (4 * len(got)) == 5 / 8
    assert grew("l_stage_ec_repair_plan_count") == 1
    assert grew("l_stage_ec_repair_plan_ns") > 0


def test_a_clay_store_rebuilds_a_shard_from_11_32_in_one_dispatch():
    store = ECStore(
        plugin="clay", profile={"k": "8", "m": "4", "d": "11"},
        stripe_width=8 * 64 * 32,
    )
    data = np.random.default_rng(11).integers(
        0, 256, 3 * store.sinfo.stripe_width, dtype=np.uint8).tobytes()
    store.put("obj", data)
    want = store.stores[6].read(store.cid, "obj")
    store.lose_shard("obj", 6)
    seq = last_seq()
    rebuilt, read_bytes, _meta = store.reconstruct_shard("obj", 6)
    assert rebuilt == want
    assert read_bytes / len(data) == 11 / 32
    (entry,) = entries_since(seq)
    assert (entry["ops"], entry["stripes"], entry["bytes_in"]) == (1, 3, read_bytes)
    # a corrupt helper is still caught by the rebuilt shard's crc and
    # widened to the verified decode (one stripe: that path hands the
    # codec whole shards)
    store.put("one", data[: store.sinfo.stripe_width])
    want = store.stores[6].read(store.cid, "one")
    store.lose_shard("one", 6)
    store.corrupt_shard("one", 2, offset=8 * 32)  # a sub-chunk the repair reads
    rebuilt, widened, _meta = store.reconstruct_shard("one", 6)
    assert rebuilt == want and widened > read_bytes // 3
