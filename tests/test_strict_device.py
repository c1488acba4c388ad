"""Nothing on the main path carries on without the device.

Where the caller asked for the device (``backend=jax``, the scrub
kernels, the mesh), a backend, compile or runtime error propagates;
only the semantic cases the fallbacks were written for (ragged
survivors, ``backend="oracle"``) still take the host path.
"""

import numpy as np
import pytest

from ceph_tpu import gf
from ceph_tpu.ec import ErasureCodeProfile, registry_instance
from ceph_tpu.ec.stripe import StripeInfo, decode_batch, encode
from ceph_tpu.ops import ec_backend, mesh, scrub_kernels


def _broken_backend(*_a, **_kw):
    raise RuntimeError("Unable to initialize backend 'tpu'")


@pytest.fixture
def broken_jax(monkeypatch):
    import jax

    monkeypatch.setattr(jax, "default_backend", _broken_backend)
    monkeypatch.setattr(jax, "devices", _broken_backend)


def test_on_tpu_and_available_devices_propagate(broken_jax):
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        ec_backend._on_tpu()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        mesh.available_devices()


def test_matrix_stripe_shards_propagates_backend_error(broken_jax):
    matrix = gf.reed_sol_vandermonde_coding_matrix(4, 2, 8)
    stripes = np.zeros((2, 4, 64), dtype=np.uint8)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        ec_backend.get_jax_backend().matrix_stripe_shards(
            matrix, stripes, 8
        )
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        ec_backend.get_jax_backend().matrix_regions(
            matrix, stripes[0], 8
        )


@pytest.mark.parametrize("backend", [None, "device"])
def test_batch_crc32c_does_not_fall_to_the_oracle(monkeypatch, backend):
    def refuse(*_a):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(scrub_kernels, "_crc_call", refuse)
    with pytest.raises(RuntimeError, match="Mosaic"):
        scrub_kernels.batch_crc32c([b"abc", b"defg"], backend=backend)
    # the oracle is still there for whoever names it
    got = scrub_kernels.batch_crc32c([b"foo bar baz"], backend="oracle")
    assert int(got[0]) == 4119623852


def test_batch_compare_does_not_fall_to_numpy(monkeypatch):
    def refuse(_ncols):
        raise RuntimeError("RESOURCE_EXHAUSTED: out of HBM")

    monkeypatch.setattr(scrub_kernels, "_compare_call", refuse)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        scrub_kernels.batch_compare([b"abcd"], [b"abcd"])
    assert list(
        scrub_kernels.batch_compare([b"abcd"], [b"abce"], backend="oracle")
    ) == [True]


def _lost_two(k=4, m=2, nobj=3, stripes=2):
    ec = registry_instance().factory(
        "jerasure",
        ErasureCodeProfile(
            technique="reed_sol_van", k=str(k), m=str(m), w="8",
            backend="jax",
        ),
    )
    sinfo = StripeInfo(k, k * 64)
    rng = np.random.default_rng(5)
    full = [
        encode(
            sinfo, ec,
            rng.integers(0, 256, stripes * k * 64, dtype=np.uint8),
        )
        for _ in range(nobj)
    ]
    survivors = [
        {i: v for i, v in shards.items() if i not in (0, 1)}
        for shards in full
    ]
    return ec, sinfo, full, survivors


def test_batched_decode_propagates_device_error(monkeypatch):
    ec, sinfo, _full, survivors = _lost_two()

    def refuse(self, *_a, **_kw):
        raise RuntimeError("XlaRuntimeError: device halted")

    monkeypatch.setattr(
        ec_backend.JaxBackend, "decode_stripes_batch", refuse
    )
    with pytest.raises(RuntimeError, match="device halted"):
        decode_batch(sinfo, ec, survivors, [0, 1])


def test_batched_decode_still_degrades_on_unaligned_survivors(monkeypatch):
    """The semantic case the fallback was written for: survivors whose
    length is not a multiple of the chunk cannot ride the batched
    dispatch — the group is still rebuilt, per object, byte-exact."""
    from ceph_tpu.ops.residency import as_host_bytes

    ec, _sinfo, full, survivors = _lost_two()
    batched = []
    real = ec_backend.JaxBackend.decode_stripes_batch
    monkeypatch.setattr(
        ec_backend.JaxBackend, "decode_stripes_batch",
        lambda self, *a, **kw: batched.append(1) or real(self, *a, **kw),
    )
    odd = StripeInfo(4, 4 * 48)  # 128-byte shards, 48-byte chunks
    out = decode_batch(odd, ec, survivors, [0, 1])
    assert not batched
    for got, want in zip(out, full):
        for p in (0, 1):
            assert as_host_bytes(got[p]) == bytes(want[p])


def test_crush_batches_beyond_the_chunk_replay_one_program(monkeypatch):
    """batch_do_rule cuts a batch larger than CHUNK_LANES into parts of
    exactly that many lanes (tail padded), byte-identical to the
    oracle."""
    from ceph_tpu.crush import jaxmap
    from ceph_tpu.tools.crushtool import build_hierarchy

    m = build_hierarchy(64, 4, 4)
    cm = jaxmap.compile_map(m)
    monkeypatch.setattr(jaxmap, "CHUNK_LANES", 256)
    seen = []
    real = jaxmap.map_chunked

    def spy(dispatch, xs, chunk=None):
        def counting(part):
            seen.append(len(part))
            return dispatch(part)

        return real(counting, xs, chunk)

    monkeypatch.setattr(jaxmap, "map_chunked", spy)
    xs = np.arange(1000)
    res, counts = jaxmap.batch_do_rule(cm, 0, xs, 3)
    assert seen == [256, 256, 256, 256]
    assert res.shape == (1000, 3) and counts.shape == (1000,)
    for x in range(0, 1000, 37):
        assert res[x, : counts[x]].tolist() == m.do_rule(0, x, 3)


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    import jax

    from ceph_tpu.common import compile_cache

    set_to = []
    monkeypatch.setattr(
        jax.config, "update", lambda k, v: set_to.append((k, v))
    )
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure_compile_cache() == str(tmp_path)
    assert set_to == []  # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    got = compile_cache.configure_compile_cache()
    assert got.endswith(".jax_cache")
    assert set_to == [("jax_compilation_cache_dir", got)]
