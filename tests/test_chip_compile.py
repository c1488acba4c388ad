"""Main-path device programs compiled for a *described* TPU v5e.

No chip is attached and nothing runs: the TPU compiler installed with
JAX compiles each program at the shapes the product dispatches and
raises what the chip's compiler would raise (Mosaic legalization,
memory that does not fit).  Interpret-mode and CPU tests cannot see
those faults.  A compile that passes here is not a chip run —
``chip_smoke.py`` is.

The topology is described inside a module-scoped fixture (never at
import: only one process may load the TPU library, and every xdist
worker imports every test file), and all of these tests live in this
one file so one worker owns the library.
"""

import os
import re

import numpy as np
import pytest

# x64 on, as in every product process: OSDs, the in-process cluster and
# the benchmark's drivers all import the CRUSH kernel next to the EC kernels
import ceph_tpu.crush.jaxmap as jaxmap

import jax
import jax.numpy as jnp

from ceph_tpu import gf
from ceph_tpu.ec import ErasureCodeProfile, registry_instance
from ceph_tpu.ops import gf_matmul, packed_gf, scrub_kernels

V5E_HBM_BYTES = 16 * 10**9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "skip"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but not read back without the chip: keep it off around them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _code(plugin, k, m):
    prof = ErasureCodeProfile(k=str(k), m=str(m), w="8")
    if plugin == "jerasure":
        prof["technique"] = "reed_sol_van"
    return registry_instance().factory(plugin, prof)


def _fits(compiled) -> int:
    ma = compiled.memory_analysis()
    total = (
        ma.temp_size_in_bytes
        + ma.argument_size_in_bytes
        + ma.output_size_in_bytes
    )
    assert total < V5E_HBM_BYTES, ma
    return total


def test_x64_is_on_as_in_the_product():
    assert jax.config.jax_enable_x64


@pytest.mark.parametrize(
    "plugin,k,m", [("jerasure", 8, 3), ("jerasure", 4, 2), ("isa", 4, 2)]
)
def test_packed_encode_kernel(one_chip, plugin, k, m):
    """matrix_stripe_shards' folded word form at ec_benchmark's 64 x 1 MiB."""
    ec = _code(plugin, k, m)
    bm = gf.jerasure_bitmatrix(np.asarray(ec.matrix, dtype=np.int64), 8)
    nwords = 64 * (1 << 20) // k // 4
    call = packed_gf.prebuilt_word_call(bm)
    compiled = call.lower(
        *[_sds((1, nwords), jnp.uint32, one_chip) for _ in range(k)]
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize(
    "k,m,stripes,chunk",
    [
        (8, 3, 64, 131072),  # ec_plugin_k8m3.encode_1m: 64 x 1 MiB
        (4, 2, 256, 4096),  # a 4 MiB write_full of the served k=4 m=2 pool
    ],
)
def test_packed_encode_kernel_reads_stripe_form(one_chip, k, m, stripes, chunk):
    """_packed_stripes' upload at a chunk of whole tiles: the caller's
    buffer as ONE u32 (B, k, chunk/512, 128) operand, folded by the
    kernel's BlockSpecs — the program is the kernel and nothing else:
    no copy or transpose of the operand, no temporaries, no padding."""
    ec = _code("jerasure", k, m)
    bm = gf.jerasure_bitmatrix(np.asarray(ec.matrix, dtype=np.int64), 8)
    shape = (stripes, k, chunk // 512, 128)
    compiled = packed_gf.prebuilt_word_call(bm).lower(
        _sds(shape, jnp.uint32, one_chip)
    ).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"\b(copy|transpose)(\.\d+)? = ", text)
    assert not re.search(r" (copy|transpose)\(", text)
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes == 0
    assert ma.argument_size_in_bytes == stripes * k * chunk
    assert [o.shape for o in compiled.out_info] == [
        (stripes, chunk // 512, 128)
    ] * m
    _fits(compiled)


def test_packed_decode_kernel_two_erasures(one_chip):
    """The per-op decode of a 1 MiB k=8,m=3 object with data chunks 2
    and 5 lost (matrix_decode -> matrix_regions)."""
    ec = _code("jerasure", 8, 3)
    rows, _survivors = gf.make_decoding_matrix(
        np.asarray(ec.matrix, dtype=np.int64), [2, 5], 8, 8
    )
    bm = gf.jerasure_bitmatrix(np.asarray(rows, dtype=np.int64), 8)
    assert packed_gf.supports(bm, 8)
    call = packed_gf.prebuilt_word_call(bm)
    compiled = call.lower(
        *[_sds((1, (1 << 20) // 8 // 4), jnp.uint32, one_chip)] * 8
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "rebuilt,survivors,nbytes",
    [
        (2, 8, 64 * 131072),  # the decode cell: 64 x 1 MiB, two lost of k=8 m=3
        (1, 4, 256 * 4096),  # a degraded read of one 4 MiB object of the k=4 m=2 pool
    ],
)
def test_shard_form_decode_program(one_chip, rebuilt, survivors, nbytes):
    """What matrix_shards runs for ec/stripe.decode off the packed
    kernel's shapes (another word size, rows that are not whole 128
    words): the survivors as uint32 words, the reconstruction
    bitmatrix an operand."""
    compiled = gf_matmul.gf_matrix_words.lower(
        _sds((rebuilt * 8, survivors * 8), jnp.int8, one_chip),
        tuple(_sds((nbytes // 4,), jnp.uint32, one_chip) for _ in range(survivors)),
        w=8,
    ).compile()
    assert "u8[" not in compiled.as_text()  # no byte array to re-tile
    _fits(compiled)


@pytest.mark.parametrize(
    "rebuilt,survivors,nbytes",
    [
        (2, 8, 64 * 131072),  # the decode cell: 64 x 1 MiB, two lost of k=8 m=3
        (1, 4, 256 * 4096),  # a degraded read of one 4 MiB object of the k=4 m=2 pool
    ],
)
def test_packed_decode_program(one_chip, rebuilt, survivors, nbytes):
    """What matrix_shards runs for a whole-shard rebuild on the chip:
    the coefficients an operand (a shape, never values), each survivor
    a (nwords/128, 128) u32 view — the program is the one kernel, with
    no byte array, no copy or transpose of an operand, no temporaries."""
    rows = nbytes // 512
    compiled = packed_gf.prebuilt_decode_call(rebuilt, survivors).lower(
        _sds((rebuilt * survivors,), jnp.int32, one_chip),
        *[_sds((rows, 128), jnp.uint32, one_chip)] * survivors,
    ).compile()
    text = compiled.as_text()
    assert text.count("custom-call(") == 1
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "u8[" not in text
    assert not re.search(r"\b(copy|transpose)(\.\d+)? = ", text)
    assert not re.search(r" (copy|transpose)\(", text)
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes == 0
    assert compiled.out_info.shape == (rebuilt, rows, 128)
    _fits(compiled)


def test_sub_chunk_repair_program(one_chip):
    """What matrix_shards runs for ec/stripe.repair at the repair
    cell's size: CLAY k=8 m=4 d=11, 64 stripes, each of the 11 helpers'
    fragments (16 of 64 sub-chunks of 2 KiB a stripe, 2 MiB) as
    uint32 words as stored, the 64 x 176 repair matrix's bitmatrix an
    operand, the fold to sub-chunk rows and the unfold of the shard
    in the program."""
    compiled = gf_matmul.gf_matrix_words.lower(
        _sds((64 * 8, 176 * 8), jnp.int8, one_chip),
        tuple(_sds((64 * 16 * 512,), jnp.uint32, one_chip) for _ in range(11)),
        w=8,
        tile=(64, 16, 64),
    ).compile()
    assert "u8[" not in compiled.as_text()  # no byte array to re-tile
    assert compiled.out_info.shape == (1, 64 * 131072 // 4)  # the 8 MiB shard
    assert _fits(compiled) < 1 << 30


@pytest.mark.parametrize(
    "b,k,m,chunk",
    [
        (64, 8, 3, 131072),  # ec_benchmark batch, 1 MiB objects
        (256, 4, 2, 4096),  # one 4 MiB object of the served k=4,m=2 pool
        (1024, 4, 2, 4096),  # a coalesced group of four of them
    ],
)
def test_bitplane_stripes_program(one_chip, b, k, m, chunk):
    """What _bitplane_dispatch runs for coalesced writes and batched
    decode (pow2-bucketed batch axis)."""
    compiled = gf_matmul.gf_matrix_stripes.lower(
        _sds((m * 8, k * 8), jnp.int8, one_chip),
        _sds((b, k, chunk), jnp.uint8, one_chip),
        w=8,
    ).compile()
    _fits(compiled)


def test_crush_chunk_program_fits_the_chip(one_chip):
    """BASELINE #5 (10,000 OSDs, 3 replicas) at the fixed lane chunk
    every larger batch is cut into; map_parts keeps the part in hand
    and PARTS_AHEAD more in flight, inside half the chip's memory."""
    from ceph_tpu.tools.crushtool import build_hierarchy

    cm = jaxmap.compile_map(build_hierarchy(10000, 40, 25))
    fn, tables = jaxmap.batched_rule_call(cm, 0, 3, None)
    compiled = fn.lower(
        _sds((jaxmap.CHUNK_LANES,), jnp.int32, one_chip),
        _sds((cm.max_devices,), jnp.int32, one_chip),
        *[_sds(t.shape, t.dtype, one_chip) for t in tables],
    ).compile()
    in_flight = 1 + jaxmap.PARTS_AHEAD
    assert in_flight * _fits(compiled) < V5E_HBM_BYTES // 2


@pytest.mark.parametrize("items", [10, 25, 40])
def test_crush_ln_lookups_keep_the_pg_batch_in_lanes(one_chip, items):
    """The straw2 logarithm as the chunk program runs it (vmapped over
    2^16 lanes x 5 speculative replicas, ``items`` bucket items: the
    root, rack and host levels of BASELINE #5).  Neither table
    look-up's one-hot may be laid out with the table index minor-most:
    the compiler then spreads every lane's index over the lanes, which
    cost 9 x the look-up's time on the chip (PERF.md section 6, PR 27).
    """
    from ceph_tpu.tools.crushtool import build_hierarchy

    cm = jaxmap.compile_map(build_hierarchy(8, 2, 2))
    lanes = jaxmap.CHUNK_LANES

    def ln(u):
        return jaxmap._crush_ln_f64(u, cm.ln_tbl1, cm.ln_tbl2)

    text = (
        jax.jit(jax.vmap(jax.vmap(ln)))
        .lower(_sds((lanes, 5, items), jnp.uint32, one_chip))
        .compile()
        .as_text()
    )
    # every boolean array over the batch with an axis beside it: the
    # one-hots, whatever the compiler fused them into
    hots = []  # (shape, size of its minor-most axis)
    for m in re.finditer(r"pred\[([0-9,]+)\]\{([0-9,]+)", text):
        dims = [int(d) for d in m.group(1).split(",")]
        if lanes in dims and len(dims) > 3:
            hots.append((dims, dims[int(m.group(2).split(",")[0])]))
    for rows in (cm.ln_tbl1.shape[0], cm.ln_tbl2.shape[0]):
        assert any(rows in dims for dims, _ in hots), (rows, hots)
    assert all(minor == lanes for _, minor in hots), hots


@pytest.mark.parametrize(
    "nrows,nchunks",
    [(64, 256), (16, 1024)],  # 1 MiB EC shards; 4 MiB whole objects
)
def test_crc32c_program(one_chip, nrows, nchunks):
    chunk = scrub_kernels._CHUNK
    compiled = scrub_kernels._crc_call(chunk, nchunks).lower(
        _sds((nrows, nchunks, chunk), jnp.uint8, one_chip),
        _sds((chunk * 8, 32), jnp.int8, one_chip),
        _sds((nchunks * 32, 32), jnp.int8, one_chip),
    ).compile()
    _fits(compiled)


def test_compare_program(one_chip):
    width = 1 << 20
    compiled = scrub_kernels._compare_call(width).lower(
        _sds((64, width), jnp.uint8, one_chip),
        _sds((64, width), jnp.uint8, one_chip),
    ).compile()
    _fits(compiled)
