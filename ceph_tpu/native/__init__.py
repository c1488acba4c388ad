"""Native runtime pieces — C compiled on demand, loaded via ctypes.

The reference's data plane is C++ throughout; here the TPU kernels are
JAX and the host runtime stays Python except where byte-granular CPU
work matters.  First resident: ceph_crc32c (shard hashes; the pure-
Python fallback is table-exact but ~1000x slower).
"""

from __future__ import annotations

import ctypes
import functools
import pathlib
import subprocess
import tempfile

_SOURCES = [
    pathlib.Path(__file__).parent / "crc32c.c",
    pathlib.Path(__file__).parent / "gf8.c",
]


@functools.lru_cache(maxsize=1)
def _lib():
    """Build (once per user cache) and load the native library; None if
    no C compiler works here.  Private 0700 cache dir + write-then-
    rename keep a shared host from injecting or racing the build.

    ISA policy: ``-mssse3`` on x86 unlocks the pshufb GF region
    kernel (universal on x86-64 silicon since ~2006) — NOT
    ``-march=native``, whose AVX-512-class output would SIGILL when a
    shared $HOME hands the cached .so to an older node; the cache
    file is keyed by machine arch for the same reason.  Compilers
    that reject the flag retry with plain -O3 (scalar loops)."""
    import platform

    build = (
        pathlib.Path.home() / ".cache" / "ceph_tpu" / "native"
    )
    build.mkdir(parents=True, exist_ok=True, mode=0o700)
    arch = platform.machine() or "unknown"
    so = build / f"libceph_tpu_native_{arch}.so"
    try:
        src_mtime = max(s.stat().st_mtime for s in _SOURCES)
        if not so.exists() or so.stat().st_mtime < src_mtime:
            with tempfile.NamedTemporaryFile(
                dir=build, suffix=".so", delete=False
            ) as tmp:
                tmp_path = pathlib.Path(tmp.name)
            srcs = [str(s) for s in _SOURCES]
            flags = (
                ["-O3", "-mssse3"]
                if arch in ("x86_64", "i686", "AMD64")
                else ["-O3"]
            )
            try:
                subprocess.run(
                    [
                        "cc", *flags, "-shared",
                        "-fPIC", *srcs, "-o", str(tmp_path),
                    ],
                    check=True,
                    capture_output=True,
                )
            except subprocess.CalledProcessError:
                subprocess.run(
                    [
                        "cc", "-O3", "-shared", "-fPIC",
                        *srcs, "-o", str(tmp_path),
                    ],
                    check=True,
                    capture_output=True,
                )
            tmp_path.replace(so)
        lib = ctypes.CDLL(str(so))
        # the same routine through a handle that keeps the interpreter
        # lock across the call, for inputs too short to be worth
        # giving it up (ceph_crc32c below)
        lib.crc32c_held = ctypes.PyDLL(str(so)).ceph_crc32c
        for crc in (lib.ceph_crc32c, lib.crc32c_held):
            crc.restype = ctypes.c_uint32
            crc.argtypes = [
                ctypes.c_uint32,
                ctypes.c_char_p,
                ctypes.c_size_t,
            ]
        try:
            u8p = ctypes.POINTER(ctypes.c_uint8)
            lib.gf8_region_mac.restype = None
            lib.gf8_region_mac.argtypes = [
                u8p, u8p, u8p, ctypes.c_size_t,
            ]
            lib.gf8_region_xor.restype = None
            lib.gf8_region_xor.argtypes = [u8p, u8p, ctypes.c_size_t]
        except AttributeError:
            # a stale cached .so without the gf8 symbols: crc32c
            # still serves; gf callers see the missing attribute and
            # keep their numpy path
            pass
        return lib
    except (OSError, subprocess.CalledProcessError, AttributeError):
        return None


@functools.lru_cache(maxsize=1)
def _py_table():
    poly = 0x1EDC6F41

    def rev8(b):
        return int(f"{b:08b}"[::-1], 2)

    def rev32(v):
        return int(f"{v:032b}"[::-1], 2)

    table = []
    for i in range(256):
        c = rev8(i) << 24
        for _ in range(8):
            c = ((c << 1) ^ poly) & 0xFFFFFFFF if c & 0x80000000 else (
                c << 1
            ) & 0xFFFFFFFF
        table.append(rev32(c))
    return table


def gf8_matrix_regions(matrix, regions):
    """GF(2^8) coding-matrix apply over byte regions through the C
    region-MAC kernel (the jerasure_matrix_encode / ec_encode_data
    hot loop): returns the (m, nbytes) uint8 parity regions, or None
    when no native library is available (callers keep the numpy
    path).  Bit-exact with gf.matrix_vector_mul_region — the pure-
    python oracle stays the independent reference."""
    import numpy as np

    lib = _lib()
    if lib is None or not hasattr(lib, "gf8_region_mac"):
        return None
    from ..gf.arith import _byte_table8

    regions = np.ascontiguousarray(regions, dtype=np.uint8)
    m, k = matrix.shape
    n = regions.shape[1]
    out = np.zeros((m, n), dtype=np.uint8)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    for i in range(m):
        out_p = out[i].ctypes.data_as(u8p)
        for j in range(k):
            c = int(matrix[i, j])
            if c == 0:
                continue
            in_p = regions[j].ctypes.data_as(u8p)
            if c == 1:
                lib.gf8_region_xor(in_p, out_p, n)
            else:
                table = _byte_table8(c)
                lib.gf8_region_mac(
                    in_p, out_p,
                    table.ctypes.data_as(u8p), n,
                )
    return out


# below this many bytes (some 30 us of checksum at 2 GB/s) the crc is
# computed holding the interpreter lock
_CRC_RELEASE_BYTES = 1 << 16


def ceph_crc32c(crc: int, data: bytes | memoryview) -> int:
    """ceph_crc32c(seed, data) — matches src/include/crc32c.h semantics
    (verified against the reference's test vectors in
    src/test/common/test_crc32c.cc)."""
    data = bytes(data)
    lib = _lib()
    if lib is not None:
        # a frame header, a heartbeat or an ack checksums in a
        # microsecond or two: letting go of the interpreter for that
        # costs a thread hand-over (a fraction of a millisecond when
        # other threads want it) some three hundred times a served
        # write.  Only a payload worth the hand-over releases it.
        if len(data) < _CRC_RELEASE_BYTES:
            return lib.crc32c_held(crc & 0xFFFFFFFF, data, len(data))
        return lib.ceph_crc32c(crc & 0xFFFFFFFF, data, len(data))
    table = _py_table()
    crc &= 0xFFFFFFFF
    for b in data:
        crc = (crc >> 8) ^ table[(crc ^ b) & 0xFF]
    return crc
