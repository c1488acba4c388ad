"""Allocator policy for a process that moves large buffers.

glibc hands a freed block back to the kernel at once when it is large
(above the mmap threshold: ``munmap``) or tops the heap (above the
trim threshold), so a caller that allocates and frees tens of MiB a
call — the stripe seam at 64 MiB an object: a folded copy, k + m
shards — touches fresh pages on every call.  On the chip's host a
fault costs some 4 us: 64 MiB is 16,384 of them, 65-70 ms where the
copy itself takes 6 (PERF.md section 6, PR 28: an encode call read
26 ms with the blocks kept and 27-196 ms, by the call, without).
Upstream links its daemons and tools against tcmalloc, which keeps
freed spans; this is the same policy for glibc.  No option, and one
owner: ``ec/backend.get_backend`` calls it when a codec asks for the
device backend, so every process that hosts such a codec -- a daemon,
a tool, the benchmark's drivers -- runs under it and no other does.
"""

from __future__ import annotations

import ctypes

# <malloc.h>
M_TRIM_THRESHOLD = -1
M_MMAP_THRESHOLD = -3

KEEP_BLOCKS_BELOW = 1 << 30  # served from the heap, not by mmap
KEEP_HEAP_TOP = (1 << 31) - 1  # free top of the heap kept up to this


def keep_large_blocks() -> bool:
    """Keep freed blocks below 1 GiB in the process (no ``munmap``, no
    heap trim) so that the next call reuses touched pages.  True when
    the C library took both settings; False where it has no
    ``mallopt`` (not glibc) — the process then runs as it did."""
    try:
        mallopt = ctypes.CDLL(None).mallopt
    except (OSError, AttributeError):
        return False
    return bool(
        mallopt(M_MMAP_THRESHOLD, KEEP_BLOCKS_BELOW)
        and mallopt(M_TRIM_THRESHOLD, KEEP_HEAP_TOP)
    )
