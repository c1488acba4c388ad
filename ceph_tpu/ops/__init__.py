"""TPU kernels for the storage compute plane.

The reference's hot kernels are CPU SIMD loops (gf-complete/isa-l GF(2^8)
region MACs, jerasure bitmatrix XOR schedules — SURVEY.md §2.1/§3.1); here
the same math is reformulated MXU-first:

GF(2^w) arithmetic is GF(2)-linear over the bits of each w-bit word, so a
Reed-Solomon coding matrix lifts to a (m·w, k·w) GF(2) bitmatrix and
``parity = M ⊗ data`` becomes ``bits_out = (B @ bits_in) mod 2`` — one int8
matmul on the systolic array per stripe batch, instead of k·m table-lookup
region passes.  XOR-schedule (bitmatrix) techniques are the same primitive
with packet-interleaved bit layout.  See ``gf_matmul`` for layout contracts
and ``packed_gf`` for the packed-lane kernel of a generator matrix.

Importing this module registers the ``jax`` erasure-code backend.
"""

from .ec_backend import JaxBackend, get_jax_backend  # noqa: F401

__all__ = ["JaxBackend", "get_jax_backend"]
