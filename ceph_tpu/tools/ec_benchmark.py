"""Flag-compatible ceph_erasure_code_benchmark
(src/test/erasure-code/ceph_erasure_code_benchmark.cc).

Same options (-s/-i/-p/-w/-e/--erased/-E/-P), same output contract —
one line ``<seconds>\\t<KB>`` so qa/workunits/erasure-code/bench.sh's
GB/s conversion works unchanged.  Extension: ``--batch B`` encodes or
decodes B buffers per iteration through the stripe seam the OSD calls
(ec/stripe.py: ECUtil::encode's and ::decode's per-stripe loops, each
in one device call).  One untimed iteration runs before the clock, so
the seconds printed hold no compilation.
"""

from __future__ import annotations

import argparse
import random
import sys
import time

import numpy as np

from ..ec import ErasureCodeProfile, registry_instance


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="ec_benchmark", description=__doc__.splitlines()[0]
    )
    p.add_argument("-v", "--verbose", action="store_true")
    p.add_argument("-s", "--size", type=int, default=1024 * 1024,
                   help="size of the buffer to be encoded")
    p.add_argument("-i", "--iterations", type=int, default=1,
                   help="number of encode/decode runs")
    p.add_argument("-p", "--plugin", default="jerasure",
                   help="erasure code plugin name")
    p.add_argument("-w", "--workload", default="encode",
                   choices=["encode", "decode"])
    p.add_argument("-e", "--erasures", type=int, default=1,
                   help="number of erasures when decoding")
    p.add_argument("--erased", type=int, action="append", default=[],
                   help="erased chunk (repeat for more than one)")
    p.add_argument("-E", "--erasures-generation", default="random",
                   choices=["random", "exhaustive"])
    p.add_argument("-P", "--parameter", action="append", default=[],
                   help="add key=value to the erasure code profile")
    p.add_argument("--batch", type=int, default=1,
                   help="buffers per call of the stripe seam (one dispatch)")
    return p.parse_args(argv)


def make_code(args):
    profile = ErasureCodeProfile()
    for kv in args.parameter:
        if kv.count("=") != 1:
            print(f"--parameter {kv} ignored: not exactly one =",
                  file=sys.stderr)
            continue
        key, value = kv.split("=")
        profile[key] = value
    profile.setdefault("k", "7")
    profile.setdefault("m", "3")
    return registry_instance().factory(args.plugin, profile)


def _batch_payload(args, ec):
    """``--batch B``: B buffers of ``--size`` as one object for the
    stripe seam (ec/stripe.py, what the OSD calls) — the StripeInfo
    whose stripe is one padded buffer, and the B buffers back to
    back."""
    from ..ec.stripe import StripeInfo

    k = ec.get_data_chunk_count()
    width = ec.get_chunk_size(args.size) * k
    one = (b"X" * args.size).ljust(width, b"\0")
    return StripeInfo(k, width), np.frombuffer(
        one * args.batch, dtype=np.uint8
    )


def run_encode(args, ec) -> tuple[float, int]:
    """One untimed iteration first (on a device backend the first
    call compiles; the printed seconds hold none of that), then the
    clock."""
    data = b"X" * args.size
    want = set(range(ec.get_chunk_count()))
    if args.batch > 1:
        # hoisted path: B buffers in one call of the stripe seam
        from ..ec import stripe

        sinfo, payload = _batch_payload(args, ec)

        def once():
            stripe.encode(sinfo, ec, payload)
    else:
        def once():
            ec.encode(want, data)

    once()
    begin = time.perf_counter()
    for _ in range(args.iterations):
        once()
    elapsed = time.perf_counter() - begin
    return elapsed, args.iterations * args.batch * (args.size // 1024)


def _display_chunks(chunks, count):
    out = "chunks "
    for c in range(count):
        out += f"({c})  " if c not in chunks else f" {c}   "
    print(out + "(X) is an erased chunk")


def _decode_exhaustive(decode, all_chunks, chunks, start, want, verbose):
    """Recursive exhaustive erasure sweep with content verification
    (decode_erasures, ceph_erasure_code_benchmark.cc:202-249)."""
    n = len(all_chunks)
    if want == 0:
        if verbose:
            _display_chunks(chunks, n)
        want_to_read = {c for c in range(n) if c not in chunks}
        decoded = decode(want_to_read, chunks)
        for c in want_to_read:
            if not np.array_equal(decoded[c], all_chunks[c]):
                raise SystemExit(
                    f"chunk {c}: recovered content differs"
                )
        return
    for i in range(start, n):
        if i not in chunks:
            continue
        one_less = {c: v for c, v in chunks.items() if c != i}
        _decode_exhaustive(decode, all_chunks, one_less, i + 1, want - 1,
                           verbose)


def run_decode(args, ec) -> tuple[float, int]:
    """As :func:`run_encode`: one untimed iteration, then the clock.
    With ``--batch B`` the B buffers' shards go through
    ``ec/stripe.decode`` — one dispatch rebuilds the erased shards of
    all B, whatever the erasure pattern; without it a pattern the
    untimed iteration did not draw may still build its program inside
    the clock."""
    n = ec.get_chunk_count()
    want = set(range(n))
    if args.batch > 1:
        from ..ec import stripe

        sinfo, payload = _batch_payload(args, ec)
        encoded = stripe.encode(sinfo, ec, payload)

        def decode(want_to_read, chunks):
            return stripe.decode(sinfo, ec, chunks, want_to_read)
    else:
        encoded = ec.encode(want, b"X" * args.size)
        decode = ec.decode
    if args.erased:
        for c in args.erased:
            encoded.pop(c, None)
        _display_chunks(encoded, n)
    rng = random.Random()

    def once():
        if args.erasures_generation == "exhaustive":
            _decode_exhaustive(
                decode, encoded, dict(encoded), 0, args.erasures,
                args.verbose,
            )
        elif args.erased:
            decode(want, encoded)
        else:
            chunks = dict(encoded)
            for _ in range(args.erasures):
                while True:
                    erasure = rng.randrange(n)
                    if erasure in chunks:
                        break
                chunks.pop(erasure)
            decode(want, chunks)

    once()
    begin = time.perf_counter()
    for _ in range(args.iterations):
        once()
    elapsed = time.perf_counter() - begin
    return elapsed, args.iterations * args.batch * (args.size // 1024)


def main(argv=None) -> int:
    args = parse_args(argv)
    from ..common.compile_cache import configure_compile_cache

    configure_compile_cache()
    ec = make_code(args)
    if args.workload == "encode":
        elapsed, kb = run_encode(args, ec)
    else:
        elapsed, kb = run_decode(args, ec)
    print(f"{elapsed:.6f}\t{kb}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
