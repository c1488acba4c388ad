"""``ceph_erasure_code_benchmark -w decode -e 1`` on a code with a
minimum-bandwidth repair (CLAY), as ``ECBackend``'s recovery read
drives it: one codec from the plugin registry, one synchronous caller,
calls back to back through the stripe seam the store itself calls —
``ec/stripe.repair`` — helper fragments in as host bytes, the lost
shard out as host bytes.

The traffic mix is data: ``mode`` ``decode`` with ``erasures`` 1,
``reads`` ``minimum`` (a helper hands over the sub-chunk runs that
``minimum_to_decode`` names and nothing more), ``stripes_per_call``
buffers of the configuration's ``buffer_bytes`` a call,
``payload_pool`` seeded payloads rotated, ``warm_calls``,
``check_sample``.  Set-up encodes each payload with the plain
reference, so the program has touched none of the shards, and cuts
from them, for every lost position, the fragments of the d helpers the
codec's ``minimum_to_decode`` names — a stripe after another, each the
named runs concatenated, as ``ECStore._repair_minimum`` reads them
(the reads themselves happen on other hosts and are no part of a
call).  A call is given one such set — the position drawn uniformly,
with replacement, before the window — and returns the lost shard.
Set-up makes every call the window can make (every position once,
then ``warm_calls`` drawn ones), so nothing is built inside it.

An op is one call: ``(t_submit, t_done, stripes_per_call *
buffer_bytes, ok)`` — the tool's own accounting, KB of object a call.

``check``, once the window has closed, holds every byte of what a
seeded sample of the window's calls returned (the last call among
them) to the plain reference twice: to the shard its encode gave that
payload, and to its ``repair_shard`` of the same fragments.  Counted a
chunk (one stripe's share of the shard): ``wrong_chunks``,
``chunks_unchecked``; and ``calls_undispatched``, the window's calls
that left no ``ec_decode`` flight-recorder entry.
"""

from __future__ import annotations

import time

import numpy as np


class Driver:
    kind = "ec_decode"

    def __init__(self, config, traffic, seed, workdir, annotate, reference):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.annotate = annotate
        self.reference = reference
        if (traffic["mode"], int(traffic["erasures"]), traffic["reads"]) != (
            "decode", 1, "minimum",
        ):
            raise ValueError(
                "a repair is mode 'decode', erasures 1, reads 'minimum'"
            )
        profile = config["profile"]
        self.k, self.m, self.d = (int(profile[key]) for key in "kmd")
        self.n = self.k + self.m
        self.stripes = int(traffic["stripes_per_call"])
        self.buffer_bytes = int(config["buffer_bytes"])
        self.call_bytes = self.stripes * self.buffer_bytes
        self.calls = 0
        self.helper_bytes = 0
        self.rebuilt_bytes = 0
        self.next_call = 0  # call indices run on from the warm-up's
        self.kept: list = []  # (call index, returned shard) to check
        self.window_calls = 0
        self.window_dispatches = 0

    # -- set-up ----------------------------------------------------------
    def setup(self) -> None:
        import ceph_tpu.ops  # noqa: F401  registers the jax backend
        from ceph_tpu.ec import ErasureCodeProfile, registry_instance, stripe

        # a program without the seam's repair fails here, at once
        stripe.repair  # noqa: B018
        self.stripe = stripe
        profile = dict(self.config["profile"])
        plugin = profile.pop("plugin")
        self.ec = registry_instance().factory(
            plugin, ErasureCodeProfile(**{k: str(v) for k, v in profile.items()})
        )
        if (self.ec.get_data_chunk_count(), self.ec.get_chunk_count()) != (
            self.k, self.n,
        ):
            raise ValueError("the plugin's k, m are not the configuration's")
        self.chunk = self.ec.get_chunk_size(self.buffer_bytes)
        sub = self.ec.get_sub_chunk_count()
        if self.chunk * self.k != self.buffer_bytes or (
            self.chunk, sub
        ) != (int(self.config["chunk_bytes"]), int(self.config["sub_chunks"])):
            raise ValueError(
                f"a {self.buffer_bytes}-byte buffer gives chunks of "
                f"{self.chunk} in {sub} sub-chunks, not the configuration's"
            )
        self.sinfo = stripe.StripeInfo(self.k, self.buffer_bytes)
        rng = np.random.default_rng(self.seed)
        pool = int(self.traffic["payload_pool"])
        # the reference's shards: what is rebuilt is held to data the
        # program never touched
        self.shard_sets = [
            self.reference.encode_shards(
                rng.bytes(self.call_bytes), self.k, self.m, self.chunk, d=self.d
            )
            for _ in range(pool)
        ]
        self.fragments = [
            [self._cut(shards, lost) for lost in range(self.n)]
            for shards in self.shard_sets
        ]
        self.sample = max(int(self.traffic["check_sample"]) - 1, 0)
        # reservoir draws for the calls whose answers are kept, and the
        # window's lost positions, with replacement: made now
        self.draws = rng.random(1 << 16)
        self.lost = rng.integers(self.n, size=1 << 16)
        # warm-up: the window's own calls, every position first
        self.window(
            seconds=None, max_units=self.n + int(self.traffic["warm_calls"])
        )
        self.calls = self.helper_bytes = self.rebuilt_bytes = 0
        self.kept = []

    def _cut(self, shards, lost: int) -> dict:
        """What the d helpers of ``lost`` hand over: the runs the codec
        asks of each, of every stripe of its shard."""
        minimum = self.ec.minimum_to_decode(
            {lost}, set(range(self.n)) - {lost}
        )
        sub = self.chunk // self.ec.get_sub_chunk_count()
        out = {}
        for helper, runs in minimum.items():
            if [tuple(run) for run in runs] != self.reference.repair_reads(
                lost, self.k, self.m, self.d
            ):
                raise ValueError(
                    f"the codec reads {runs} of helper {helper}, not what "
                    "the reference's repair is given"
                )
            rows = shards[helper].reshape(self.stripes, -1, sub)
            out[helper] = np.concatenate(
                [rows[:, first : first + count] for first, count in runs], axis=1
            ).reshape(-1)
        return out

    def _lost(self, call: int) -> int:
        """The position call number ``call`` has lost: set-up's first
        calls walk every position once, every later call takes the
        next of the seeded draws."""
        if call < self.n:
            return call
        return int(self.lost[(call - self.n) % len(self.lost)])

    # -- the loop ----------------------------------------------------------
    def _dispatches(self) -> int:
        from ceph_tpu.ops.profiler import dispatch_profiler

        return dispatch_profiler().totals().get(self.kind, {}).get("dispatches", 0)

    def window(self, seconds, max_units=None) -> dict:
        """Calls back to back until ``seconds`` have passed; the call in
        flight at that moment is finished and counted."""
        stripe, sinfo, ec = self.stripe, self.sinfo, self.ec
        pool = len(self.shard_sets)
        sample, draws = self.sample, self.draws
        ops, kept, newest = [], [], None
        first = self.next_call
        dispatched = self._dispatches()
        t0 = time.perf_counter()
        while True:
            if max_units is not None and len(ops) >= max_units:
                break
            t = time.perf_counter()
            if seconds is not None and t - t0 >= seconds:
                break
            call = first + len(ops)
            lost = self._lost(call)
            fragments = self.fragments[call % pool][lost]
            with self.annotate("bench:repair"):
                out = stripe.repair(sinfo, ec, fragments, lost)
            ops.append((t, time.perf_counter(), self.call_bytes, True))
            self.helper_bytes += sum(len(f) for f in fragments.values())
            self.rebuilt_bytes += len(out)
            # a uniform sample of the calls before the newest (algorithm
            # R on draws made in set-up), so that the last call, always
            # kept, is never also one of the sample; the references
            # keep the answers alive
            if newest is not None:
                i = len(ops) - 2
                if i < sample:
                    kept.append(newest)
                elif sample:
                    j = int(draws[i % len(draws)] * (i + 1))
                    if j < sample:
                        kept[j] = newest
            newest = (call, out)
        self.next_call = first + len(ops)
        self.calls += len(ops)
        self.window_calls = len(ops)
        self.window_dispatches = self._dispatches() - dispatched
        self.kept = kept + [newest] if ops else []
        return {"ops": ops, "units": len(ops), "t0": t0}

    def counters(self) -> dict:
        return {
            "calls": self.calls,
            "helper_bytes": self.helper_bytes,
            "rebuilt_bytes": self.rebuilt_bytes,
        }

    # -- correctness -------------------------------------------------------
    def check(self) -> dict:
        """Every byte of the shard each kept call returned, against the
        reference's encoded shard of that payload and against its
        repair of the same fragments; a chunk is one stripe's share."""
        pool = len(self.shard_sets)
        wrong = compared = 0
        for call, out in self.kept:
            lost = self._lost(call)
            got = np.asarray(out, dtype=np.uint8).reshape(-1)
            encoded = self.shard_sets[call % pool][lost]
            if got.shape != encoded.shape:
                continue
            repaired = self.reference.repair_shard(
                self.fragments[call % pool][lost], lost, self.k, self.m,
                self.chunk, d=self.d,
            )
            compared += self.stripes
            differs = (got != encoded) | (got != repaired)
            wrong += int(differs.reshape(self.stripes, -1).any(axis=1).sum())
        calls = min(int(self.traffic["check_sample"]), self.window_calls)
        owed = max(calls, len(self.kept)) * self.stripes
        return {
            "wrong_chunks": (wrong, 0),
            "chunks_unchecked": (owed - compared, 0),
            "calls_undispatched": (
                max(self.window_calls - self.window_dispatches, 0), 0
            ),
        }

    # -- faults (control.py and the tests plant them; never a run) ---------
    def _patch(self, make):
        original = self.stripe.repair
        self.stripe.repair = make(original)
        return lambda: setattr(self.stripe, "repair", original)

    def fault_control(self):
        """The control: the reference with its guarantee broken, put in
        the codec's place — one helper's fragment is left out of the
        coupling."""
        ref, k, m, d, chunk = self.reference, self.k, self.m, self.d, self.chunk

        def make(_original):
            def repair(sinfo, ec, fragments, lost):
                return ref.repair_shard(
                    fragments, lost, k, m, chunk, d=d, guarantee="broken"
                )
            return repair
        return self._patch(make)

    def fault_altered_answer(self):
        """One byte of every call's answer altered where it is
        produced."""
        def make(original):
            def altered(*args, **kwargs):
                shard = np.array(original(*args, **kwargs), dtype=np.uint8)
                shard[len(shard) // 2] ^= 0x01
                return shard
            return altered
        return self._patch(make)

    def close(self) -> None:
        self.kept = []
