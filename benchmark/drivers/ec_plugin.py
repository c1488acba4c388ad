"""``ceph_erasure_code_benchmark`` at the plugin boundary: one codec
from the plugin registry (``registry_instance().factory``, as
``osd/ec_pg.ECCodec`` makes it), one synchronous caller, calls back to
back through the stripe seam the OSD itself calls — ``ec/stripe.encode``
and ``ec/stripe.decode`` — host bytes in, host bytes out.

The traffic mix is data: ``mode`` (``encode`` = the tool's ``-w
encode``, ``decode`` = ``-w decode -e <erasures> -E random``),
``stripes_per_call`` buffers of the configuration's ``buffer_bytes`` a
call (the tool's ``--batch``), ``payload_pool`` seeded payloads
rotated, ``warm_calls``, ``check_sample``.  A decode call is given the
shards of one payload — made in set-up by the plain reference's
encode, so the program has touched none of them — less a seeded set
of ``erasures`` positions, the same for every stripe of the call: one
of the C(k+m, erasures) combinations drawn uniformly, with
replacement, before the window (the tool's ``-E random``); it returns
the lost shards rebuilt.  Set-up makes every call the window can make
(for decode: every combination once, then ``warm_calls`` drawn ones),
so nothing is built inside it.

An op is one call: ``(t_submit, t_done, stripes_per_call *
buffer_bytes, ok)`` — the tool's own accounting, KB of object a call,
for decode too.

``check``, once the window has closed, holds every byte of what a
seeded sample of the window's calls returned (the last call among
them; the loop keeps references, it copies and sums nothing) to the
plain reference: encode, all k+m shards against ``encode_shards``;
decode, the rebuilt shards against ``decode_shards`` of the same
survivors.  Counted a chunk (one stripe's share of a shard):
``wrong_chunks``, ``chunks_unchecked``; and ``calls_undispatched``,
the window's calls that left no flight-recorder entry of their kind.
"""

from __future__ import annotations

import itertools
import time

import numpy as np


class Driver:
    def __init__(self, config, traffic, seed, workdir, annotate, reference):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.annotate = annotate
        self.reference = reference
        self.mode = traffic["mode"]
        if self.mode not in ("encode", "decode"):
            raise ValueError(f"mode is 'encode' or 'decode', not {self.mode!r}")
        self.kind = f"ec_{self.mode}"
        profile = config["profile"]
        self.k, self.m = int(profile["k"]), int(profile["m"])
        self.n = self.k + self.m
        self.stripes = int(traffic["stripes_per_call"])
        self.buffer_bytes = int(config["buffer_bytes"])
        self.call_bytes = self.stripes * self.buffer_bytes
        self.calls = 0
        self.next_call = 0  # call indices run on from the warm-up's
        self.kept: list = []  # (call index, returned shards) to check
        self.window_calls = 0
        self.window_dispatches = 0

    # -- set-up ----------------------------------------------------------
    def setup(self) -> None:
        import ceph_tpu.ops  # noqa: F401  registers the jax backend
        from ceph_tpu.ec import ErasureCodeProfile, registry_instance, stripe

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
        if self.chunk * self.k != self.buffer_bytes:
            raise ValueError(
                f"a {self.buffer_bytes}-byte buffer is not k whole chunks "
                f"of {self.chunk}"
            )
        self.sinfo = stripe.StripeInfo(self.k, self.buffer_bytes)
        rng = np.random.default_rng(self.seed)
        pool = int(self.traffic["payload_pool"])
        self.payloads = [
            np.frombuffer(rng.bytes(self.call_bytes), dtype=np.uint8)
            for _ in range(pool)
        ]
        sample = max(int(self.traffic["check_sample"]) - 1, 0)
        self.sample = sample
        # reservoir draws for the calls whose answers are kept, made now
        self.draws = rng.random(1 << 16)
        warm = int(self.traffic["warm_calls"])
        if self.mode == "decode":
            # the reference's shards: what is rebuilt is held to data
            # the program never touched
            self.shard_sets = [
                dict(enumerate(self.reference.encode_shards(
                    p, self.k, self.m, self.chunk
                )))
                for p in self.payloads
            ]
            self.combos = list(
                itertools.combinations(range(self.n), int(self.traffic["erasures"]))
            )
            # the window's draws, with replacement, made now
            self.lost = rng.integers(len(self.combos), size=1 << 16)
            warm += len(self.combos)
            self.payloads = [None] * pool  # the shard sets stand for them
        # warm-up: the window's own calls
        self.window(seconds=None, max_units=warm)
        self.calls = 0
        self.kept = []

    def _lost(self, call: int) -> tuple:
        """The positions call number ``call`` has lost: set-up's first
        calls walk every combination once, every later call takes the
        next of the seeded draws."""
        n = len(self.combos)
        if call < n:
            return self.combos[call]
        return self.combos[self.lost[(call - n) % len(self.lost)]]

    # -- the loop ----------------------------------------------------------
    def _dispatches(self) -> int:
        from ceph_tpu.ops.profiler import dispatch_profiler

        return dispatch_profiler().totals().get(self.kind, {}).get("dispatches", 0)

    def window(self, seconds, max_units=None) -> dict:
        """Calls back to back until ``seconds`` have passed; the call in
        flight at that moment is finished and counted."""
        stripe, sinfo, ec = self.stripe, self.sinfo, self.ec
        decode = self.mode == "decode"
        pool = len(self.payloads)
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
            with self.annotate("bench:" + self.mode):
                if decode:
                    lost = self._lost(call)
                    shards = self.shard_sets[call % pool]
                    out = stripe.decode(
                        sinfo, ec,
                        {p: s for p, s in shards.items() if p not in lost},
                        lost,
                    )
                else:
                    out = stripe.encode(sinfo, ec, self.payloads[call % pool])
            ops.append((t, time.perf_counter(), self.call_bytes, True))
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
        return {"calls": self.calls}

    # -- correctness -------------------------------------------------------
    def check(self) -> dict:
        """Every byte of every shard the kept calls returned, against
        the reference; a chunk is one stripe's share of a shard."""
        pool = len(self.payloads)
        wrong = compared = 0
        for call, out in self.kept:
            if self.mode == "encode":
                want = dict(enumerate(self.reference.encode_shards(
                    self.payloads[call % pool], self.k, self.m, self.chunk
                )))
            else:
                lost = self._lost(call)
                shards = self.shard_sets[call % pool]
                ref = self.reference.decode_shards(
                    {p: s for p, s in shards.items() if p not in lost},
                    self.k, self.m, self.chunk,
                )
                want = {p: ref[p] for p in lost}
            for p, ref_shard in want.items():
                got = out.get(p) if isinstance(out, dict) else None
                if got is None:
                    continue
                got = np.asarray(got, dtype=np.uint8).reshape(-1)
                if got.shape != ref_shard.shape:
                    continue
                compared += self.stripes
                differs = got.reshape(self.stripes, -1) != ref_shard.reshape(
                    self.stripes, -1
                )
                wrong += int(differs.any(axis=1).sum())
        # what the sample owes: so many calls, each so many shards
        calls = min(int(self.traffic["check_sample"]), self.window_calls)
        shards = self.n if self.mode == "encode" else int(self.traffic["erasures"])
        owed = max(calls, len(self.kept)) * shards * self.stripes
        return {
            "wrong_chunks": (wrong, 0),
            "chunks_unchecked": (owed - compared, 0),
            "calls_undispatched": (
                max(self.window_calls - self.window_dispatches, 0), 0
            ),
        }

    # -- faults (control.py and the tests plant them; never a run) ---------
    def _patch(self, name: str, make):
        original = getattr(self.stripe, name)
        setattr(self.stripe, name, make(original))
        return lambda: setattr(self.stripe, name, original)

    def fault_control(self):
        """The control: the reference with its guarantee broken, put in
        the codec's place — encode's last coding row leaves the last
        data chunk out; decode leaves the last survivor out of every
        rebuilt shard."""
        ref, k, m, chunk = self.reference, self.k, self.m, self.chunk
        if self.mode == "encode":
            def make(_original):
                def encode(sinfo, ec, data, want=None):
                    return dict(enumerate(ref.encode_shards(
                        data, k, m, chunk, guarantee="broken"
                    )))
                return encode
        else:
            def make(_original):
                def decode(sinfo, ec, shards, want):
                    out = ref.decode_shards(
                        shards, k, m, chunk, guarantee="broken"
                    )
                    return {p: out[p] for p in want}
                return decode
        return self._patch(self.mode, make)

    def fault_altered_answer(self):
        """One byte of the last shard of every call's answer altered
        where it is produced."""
        def make(original):
            def altered(*args, **kwargs):
                out = original(*args, **kwargs)
                last = max(out)
                shard = np.array(out[last], dtype=np.uint8)
                shard[len(shard) // 2] ^= 0x01
                out[last] = shard
                return out
            return altered
        return self._patch(self.mode, make)

    def close(self) -> None:
        self.kept = []
