"""``ceph_tpu/tools/ec_benchmark.py``: ``--batch`` goes through the
stripe seam (ec/stripe.encode, ec/stripe.decode), one untimed
iteration runs before the clock, and the output stays the tool's
``<seconds>\\t<KB>``."""

from __future__ import annotations

import pytest

import ceph_tpu.ops  # noqa: F401  registers the jax backend
from ceph_tpu.ec import stripe
from ceph_tpu.ops.profiler import dispatch_profiler
from ceph_tpu.tools import ec_benchmark

COMMON = ["-p", "jerasure", "-P", "technique=reed_sol_van", "-P", "k=4",
          "-P", "m=2", "-P", "backend=jax", "-s", "16384"]


def _last_seq():
    entries = dispatch_profiler().history()["entries"]
    return max((e["seq"] for e in entries), default=0)


def _records(kind, since):
    """The ring's records of ``kind`` after ``since``: the ring drops
    its oldest, so its length is no count of dispatches."""
    entries = dispatch_profiler().history(kind)["entries"]
    return [e for e in entries if e["seq"] > since]


def _run(capsys, argv):
    assert ec_benchmark.main(COMMON + argv) == 0
    seconds, kb = capsys.readouterr().out.strip().splitlines()[-1].split("\t")
    return float(seconds), int(kb)


@pytest.mark.parametrize("workload,kind,extra", [
    ("encode", "ec_encode", []),
    ("decode", "ec_decode", ["-e", "2"]),
    ("decode", "ec_decode", ["--erased", "0", "--erased", "5"]),
])
def test_batch_is_one_dispatch_of_the_stripe_seam_an_iteration(
    capsys, monkeypatch, workload, kind, extra
):
    calls = []
    seam = getattr(stripe, workload)
    monkeypatch.setattr(
        stripe, workload, lambda *a, **kw: calls.append(1) or seam(*a, **kw))
    seen = _last_seq()
    seconds, kb = _run(capsys, ["-w", workload, "-i", "3", "--batch", "4"] + extra)
    assert seconds > 0 and kb == 3 * 4 * 16
    # three timed iterations and the untimed one before the clock
    assert len(calls) == 4
    new = _records(kind, seen)
    assert len(new) == 4
    assert all((r["backend"], r["ops"], r["stripes"]) == ("jax", 1, 4) for r in new)


def test_exhaustive_decode_verifies_every_pair_through_the_seam(capsys):
    seen = _last_seq()
    _seconds, kb = _run(capsys, ["-w", "decode", "-i", "1", "--batch", "2",
                                 "-e", "2", "-E", "exhaustive"])
    assert kb == 2 * 16
    assert len(_records("ec_decode", seen)) == 2 * 15  # untimed + timed, 15 pairs


def test_without_batch_the_plugin_is_called_as_upstream_calls_it(capsys):
    seen = _last_seq()
    _seconds, kb = _run(capsys, ["-w", "encode", "-i", "2"])
    assert kb == 2 * 16
    assert _records("ec_encode", seen) == []  # ec.encode, not the seam
    _seconds, kb = _run(capsys, ["-w", "decode", "-i", "2", "-e", "1"])
    assert kb == 2 * 16


def test_a_bitmatrix_technique_batches_through_the_seams_loop(capsys):
    argv = ["-p", "jerasure", "-P", "technique=cauchy_good", "-P", "k=4", "-P", "m=2",
            "-s", "16384", "-w", "encode", "-i", "1", "--batch", "2"]
    assert ec_benchmark.main(argv) == 0
    assert capsys.readouterr().out.strip().endswith("\t32")
