"""Share of a remap's host work on fetched parts — the oracle's
fallback lanes, the widening, the fix-ups, the write into the tables —
that ran while a later part of the same remap was on the device:
``l_tpu_crush_host_overlapped_ns`` over ``l_tpu_crush_host_ns``, both
counted where the parts are handed out (``jaxmap.map_parts``), over the
traced window.  A remap of one part has nothing issued ahead and reads
0; one of 16 parts whose host work is even reads 15/16.  A program
from before ISSUE 37 has neither counter and reads nothing."""


def read(run):
    counters = run["counters"]
    host = counters.get("l_tpu_crush_host_ns")
    overlapped = counters.get("l_tpu_crush_host_overlapped_ns")
    if host is None or overlapped is None:
        return None
    return 100.0 * overlapped / host if host else 0.0
