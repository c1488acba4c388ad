"""Median time an op of the traced window waited at the client before
the Objecter took it up: its latency, submit to ack on the client's
clock, less the duration of its ``client_op`` span (the k-th op
completed on a name pairs with the k-th span finished on it)."""

import collections
import statistics


def read(run):
    spans = collections.defaultdict(collections.deque)
    for s in run["spans"]:
        if s["name"] == "client_op":
            spans[s["oid"]].append(s["duration_s"])
    waits = [
        (op[1] - op[0]) - spans[op[4]].popleft()
        for op in run["ops"]
        if len(op) > 4 and spans.get(op[4])
    ]
    if not waits:
        return None
    return 1e3 * statistics.median(waits)
