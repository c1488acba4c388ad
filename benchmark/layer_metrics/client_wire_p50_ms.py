"""Median time an op of the traced window was the Objecter's (target,
send, retries, reply): the duration of its root span ``client_op``,
opened when one of the client's aio threads takes the op up."""

import statistics


def read(run):
    durations = [s["duration_s"] for s in run["spans"] if s["name"] == "client_op"]
    if not durations:
        return None
    return 1e3 * statistics.median(durations)
