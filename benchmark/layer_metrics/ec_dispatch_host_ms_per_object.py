"""Host wall of the ``ec_encode`` device dispatches (upload, issue
and the fetch the host waits in) per object written: the flight
recorder's ``wall_s`` over the client ops of the traced window, each
of which writes one object (the recorder's own ``ops`` counts the same
objects; a window of reads dispatches nothing and reads 0)."""


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    if not ops or "dispatch.ec_encode.wall_s" not in counters:
        return None
    return 1e3 * counters["dispatch.ec_encode.wall_s"] / ops
