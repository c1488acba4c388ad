"""Messenger time a client op costs, sub-ops included: the
``msgr_send`` and ``msgr_recv`` spans (``l_stage_msgr_{send,recv}_ns``)
of the client and of every OSD over the traced window, per client op.
Summed over threads that run at once, so it is work charged to an op,
not time on its critical path.  The two forms of a message differ: a
bare one (client -> OSD) is encode + frame write, and frame header
read -> decoded; an enveloped one (OSD <-> OSD session) begins with
the inner frame's encode on the sender's thread, so its send holds the
hand-off to the loop thread, and ends when the inner frame is parsed
on the dispatch strand, so its receive holds the wait for that
strand."""

KEYS = ("l_stage_msgr_send_ns", "l_stage_msgr_recv_ns")


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    if not ops or not any(key in counters for key in KEYS):
        return None
    return 1e-6 * sum(counters.get(key, 0) for key in KEYS) / ops
