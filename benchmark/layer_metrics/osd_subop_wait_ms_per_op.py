"""Time the primary's op strand spends from the first ``MOSDRepOp``
sent to the last ack, per client op: the ``sub_op_wait`` spans of the
traced window (``l_stage_sub_op_wait_ns``).  A write opens exactly one
such span, so in a window of writes this is the span's mean; a window
of reads opens none and reads 0."""


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    if not ops or "l_stage_sub_op_wait_ns" not in counters:
        return None
    return 1e-6 * counters["l_stage_sub_op_wait_ns"] / ops
