"""How many of a write's sub-ops are on the wire at once: the summed
``sub_op_rtt`` spans (one a peer, from just before its ``MOSDRepOp``
is sent to its reply resolved: ``l_stage_sub_op_rtt_ns``) over the
``sub_op_wait`` spans that hold them (``l_stage_sub_op_wait_ns``).
Round trips made one after another read 1.0, the k+m-1 of a stripe
made all at once read k+m-1.  A program that records no ``sub_op_rtt``
(the tree before the fan-out) reads nothing; a window of reads, which
opens no ``sub_op_wait``, has no sub-op on the wire and reads 0 (the
benchmark's own read rehearsal wants a number of every reader of the
write cell, as of ``osd_subop_wait_ms_per_op``)."""


def read(run):
    rtt = run["counters"].get("l_stage_sub_op_rtt_ns")
    waited = run["counters"].get("l_stage_sub_op_wait_ns")
    if rtt is None or waited is None:
        return None
    return rtt / waited if waited else 0.0
