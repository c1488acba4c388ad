"""Mean time of the primary's ``osd_op`` span that none of its child
spans on the op strand covers (admission, the object context, the PG
log, whatever has no span of its own): ``l_stage_osd_op_self_ns`` over
``l_stage_osd_op_count``, over the traced window."""


def read(run):
    counters = run["counters"]
    count = counters.get("l_stage_osd_op_count", 0)
    if not count:
        return None
    return 1e-6 * counters["l_stage_osd_op_self_ns"] / count
