"""Mean wait of a client op on the primary's scheduler queue: the
``osd_queue_wait`` spans of the traced window, ``ms_dispatch``'s
enqueue to the op strand taking the op off the queue
(``l_stage_osd_queue_wait_{ns,count}``)."""


def read(run):
    counters = run["counters"]
    count = counters.get("l_stage_osd_queue_wait_count", 0)
    if not count:
        return None
    return 1e-6 * counters["l_stage_osd_queue_wait_ns"] / count
