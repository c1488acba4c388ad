"""Mean wait of an op for one of the client's aio threads
(``Rados._pool``): the ``client_aio_wait`` spans of the traced window,
pool submit to the thread taking the op up, from the stage counters
every finished span feeds (``l_stage_client_aio_wait_{ns,count}``)."""


def read(run):
    counters = run["counters"]
    count = counters.get("l_stage_client_aio_wait_count", 0)
    if not count:
        return None
    return 1e-6 * counters["l_stage_client_aio_wait_ns"] / count
