"""Object-store commit time a client op costs: the ``store_commit``
spans (``store.queue_transaction`` on the primary and on every
replica) of the traced window, per client op
(``l_stage_store_commit_ns``); 0 for a window of reads."""


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    if not ops or "l_stage_store_commit_ns" not in counters:
        return None
    return 1e-6 * counters["l_stage_store_commit_ns"] / ops
