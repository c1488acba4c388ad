"""Time the write-ahead logs' writer threads spend in barriers a
client op costs: the ``wal_barrier`` spans (a group's records framed
into the log and the one fsync behind them, ``WALStore._commit_batch``:
``l_stage_wal_barrier_ns``) of every OSD over the traced window, per
client op.  Summed over ten logs that sync beside each other: work
charged to an op, of which the primary's and the slowest replica's
lie on its path.  A program without the span (the tree before it, a
memstore cluster) or a window in which none ran reads nothing."""


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    spent = counters.get("l_stage_wal_barrier_ns", 0)
    if not ops or not spent:
        return None
    return 1e-6 * spent / ops
