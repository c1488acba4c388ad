"""Time the write-ahead logs' drain threads spend applying a client
op's records to the store below: the ``wal_apply`` spans (the inner
``queue_transaction`` in ``WALStore._apply_one``: extent allocation,
the blob's crc, the block file's write, the KV frame, and the fsyncs
that ``store_fsync_ms_per_op`` reads apart:
``l_stage_wal_apply_ns``) of every OSD over the traced window, per
client op.  A shard over ``wal_prefer_deferred_size`` is acknowledged
only after it.  A program without the span, or a window in which none
ran, reads nothing."""


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    spent = counters.get("l_stage_wal_apply_ns", 0)
    if not ops or not spent:
        return None
    return 1e-6 * spent / ops
