"""What making the applies durable costs a client op: the
``store_fsync`` spans (each ``os.fsync`` of a BlockStore's block file
and KV log, ``store/framed_log.fsync``: ``l_stage_store_fsync_ns``) of
every OSD over the traced window, per client op.  The medium's share
of a commit; the write-ahead log's own barrier fsync is inside
``wal_barrier_ms_per_op``.  A store opened ``sync=False`` (the tree
that did not read the spec's ``sync``), a memstore cluster and a
program without the span read nothing, as does a window without an
fsync."""


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    spent = counters.get("l_stage_store_fsync_ns", 0)
    if not ops or not spent:
        return None
    return 1e-6 * spent / ops
