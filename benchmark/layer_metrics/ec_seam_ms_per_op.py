"""Host time of the EC seam a client op costs: the ``ec_prepare``
(dedup, old-meta lookup, min_size), ``ec_encode`` (the codec call, the
device dispatch inside it) and ``txn_build`` (shard transactions, log
entry, info) spans of the traced window, per client op; 0 for a
window of reads."""

STAGES = ("ec_prepare", "ec_encode", "txn_build")


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    keys = [f"l_stage_{stage}_ns" for stage in STAGES]
    if not ops or not any(key in counters for key in keys):
        return None
    return 1e-6 * sum(counters.get(key, 0) for key in keys) / ops
