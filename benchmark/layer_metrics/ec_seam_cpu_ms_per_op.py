"""CPU time the EC seam's thread spent on a client op: the thread usage
of the ``ec_prepare``, ``ec_encode`` and ``txn_build`` spans
(``l_stage_<stage>_cpu_ns``) of the traced window, per client op, the
same quotient as ``ec_seam_ms_per_op`` takes of their durations; what
that has over this is the seam's waiting.  0 for a window of reads; a
program that counts no thread usage reads nothing."""

STAGES = ("ec_prepare", "ec_encode", "txn_build")


def read(run):
    counters = run["counters"]
    ops = counters.get("client.ops_done", 0)
    keys = [f"l_stage_{stage}_cpu_ns" for stage in STAGES]
    if not ops or not any(key in counters for key in keys):
        return None
    return 1e-6 * sum(counters.get(key, 0) for key in keys) / ops
