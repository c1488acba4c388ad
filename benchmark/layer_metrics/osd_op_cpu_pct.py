"""Share of the primary's ``osd_op`` spans in which their thread ran on
a core: ``l_stage_osd_op_cpu_ns`` (``getrusage(RUSAGE_THREAD)`` at the
span's enter and finish, children included) over ``l_stage_osd_op_ns``,
over the traced window.  The rest is waiting: for the sub-ops' replies,
for a lock, for the interpreter.  A program that counts no thread usage
reads nothing; a window that finished no ``osd_op`` reads 0."""


def read(run):
    counters = run["counters"]
    cpu = counters.get("l_stage_osd_op_cpu_ns")
    if cpu is None:
        return None
    total = counters.get("l_stage_osd_op_ns", 0)
    return 100.0 * cpu / total if total else 0.0
