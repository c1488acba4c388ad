"""How many of the host's cores the process kept busy: the CPU time of
all its threads (``l_process_cpu_ns``, ``getrusage(RUSAGE_SELF)`` read
in at each dump of the kernel set) over the traced window's span from
its first submit to its last completion.  Read by its stem for each
split: ``host_cores_busy.ecpool`` (the served write) and
``host_cores_busy.crush`` (the remap).  A program that keeps no such
counter reads nothing."""


def read(run):
    cpu_ns = run["counters"].get("l_process_cpu_ns")
    if cpu_ns is None:
        return None
    return cpu_ns / 1e9 / run["client"]["span_s"]
