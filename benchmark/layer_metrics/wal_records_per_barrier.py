"""Group-commit occupancy of the OSDs' write-ahead logs: records
committed over the fsync barriers that made them durable
(``os_wal.appends / os_wal.barriers``, the ``l_os_wal_*`` counters
summed over the OSDs by the driver), over the traced window.  1.0 is a
log that finds nothing to group: every shard commit pays a barrier of
its own.  A memstore window has no such counter and reads nothing."""


def read(run):
    counters = run["counters"]
    barriers = counters.get("os_wal.barriers", 0)
    if not barriers or "os_wal.appends" not in counters:
        return None
    return counters["os_wal.appends"] / barriers
