"""Write amplification at the write-ahead log: transaction payload
bytes the OSDs' logs took (``os_wal.append_bytes``: every shard and
its attributes, the PG log entry riding in the same record) over the
bytes of client ops acknowledged in the traced window.  (k+m)/k = 1.5
is the erasure code's own share; the block file takes as much again.
A memstore window has no such counter and reads nothing."""


def read(run):
    counters = run["counters"]
    acked = run["client"]["amount"]
    if not acked or "os_wal.append_bytes" not in counters:
        return None
    return counters["os_wal.append_bytes"] / acked
