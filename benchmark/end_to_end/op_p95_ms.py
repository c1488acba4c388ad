"""95th percentile of the latency of every client op of the window,
submit to ack, on the client's clock."""


def read(run):
    return run["client"]["p95_ms"]
