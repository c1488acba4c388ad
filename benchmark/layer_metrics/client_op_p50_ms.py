"""Median latency of the client ops of the traced window, submit to
ack, on the client's clock."""


def read(run):
    return run["client"]["p50_ms"]
