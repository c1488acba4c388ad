"""Bytes of client ops acknowledged in the window (10^6 bytes) over
the whole window, from the first submit to the last ack."""


def read(run):
    return run["client"]["amount"] / 1e6 / run["client"]["span_s"]
