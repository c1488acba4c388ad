"""PG mappings materialised on the host over the whole window, from
the first remap's start to the end of the one in flight at its close."""


def read(run):
    return run["client"]["amount"] / run["client"]["span_s"]
