"""Device busy time of the traced window over the bytes of object its
plugin calls acknowledged (the tool's accounting: 64 MiB a call)."""


def read(run):
    trace = run["trace"]
    amount = run["client"]["amount"]
    if "calls" not in run["counters"] or not trace or not amount:
        return None
    if trace["busy_s"] <= 0:
        return None
    return 1e9 * trace["busy_s"] / amount
