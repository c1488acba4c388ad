"""Device busy time of the traced window over the PG mappings
completed in it."""


def read(run):
    trace = run["trace"]
    if not trace or trace["busy_s"] <= 0 or not run["client"]["amount"]:
        return None
    return 1e9 * trace["busy_s"] / run["client"]["amount"]
