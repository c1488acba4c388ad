"""Process start to the first measured op: boot, data, warm-up and,
in a run that compiles, compilation."""


def read(run):
    return run["setup_s"]
