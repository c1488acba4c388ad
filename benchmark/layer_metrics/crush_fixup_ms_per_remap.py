"""Host clock around the numpy fix-up stages of a remap (exists,
upmap, up, primary affinity, pg_temp): ``OSDMapMapping.perf``
``fixup_stages`` sum over the remaps of the traced window."""


def read(run):
    counters = run["counters"]
    remaps = counters.get("remaps", 0)
    if not remaps or "mapping.fixup_stages.sum" not in counters:
        return None
    return 1e3 * counters["mapping.fixup_stages.sum"] / remaps
