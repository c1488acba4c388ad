"""Host clock around the CRUSH stage of a remap (kernel dispatch,
fetch and oracle fallback): ``OSDMapMapping.perf`` ``crush_stage`` sum
over the remaps of the traced window."""


def read(run):
    remaps = run["counters"].get("remaps", 0)
    if not remaps:
        return None
    return 1e3 * run["counters"]["mapping.crush_stage.sum"] / remaps
