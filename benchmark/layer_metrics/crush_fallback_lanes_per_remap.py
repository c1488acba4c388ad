"""Lanes the kernel handed back to the scalar oracle, per remap:
``l_tpu_crush_fallback_lanes`` over the remaps of the traced window."""


def read(run):
    remaps = run["counters"].get("remaps", 0)
    if not remaps:
        return None
    return run["counters"].get("l_tpu_crush_fallback_lanes", 0) / remaps
