"""Share of a remap's numpy fix-up spans (exists, upmap, up, primary
affinity, pg_temp) in which their thread ran on a core: the summed
``l_stage_fixup_<stage>_cpu_ns`` over the summed
``l_stage_fixup_<stage>_ns``, over the traced window.  Near 100% a
slow fix-up is slow on the CPU; below it, the thread waited, for the
interpreter or the transfers beside it.  A program that counts no
thread usage reads nothing."""

STAGES = ("exists", "upmap", "up", "affinity", "temp")


def read(run):
    counters = run["counters"]
    cpu = [counters.get(f"l_stage_fixup_{stage}_cpu_ns") for stage in STAGES]
    if all(value is None for value in cpu):
        return None
    total = sum(counters.get(f"l_stage_fixup_{stage}_ns", 0) for stage in STAGES)
    return 100.0 * sum(value or 0 for value in cpu) / total if total else 0.0
