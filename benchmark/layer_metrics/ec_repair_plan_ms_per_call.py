"""Host time a repair call spends on its plan: the ``ec_repair_plan``
span round the codec's ``repair_matrix`` hook (the matrix's build at a
(lost, helpers)' first sight, a dictionary hit after), over the
driver's ``calls``.  A program without the span gives nothing."""


def read(run):
    counters = run["counters"]
    calls = counters.get("calls", 0)
    if not calls or "l_stage_ec_repair_plan_ns" not in counters:
        return None
    return 1e-6 * counters["l_stage_ec_repair_plan_ns"] / calls
