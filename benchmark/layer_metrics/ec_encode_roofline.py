"""Share of the HBM roofline that encoding the window's bytes reached:
the least time the chip's memory could take to read the k data chunks
and write the m parity chunks of every byte acknowledged, over the
time in which any operation ran on the device.  It reads the work and
the device's whole busy time, never a kernel's name.  Bound: HBM."""


def read(run):
    trace, peaks = run["trace"], run["peaks"]
    if not trace or not peaks or trace["busy_s"] <= 0:
        return None
    profile = run["config"]["profile"]
    moved = run["client"]["amount"] * (1 + int(profile["m"]) / int(profile["k"]))
    if moved <= 0:
        return None
    least_s = moved / (peaks["hbm_GBps"] * 1e9)
    return 100.0 * least_s / trace["busy_s"]
