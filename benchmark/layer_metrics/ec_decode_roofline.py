"""Share of the HBM roofline that rebuilding the window's lost chunks
reached: the least time the chip's memory could take to read the k
survivors and write the ``erasures`` rebuilt chunks of every byte
acknowledged — amount x (1 + erasures/k) at the published HBM rate —
over the time in which any operation ran on the device.  It reads the
work and the device's whole busy time, never a kernel's name.
Bound: HBM."""


def read(run):
    trace, peaks, traffic = run["trace"], run["peaks"], run["traffic"]
    if traffic.get("mode") != "decode" or "erasures" not in traffic:
        return None
    if not trace or not peaks or trace["busy_s"] <= 0:
        return None
    k = int(run["config"]["profile"]["k"])
    moved = run["client"]["amount"] * (1 + int(traffic["erasures"]) / k)
    if moved <= 0:
        return None
    least_s = moved / (peaks["hbm_GBps"] * 1e9)
    return 100.0 * least_s / trace["busy_s"]
