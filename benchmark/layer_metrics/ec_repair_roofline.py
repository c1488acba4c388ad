"""Share of the HBM roofline that the window's fractional repairs
reached: the least time the chip's memory could take for the work,
whatever implements it — read the d helpers' fragments, 1/q of a chunk
each (q = d - k + 1), and write the rebuilt chunk, of every byte of
object acknowledged: amount x (d / (k q) + 1 / k) at the published HBM
rate — over the time in which any operation ran on the device.  It
reads the work and the device's whole busy time, never a kernel's
name.  Bound: HBM."""


def read(run):
    trace, peaks, traffic = run["trace"], run["peaks"], run["traffic"]
    profile = run["config"].get("profile", {})
    if traffic.get("reads") != "minimum" or "d" not in profile:
        return None
    if not trace or not peaks or trace["busy_s"] <= 0:
        return None
    k, d = int(profile["k"]), int(profile["d"])
    moved = run["client"]["amount"] * (d / (k * (d - k + 1)) + 1 / k)
    if moved <= 0:
        return None
    least_s = moved / (peaks["hbm_GBps"] * 1e9)
    return 100.0 * least_s / trace["busy_s"]
