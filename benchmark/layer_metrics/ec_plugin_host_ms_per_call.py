"""Host time of the EC seam a plugin call costs outside its device
dispatch's stages: the ``ec_fold`` (the stripes transposed into
regions), ``ec_unfold`` (the result rows copied back out of word
form), ``ec_assemble`` (the shards laid out a position each) and
``ec_plan`` (the reconstruction matrix of a decode) spans of the
traced window, over the driver's ``calls``.  A program without those
spans gives nothing."""

STAGES = ("ec_fold", "ec_unfold", "ec_assemble", "ec_plan")


def read(run):
    counters = run["counters"]
    calls = counters.get("calls", 0)
    keys = [f"l_stage_{stage}_ns" for stage in STAGES]
    if not calls or not any(key in counters for key in keys):
        return None
    return 1e-6 * sum(counters.get(key, 0) for key in keys) / calls
