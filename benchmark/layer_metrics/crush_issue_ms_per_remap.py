"""Host time a remap spends issuing its chunks of the CRUSH program:
the flight recorder's ``compute`` stage of the ``crush`` dispatches
(``dispatch.crush.compute_s``), bracketed round each chunk's issue in
``jaxmap.map_chunked``, over the remaps of the traced window.  On a
program from before ISSUE 26 that stage held the fetches too."""


def read(run):
    counters = run["counters"]
    remaps = counters.get("remaps", 0)
    if not remaps or "dispatch.crush.compute_s" not in counters:
        return None
    return 1e3 * counters["dispatch.crush.compute_s"] / remaps
