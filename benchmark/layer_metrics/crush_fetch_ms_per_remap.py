"""Host time a remap spends fetching its chunks' results, which is
where the host waits for the device: the flight recorder's ``sync``
stage of the ``crush`` dispatches (``dispatch.crush.sync_s``),
bracketed round each chunk's ``np.asarray`` in ``jaxmap.map_chunked``,
over the remaps of the traced window.  On a program from before
ISSUE 26 the fetches sat in the ``compute`` stage and this reads
near 0."""


def read(run):
    counters = run["counters"]
    remaps = counters.get("remaps", 0)
    if not remaps or "dispatch.crush.sync_s" not in counters:
        return None
    return 1e3 * counters["dispatch.crush.sync_s"] / remaps
