"""Host time a plugin call spends in its dispatch's ``upload`` stage,
the ``device_put`` of its input rows: the flight recorder's
``dispatch.<ec_encode|ec_decode>.transfer_s``, by the cell's ``mode``,
over the driver's ``calls``.  A program that brackets no upload there
(the stage reads 0) gives nothing."""


def read(run):
    counters = run["counters"]
    calls = counters.get("calls", 0)
    mode = run["traffic"].get("mode")
    seconds = counters.get(f"dispatch.ec_{mode}.transfer_s", 0)
    if not calls or not seconds:
        return None
    return 1e3 * seconds / calls
