"""Client writes folded into one ``ec_encode`` dispatch: the flight
recorder's ops over its dispatches, over the traced window."""


def read(run):
    dispatches = run["counters"].get("dispatch.ec_encode.dispatches", 0)
    if not dispatches:
        return None
    return run["counters"]["dispatch.ec_encode.ops"] / dispatches
