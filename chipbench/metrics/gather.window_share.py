"""Host time spent copying device results back and assembling them into
Arrow (``gather_ns``: the resolvers' wall less the time they were blocked
on the device) as a share of the window. The chip is idle meanwhile."""


def read(window, counters, trace):
    ns = counters.get("gather_ns")
    if ns is None:
        return None
    return 100.0 * ns / 1e9 / window["seconds"]
