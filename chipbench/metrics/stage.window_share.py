"""Host time spent staging Arrow columns into HBM (``stage_ns``: the
stage-cache misses of the window's device attempts) as a share of the
window. A program without the counter reports nothing."""


def read(window, counters, trace):
    ns = counters.get("stage_ns")
    if ns is None:
        return None
    return 100.0 * ns / 1e9 / window["seconds"]
