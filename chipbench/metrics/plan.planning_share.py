"""Host time spent planning (optimize, translate, fuse, plan-cache lookup
and rehydration) as a share of the window."""


def read(window, counters, trace):
    ns = counters.get("planning_wall_ns")
    if ns is None:
        return None
    return 100.0 * ns / 1e9 / window["seconds"]
