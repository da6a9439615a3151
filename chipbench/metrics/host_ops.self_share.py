"""The operators' own host work (``op_self_host_ns``: their self time less
the stage, dispatch, wait and gather time their device attempts recorded
inside them) as a share of the window."""


def read(window, counters, trace):
    ns = counters.get("op_self_host_ns")
    if ns is None:
        return None
    return 100.0 * ns / 1e9 / window["seconds"]
