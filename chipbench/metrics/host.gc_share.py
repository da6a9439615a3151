"""Pauses of the generation-1 and -2 garbage collections that the threads
running the queries made (``gc_pause_ns``), as a share of the window. A
cause, not a layer: each pause lands inside whatever layer was running, so
this share overlaps the others. A program without the counter reports
nothing."""


def read(window, counters, trace):
    ns = counters.get("gc_pause_ns")
    if ns is None:
        return None
    return 100.0 * ns / 1e9 / window["seconds"]
