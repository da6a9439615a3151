"""Host time spent building device programs' cache keys from their
expression trees and looking them up (``dispatch_lookup_ns``, a part of
``device_dispatch_ns``), as a share of the window. A program without the
counter reports nothing."""


def read(window, counters, trace):
    ns = counters.get("dispatch_lookup_ns")
    if ns is None:
        return None
    return 100.0 * ns / 1e9 / window["seconds"]
