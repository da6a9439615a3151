"""Host time in each query's end-of-run hooks (``entry_finish_ns``:
teardown, metrics and health gauges, the query record, the history fold,
persist), as a share of the window. A program without the counter reports
nothing."""


def read(window, counters, trace):
    ns = counters.get("entry_finish_ns")
    if ns is None:
        return None
    return 100.0 * ns / 1e9 / window["seconds"]
