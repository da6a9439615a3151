"""Host time the entry layer spends around each query's plan, as a share
of the window: ``entry_setup_ns`` (from ``collect()`` to the plan stream's
first pull, less planning), ``entry_finish_ns`` (the query's end-of-run
hooks) and ``entry_convert_ns`` (``to_pydict`` and the like on the
result). The chip is idle meanwhile. A program without the counters
reports nothing."""

_KEYS = ("entry_setup_ns", "entry_finish_ns", "entry_convert_ns")


def read(window, counters, trace):
    if any(counters.get(k) is None for k in _KEYS):
        return None
    return 100.0 * sum(counters[k] for k in _KEYS) / 1e9 / window["seconds"]
