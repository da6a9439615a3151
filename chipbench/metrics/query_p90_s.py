"""90th percentile of every query's wall in the window, whatever its kind
(host clock from ``build()`` to the fetched dict)."""

import statistics


def read(window, counters, trace):
    walls = [q["wall_s"] for q in window["queries"]]
    if len(walls) < 20:
        return None  # no tail to speak of
    return statistics.quantiles(walls, n=10, method="inclusive")[8]
