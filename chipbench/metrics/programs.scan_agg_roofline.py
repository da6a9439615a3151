"""The window's queries against the HBM roofline: the least bytes each
query must read (its columns x rows x 4 B, from the query's own file) over
the chip's peak bandwidth, divided by all device-op time inside the
queries' spans. The numerator is what the queries need, not what a kernel
reads, so it stays true whichever program does the work."""


def read(window, counters, trace):
    if not trace or not trace["in_query_busy_s"]:
        return None
    least_s = window["min_bytes"] / window["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["in_query_busy_s"]
