"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read after
the window: resident tables plus the largest intermediates."""


def read(window, counters, trace):
    peak = window["memory_peak_bytes"]
    return peak / 2 ** 30 if peak else None
