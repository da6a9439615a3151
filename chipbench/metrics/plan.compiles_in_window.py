"""Programs compiled inside the measured window: segment compiles the
planner counted plus files the persistent compile cache gained. Warm-up is
there so that this reads 0."""


def read(window, counters, trace):
    return float(counters.get("segment_compiles", 0)
                 + window["cache_files_added"])
