"""Process start to window start: generation, staging, compile-cache loads
(or compiles, in a checkout's first run) and warm-up."""


def read(window, counters, trace):
    return window["setup_s"]
