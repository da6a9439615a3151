"""Window length over queries completed in it: all the work over all the
time, so a stall anywhere in the window moves it."""


def read(window, counters, trace):
    return window["seconds"] / len(window["queries"])
