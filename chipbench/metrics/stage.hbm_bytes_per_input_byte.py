"""Peak HBM over the bytes of the columns the cell's queries read, at 32-bit
width: what staging and intermediates add to the data itself."""


def read(window, counters, trace):
    if not window["memory_peak_bytes"]:
        return None
    return window["memory_peak_bytes"] / window["input_bytes"]
