"""The measurement checking itself: idle time on the device's clock that no
program counter owns. The trace's idle seconds (window less busy) less the
host time the program recorded while the chip had nothing to do (planning,
staging, dispatch, gather, host-operator self time; not ``device_wait_ns``,
during which the chip is busy), as a share of the traced window. Signed:
host work that overlapped device work makes it negative."""

_OWNED = ("planning_wall_ns", "stage_ns", "device_dispatch_ns", "gather_ns",
          "op_self_host_ns")


def read(window, counters, trace):
    if not trace or not trace["busy_s"]:
        return None
    if any(counters.get(k) is None for k in _OWNED):
        return None
    idle_s = trace["window_s"] - trace["busy_s"]
    owned_s = sum(counters[k] for k in _OWNED) / 1e9
    return 100.0 * (idle_s - owned_s) / trace["window_s"]
