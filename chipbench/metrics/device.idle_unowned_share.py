"""Idle time on the device's clock that no program counter owns: the
trace's idle seconds (window less busy) less the host time the program
recorded while the chip had nothing to do (planning, staging, dispatch,
gather, host-operator self time, the SQL front end, and the entry layer's
set-up, finish and conversion; not ``device_wait_ns``, during which the
chip is busy, nor ``gc_pause_ns``, which lands inside the others), as a
share of the traced window. Signed: host work that overlapped device work
makes it negative. What is left is the caller's own time between queries
(building each DataFrame) and what runs outside every region."""

_OWNED = ("planning_wall_ns", "stage_ns", "device_dispatch_ns", "gather_ns",
          "op_self_host_ns", "entry_setup_ns", "entry_finish_ns",
          "entry_convert_ns")
_OPTIONAL = ("sql_plan_ns",)  # only a query that came as SQL text has it


def read(window, counters, trace):
    if not trace or not trace["busy_s"]:
        return None
    if any(counters.get(k) is None for k in _OWNED):
        return None
    idle_s = trace["window_s"] - trace["busy_s"]
    owned_s = sum(counters.get(k, 0) for k in _OWNED + _OPTIONAL) / 1e9
    return 100.0 * (idle_s - owned_s) / trace["window_s"]
