"""Operator executions that ran on the device, as a share of all operator
executions the routing layer counted (``device_*`` against ``host_*``)."""

DEVICE_OPS = ("device_aggregations", "device_projections", "device_filters",
              "device_fused_maps", "device_join_probes", "device_sorts",
              "device_distincts", "device_resident_segments")


def read(window, counters, trace):
    on_device = sum(counters.get(k, 0) for k in DEVICE_OPS)
    on_host = sum(v for k, v in counters.items() if k.startswith("host_"))
    if on_device + on_host == 0:
        return None
    return 100.0 * on_device / (on_device + on_host)
