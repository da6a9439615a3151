"""The trace reduction and the per-layer readers, on hand-made events: no
chip, no profiler."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import run, trace  # noqa: E402

DEV = "/device:TPU:0/XLA Ops"
HOST = "/host:CPU/python"

#   window [1000, 11000); q1 [2000, 5000); q6 [6000, 8000)
EVENTS = [
    (HOST, "chipbench:window", 1000, 10000),
    (HOST, "chipbench:q:q1", 2000, 3000),
    (HOST, "chipbench:q:q6", 6000, 2000),
    (DEV, "fusion.1", 2500, 1000),
    (DEV, "fusion.2", 3000, 1000),      # overlaps fusion.1: union 1500
    (DEV, "fusion.1", 4500, 400),
    (DEV, "copy", 6500, 500),
    (DEV, "stray", 9000, 500),          # on the device, in no query
    (DEV, "early", 0, 1200),            # 200 ns of it inside the window
    (HOST, "some other span", 0, 5),
]


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(EVENTS)


def test_union_merges_overlaps_and_drops_empty_intervals():
    assert trace.union([(5, 7), (1, 3), (2, 4), (7, 7), (6, 9)]) == \
        [[1, 4], [5, 9]]
    assert trace.total(trace.clip([[1, 4], [5, 9]], 3, 6)) == 2


def test_busy_is_the_union_clipped_to_the_window(reduced):
    assert reduced["window_s"] == pytest.approx(10000e-9)
    assert reduced["busy_s"] == pytest.approx((1500 + 400 + 500 + 500
                                               + 200) * 1e-9)
    assert reduced["devices"] == 1


def test_device_time_inside_query_spans(reduced):
    assert reduced["per_query_busy_s"] == pytest.approx(
        {"q1": 1900e-9, "q6": 500e-9})
    assert reduced["in_query_busy_s"] == pytest.approx(2400e-9)


def test_gaps_are_named_by_what_the_host_was_doing(reduced):
    gaps = dict(map(tuple, reduced["idle_gaps"]))
    assert gaps == pytest.approx({
        "q1:before_first_op": 500e-9, "q1:between_ops": 500e-9,
        "q1:after_last_op": 100e-9,
        "q6:before_first_op": 500e-9, "q6:after_last_op": 1000e-9,
        # 1000..2000, 5000..6000, 8000..11000 less 'early' and 'stray'
        "no_request_in_flight": (5000 - 200 - 500) * 1e-9})
    # the gaps are the whole of the idle time, and sorted longest first
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])
    assert [g[0] for g in reduced["idle_gaps"]][0] == "no_request_in_flight"


def test_top_device_operations_sum_by_name(reduced):
    ops = dict(map(tuple, reduced["device_ops"]))
    assert ops["fusion.1"] == pytest.approx(1400e-9)
    assert reduced["device_ops"][0][0] == "fusion.1"
    assert len(reduced["device_ops"]) <= 10


def test_busy_time_averages_over_devices():
    two = EVENTS + [("/device:TPU:1/XLA Ops", "fusion.1", 2000, 1000)]
    out = trace.reduce(two)
    assert out["devices"] == 2
    assert out["busy_s"] == pytest.approx((3100 + 1000) / 2 * 1e-9)


def test_no_events_reduce_to_nothing_to_read():
    out = trace.reduce([])
    assert out["busy_s"] == 0.0 and out["devices"] == 0


def _read(metric, window, counters=None, reduced=None):
    return run.load_module("metrics", metric).read(window, counters or {},
                                                   reduced)


def test_roofline_and_idle_share_arithmetic(reduced):
    window = {"min_bytes": 819, "peaks": {"hbm_bytes_per_s": 819e9}}
    # 819 B at 819 GB/s is 1 ns; 2400 ns of device time inside the queries
    assert _read("programs.scan_agg_roofline", window, reduced=reduced) == \
        pytest.approx(100.0 / 2400)
    assert _read("device.idle_share", window, reduced=reduced) == \
        pytest.approx(100.0 * (1 - 3100 / 10000))


@pytest.mark.parametrize("metric", ["programs.scan_agg_roofline",
                                    "device.idle_share"])
def test_a_reader_with_nothing_to_read_returns_nothing(metric):
    window = {"min_bytes": 819, "peaks": {"hbm_bytes_per_s": 819e9}}
    assert _read(metric, window, reduced=None) is None
    assert _read(metric, window, reduced=trace.reduce([])) is None


def test_counter_readers():
    window = {"seconds": 2.0, "cache_files_added": 1,
              "memory_peak_bytes": 3 * 2 ** 30, "input_bytes": 2 ** 30,
              "queries": [{"name": "q", "wall_s": w / 100}
                          for w in range(1, 101)], "setup_s": 12.5}
    counters = {"planning_wall_ns": 50_000_000, "segment_compiles": 2,
                "device_aggregations": 3, "device_sorts": 1,
                "host_sorts": 1, "host_projections": 3,
                "device_agg_dispatches": 5}
    assert _read("plan.planning_share", window, counters) == \
        pytest.approx(2.5)
    assert _read("plan.compiles_in_window", window, counters) == 3.0
    assert _read("routing.device_op_share", window, counters) == \
        pytest.approx(50.0)
    assert _read("stage.hbm_bytes_per_input_byte", window) == \
        pytest.approx(3.0)
    assert _read("peak_hbm_gib", window) == pytest.approx(3.0)
    assert _read("query_s", window) == pytest.approx(0.02)
    assert _read("query_p90_s", window) == pytest.approx(0.901)
    assert _read("setup_s", window) == 12.5
    assert _read("routing.device_op_share", window, {}) is None
