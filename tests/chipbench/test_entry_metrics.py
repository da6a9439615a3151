"""The five readers of the host time that no layer owned before: the entry
layer's share and its finish hooks' share, the dispatch frames' key
lookups, the garbage collector's pauses, and the idle time that is left
when every counter has taken its own (``device.idle_unowned_share``). Each
``read`` on hand-made ``window``, ``counters`` and ``trace`` dicts, and on
the counters of queries the engine ran here."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from chipbench.run import load_module  # noqa: E402

WINDOW = {"seconds": 20.0}
# 10 idle seconds of a 40 s traced window
TRACE = {"window_s": 40.0, "busy_s": 30.0}
COUNTERS = {"planning_wall_ns": 1_000_000_000, "stage_ns": 2_000_000_000,
            "device_dispatch_ns": 500_000_000, "gather_ns": 1_000_000_000,
            "op_self_host_ns": 1_500_000_000, "device_wait_ns": 25_000_000_000,
            "sql_plan_ns": 200_000_000, "entry_setup_ns": 400_000_000,
            "entry_finish_ns": 1_200_000_000, "entry_convert_ns": 600_000_000,
            "dispatch_lookup_ns": 100_000_000, "gc_pause_ns": 300_000_000,
            "xla_compiles": 0}
# name, the counters it reads, its share of WINDOW
SHARES = [("entry.window_share",
           ("entry_setup_ns", "entry_finish_ns", "entry_convert_ns"), 11.0),
          ("entry.finish_share", ("entry_finish_ns",), 6.0),
          ("dispatch.lookup_share", ("dispatch_lookup_ns",), 0.5),
          ("host.gc_share", ("gc_pause_ns",), 1.5)]
UNOWNED = "device.idle_unowned_share"
OWNED = ("planning_wall_ns", "stage_ns", "device_dispatch_ns", "gather_ns",
         "op_self_host_ns", "entry_setup_ns", "entry_finish_ns",
         "entry_convert_ns")


def _read(name, window=WINDOW, counters=COUNTERS, trace=None):
    return load_module("metrics", name).read(window, counters, trace)


@pytest.mark.parametrize("name,keys,share", SHARES)
def test_a_share_is_its_counters_over_the_window(name, keys, share):
    assert _read(name) == pytest.approx(share)
    assert _read(name, counters={**COUNTERS, **{k: 0 for k in keys}}) == 0.0
    # a program without a counter it reads (the parent commit): nothing
    for k in keys:
        without = {c: v for c, v in COUNTERS.items() if c != k}
        assert _read(name, counters=without) is None


def test_idle_unowned_is_idle_less_every_owner_the_sql_front_end_too():
    # 10 s idle, 8.4 s owned: the wait (chip busy) and the collector's
    # pauses (inside the others) are not subtracted
    assert _read(UNOWNED, trace=TRACE) == pytest.approx(100.0 * 1.6 / 40.0)
    # a program that planned no SQL text has no sql_plan_ns: read as 0
    no_sql = {k: v for k, v in COUNTERS.items() if k != "sql_plan_ns"}
    assert _read(UNOWNED, counters=no_sql, trace=TRACE) == \
        pytest.approx(100.0 * 1.8 / 40.0)
    # host work that overlapped device work: more owned than idle, negative
    busier = {**COUNTERS, "entry_finish_ns": 4_200_000_000}
    assert _read(UNOWNED, counters=busier, trace=TRACE) == \
        pytest.approx(-3.5)


def test_idle_unowned_needs_a_device_trace_and_every_region_counter():
    assert _read(UNOWNED, trace=None) is None
    assert _read(UNOWNED, trace={}) is None
    assert _read(UNOWNED, trace={"window_s": 40.0, "busy_s": 0.0}) is None
    for k in OWNED:
        without = {c: v for c, v in COUNTERS.items() if c != k}
        assert _read(UNOWNED, counters=without, trace=TRACE) is None


def test_the_host_shares_the_entry_layer_and_the_unowned_rest_add_up():
    """With the entry layer's share every counter that
    ``device.idle_unowned_share`` subtracts has a share: the five host
    shares, the SQL front end's and the entry layer's add up with the rest
    to ``device.idle_share`` where the two windows are one."""
    window = {"seconds": TRACE["window_s"]}
    owned = sum(_read(name, window=window) for name in (
        "plan.planning_share", "stage.window_share", "dispatch.window_share",
        "gather.window_share", "host_ops.self_share", "entry.window_share"))
    sql = 100.0 * COUNTERS["sql_plan_ns"] / 1e9 / window["seconds"]
    rest = _read(UNOWNED, trace=TRACE)
    assert owned + sql + rest == pytest.approx(
        _read("device.idle_share", trace=TRACE))
    # the unowned rest is the old unexplained rest less what it now names
    old = _read("device.idle_unexplained_share", trace=TRACE)
    assert old - rest == pytest.approx(
        _read("entry.window_share", window=window) + sql)


@pytest.fixture
def device_path():
    import daft_tpu as dt

    cfg = dt.get_context().execution_config
    names = ("use_device_kernels", "device_min_rows", "enable_result_cache")
    saved = {k: getattr(cfg, k) for k in names}
    cfg.use_device_kernels = True
    cfg.device_min_rows = 1
    cfg.enable_result_cache = False
    yield
    for k, v in saved.items():
        setattr(cfg, k, v)


def test_the_readers_read_what_the_engine_counts(device_path):
    """Queries the engine ran here on its device path, read as the
    benchmark reads a window: each query's counters summed. Every reader
    finds its counters."""
    import daft_tpu as dt
    from daft_tpu import col

    frame = dt.from_pydict({"k": [i % 7 for i in range(5000)],
                            "v": [float(i) for i in range(5000)]}).collect()
    counters: dict = {}
    for _ in range(3):
        df = frame.where(col("v") > 10.0).groupby("k").agg(
            col("v").sum().alias("s")).sort("k")
        df.collect().to_pydict()
        for k, v in df.stats.snapshot()["counters"].items():
            counters[k] = counters.get(k, 0) + v
    assert counters["device_agg_dispatches"] == 3
    window = {"seconds": 1.0}
    for name, _, _ in SHARES:
        assert _read(name, window=window, counters=counters) >= 0.0, name
    assert _read("entry.window_share", window=window, counters=counters) > 0
    assert _read(UNOWNED, counters=counters,
                 trace={"window_s": 10.0, "busy_s": 1.0}) is not None
