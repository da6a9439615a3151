"""The per-layer metrics that read the program's own counters (ISSUE 27):
each ``read`` on hand-made ``window``, ``counters`` and ``trace`` dicts, and
one rehearsed ``--trace 1`` run of ``tpch1-join`` that reports them."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from quiet import quiet_env, quietly  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from chipbench.run import load_module  # noqa: E402

WINDOW = {"seconds": 20.0}
# 10 idle seconds of a 40 s traced window
TRACE = {"window_s": 40.0, "busy_s": 30.0}
COUNTERS = {"planning_wall_ns": 1_000_000_000, "stage_ns": 2_000_000_000,
            "device_dispatch_ns": 500_000_000, "gather_ns": 3_000_000_000,
            "op_self_host_ns": 1_500_000_000, "device_wait_ns": 25_000_000_000,
            "xla_compiles": 0}
SHARES = [("stage.window_share", "stage_ns", 10.0),
          ("gather.window_share", "gather_ns", 15.0),
          ("host_ops.self_share", "op_self_host_ns", 7.5)]
NEW = [name for name, _, _ in SHARES] + ["plan.xla_compiles_in_window",
                                         "device.idle_unexplained_share"]


def _read(name, window=WINDOW, counters=COUNTERS, trace=None):
    return load_module("metrics", name).read(window, counters, trace)


@pytest.mark.parametrize("name,counter,share", SHARES)
def test_a_share_is_its_counter_over_the_window(name, counter, share):
    assert _read(name) == pytest.approx(share)
    assert _read(name, counters={**COUNTERS, counter: 0}) == 0.0
    # a program without the counter (the parent commit) reports nothing
    without = {k: v for k, v in COUNTERS.items() if k != counter}
    assert _read(name, counters=without) is None


def test_xla_compiles_reads_zero_as_zero_and_absent_as_nothing():
    name = "plan.xla_compiles_in_window"
    assert _read(name) == 0.0
    assert _read(name, counters={"xla_compiles": 3}) == 3.0
    assert _read(name, counters={"segment_compiles": 10}) is None


def test_idle_unexplained_is_idle_less_what_the_counters_own():
    name = "device.idle_unexplained_share"
    # 10 s idle, 8 s owned (the wait is not: the chip is busy then)
    assert _read(name, trace=TRACE) == pytest.approx(100.0 * 2.0 / 40.0)
    # host work that overlapped device work: more owned than idle, negative
    busier = {**COUNTERS, "op_self_host_ns": 9_500_000_000}
    assert _read(name, counters=busier, trace=TRACE) == pytest.approx(-15.0)
    # no owner at all: all of the idle time is unexplained
    nothing = {k: 0 for k in COUNTERS}
    assert _read(name, counters=nothing, trace=TRACE) == pytest.approx(25.0)


def test_idle_unexplained_needs_a_device_trace_and_every_counter():
    name = "device.idle_unexplained_share"
    assert _read(name, trace=None) is None
    assert _read(name, trace={}) is None
    assert _read(name, trace={"window_s": 40.0, "busy_s": 0.0}) is None
    for k in ("planning_wall_ns", "stage_ns", "device_dispatch_ns",
              "gather_ns", "op_self_host_ns"):
        without = {c: v for c, v in COUNTERS.items() if c != k}
        assert _read(name, counters=without, trace=TRACE) is None


def test_the_manifest_lists_the_new_metrics_for_both_cells():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    assert list(layer)[-5:] == NEW  # appended, in the issue's order
    for name in NEW:
        m = layer[name]
        assert m["moves"] == "query_s" and m["better"] == "lower"
        assert m["workloads"] == ["tpch10-scan-agg", "tpch1-join"]
    assert layer["stage.window_share"]["layer"] == \
        layer["stage.hbm_bytes_per_input_byte"]["layer"]
    assert layer["plan.xla_compiles_in_window"]["layer"] == "plan"
    assert layer["device.idle_unexplained_share"]["source"] == "device_trace"


def test_a_rehearsed_traced_join_run_reports_the_counter_metrics():
    proc = subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"),
         "--workload", "tpch1-join", "--seed", "2147483811",
         "--seconds", "1.5", "--trace", "1"],
        cwd=REPO, env=quiet_env(CHIPBENCH_REHEARSE="1"),
        capture_output=True, text=True, timeout=900, preexec_fn=quietly)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    metrics = result["metrics"]
    for name in NEW[:4]:
        assert metrics[name]["unit"] == layer[name]["unit"], name
        assert metrics[name]["value"] >= 0.0
    assert metrics["plan.xla_compiles_in_window"]["value"] == 0.0
    assert metrics["gather.window_share"]["value"] > 0.0
    assert metrics["host_ops.self_share"]["value"] > 0.0
    # the shares are of one window: together they stay inside it
    assert sum(metrics[n]["value"] for n in NEW[:3]) < 100.0
    # a device time cannot be read on the CPU, and is left out
    assert "device.idle_unexplained_share" not in metrics
