"""tools/trace_gaps.py: idle device time inside a query, named by the
innermost ``daft_tpu:`` span live at the time. Hand-made events, no run."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from tools import trace_gaps  # noqa: E402

DEV = "/device:TPU:0/XLA Ops"
HOST = "/host:CPU/main"
POOL = "/host:CPU/daft-exec_0"


def _span(name, start, end, line=HOST):
    return (line, "daft_tpu:" + name, start, end - start)


def test_innermost_is_the_live_span_that_started_last():
    segs = trace_gaps.innermost_segments([
        (0, 100, "op:Join"), (10, 40, "phase:dispatch"),
        (20, 30, "phase:stage"), (60, 90, "phase:gather"),
        (70, 80, "phase:device.wait")])
    assert segs == [[0, 10, "op:Join"], [10, 20, "phase:dispatch"],
                    [20, 30, "phase:stage"], [30, 40, "phase:dispatch"],
                    [40, 60, "op:Join"], [60, 70, "phase:gather"],
                    [70, 80, "phase:device.wait"], [80, 90, "phase:gather"],
                    [90, 100, "op:Join"]]
    # a worker thread's span that began later wins over the driver's
    segs = trace_gaps.innermost_segments([(0, 50, "op:Sort"),
                                          (20, 30, "op:Map")])
    assert [s[2] for s in segs] == ["op:Sort", "op:Map", "op:Sort"]


def test_gaps_inside_a_query_are_named_by_span_and_kind():
    events = [
        (HOST, "chipbench:window", 0, 1000),
        (HOST, "chipbench:q:q3", 100, 800),           # the query: [100, 900)
        (DEV, "fusion.1", 200, 100),                  # busy [200, 300)
        (DEV, "while.2", 500, 100),                   # busy [500, 600)
        _span("phase:plan", 110, 150),
        _span("op:Join", 160, 880),
        _span("phase:dispatch", 170, 210),
        _span("phase:gather", 290, 420),              # 120 ns of it idle
        _span("phase:join.expand", 350, 400),         # 50 ns inside gather
        _span("op:Map", 430, 480, line=POOL),         # a worker's span
        _span("phase:join.assemble", 610, 700),
    ]
    got = trace_gaps.attribute(events)
    assert set(got) == {"q3"}
    q3 = got["q3"]
    # [100, 200): 10 bare, plan 40, 10 bare, Join 10, dispatch 30
    assert q3["before_first_op"] == {
        trace_gaps.NO_SPAN: 20, "phase:plan": 40, "op:Join": 10,
        "phase:dispatch": 30}
    # [300, 500): gather 70 (120 less the 50 of expand), expand 50, Map 50,
    # Join the rest
    assert q3["between_ops"] == {"phase:gather": 70, "phase:join.expand": 50,
                                 "op:Map": 50, "op:Join": 30}
    # [600, 900): Join 10 + 180, assemble 90, 20 bare after the operator
    assert q3["after_last_op"] == {"op:Join": 190, "phase:join.assemble": 90,
                                   trace_gaps.NO_SPAN: 20}
    summary = trace_gaps.summarise(got)["q3"]
    assert summary["idle_s"]["between_ops"] == pytest.approx(200e-9)
    assert summary["between_ops_named_share"] == 1.0
    # an operator's own time reads apart from the time a phase owns
    assert summary["between_ops_share_by_kind"] == {
        "op": pytest.approx(80 / 200), "phase": pytest.approx(120 / 200)}
    assert summary["by_span"]["between_ops"][0] == ["phase:gather",
                                                     pytest.approx(70e-9)]


def test_time_outside_every_span_counts_against_the_named_share():
    events = [
        (HOST, "chipbench:q:q5", 0, 400),
        (DEV, "a", 0, 100), (DEV, "b", 300, 100),     # idle [100, 300)
        _span("op:Agg", 100, 250),
        (HOST, "chipbench:q:q5", 1000, 400),          # a second q5, no span
        (DEV, "a", 1000, 100), (DEV, "b", 1300, 100),
    ]
    q5 = trace_gaps.attribute(events)["q5"]
    assert q5["between_ops"] == {"op:Agg": 150, trace_gaps.NO_SPAN: 250}
    share = trace_gaps.summarise({"q5": q5})["q5"]["between_ops_named_share"]
    assert share == pytest.approx(150 / 400)
    assert trace_gaps.summarise({"q5": q5})["q5"][
        "between_ops_share_by_kind"] == {
            trace_gaps.NO_SPAN: pytest.approx(250 / 400),
            "op": pytest.approx(150 / 400)}


def test_a_query_with_no_device_operation_is_one_gap():
    events = [(HOST, "chipbench:q:q1", 0, 100), _span("op:Scan", 20, 60)]
    q1 = trace_gaps.attribute(events)["q1"]
    assert q1["before_first_op"] == {"op:Scan": 40, trace_gaps.NO_SPAN: 60}
    assert q1["between_ops"] == {} and q1["after_last_op"] == {}
    assert trace_gaps.summarise({"q1": q1})["q1"][
        "between_ops_named_share"] is None
