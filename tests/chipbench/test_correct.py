"""What decides ``correct``: the comparison, its control at a test size, and
whole rehearsed runs with the timed path broken underneath."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from chipbench import compare, control, run  # noqa: E402
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from quiet import quiet_env, quietly  # noqa: E402

CELLS = ("tpch10-scan-agg", "tpch1-join")


# ------------------------------------------------------------- compare.py

def test_gaps_of_an_equal_answer_are_zero():
    want = {"k": ["a", "b"], "n": [3, 4], "x": [1.5, 2.5]}
    assert compare.gaps({k: list(v) for k, v in want.items()}, want) == (0.0, 0)


@pytest.mark.parametrize("got,rel,mismatched", [
    ({"k": ["a", "b"], "n": [3, 4], "x": [1.5, 2.5000025]}, 1e-6, 0),
    ({"k": ["a", "c"], "n": [3, 4], "x": [1.5, 2.5]}, 0.0, 1),
    ({"k": ["a", "b"], "n": [3, 5], "x": [1.5, 2.5]}, 0.0, 1),
    ({"k": ["a"], "n": [3], "x": [1.5]}, 0.0, 3),               # a row short
    ({"k": ["a", "b"], "x": [1.5, 2.5]}, 0.0, 1),               # a column short
    ({"k": ["a", "b"], "n": [3, 4], "x": [1.5, 2.5], "y": [0, 0]}, 0.0, 1),
])
def test_gaps_count_every_kind_of_difference(got, rel, mismatched):
    want = {"k": ["a", "b"], "n": [3, 4], "x": [1.5, 2.5]}
    got_rel, got_mis = compare.gaps(got, want)
    assert got_rel == pytest.approx(rel, rel=1e-3, abs=1e-15)
    assert got_mis == mismatched


def test_a_nan_or_a_missing_float_is_the_worst_gap_and_valid_json():
    want = {"x": [1.0]}
    for got in ({"x": [float("nan")]}, {"x": [None]}):
        rel, _ = compare.gaps(got, want)
        assert rel > 1e100
        assert "Infinity" not in json.dumps(rel)


def test_judge_takes_the_widest_gap_over_all_answers_of_the_window():
    refs = {"q": {"x": [100.0]}, "r": {"n": [7]}}
    answers = [("q", {"x": [100.0]}), ("q", {"x": [100.00001]}),
               ("r", {"n": [7]})]
    limits = {"rel_gap": 2e-6, "mismatched": 0}
    correct, compared, by_query = compare.judge(answers, refs, limits)
    assert compared["rel_gap"]["value"] == pytest.approx(1e-7, rel=1e-3)
    assert compared["rel_gap"]["limit"] == 2e-6
    assert correct and by_query["r"] == {"rel_gap": 0.0, "mismatched": 0}
    answers.append(("r", {"n": [8]}))
    correct, compared, _ = compare.judge(answers, refs, limits)
    assert not correct and compared["mismatched"]["value"] == 1


def test_a_window_with_no_answers_is_not_correct():
    correct, _, _ = compare.judge([], {}, {"rel_gap": 1.0, "mismatched": 0})
    assert not correct


# ------------------------------------------------- the device path, or not

def test_a_moved_failure_counter_or_a_missed_floor_fails_the_query():
    floors = {"device_aggregations": 1}
    clean = {"device_aggregations": 1, "host_sorts": 1}
    assert run.off_device_path(clean, floors) == {}
    assert run.off_device_path(dict(clean, device_attempt_errors=2), floors) \
        == {"device_attempt_errors": 2}
    assert run.off_device_path(dict(clean, device_agg_fallbacks=1), floors) \
        == {"device_agg_fallbacks": 1}
    assert run.off_device_path(dict(clean, device_breaker_trips=1), floors) \
        == {"device_breaker_trips": 1}
    assert run.off_device_path({"host_aggregations": 1}, floors) \
        == {"device_aggregations": 0}


# ------------------------------------------------------------- the control

@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("seed", [11, 2_147_483_777, 4_000_000_001])
def test_the_bfloat16_control_comes_out_as_not_correct(cell, seed):
    out = control.control(cell, seed, scale=0.02)
    assert out["correct"] is False
    gap = out["compared"]["rel_gap"]
    assert gap["value"] > 3 * gap["limit"]
    assert out["compared"]["mismatched"]["value"] == 0


# ------------------------------- whole runs, the timed path broken beneath

def _broken(fault, cell=CELLS[0]):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "chipbench",
                                      "broken_run.py"), fault,
         "--workload", cell, "--seed", "2147483999", "--seconds", "0.5",
         "--trace", "0"],
        cwd=REPO, env=quiet_env(), capture_output=True, text=True,
        timeout=900, preexec_fn=quietly)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("cell", CELLS)
def test_an_altered_answer_is_not_correct(cell):
    result, err = _broken("answer_altered", cell)
    assert result["correct"] is False
    gap = result["compared"]["rel_gap"]
    assert gap["value"] == pytest.approx(1e-4, rel=0.05) and \
        gap["value"] > gap["limit"]
    assert result["compared"]["mismatched"]["value"] == 0
    # the numbers compared are the last lines on standard error, too
    assert "compared rel_gap" in err and err.strip().endswith(
        "correct = False")
    assert list(result)[-1] == "compared"


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_rows_left_out_is_not_correct(cell):
    result, _ = _broken("half_rows", cell)
    assert result["correct"] is False
    assert result["compared"]["rel_gap"]["value"] > 0.1     # the sums halve
    assert result["compared"]["mismatched"]["value"] > 0    # counts and keys too


def test_answers_from_the_host_path_are_right_and_count_as_failed():
    result, _ = _broken("device_error")
    assert result["correct"] is True
    assert result["failed"] == result["attempted"] > 0
    why = result["window"]["failed_why"]
    assert all("device_attempt_errors" in v for v in why.values()), why
