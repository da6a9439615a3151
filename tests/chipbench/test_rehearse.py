"""``chipbench/run.py`` end to end on the CPU at a test scale, and the runs it
has to refuse. Every run is a process of its own, as on the chip."""

import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from quiet import quiet_env, quietly  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ("tpch10-scan-agg", "tpch1-join")


def _run(cell, trace, cwd=REPO, rehearse=True, seconds="1.5"):
    env = quiet_env(
        BENCH_RUN="the driver sets this; the benchmark ignores it")
    if rehearse:
        env["CHIPBENCH_REHEARSE"] = "1"
    return subprocess.run(
        [sys.executable, os.path.join("chipbench", "run.py"),
         "--workload", cell, "--seed", "2147483747", "--seconds", seconds,
         "--trace", str(trace)],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=900,
        preexec_fn=quietly)


def _manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _rehearse(cell, trace):
    proc = _run(cell, trace)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stdout


@pytest.fixture(scope="module")
def untraced():
    """One ``--trace 0`` run, of the scan cell."""
    return _rehearse(CELLS[0], 0)


@pytest.fixture(scope="module")
def traced():
    """One ``--trace 1`` run, of the join cell."""
    return _rehearse(CELLS[1], 1)


@pytest.fixture(params=["untraced", "traced"])
def rehearsed(request):
    return request.getfixturevalue(request.param)


def test_the_last_line_has_the_contracts_keys(rehearsed):
    result, _ = rehearsed
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in result
    assert list(result)[-1] == "compared"
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    for c in result["compared"].values():
        assert c["value"] <= c["limit"]


def test_a_rehearsal_names_the_cpu_as_its_device(rehearsed):
    device = rehearsed[0]["device"]
    assert device["platform"] == "cpu" and device["count"] >= 1
    assert "memory_peak_bytes" in device and "kind" in device


def test_the_window_ends_on_a_whole_pass_and_set_up_is_itemised(rehearsed):
    result, stdout = rehearsed
    assert result["attempted"] % len(result["window"]["mean_wall_s"]) == 0
    assert result["window"]["seconds"] >= 1.5
    setup = next(json.loads(line)["setup"] for line in stdout.splitlines()
                 if line.startswith('{"setup"'))
    assert {"generate_s", "from_arrow_s", "warm_pass_s", "rows"} <= set(setup)
    assert len(setup["warm_pass_s"]) == 2


def _by_name(group):
    return {m["name"]: m for m in _manifest()[group]}


def test_an_untraced_run_reports_the_cells_end_to_end_metrics(untraced):
    result, _ = untraced
    e2e = _by_name("end_to_end")
    assert "breakdown" not in result
    assert {"query_s", "setup_s"} <= set(result["metrics"]) <= set(e2e)
    for name, m in result["metrics"].items():
        assert m["unit"] == e2e[name]["unit"] and m["value"] > 0
        assert CELLS[0] in e2e[name].get("workloads", [CELLS[0]])


def test_a_traced_run_reports_the_cells_per_layer_metrics(traced):
    result, _ = traced
    layer = _by_name("per_layer")
    assert "breakdown" in result
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert result["device"]["window_s"] > 1.0
    # counters can be read on the CPU; device times cannot, and are left out
    assert {"plan.planning_share", "plan.compiles_in_window",
            "routing.device_op_share"} <= set(result["metrics"]) <= set(layer)
    assert "device.idle_share" not in result["metrics"]
    assert result["metrics"]["plan.compiles_in_window"]["value"] == 0
    for name, m in result["metrics"].items():
        assert m["unit"] == layer[name]["unit"]
        assert CELLS[1] in layer[name]["workloads"]


def test_a_machine_without_a_tpu_is_refused_with_no_result():
    proc = _run(CELLS[0], 0, rehearse=False)
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ""
    assert "not a TPU" in proc.stderr


def test_an_unknown_cell_is_refused_with_no_result():
    proc = _run("no-such-cell", 0)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


@pytest.fixture(scope="module")
def bare_checkout(tmp_path_factory):
    """Only ``BENCHMARK.json`` and the files under ``paths``."""
    root = tmp_path_factory.mktemp("bare")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    for path in _manifest()["paths"]:
        shutil.copytree(os.path.join(REPO, path), root / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    return root


def test_without_the_engine_the_run_is_refused_with_no_result(bare_checkout):
    proc = _run(CELLS[0], 0, cwd=bare_checkout)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "engine is not beside" in proc.stderr


def test_a_cell_a_query_and_a_metric_are_added_as_files_alone(bare_checkout):
    """What a later PR does: new files and new manifest entries, no edit to
    a file that is there (chipbench/README.md says how)."""
    root = bare_checkout
    bench = root / "chipbench"
    os.symlink(os.path.join(REPO, "daft_tpu"), root / "daft_tpu")
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    config = json.loads((bench / "configs" / "tpch-sf1-1chip.json")
                        .read_text())
    config.update(name="tpch-sf2-1chip", scale=2.0, rehearse_scale=0.02)
    (bench / "configs" / "tpch-sf2-1chip.json").write_text(
        json.dumps(config))
    (bench / "traffic" / "q6_q14ish_closed.json").write_text(json.dumps(
        {"loop": "closed", "clients": 1, "queries": ["q6", "q14ish"],
         "warmup_passes": 1}))
    (bench / "queries" / "q14ish.py").write_text('''
import pyarrow.compute as pc

COLUMNS = {"lineitem": ["l_quantity", "l_extendedprice"]}
floors = {"device_aggregations": 1}


def build(frames):
    from daft_tpu import col
    return (frames["lineitem"].where(col("l_quantity") < 10)
            .agg(col("l_extendedprice").sum().alias("promo")))


def reference(tables):
    li = tables["lineitem"]
    t = li.filter(pc.less(li["l_quantity"], 10))
    return {"promo": [pc.sum(t["l_extendedprice"]).as_py()]}


def min_bytes(row_counts):
    return 8 * row_counts["lineitem"]
''')
    (bench / "metrics" / "entry.queries_in_window.py").write_text(
        "def read(window, counters, trace):\n"
        "    return float(len(window['queries']))\n")
    manifest = _manifest()
    manifest["configs"].append(
        {"name": "tpch-sf2-1chip", "source": config["source"],
         "file": "chipbench/configs/tpch-sf2-1chip.json",
         "reduced": config["reduced"], "why": "a test's"})
    manifest["workloads"].append(
        {"name": "tpch2-new", "config": "tpch-sf2-1chip",
         "traffic": "q6_q14ish_closed", "chips": 1, "why": "a test's"})
    manifest["per_layer"].append(
        {"name": "entry.queries_in_window", "unit": "count",
         "better": "higher", "source": "program_counter", "layer": "entry",
         "moves": "query_s", "workloads": ["tpch2-new"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    proc = _run("tpch2-new", 1, cwd=root, seconds="0.5")
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["window"]["mean_wall_s"]) == {"q6", "q14ish"}
    assert result["metrics"]["entry.queries_in_window"]["value"] == \
        result["attempted"]
    assert "programs.scan_agg_roofline" not in result["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before
