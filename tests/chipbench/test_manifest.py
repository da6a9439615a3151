"""``BENCHMARK.json`` against the contract's limits, and the files it names.
Static: no run, no JAX."""

import json
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "chipbench")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def _metrics(manifest):
    return manifest["end_to_end"] + manifest["per_layer"]


def test_the_manifest_has_exactly_the_contracts_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["chipbench", "tests/chipbench"]
    assert manifest["command"] == ["python3", "chipbench/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024


def test_every_name_and_unit_is_inside_the_allowed_characters(manifest):
    names = [m["name"] for m in _metrics(manifest)]
    names += [c["name"] for c in manifest["configs"]]
    names += [k for c in manifest["configs"] for k in c["reduced"]]
    for w in manifest["workloads"]:
        names += [w["name"], w["config"], w["traffic"]]
    for name in names:
        assert NAME.match(name), name
    for m in _metrics(manifest):
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher"), m
        assert m["source"] in SOURCES, m
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        got = [e["name"] for e in manifest[group]]
        assert len(got) == len(set(got)), group
    lines = [w["why"] for w in manifest["workloads"]]
    lines += [c["why"] for c in manifest["configs"]]
    lines += [c["source"] for c in manifest["configs"]]
    lines += [m["layer"] for m in manifest["per_layer"]]
    for text in lines:
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_entries_have_just_the_contracts_keys(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_bounds_and_the_set_up_metric(manifest):
    by_name = {m["name"]: m for m in manifest["end_to_end"]}
    assert by_name["setup_s"]["bound"] == 0.25
    assert "workloads" not in by_name["setup_s"]


def test_every_cell_reports_what_the_contract_asks(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for cell in cells:
        e2e = [m["name"] for m in manifest["end_to_end"]
               if cell in m.get("workloads", cells)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in m.get("workloads", cells)
                   for m in manifest["per_layer"]), cell
    four = sum(1 for w in manifest["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(cells) // 2)


def test_each_per_layer_metric_moves_a_metric_all_its_cells_report(manifest):
    cells = [w["name"] for w in manifest["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells)
           for m in manifest["end_to_end"]}
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e, m
        assert "workloads" in m, f"{m['name']}: list the cells from the start"
        for cell in m["workloads"]:
            assert cell in cells and cell in e2e[m["moves"]], (m, cell)
    layers = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_every_entry_has_its_file(manifest):
    for m in _metrics(manifest):
        assert os.path.isfile(os.path.join(BENCH, "metrics",
                                           m["name"] + ".py")), m["name"]
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    for c in manifest["configs"]:
        assert c["file"].startswith("chipbench/")
        with open(os.path.join(REPO, c["file"])) as f:
            config = json.load(f)
        for key in ("source", "guarantees", "reduced", "assumed", "compare",
                    "engine", "dataset", "scale"):
            assert key in config, (c["name"], key)
        assert config["source"] == c["source"]
        assert config["reduced"] == c["reduced"]
        assert config["engine"]["enable_result_cache"] is False
        assert config["engine"]["use_device_kernels"] is True
        assert os.path.isfile(os.path.join(BENCH, "datasets",
                                           config["dataset"] + ".py"))
    for w in manifest["workloads"]:
        with open(os.path.join(BENCH, "traffic",
                               w["traffic"] + ".json")) as f:
            traffic = json.load(f)
        for q in traffic["queries"]:
            assert os.path.isfile(os.path.join(BENCH, "queries", q + ".py"))


def test_the_peaks_table_names_its_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "Google Cloud" in v5e["source"]
