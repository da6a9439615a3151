"""The bring-up contract on a machine with no chip.

chip_smoke.py and bench.py refuse a machine without a TPU (non-zero exit, no
result on stdout); every chip_smoke leg passes through its explicit CPU
argument at a test scale; the compile cache is placed from outside; a failing
device attempt is counted, logged and shown while the host path answers; dist
workers never get the accelerator platform; a bench rung error fails the run.
"""

import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import daft_tpu
from daft_tpu import col, faults
from daft_tpu.context import get_context

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEGS = ("resident", "sql", "subquery", "scan", "serving", "resize", "mesh")


def _run(args, cwd=REPO, env_extra=None, timeout=600):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def cpu_smoke(tmp_path_factory):
    """ONE run of every leg on the 8-device virtual CPU mesh."""
    out = tmp_path_factory.mktemp("chip_smoke_out")
    return _run(["chip_smoke.py", "--cpu", "--scale", "0.01",
                 "--out", str(out)])


@pytest.mark.parametrize("leg", LEGS)
def test_chip_smoke_leg_passes_on_cpu(cpu_smoke, leg):
    assert f"[{leg}] ok" in cpu_smoke.stdout, (
        cpu_smoke.stdout[-4000:] + cpu_smoke.stderr[-2000:])


def test_chip_smoke_last_line_is_the_device_json(cpu_smoke):
    assert cpu_smoke.returncode == 0, cpu_smoke.stdout[-4000:]
    last = json.loads(cpu_smoke.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": 8}}
    # 32-bit mode, device path on, and CPU compiles stay out of the cache
    header = cpu_smoke.stdout.splitlines()[0]
    assert "x64=False" in header and "compile_cache=None" in header


def test_chip_smoke_refuses_a_machine_without_a_tpu():
    r = _run(["chip_smoke.py"], env_extra={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""  # no leg, no metric, no JSON
    assert "not a TPU" in r.stderr


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _run(["chip_smoke.py", "--cpu", "--scale", "0.01"], cwd=str(tmp_path),
             env_extra={"PYTHONPATH": ""})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


# ---------------------------------------------------------------------------
# bench.py
# ---------------------------------------------------------------------------

def test_bench_refuses_a_machine_without_a_tpu():
    r = _run(["bench.py", "0.01"], env_extra={"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert r.stdout.strip() == ""  # nothing under a device metric's name
    assert "not a TPU" in r.stderr


class _FakeTpu:
    platform, device_kind = "tpu", "fake"


def test_bench_rung_error_makes_main_fail(monkeypatch, capsys):
    import jax

    import bench

    monkeypatch.setattr(jax, "devices", lambda: [_FakeTpu()])
    monkeypatch.setattr(bench, "_bench_env", lambda: {})
    good = {"metric": "m", "value": 1.0}
    monkeypatch.setattr(bench, "run_device_rungs", lambda scale: dict(good))
    assert bench.main(["0.01"]) == 0
    assert json.loads(capsys.readouterr().out)["device"]["platform"] == "tpu"
    # a rung that raised left an *_error key (top level or nested)
    for bad in ({"q3_error": "RuntimeError: boom"},
                {"serving": {"knee_error": "x"}},
                {"value": 0, "error": "device_parity_mismatch"}):
        monkeypatch.setattr(bench, "run_device_rungs",
                            lambda scale, bad=bad: {**good, **bad})
        assert bench.main(["0.01"]) == 1, bad
    assert "rung error" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# compile cache placement
# ---------------------------------------------------------------------------

@pytest.fixture
def cache_helper():
    from daft_tpu.kernels import compile_cache

    compile_cache.configure_compile_cache.cache_clear()
    yield compile_cache
    compile_cache.configure_compile_cache.cache_clear()


def test_compile_cache_honours_the_environment(cache_helper, monkeypatch,
                                               tmp_path):
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache_helper.configure_compile_cache() == str(tmp_path)
    assert cache_helper.compile_cache_dir() == str(tmp_path)
    # jax reads the variable itself: no directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_the_checkout(cache_helper, monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache_helper.compile_cache_dir() == os.path.join(REPO,
                                                            ".jax_cache")


def test_cpu_runs_stay_out_of_the_compile_cache(cache_helper, monkeypatch):
    import jax

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert cache_helper.configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None
    assert not os.path.exists(os.path.join(REPO, ".jax_cache"))


# ---------------------------------------------------------------------------
# a failing device attempt is loud, and the answer still right
# ---------------------------------------------------------------------------

@pytest.fixture
def cfg():
    ctx = get_context()
    old = ctx.execution_config
    ctx.execution_config = dataclasses.replace(
        old, enable_result_cache=False, use_device_kernels=True,
        device_min_rows=1, executor_threads=1)
    yield ctx.execution_config
    ctx.execution_config = old
    faults.disarm()


def test_device_attempt_error_is_counted_logged_and_shown(cfg):
    from daft_tpu.obs import log as obs_log

    seen = []
    obs_log.add_sink(seen.append)
    faults.arm("device.kernel", "first_n", n=1)
    try:
        df = (daft_tpu.from_pydict({"x": np.arange(5000, dtype=np.int64)})
              .select((col("x") * 2 + 1).alias("y")))
        text = df.explain_analyze()
    finally:
        obs_log.remove_sink(seen.append)
    assert df.to_pydict()["y"] == [2 * i + 1 for i in range(5000)]
    c = df.stats.snapshot()["counters"]
    assert c.get("device_attempt_errors") == 1, c
    assert c.get("host_projections", 0) >= 1, c  # the host path answered
    assert "InjectedFault" in df.stats.device_error
    assert "device errors: 1 attempt(s)" in text and "InjectedFault" in text
    rec = df.last_query_record()
    assert rec["outcome"] == "ok"
    assert "InjectedFault" in rec["device_error"]
    assert rec["events"]["device_attempt_errors"] == 1
    lines = [r for r in seen if r.get("event") == "device_attempt_error"]
    assert len(lines) == 1 and "InjectedFault" in lines[0]["error"]


def test_clean_query_reports_no_device_error(cfg):
    df = (daft_tpu.from_pydict({"x": np.arange(5000, dtype=np.int64)})
          .select((col("x") + 1).alias("y")))
    df.collect()
    assert df.stats.device_error is None
    assert "device_attempt_errors" not in df.stats.snapshot()["counters"]
    assert "device_error" not in df.last_query_record()


# ---------------------------------------------------------------------------
# one process per chip
# ---------------------------------------------------------------------------

def test_dist_worker_environment_is_forced_onto_cpu(monkeypatch):
    from daft_tpu.dist.supervisor import _repo_root, _worker_env

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    env = _worker_env(_repo_root())
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["PYTHONPATH"].split(os.pathsep)[0] == _repo_root()
