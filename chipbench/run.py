"""chipbench: one run of one cell of ``BENCHMARK.json``.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the chip. It finds everything by name: the cell in
``BENCHMARK.json``, then ``configs/<config>.json``, ``traffic/<traffic>.json``,
``queries/<q>.py``, ``datasets/<name>.py`` and ``metrics/<metric>.py`` beside
this file. It generates the configuration's tables from ``--seed``, makes them
resident (``dt.from_arrow(table).collect()``), warms every query shape, then
drives ``build(frames).collect().to_pydict()`` in a closed loop for
``--seconds`` and to the end of that whole pass. After the window it reads the
peak of device memory, computes each query's plain reference and compares
every answer the window produced. The last line of stdout is the result.

A platform other than ``tpu`` is refused (exit 2, no result) unless
``CHIPBENCH_REHEARSE=1``: then the run is on the CPU at the configuration's
``rehearse_scale`` and the result names ``cpu`` as its device; tier-1 uses it.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, as near as Python lets us

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, trace as trace_mod  # noqa: E402

# counters that mean a device path was refused, broke or was bypassed
# (chip_smoke.py's list): a query in which one moved counts as failed
_FAILURE_NAMES = ("degraded_completions", "segment_fallbacks",
                  "degraded_shuffles", "degraded_sketch_merges",
                  "device_attempt_errors")


class Refused(Exception):
    """The run cannot be made here; exit 2 and print no result."""


def failure_counters(counters: dict) -> dict:
    return {k: v for k, v in counters.items() if v and (
        k in _FAILURE_NAMES or k.endswith("_breaker_trips")
        or (k.startswith("device_") and k.endswith("_fallbacks")))}


def off_device_path(counters: dict, floors: dict) -> dict:
    """Why a query does not count as answered by the device path: failure
    counters that moved and device counters under the query's floors."""
    bad = failure_counters(counters)
    bad.update({k: counters.get(k, 0) for k, floor in floors.items()
                if counters.get(k, 0) < floor})
    return bad


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise Refused(f"no {kind}/{name}.py beside run.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(*parts) -> dict:
    path = os.path.join(*parts)
    if not os.path.isfile(path):
        raise Refused(f"no {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def cache_files(path) -> set:
    """Names of the persistent compile cache's files (one or two a
    program; a name starts with the jitted function's)."""
    if not path or not os.path.isdir(path):
        return set()
    return {f for _, _, files in os.walk(path) for f in files}


def cell_metrics(manifest: dict, cell: str, group: str) -> list:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in manifest[group]
            if cell in m.get("workloads", [cell])]


def union_columns(queries: dict) -> dict:
    columns: dict = {}
    for q in queries.values():
        for table, cols in q.COLUMNS.items():
            have = columns.setdefault(table, [])
            have.extend(c for c in cols if c not in have)
    return columns


def run_window(order, queries, frames, seconds, annotate):
    """The closed loop: whole passes until one ends at or after ``seconds``.
    Returns ``(records, answers, window seconds)``."""
    records, answers = [], []
    start = time.perf_counter()
    while True:
        for name in order:
            q = queries[name]
            with annotate(trace_mod.QUERY_SPAN + name):
                t0 = time.perf_counter()
                df = q.build(frames)
                got = df.collect().to_pydict()
                t1 = time.perf_counter()
            records.append({"name": name, "wall_s": t1 - t0,
                            "counters": df.stats.snapshot()["counters"]})
            answers.append((name, got))
        if time.perf_counter() - start >= seconds:
            break
    return records, answers, t1 - start


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except Refused as e:
        print(f"chipbench: refused: {e}", file=sys.stderr)
        return 2


def load_cell(workload: str) -> tuple:
    """Everything a cell names, found by name: ``(manifest, cell, config,
    traffic, queries, dataset)``; ``queries`` in the traffic's order."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == workload), None)
    if cell is None:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    config = load_json(HERE, "configs", cell["config"] + ".json")
    traffic = load_json(HERE, "traffic", cell["traffic"] + ".json")
    if traffic["loop"] != "closed" or traffic["clients"] != 1:
        raise Refused("this generator drives one closed-loop client")
    queries = {name: load_module("queries", name)
               for name in traffic["queries"]}
    dataset = load_module("datasets", config["dataset"])
    return manifest, cell, config, traffic, queries, dataset


@contextlib.contextmanager
def profiled(jax, on: bool):
    """The profiler round the window of a ``--trace 1`` run. Yields the
    span maker and a dict that holds ``trace.reduce``'s result afterwards
    (nothing in an untraced run)."""
    out: dict = {}
    if not on:
        yield contextlib.nullcontext, out
        return
    with tempfile.TemporaryDirectory(prefix="chipbench_trace_") as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1  # TraceAnnotation's level
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            yield jax.profiler.TraceAnnotation, out
        finally:
            jax.profiler.stop_trace()
        out.update(trace_mod.reduce(trace_mod.load_events(trace_dir)))
        if not out["devices"]:  # what to look at by hand
            print(json.dumps({"trace_lines": trace_mod.plane_summary(
                trace_dir)}), flush=True)


def tally(records: list, queries: dict) -> tuple:
    """``(failed, why, counters)``: queries that left the device path, the
    first reason of each kind, and the window's counters summed."""
    failed, why, counters = 0, {}, {}
    for rec in records:
        bad = off_device_path(rec["counters"], queries[rec["name"]].floors)
        if bad:
            failed += 1
            why.setdefault(rec["name"], bad)
        for k, v in rec["counters"].items():
            if isinstance(v, (int, float)):
                counters[k] = counters.get(k, 0) + v
    return failed, why, counters


def run(args) -> int:
    rehearse = os.environ.get("CHIPBENCH_REHEARSE") == "1"
    manifest, cell, config, traffic, queries, dataset = load_cell(
        args.workload)
    order = traffic["queries"]
    peaks_table = load_json(HERE, "peaks.json")

    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    peaks = peaks_table.get(kind)
    if not rehearse:
        if platform != "tpu":
            raise Refused(f"jax found platform {platform!r}, not a TPU")
        if len(devices) < cell["chips"]:
            raise Refused(f"{len(devices)} chip(s), the cell asks for "
                          f"{cell['chips']}")
        if peaks is None:
            raise Refused(f"device kind {kind!r} is not in peaks.json")
    try:
        import daft_tpu as dt
        from daft_tpu.kernels.compile_cache import configure_compile_cache
    except ImportError as e:
        raise Refused(f"the engine is not beside chipbench/ ({e})")

    cache_dir = configure_compile_cache()
    files_at_start = cache_files(cache_dir)
    scale = config["rehearse_scale"] if rehearse else config["scale"]
    engine = dict(config["engine"])
    if rehearse:
        # the device threshold shrinks with the data (chip_smoke.py's rule)
        engine["device_min_rows"] = max(8, int(4096 * min(1.0, scale)))
    dt.set_execution_config(**engine)

    # ---- set-up: generate, make resident, warm every shape ----
    t = time.perf_counter()
    columns = union_columns(queries)
    tables = dataset.generate(scale, args.seed, columns)
    rows = {name: table.num_rows for name, table in tables.items()}
    t_generate = time.perf_counter() - t

    t = time.perf_counter()
    frames = {name: dt.from_arrow(table).collect()
              for name, table in tables.items()}
    t_stage = time.perf_counter() - t

    t_warm = []
    for _ in range(traffic["warmup_passes"]):
        t = time.perf_counter()
        for name in order:
            queries[name].build(frames).collect().to_pydict()
        t_warm.append(round(time.perf_counter() - t, 3))
    files_warm = cache_files(cache_dir)
    setup_s = time.perf_counter() - T0
    print(json.dumps({"setup": {
        "rows": rows, "generate_s": round(t_generate, 3),
        "from_arrow_s": round(t_stage, 3), "warm_pass_s": t_warm,
        "setup_s": round(setup_s, 3), "compile_cache": cache_dir,
        "cache_files_at_start": len(files_at_start),
        "cache_files_after_warmup": len(files_warm),
        "compiled_in_warmup": sorted(files_warm - files_at_start)[:40]}}),
        flush=True)

    # ---- the measured window ----
    with profiled(jax, bool(args.trace)) as (annotate, traced):
        with annotate(trace_mod.WINDOW_SPAN):
            records, answers, seconds = run_window(
                order, queries, frames, args.seconds, annotate)
    reduced = traced or None

    memory_peak = max(((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices[:cell["chips"]]), default=0)
    files_end = cache_files(cache_dir)

    # ---- the plain reference, after the window, and the comparison ----
    del frames
    t = time.perf_counter()
    references = {name: q.reference(tables) for name, q in queries.items()}
    t_reference = time.perf_counter() - t
    correct, compared, by_query = compare.judge(
        answers, references, config["compare"])

    failed, why_failed, counters = tally(records, queries)

    window = {
        "seconds": seconds, "setup_s": setup_s,
        "queries": records,
        "rows": rows, "memory_peak_bytes": memory_peak,
        "input_bytes": sum(4 * rows[t_] * len(cols)
                           for t_, cols in columns.items()),
        "min_bytes": sum(queries[r["name"]].min_bytes(rows)
                         for r in records),
        "cache_files_added": len(files_end - files_warm), "peaks": peaks,
    }
    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(manifest, cell["name"], group):
        value = load_module("metrics", m["name"]).read(
            window, counters, reduced)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(records),
              "failed": failed, "metrics": metrics, "device": device}
    if reduced is not None:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    by_kind: dict = {}
    for r in records:
        by_kind.setdefault(r["name"], []).append(r["wall_s"])
    result["window"] = {
        "seconds": seconds, "reference_s": t_reference,
        "cache_files_added": window["cache_files_added"],
        "mean_wall_s": {k: sum(v) / len(v) for k, v in by_kind.items()},
        "walls_s": [round(r["wall_s"], 4) for r in records],
        "by_query": by_query, "failed_why": why_failed}
    result["compared"] = compared
    dt.shutdown()
    sys.stdout.flush()
    for name, c in compared.items():
        print(f"chipbench: compared {name} = {c['value']!r} "
              f"(limit {c['limit']!r})", file=sys.stderr)
    print(f"chipbench: correct = {correct}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
