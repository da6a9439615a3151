"""Prove a cell on the chip: all of its runs in one chip call.

    chiprun --timeout 3000 -- python chipbench/prove.py --workload <cell> \
        --plan cold:1,set:6,set:6,seeds:6,traced:3,control:3

A parent that stays off JAX (the chip belongs to one process at a time) and
starts ``run.py`` once per run, one after the other. The plan's steps:

- ``cold:N``   N untraced runs on seeds of their own (the first compiles);
- ``set:N``    a set of N untraced runs at the manifest's ``run_seconds``;
               every set uses the same N seeds, as the bound's rule asks;
- ``seeds:N``  N short untraced runs (``--seed-seconds``), each on a new seed;
- ``traced:N`` N ``--trace 1`` runs, each on a new seed;
- ``control:N`` ``control.py`` on N seeds, at the cell's own size.

Every run's set-up line and result line go to
``<out>/<cell>.jsonl`` as they come; the summary at the end gives, for each
set and metric, the median and the spread (interquartile distance over the
median, ``statistics.quantiles(n=4)``), and for each run ``correct``, the
numbers compared and the compile-cache files it found and added.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def last_json(text: str, key: str):
    """The last line of ``text`` that is a JSON object holding ``key``."""
    for line in reversed(text.splitlines()):
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if key in obj:
                return obj
    return None


def spread(values: list) -> float:
    """Interquartile distance as a share of the median."""
    if len(values) < 2 or not statistics.median(values):
        return 0.0
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def one_run(script: str, argv: list, timeout: float) -> dict:
    t0 = time.time()
    proc = subprocess.run([sys.executable, os.path.join(HERE, script)] + argv,
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=timeout)
    return {"rc": proc.returncode, "held_s": round(time.time() - t0, 1),
            "setup": (last_json(proc.stdout, "setup") or {}).get("setup"),
            "result": last_json(proc.stdout, "correct"),
            "trace_lines": last_json(proc.stdout, "trace_lines"),
            "stderr_tail": proc.stderr[-1500:]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--plan", required=True)
    ap.add_argument("--seconds", type=float, default=None,
                    help="window of cold, set and traced runs; the "
                         "manifest's run_seconds when left out")
    ap.add_argument("--seed-seconds", type=float, default=5.0)
    ap.add_argument("--seed0", type=int, default=2_147_483_700)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "prove"))
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    log_path = os.path.join(args.out, args.workload + ".jsonl")

    fresh = iter(range(args.seed0 + 1000, args.seed0 + 2000))
    rows, n_set = [], 0
    for step in args.plan.split(","):
        kind, _, n = step.partition(":")
        n = int(n or 1)
        if kind == "set":
            n_set += 1
            tag, seeds = f"set{n_set}", [args.seed0 + i for i in range(n)]
        else:
            tag, seeds = kind, [next(fresh) for _ in range(n)]
        for seed in seeds:
            if kind == "control":
                row = one_run("control.py", ["--workload", args.workload,
                                             "--seed", str(seed)], 1200)
            else:
                row = one_run("run.py", [
                    "--workload", args.workload, "--seed", str(seed),
                    "--seconds", str(args.seed_seconds if kind == "seeds"
                                     else seconds),
                    "--trace", "1" if kind == "traced" else "0"], 1500)
            row.update(tag=tag, seed=seed)
            rows.append(row)
            with open(log_path, "a") as f:
                f.write(json.dumps(row) + "\n")
            print(one_line(row), flush=True)

    print("\n== spreads (interquartile distance over median) ==")
    for tag in sorted({r["tag"] for r in rows}):
        group = [r["result"] for r in rows
                 if r["tag"] == tag and r["result"] and "metrics" in r["result"]]
        names = sorted({m for res in group for m in res["metrics"]})
        for name in names:
            vals = [res["metrics"][name]["value"] for res in group
                    if name in res["metrics"]]
            print(f"{tag:8s} {name:34s} n={len(vals)} "
                  f"median={statistics.median(vals):.6g} "
                  f"spread={100 * spread(vals):.3f}% "
                  f"min={min(vals):.6g} max={max(vals):.6g}")
    bad = [r for r in rows if r["tag"] != "control"
           and not (r["result"] and r["result"]["correct"]
                    and not r["result"]["failed"])]
    held = [r for r in rows if r["tag"] == "control"
            and r["result"] and r["result"]["correct"]]
    print(f"\nruns not correct or with failed queries: {len(bad)}; "
          f"control runs that passed as correct: {len(held)}")
    return 1 if bad or held else 0


def one_line(row: dict) -> str:
    """A run as one printed line: what the builder reads first."""
    res, setup = row["result"], row["setup"] or {}
    head = f"{row['tag']} seed={row['seed']} rc={row['rc']}"
    if not res:
        return f"{head} NO RESULT {row['stderr_tail'][-600:]!r}"
    shown = {"correct": res["correct"],
             "compared": {k: v["value"] for k, v in res["compared"].items()}}
    if "metrics" in res:  # a run of run.py; a control has none
        shown.update(
            held_s=row["held_s"], attempted=res["attempted"],
            failed=res["failed"],
            metrics={k: v["value"] for k, v in res["metrics"].items()},
            window={k: res["window"][k] for k in (
                "seconds", "reference_s", "cache_files_added",
                "mean_wall_s", "failed_why")},
            setup={k: setup.get(k) for k in (
                "generate_s", "from_arrow_s", "warm_pass_s",
                "cache_files_at_start", "compiled_in_warmup")},
            device=res["device"])
        if "breakdown" in res:
            shown["breakdown"] = res["breakdown"]
    else:
        shown["by_query"] = res["by_query"]
    return f"{head} {json.dumps(shown)}"


if __name__ == "__main__":
    sys.exit(main())
