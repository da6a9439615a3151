"""residency-check: what a cell's window leaves in the stage caches of its
resident frames, and how each query met its partitions.

    python tools/residency_check.py --workload tpch1-sql-subquery --seed 7 --seconds 30

Sets the cell up as ``chipbench/run.py`` does (``tools/trace_gaps.set_up``:
generate, make resident, warm), records the stage-cache keys of every
partition of every resident frame, drives one window with ``run_window``
and records them again. Prints one JSON line: for each query kind the
median over the window of each counter in ``COUNTERS``, and for each frame
whether its keys are the ones it held before the window (and the keys
gained or lost where not). Exits 1 if a frame's keys changed: a device map
over a partition larger than a morsel runs over a stage view that keeps
nothing new, and every lane a query needs again was resident after the
warm-up. One process that holds the chip; ``CHIPBENCH_REHEARSE=1``
rehearses on the CPU at the configuration's ``rehearse_scale``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402
from tools.trace_gaps import set_up  # noqa: E402

COUNTERS = ("stream_morsels", "device_maps_unsplit", "stage_bytes",
            "stage_columns", "device_filter_dispatches",
            "device_fused_map_dispatches", "device_projection_dispatches",
            "gather_bytes")


def stage_keys(frames: dict) -> dict:
    """``{frame: [sorted keys of each partition's stage cache]}``."""
    return {name: [sorted(map(repr, p.device_stage_cache()))
                   for p in df._result.partitions]
            for name, df in frames.items()}


def compare_keys(before: dict, after: dict) -> dict:
    """Per frame: ``{"same": bool}``, with ``gained`` and ``lost`` keys
    where the two differ."""
    out = {}
    for name, parts in before.items():
        got = after[name]
        same = parts == got
        entry = {"same": same}
        if not same:
            entry["gained"] = sorted({k for p in got for k in p}
                                     - {k for p in parts for k in p})
            entry["lost"] = sorted({k for p in parts for k in p}
                                   - {k for p in got for k in p})
        out[name] = entry
    return out


def per_query(records: list) -> dict:
    """The median of each of ``COUNTERS`` over each query kind's runs."""
    seen: dict = defaultdict(lambda: defaultdict(list))
    for r in records:
        for k in COUNTERS:
            seen[r["name"]][k].append(r["counters"].get(k, 0))
    return {q: {k: statistics.median(v) for k, v in c.items()}
            for q, c in seen.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    try:
        _, order, queries, frames, platform = set_up(args.workload,
                                                     args.seed)
    except bench.Refused as e:
        print(f"residency_check: refused: {e}", file=sys.stderr)
        return 2
    import contextlib

    import daft_tpu as dt

    before = stage_keys(frames)
    records, _, seconds = bench.run_window(
        order, queries, frames, args.seconds, contextlib.nullcontext)
    keys = compare_keys(before, stage_keys(frames))
    dt.shutdown()
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "platform": platform, "window_s": seconds,
                      "queries": len(records),
                      "per_query": per_query(records),
                      "keys": keys}))
    return 0 if all(v["same"] for v in keys.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
