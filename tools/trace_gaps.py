"""trace-gaps: the device's idle seconds inside each query, by the program's
own span.

    python tools/trace_gaps.py --workload tpch1-join --seed 7 --seconds 30

chipbench's reducer names a gap by query only (``q5:between_ops``): it keeps
``chipbench:`` spans alone. This tool drives one cell's window with
``chipbench/run.py``'s own ``load_cell`` / ``run_window`` under its own
``jax.profiler`` session, into a directory it keeps (``--trace-dir``), and
attributes every moment in which no operation ran on the device to the
innermost ``daft_tpu:<kind>:<name>`` span live at that moment (the queries
armed their Profilers on the device timeline because the session was live:
daft_tpu/profile/timeline.py). Per query kind it prints the idle seconds of
``before_first_op`` / ``between_ops`` / ``after_last_op`` by span, and the
share of ``between_ops`` that lies inside a named span.

One process that holds the chip. ``CHIPBENCH_REHEARSE=1`` rehearses on the
CPU at the configuration's ``rehearse_scale``: there is no device plane
then, so the whole of each query reads as one ``before_first_op`` gap, which
still shows where the host's time goes. ``attribute`` takes plain events and
is tested on hand-made ones (tests/test_trace_gaps.py).
"""

from __future__ import annotations

import argparse
import glob
import heapq
import json
import os
import sys
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402
from chipbench import trace as trace_mod  # noqa: E402

SPAN_PREFIX = "daft_tpu:"
NO_SPAN = "(no daft_tpu span)"
GAP_KINDS = ("before_first_op", "between_ops", "after_last_op")


def load_events(trace_dir: str) -> list:
    """``(line, name, start_ns, dur_ns)`` of the device's operations, the
    benchmark's ``chipbench:`` spans and the program's ``daft_tpu:`` spans."""
    from jax.profiler import ProfileData

    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"{len(found)} xplane files under {trace_dir}, expected 1")
    events = []
    for plane in ProfileData.from_file(found[0]).planes:
        device = plane.name.startswith(trace_mod.DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name != trace_mod.DEVICE_OPS_LINE:
                continue
            label = f"{plane.name}/{line.name}"
            for ev in line.events:
                if device or ev.name.startswith(
                        (SPAN_PREFIX, trace_mod.SPAN_PREFIX)):
                    events.append((label, ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)))
    return events


def innermost_segments(spans: list) -> list:
    """Disjoint ``[start, end, name]`` pieces of the timeline, each named by
    the live span that started last (on one thread that is the innermost;
    across threads, the work begun most recently). ``spans``:
    ``(start, end, name)``."""
    points = sorted({t for s, e, _ in spans for t in (s, e)})
    starts = sorted(spans)
    live: list = []  # max-heap on start: (-start, end, name)
    out: list = []
    i = 0
    for t, nxt in zip(points, points[1:]):
        while i < len(starts) and starts[i][0] <= t:
            s, e, name = starts[i]
            heapq.heappush(live, (-s, e, name))
            i += 1
        while live and live[0][1] <= t:
            heapq.heappop(live)
        if not live:
            continue
        name = live[0][2]
        if out and out[-1][2] == name and out[-1][1] == t:
            out[-1][1] = nxt
        else:
            out.append([t, nxt, name])
    return out


def attribute(events: list) -> dict:
    """``{query: {gap kind: {span: idle ns}}}`` over all the query spans of
    ``events``: the device's idle time inside each ``chipbench:q:<query>``
    span, split as ``chipbench.trace.reduce`` splits it and named by the
    innermost ``daft_tpu:`` span (less the prefix), ``NO_SPAN`` where none
    was live. Several devices: idle means every device idle."""
    device, spans, queries = [], [], []
    for line, name, start, dur in events:
        if name.startswith(SPAN_PREFIX):
            spans.append((start, start + dur, name[len(SPAN_PREFIX):]))
        elif name.startswith(trace_mod.QUERY_SPAN):
            queries.append((start, start + dur,
                            name[len(trace_mod.QUERY_SPAN):]))
        elif line.startswith(trace_mod.DEVICE_PLANE):
            device.append((start, start + dur))
    busy = trace_mod.union(device)
    named = innermost_segments(spans)
    out: dict = defaultdict(lambda: {k: defaultdict(int) for k in GAP_KINDS})
    j = 0
    for qs, qe, query in sorted(queries):
        inside = trace_mod.clip(busy, qs, qe)
        if not inside:
            gaps = [("before_first_op", qs, qe)]
        else:
            gaps = [("before_first_op", qs, inside[0][0]),
                    ("after_last_op", inside[-1][1], qe)]
            gaps += [("between_ops", a[1], b[0])
                     for a, b in zip(inside, inside[1:])]
        for kind, lo, hi in sorted(gaps, key=lambda g: g[1]):
            if hi <= lo:
                continue
            # gaps come in time order and do not overlap: j only advances
            while j < len(named) and named[j][1] <= lo:
                j += 1
            covered = 0
            k = j
            while k < len(named) and named[k][0] < hi:
                s, e = max(named[k][0], lo), min(named[k][1], hi)
                if e > s:
                    out[query][kind][named[k][2]] += e - s
                    covered += e - s
                k += 1
            if hi - lo > covered:
                out[query][kind][NO_SPAN] += hi - lo - covered
    return {q: {k: dict(v) for k, v in kinds.items()}
            for q, kinds in out.items()}


def summarise(by_query: dict, top: int = 8) -> dict:
    """Seconds, longest first, and the share of ``between_ops`` inside a
    named span: in all, and by the innermost span's kind. ``phase`` is
    time a phase owns; ``op`` is an operator's own time outside every
    phase, which is near automatic (the root operator's span is live for
    the whole pull), so the two are not evidence of the same strength."""
    out = {}
    for query, kinds in sorted(by_query.items()):
        between = kinds["between_ops"]
        total = sum(between.values())
        by_kind: dict = defaultdict(int)
        for name, ns in between.items():
            by_kind[name.split(":", 1)[0] if name != NO_SPAN else name] += ns
        rows = {kind: [[name, ns / 1e9] for name, ns in sorted(
            spans.items(), key=lambda kv: -kv[1])[:top]]
            for kind, spans in kinds.items()}
        out[query] = {
            "idle_s": {k: sum(v.values()) / 1e9 for k, v in kinds.items()},
            "between_ops_named_share": (
                1.0 - between.get(NO_SPAN, 0) / total if total else None),
            "between_ops_share_by_kind": {
                k: ns / total for k, ns in sorted(by_kind.items())},
            "by_span": rows}
    return out


def set_up(workload: str, seed: int) -> tuple:
    """Set the cell up as ``chipbench/run.py`` does: generate, make
    resident, warm. ``(cell, order, queries, frames, platform)``."""
    rehearse = os.environ.get("CHIPBENCH_REHEARSE") == "1"
    _, cell, config, traffic, queries, dataset = bench.load_cell(workload)
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not rehearse:
        raise bench.Refused(f"jax found platform {platform!r}, not a TPU")
    import daft_tpu as dt
    from daft_tpu.kernels.compile_cache import configure_compile_cache

    configure_compile_cache()
    scale = config["rehearse_scale"] if rehearse else config["scale"]
    engine = dict(config["engine"])
    if rehearse:
        engine["device_min_rows"] = max(8, int(4096 * min(1.0, scale)))
    dt.set_execution_config(**engine)
    tables = dataset.generate(scale, seed, bench.union_columns(queries))
    frames = {name: dt.from_arrow(table).collect()
              for name, table in tables.items()}
    order = traffic["queries"]
    for _ in range(traffic["warmup_passes"]):
        for name in order:
            queries[name].build(frames).collect().to_pydict()
    return cell, order, queries, frames, platform


def drive(args) -> tuple:
    """Set the cell up as ``chipbench/run.py`` does, trace one window."""
    cell, order, queries, frames, platform = set_up(args.workload, args.seed)
    import jax

    import daft_tpu as dt

    trace_dir = os.path.join(args.trace_dir, f"{cell['name']}-{args.seed}")
    os.makedirs(trace_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1  # TraceAnnotation's level
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            records, _, seconds = bench.run_window(
                order, queries, frames, args.seconds,
                jax.profiler.TraceAnnotation)
    finally:
        jax.profiler.stop_trace()
    dt.shutdown()
    return trace_dir, records, seconds, platform


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace-dir", default=os.path.join(ROOT,
                                                        "trace_gaps_out"),
                    help="where the profiler's trace is kept")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "trace_gaps"),
                    help="where the summary <cell>-<seed>.json goes")
    args = ap.parse_args(argv)
    try:
        trace_dir, records, seconds, platform = drive(args)
    except bench.Refused as e:
        print(f"trace_gaps: refused: {e}", file=sys.stderr)
        return 2
    events = load_events(trace_dir)
    summary = summarise(attribute(events))
    walls: dict = defaultdict(list)
    for r in records:
        walls[r["name"]].append(r["wall_s"])
    result = {"workload": args.workload, "seed": args.seed,
              "platform": platform, "window_s": seconds,
              "queries": len(records), "trace_dir": trace_dir,
              "reduced": {k: v for k, v in trace_mod.reduce(
                  [e for e in events
                   if not e[1].startswith(SPAN_PREFIX)]).items()
                  if k in ("window_s", "busy_s", "idle_gaps")},
              "mean_wall_s": {k: sum(v) / len(v) for k, v in walls.items()},
              "gaps": summary}
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out,
                           f"{args.workload}-{args.seed}.json"), "w") as f:
        json.dump(result, f, indent=1)
    for query, s in summary.items():
        share = s["between_ops_named_share"]
        print(f"{query}: idle " + ", ".join(
            f"{k} {v:.3f} s" for k, v in s["idle_s"].items())
            + (f"; between_ops inside a named span: {100 * share:.1f}% ("
               + ", ".join(f"{k} {100 * v:.1f}%" for k, v in
                           s["between_ops_share_by_kind"].items()) + ")"
               if share is not None else ""))
        for kind in GAP_KINDS:
            for name, secs in s["by_span"][kind]:
                print(f"  {kind:16s} {secs:9.3f} s  {name}")
    print(json.dumps({k: result[k] for k in ("workload", "platform",
                                              "window_s", "queries",
                                              "trace_dir", "reduced",
                                              "mean_wall_s")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
