"""From a profiler trace to device busy time, idle gaps and top operations.

``load_events`` flattens the profiler's ``.xplane.pb`` into a plain list of
``(line, name, start_ns, dur_ns)``; ``reduce`` takes only that list, so it is
tested on hand-made events with no chip. Lines:

- a device's operations: ``/device:TPU:<n>/XLA Ops``;
- the benchmark's own spans, written with ``jax.profiler.TraceAnnotation``:
  any line, a name that starts with ``chipbench:`` — ``chipbench:window``
  round the measured window and ``chipbench:q:<query>`` round each query.

Busy time is the union of the intervals in which an operation ran on the
device, clipped to the window and averaged over the devices seen. A gap is
named by what the host was doing: ``<query>:before_first_op``,
``<query>:between_ops``, ``<query>:after_last_op`` inside a query's span and
``no_request_in_flight`` outside every span.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

SPAN_PREFIX = "chipbench:"
WINDOW_SPAN = SPAN_PREFIX + "window"
QUERY_SPAN = SPAN_PREFIX + "q:"
DEVICE_PLANE = "/device:"
DEVICE_OPS_LINE = "XLA Ops"


def _xplane(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"{len(found)} xplane files under {trace_dir}, expected 1")
    return found[0]


def load_events(trace_dir: str) -> list:
    """Device operations and ``chipbench:`` spans of the one trace under
    ``trace_dir``, as ``(line, name, start_ns, dur_ns)``."""
    from jax.profiler import ProfileData

    events = []
    for plane in ProfileData.from_file(_xplane(trace_dir)).planes:
        device = plane.name.startswith(DEVICE_PLANE)
        for line in plane.lines:
            if device and line.name != DEVICE_OPS_LINE:
                continue
            label = f"{plane.name}/{line.name}"
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    events.append((label, ev.name, int(ev.start_ns),
                                   int(ev.duration_ns)))
    return events


def plane_summary(trace_dir: str) -> list:
    """``[(plane, line, events)]`` of the trace: what to look at by hand
    when ``load_events`` finds no device line."""
    from jax.profiler import ProfileData

    return [(plane.name, line.name, sum(1 for _ in line.events))
            for plane in ProfileData.from_file(_xplane(trace_dir)).planes
            for line in plane.lines]


def short_name(name: str, width: int = 96) -> str:
    """On a TPU an operation's name is its whole HLO line, operands and all:
    keep the operation and its result shape, which tell two programs'
    ``%fusion`` apart, and cut the rest."""
    return name.lstrip("%")[:width]


def union(intervals: list) -> list:
    """Sorted, merged ``[start, end)`` intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        elif e > s:
            merged.append([s, e])
    return merged


def clip(intervals: list, lo: int, hi: int) -> list:
    """The parts of merged ``intervals`` inside ``[lo, hi)``."""
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def total(intervals: list) -> int:
    return sum(e - s for s, e in intervals)


def reduce(events: list) -> dict:
    """What the per-layer readers and the result line take from a trace.

    Returns ``window_s``, ``busy_s`` (averaged over devices), ``devices``,
    ``in_query_busy_s`` (device time inside query spans, averaged over
    devices), ``per_query_busy_s`` ``{query: seconds}``, ``device_ops`` and
    ``idle_gaps`` (each ``[[name, seconds], ...]``, longest first, at most
    10). With no window span the window is the extent of all events.
    """
    device_lines: dict = defaultdict(list)
    ops: dict = defaultdict(int)
    queries = []
    window = None
    for line, name, start, dur in events:
        if name.startswith(SPAN_PREFIX):
            if name == WINDOW_SPAN:
                window = (start, start + dur)
            elif name.startswith(QUERY_SPAN):
                queries.append((start, start + dur,
                                name[len(QUERY_SPAN):]))
        elif line.startswith(DEVICE_PLANE):
            device_lines[line].append((start, start + dur, name))
    if window is None:
        every = [(s, e) for evs in device_lines.values()
                 for s, e, _ in evs] + [(s, e) for s, e, _ in queries]
        if not every:
            return {"window_s": 0.0, "busy_s": 0.0, "devices": 0,
                    "in_query_busy_s": 0.0, "per_query_busy_s": {},
                    "device_ops": [], "idle_gaps": []}
        window = (min(s for s, _ in every), max(e for _, e in every))
    lo, hi = window
    spans = _disjoint(sorted(queries), lo, hi)
    n_dev = max(len(device_lines), 1)

    busy = in_query = 0
    per_query: dict = defaultdict(int)
    gaps: dict = defaultdict(int)
    for evs in device_lines.values():
        merged = clip(union([(s, e) for s, e, _ in evs]), lo, hi)
        busy += total(merged)
        for s, e, name in evs:
            if e > lo and s < hi:
                ops[name] += min(e, hi) - max(s, lo)
        covered = lo
        for qs, qe, qname in spans + [(hi, hi, None)]:
            # between two spans the host has no request in flight
            gaps["no_request_in_flight"] += (
                qs - covered - total(clip(merged, covered, qs)))
            covered = qe
            if qname is None:
                break
            inside = clip(merged, qs, qe)
            in_query += total(inside)
            per_query[qname] += total(inside)
            if not inside:
                gaps[f"{qname}:before_first_op"] += qe - qs
                continue
            gaps[f"{qname}:before_first_op"] += inside[0][0] - qs
            gaps[f"{qname}:after_last_op"] += qe - inside[-1][1]
            gaps[f"{qname}:between_ops"] += sum(
                b[0] - a[1] for a, b in zip(inside, inside[1:]))

    def top(table: dict) -> list:
        rows = sorted(((k, v / n_dev / 1e9) for k, v in table.items()
                       if v > 0), key=lambda kv: -kv[1])
        return [[short_name(k), v] for k, v in rows[:10]]

    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / n_dev / 1e9,
        "devices": len(device_lines),
        "in_query_busy_s": in_query / n_dev / 1e9,
        "per_query_busy_s": {k: v / n_dev / 1e9
                             for k, v in per_query.items()},
        "device_ops": top(ops),
        "idle_gaps": top(gaps),
    }


def _disjoint(queries: list, lo: int, hi: int) -> list:
    """Query spans cut so that none overlaps the one before (one client
    never overlaps; several clients may)."""
    out, covered = [], lo
    for qs, qe, name in queries:
        qs, qe = max(qs, lo, covered), min(qe, hi)
        if qe > qs:
            out.append((qs, qe, name))
            covered = qe
    return out
