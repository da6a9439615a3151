"""Chrome-trace + progress instrumentation.

Role-equivalent to the reference's chrome-trace layer
(src/common/tracing/src/lib.rs:13-55, armed by DAFT_DEV_ENABLE_CHROME_TRACE
and re-armed per query by the native executor) and its tqdm progress bars
(daft/runners/progress_bar.py). Events are buffered in a bounded RING
(evictions counted, reported as droppedEvents) and written as one
chrome://tracing-compatible JSON array; since PR 6 the per-op duration
events are rendered FROM the structured profiler's span tree
(daft_tpu/profile/) at each query's end — one consolidated writer,
re-armed per query — so the trace carries the same cross-thread
attribution the QueryProfile does. The file's clock is the host's
``perf_counter``; to see the same spans beside the device's operations,
start a ``jax.profiler`` trace and run the query: the Profiler then writes
every span into that trace as a ``daft_tpu:<kind>:<name>`` annotation
(daft_tpu/profile/timeline.py).

Enable with the env var DAFT_TPU_CHROME_TRACE=<path> (armed at import/query
time) or programmatically:

    with daft_tpu.tracing.chrome_trace("/tmp/q1.json"):
        df.collect()
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Callable, Deque, Optional

# Buffer cap: a RING — past it the OLDEST events are evicted and counted
# (dropped_events()), so a long-running armed process keeps the most recent
# window instead of growing without bound. The flush metadata records the
# drop count so a truncated trace is never mistaken for a complete one.
DEFAULT_BUFFER_CAP = 200_000

_lock = threading.Lock()
_events: Deque[dict] = deque(maxlen=DEFAULT_BUFFER_CAP)
_dropped = 0
_path: Optional[str] = None
_t0_us: float = 0.0
# thread name -> chrome tid, stable for the LIFETIME of one armed trace:
# the consolidated multi-query file must keep each real thread on one lane
_tids: dict = {}

_progress_cb: Optional[Callable[[str, int], None]] = None


def active() -> bool:
    return _path is not None


def _now_us() -> float:
    return time.perf_counter_ns() / 1000.0


def set_buffer_cap(cap: int) -> None:
    """Resize the ring (keeps the newest events that fit; tests use this to
    exercise eviction cheaply)."""
    global _events, _dropped
    with _lock:
        old = list(_events)
        _events = deque(old[-cap:] if cap else [], maxlen=max(1, cap))
        _dropped += max(0, len(old) - cap)


def dropped_events() -> int:
    with _lock:
        return _dropped


def tail(n: int = 2000) -> list:
    """The newest ``n`` buffered chrome events (oldest first) — what the
    flight recorder's diagnostics bundles snapshot when a trace is armed."""
    with _lock:
        evs = list(_events)
    return evs[-n:]


def enable(path: str) -> None:
    """Start buffering events; flush() writes them to `path`."""
    global _path, _t0_us, _dropped
    with _lock:
        _path = path
        _t0_us = _now_us()
        _events.clear()
        _dropped = 0
        _tids.clear()


def _append_locked(ev: dict) -> None:
    # runs under _lock (every caller holds it); the lock-discipline rule is
    # lexical and cannot see through the helper
    global _dropped
    if _events.maxlen is not None and len(_events) == _events.maxlen:
        # the ring evicts its oldest entry on this append
        _dropped += 1  # daftlint: disable=DTL002
    _events.append(ev)


def add_event(name: str, start_us: float, dur_us: float, tid: int = 0,
              args: Optional[dict] = None) -> None:
    if _path is None:
        return
    ev = {"name": name, "ph": "X", "pid": os.getpid(), "tid": tid,
          "ts": start_us - _t0_us, "dur": dur_us}
    if args:
        ev["args"] = args
    with _lock:
        _append_locked(ev)


def add_instant(name: str, args: Optional[dict] = None) -> None:
    """Zero-duration marker (chrome-trace 'instant' event) — used for
    discrete occurrences like injected faults and breaker trips, which have
    no wall time but matter when lining up a failure against the pipeline."""
    if _path is None:
        return
    ev = {"name": name, "ph": "i", "s": "g", "pid": os.getpid(), "tid": 0,
          "ts": _now_us() - _t0_us}
    if args:
        ev["args"] = args
    with _lock:
        _append_locked(ev)


def add_span_events(profiler) -> None:
    """Render a finished query's span tree + typed events into the chrome
    buffer (the consolidated writer: execution no longer emits per-pull
    chrome events itself — the span tree is the single source). Threads map
    to chrome tids by first appearance, stable across the armed trace's
    lifetime; span phases and attrs ride in `args` so the trace viewer
    shows the same breakdown the QueryProfile carries. Incremental: only
    spans/events not yet rendered are emitted, so an AQE query's per-stage
    flushes never duplicate earlier stages."""
    if _path is None:
        return
    spans, events = profiler.drain_for_chrome()
    pid = os.getpid()
    with _lock:
        t0 = _t0_us
        for sp in spans:
            tid = _tids.setdefault(sp.thread, len(_tids))
            args = {"span": sp.sid, "kind": sp.kind}
            if sp.parent is not None:
                args["parent"] = sp.parent
            if sp.part is not None:
                args["part"] = sp.part
            if sp.phases:
                args.update({f"phase.{k}": v for k, v in sp.phases.items()})
            if sp.attrs:
                args.update(sp.attrs)
            _append_locked({
                "name": sp.name, "ph": "X", "pid": pid, "tid": tid,
                "ts": sp.t0_ns / 1000.0 - t0, "dur": sp.dur_ns / 1000.0,
                "args": args})
        for ev in events:
            _append_locked({
                "name": ev["kind"], "ph": "i", "s": "g", "pid": pid,
                "tid": 0, "ts": ev["t_ns"] / 1000.0 - t0,
                "args": dict(ev.get("attrs") or {})})


def flush(keep: bool = False) -> Optional[str]:
    """Write buffered events atomically w.r.t. concurrent emits: the buffer
    is snapshotted (and, unless ``keep``, cleared) under the lock in one
    step, then written outside it — an emit racing the file write lands in
    the next flush, never lost or duplicated. ``keep=True`` is the
    per-query re-arming mode: the file on disk always reflects everything
    so far, and later queries keep appending."""
    global _dropped
    with _lock:
        path = _path
        if path is None:
            return None
        evs = list(_events)
        dropped = _dropped
        if not keep:
            # the written file records this window's drops; the next
            # window starts with a clean count (a later complete batch
            # must not be mislabeled as truncated)
            _events.clear()
            _dropped = 0
    doc = {"traceEvents": evs, "displayTimeUnit": "ms"}
    if dropped:
        doc["droppedEvents"] = dropped
    with open(path, "w") as f:
        json.dump(doc, f)
    return path


def flush_query() -> Optional[str]:
    """Query-end flush: rewrite the armed trace file with everything
    buffered so far, KEEPING the buffer — every query re-arms the same
    consolidated writer, and the file survives a process kill between
    queries (reference: the native executor's per-query chrome re-arming)."""
    return flush(keep=True)


def disable() -> None:
    global _path
    with _lock:
        _path = None
        _events.clear()
        _tids.clear()


@contextmanager
def chrome_trace(path: str):
    """Trace every query run inside the block into one chrome-trace file."""
    enable(path)
    try:
        yield
    finally:
        flush()
        disable()


# armed from the environment once, like the reference's DAFT_DEV_ENABLE_CHROME_TRACE;
# the atexit hook guarantees the file is written even though no context manager
# wraps the process, and bounds the buffer's lifetime to the process
_env_path = os.environ.get("DAFT_TPU_CHROME_TRACE")
if _env_path:
    import atexit

    enable(_env_path)
    atexit.register(flush)


# ---------------------------------------------------------------------------
# progress
# ---------------------------------------------------------------------------

def set_progress_callback(cb: Optional[Callable[[str, int], None]]) -> None:
    """cb(op_name, rows_emitted) fires per produced partition (None clears)."""
    global _progress_cb
    _progress_cb = cb


def report_progress(op_name: str, rows: int) -> None:
    cb = _progress_cb
    if cb is not None:
        cb(op_name, rows)


class ProgressBar:
    """Terminal progress UI (reference: daft/runners/progress_bar.py): one
    tqdm bar per operator when tqdm is importable, a plain carriage-return
    line otherwise. Enable with `progress_bars()` (or DAFT_TPU_PROGRESS=1,
    wired in context.py); disable with `progress_bars(False)`."""

    def __init__(self, use_tqdm: Optional[bool] = None):
        if use_tqdm is None:
            try:
                import tqdm  # noqa: F401

                use_tqdm = True
            except ImportError:
                use_tqdm = False
        self._use_tqdm = use_tqdm
        self._bars = {}
        self._counts = {}

    def __call__(self, op_name: str, rows: int) -> None:
        if self._use_tqdm:
            from tqdm import tqdm

            bar = self._bars.get(op_name)
            if bar is None:
                bar = self._bars[op_name] = tqdm(
                    desc=op_name, unit=" rows", position=len(self._bars),
                    leave=False)
            bar.update(rows)
        else:
            import sys

            self._counts[op_name] = self._counts.get(op_name, 0) + rows
            line = " | ".join(f"{k}: {v:,}" for k, v in self._counts.items())
            print("\r" + line[:160], end="", file=sys.stderr, flush=True)

    def close(self) -> None:
        for bar in self._bars.values():
            bar.close()
        self._bars.clear()
        if self._counts:
            import sys

            print("", file=sys.stderr)
        self._counts.clear()


def query_finished() -> None:
    """Close per-query progress state (bars restart fresh next query)."""
    cb = _progress_cb
    if isinstance(cb, ProgressBar):
        cb.close()


def progress_bars(enable: bool = True) -> None:
    """Toggle terminal progress reporting for subsequent queries."""
    global _progress_cb
    if isinstance(_progress_cb, ProgressBar):
        _progress_cb.close()
    set_progress_callback(ProgressBar() if enable else None)
