"""The program's spans on the device's clock, and the always-on counters
that come from the same clock reads.

Four pieces, all feeding the one recorder (:class:`~.spans.Profiler`):

- :func:`device_trace_live` / :func:`arm_for_query` — a query that starts
  while a ``jax.profiler`` session is live arms its own Profiler with the
  device timeline on: every span it opens is also a
  ``jax.profiler.TraceAnnotation`` named ``daft_tpu:<kind>:<name>``, an event
  of the same xplane, on the same clock, as ``/device:TPU:0/XLA Ops``.
- :class:`DeviceFrame` — the accounting of one region on this thread: a
  device attempt or resolve (``ExecutionContext._device_attempt`` /
  ``_device_resolve``), planning (``adapt.plancache.plan_query``) or a
  region of the entry layer (``entry.setup``, ``entry.finish``,
  ``entry.convert``: ``DataFrame.collect`` / ``to_pydict``, and the
  ``finally`` of ``execution.execute_plan``). The kernel modules reach the
  query's RuntimeStats through it (:func:`timed`, :func:`part`,
  :func:`add`). Time a nested region or frame owns is taken off the
  frame's own counter, so no nanosecond is counted twice; a part is named
  inside the frame's own time and taken off nothing. Counters flush with
  one locked add when the frame closes, armed or not; spans only when the
  profiler is armed, from the same two clock reads.
- the ``jax.monitoring`` listeners (:func:`listen_for_compiles`) that credit
  XLA compiles and persistent cache loads to the frame running on the
  compiling thread.
- :func:`host_query`, round the entry layer's regions: the process's
  garbage collections while the query runs (``gc.get_stats()`` deltas) and
  the pauses of the generation-1 and -2 collections its own thread made
  (one ``gc.callbacks`` hook a process).

Counters: ``stage_ns``/``stage_bytes``/``stage_columns`` (Arrow to HBM, cache
misses only), ``device_dispatch_ns`` (an attempt's wall less what it owns
inside), ``device_wait_ns`` (blocked until outputs are ready: the chip is
busy), ``gather_ns``/``gather_bytes`` (copy back and assemble: the chip is
idle), ``xla_compiles``/``xla_compile_ns``/``xla_cache_loads``;
``dispatch_lookup_ns`` (building a program's cache key and looking it up)
and ``dispatch_call_ns`` (the jitted call's host side), parts of
``device_dispatch_ns``; ``planning_wall_ns``; ``entry_setup_ns``,
``entry_finish_ns`` (with its parts ``entry_finish_<hook>_ns``),
``entry_convert_ns``; ``gc_collections``, ``gc_collections_gen2``,
``gc_pause_ns`` (a cause, not a region: a pause lands inside whatever
region was running).
"""

from __future__ import annotations

import gc
import sys
import threading
import time
from typing import Optional

from .spans import _NOOP, Profiler

__all__ = ["device_trace_live", "arm_for_query", "DeviceFrame",
           "current_frame", "end_frame", "timed", "part", "add",
           "listen_for_compiles", "host_query"]


def device_trace_live() -> bool:
    """Is a ``jax.profiler`` trace session live in this process? The one
    place that asks jax (0.9.0 keeps the session on
    ``jax._src.profiler._profile_state``); a process that never imported
    jax's profiler has none."""
    mod = sys.modules.get("jax._src.profiler")
    return mod is not None and mod._profile_state.profile_session is not None


def arm_for_query(stats, query_id: str, profile=None):
    """Decide, where a query begins and before it is planned, whether its
    RuntimeStats gets a live Profiler: ``profile=`` (None defers to
    ``cfg.enable_profiling``), an armed chrome trace, or a live device
    trace. Returns what ``profile=`` resolved to (the caller builds the
    QueryProfile artifact from it). The slow-query arm needs the plan's
    fingerprint and stays in ``execute_plan``."""
    from .. import tracing
    from ..context import get_context

    want = (profile if profile is not None
            else get_context().execution_config.enable_profiling)
    live = device_trace_live()
    if want or live or tracing.active():
        early = stats.profiler
        stats.profiler = Profiler(query_id=query_id, device_timeline=live)
        if early.armed:
            # dt.sql() armed these stats while it planned: its front-end
            # spans (sql.parse, sql.plan, sql.decorrelate) are the query's
            stats.profiler.splice(
                [sp.as_dict() for sp in early.spans_snapshot()],
                early.events_snapshot(), None, 0)
    return want


# .frame: the innermost open DeviceFrame; .gc: the pauses of the query this
# thread runs (host_query), .gc_t0: when the running collection began
_tl = threading.local()


def _begin(stats, name: str):
    """``(span or None, t0_ns)``: one clock read either way."""
    prof = stats.profiler
    if prof.armed:
        sp = prof.begin(name, kind="phase")
        return sp, sp.t0_ns
    return None, time.perf_counter_ns()


def _end(stats, sp, t0: int) -> int:
    """Close what ``_begin`` opened; the elapsed ns, one clock read."""
    if sp is None:
        return time.perf_counter_ns() - t0
    stats.profiler.end(sp)
    return sp.dur_ns


class DeviceFrame:
    """``with DeviceFrame(stats, "dispatch", "device_dispatch_ns")``: the
    accounting of one region on this thread (see the module docstring). Its
    own counter gets the frame's wall less what nested regions and frames
    owned; everything pending flushes with one locked add at close."""

    __slots__ = ("stats", "name", "key", "owned_ns", "in_part", "_adds",
                 "_prev", "_sp", "_t0")

    def __init__(self, stats, name: str, key: str):
        self.stats = stats
        self.name = name
        self.key = key
        self.owned_ns = 0
        self.in_part = False
        self._adds = {}

    def add(self, key: str, n: int) -> None:
        self._adds[key] = self._adds.get(key, 0) + n

    def __enter__(self):
        self._prev = getattr(_tl, "frame", None)
        _tl.frame = self
        self._sp, self._t0 = _begin(self.stats, self.name)
        return self

    def __exit__(self, *exc):
        wall = _end(self.stats, self._sp, self._t0)
        _tl.frame = prev = self._prev
        if prev is not None:
            prev.owned_ns += wall
        self.add(self.key, wall - self.owned_ns)
        self.stats.bump_many(self._adds)
        return False


class _Region:
    """One timed region inside a frame: a span when armed, and ``key`` (if
    any) in the frame's pending counters, from the same two clock reads."""

    __slots__ = ("_frame", "_name", "_key", "_sp", "_t0")

    def __init__(self, frame: DeviceFrame, name: str, key: Optional[str]):
        self._frame = frame
        self._name = name
        self._key = key

    def __enter__(self):
        self._sp, self._t0 = _begin(self._frame.stats, self._name)
        return self

    def __exit__(self, *exc):
        frame = self._frame
        ns = _end(frame.stats, self._sp, self._t0)
        frame.owned_ns += ns
        if self._key is not None:
            frame.add(self._key, ns)
        return False


class _Part:
    """One named part of a frame's own time: a span when armed, and ``key``
    in the frame's pending counters, less what nested regions and frames
    owned meanwhile, from the same two clock reads. Not added to the
    frame's ``owned_ns``: the frame's own counter keeps it."""

    __slots__ = ("_frame", "_name", "_key", "_sp", "_t0", "_owned0")

    def __init__(self, frame: DeviceFrame, name: str, key: str):
        self._frame = frame
        self._name = name
        self._key = key

    def __enter__(self):
        frame = self._frame
        frame.in_part = True
        self._owned0 = frame.owned_ns
        self._sp, self._t0 = _begin(frame.stats, self._name)
        return self

    def __exit__(self, *exc):
        frame = self._frame
        ns = _end(frame.stats, self._sp, self._t0)
        frame.in_part = False
        frame.add(self._key, ns - (frame.owned_ns - self._owned0))
        return False


def current_frame() -> Optional[DeviceFrame]:
    return getattr(_tl, "frame", None)


def end_frame(stats, key: str) -> None:
    """Close this thread's innermost frame if it is ``stats``'s frame of
    ``key``; else nothing. For a frame that ends in another function than
    the one that opened it: ``DataFrame.collect`` opens ``entry.setup``,
    and the plan stream's first pull (or ``collect`` itself, where the plan
    never ran) closes it."""
    frame = current_frame()
    if frame is not None and frame.stats is stats and frame.key == key:
        frame.__exit__(None, None, None)


def timed(name: str, key: Optional[str] = None):
    """A timed region of this thread's device frame (``stage``,
    ``device.wait``, ...): span ``name`` when armed, ``key`` always. With
    ``key`` None the time is only taken off the frame's own counter (it
    stays the enclosing operator's self time). No frame: a no-op."""
    frame = current_frame()
    if frame is None:
        return _NOOP
    return _Region(frame, name, key)


def part(name: str, key: str):
    """A part of this thread's frame's own time (``dispatch.lookup``,
    ``dispatch.call`` inside a ``dispatch`` frame; the hooks of
    ``entry.finish``): span ``name`` when armed, ``key`` always, and the
    frame's own counter unchanged, so the parts of a frame add up to no
    more than it. No frame, or inside another part: a no-op."""
    frame = current_frame()
    if frame is None or frame.in_part:
        return _NOOP
    return _Part(frame, name, key)


def add(key: str, n: int) -> None:
    """Add to a counter of this thread's device frame (bytes, columns)."""
    frame = current_frame()
    if frame is not None:
        frame.add(key, n)


# --------------------------------------------------------- host runtime: gc
def _gc_counts() -> tuple:
    """The process's collections so far: all generations, the second."""
    stats = gc.get_stats()
    return sum(g["collections"] for g in stats), stats[2]["collections"]


def _on_gc(phase: str, info: dict) -> None:
    """``gc.callbacks`` hook: time the generation-1 and -2 collections a
    thread running a query (``host_query``) makes. It takes no lock: a
    collection can start while this thread holds one."""
    gen = info["generation"]
    if gen == 0:
        return
    pauses = getattr(_tl, "gc", None)
    if pauses is None:
        return
    now = time.perf_counter_ns()
    if phase == "start":
        _tl.gc_t0 = now
    else:
        t0 = _tl.gc_t0
        pauses.append((gen, t0, now - t0))


class _HostQuery:
    __slots__ = ("_stats", "_prev", "_pauses", "_counts")

    def __init__(self, stats):
        self._stats = stats

    def __enter__(self):
        self._prev = getattr(_tl, "gc", None)
        _tl.gc = self._pauses = []
        self._counts = _gc_counts()
        return self

    def __exit__(self, *exc):
        total, gen2 = _gc_counts()
        _tl.gc = self._prev
        pauses = self._pauses
        self._stats.bump_many({
            "gc_collections": total - self._counts[0],
            "gc_collections_gen2": gen2 - self._counts[1],
            "gc_pause_ns": sum(ns for _, _, ns in pauses)})
        prof = self._stats.profiler
        if prof.armed:
            for gen, t0, ns in pauses:
                prof.event("gc", generation=gen, t0_ns=t0, dur_ns=ns)
        return False


def host_query(stats):
    """``with host_query(stats)``: this thread runs ``stats``'s query. At
    the close ``gc_collections`` / ``gc_collections_gen2`` get the process's
    collections meanwhile (``gc.get_stats()`` deltas: all generations, the
    second), ``gc_pause_ns`` the pauses of the generation-1 and -2
    collections this thread made, and an armed profiler a ``gc`` event
    each. The pause of a collection on a thread that runs no query counts
    nowhere."""
    return _HostQuery(stats)


gc.callbacks.append(_on_gc)  # once a process: the import lock sees to it


# ---------------------------------------------------------------- XLA compiles
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_event(event: str, **kwargs) -> None:
    # a persistent-cache hit fires inside the interval the duration event
    # closes, on the same thread: remember it until then
    if event == _CACHE_HIT_EVENT:
        _tl.cache_hit = True


def _on_duration(event: str, duration: float, **kwargs) -> None:
    if event != _COMPILE_EVENT:
        return
    loaded = getattr(_tl, "cache_hit", False)
    _tl.cache_hit = False
    frame = current_frame()
    if frame is None:
        return
    ns = int(duration * 1e9)
    if loaded:
        frame.add("xla_cache_loads", 1)
    else:
        frame.add("xla_compiles", 1)
        frame.add("xla_compile_ns", ns)
    prof = frame.stats.profiler
    if prof.armed:
        prof.event("compile.xla", dur_ns=ns, cache_load=loaded)


def listen_for_compiles() -> None:
    """Register the two ``jax.monitoring`` listeners. ``kernels/device.py``
    calls this as it is imported: once a process (the import lock sees to
    that), before a program of the kernel layer can compile, and only in a
    process that has imported jax anyway. A frame pays nothing for them."""
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
