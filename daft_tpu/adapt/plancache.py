# daftlint: migrated
"""Process-level plan/program cache: fingerprint -> planned artifacts.

One entry per (canonical fingerprint, config key): the optimized logical
plan, the translated+fused physical plan (compiled ``FusedProgram``s
included), and the FDO decisions baked into it. Entries hold a small LRU
of *bindings* — the exact, literal- and mtime-bearing structural keys
(``runners.plan_cache_key``) — so ``WHERE x > 5`` and ``WHERE x > 9``
share one entry (shape, byte accounting, demotion state, FDO
expectations) while each literal binding serves its own compiled plan.

Guarantees:

- **warm path**: a hit performs zero ``optimize()`` / ``translate()`` /
  fuse-compile calls (pinned by test) — the cached physical tree is
  *rehydrated* (structural clone with per-query state reset: FusedMapOp
  record latches, join-filter slots) so concurrent serving queries never
  share mutable operator state, and results are byte-identical to a cold
  plan.
- **invalidation**: the binding key embeds source mtime/size and literal
  values; the config key embeds the FULL ExecutionConfig; ``CACHE_VERSION``
  + the runtime generation cover engine/planner changes; FDO revalidation
  (``revalidate``) drops entries whose recorded decision expectations no
  longer match history; ``demote`` drops a shape after a runtime
  mispredict. No stale plan is ever served.
- **bounded**: total estimated bytes are LRU-shed under
  ``cfg.plan_cache_bytes``, charged to the MemoryLedger's
  ``plan_cache_bytes`` account.
- **failing open**: any cache-layer defect (including the armed
  ``plancache.lookup`` fault site) degrades to uncached planning, never a
  query failure. Concurrent misses on one binding build exactly once
  (single-flight); waiters that time out plan uncached.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

from ..obs.log import get_logger

__all__ = ["PlanCache", "PLAN_CACHE", "CACHE_VERSION", "plan_query",
           "clone_plan"]

logger = get_logger("plancache")

# bump when planner/executor internals change plan semantics (also part of
# every lookup key, so stale artifacts from before a bump can never serve)
CACHE_VERSION = 1

_BINDINGS_PER_ENTRY = 8
_SINGLE_FLIGHT_WAIT_S = 30.0


class CompiledPlan:
    """One binding's planned artifacts. ``fdo_expect`` is the list of FDO
    decision expectations baked into THIS compiled plan — per binding,
    not per entry, because two literal bindings of one shape can compile
    under different history states and each must revalidate against what
    IT decided (fdo.still_valid re-derives them as history evolves)."""

    __slots__ = ("optimized", "physical", "nbytes", "fdo_expect")

    def __init__(self, optimized, physical, nbytes: int, fdo_expect=None):
        self.optimized = optimized
        self.physical = physical
        self.nbytes = nbytes
        self.fdo_expect = fdo_expect or []


class _Entry:
    __slots__ = ("canonical_fp", "cfg_key", "bindings",
                 "nbytes", "last_used", "hits")

    def __init__(self, canonical_fp: str, cfg_key: str):
        self.canonical_fp = canonical_fp
        self.cfg_key = cfg_key
        # exact binding key -> CompiledPlan (small LRU: literal variants)
        self.bindings: "OrderedDict[str, CompiledPlan]" = OrderedDict()
        self.nbytes = 0
        self.last_used = time.monotonic()
        self.hits = 0


def _estimate_plan_bytes(optimized, physical) -> int:
    """Working estimate for the byte cap: a cheap structural term (plans
    are python object graphs; exact accounting is not worth a deep walk)
    PLUS the in-memory source partitions a cached plan would PIN — a plan
    over a large from_pydict frame holds its data alive beyond the
    DataFrame's lifetime, so that data must count against (and a frame
    beyond the cap must exclude the plan from) the cache."""
    from ..physical import InMemoryOp

    def pinned(op) -> int:
        n = 0
        if isinstance(op, InMemoryOp):
            for p in op.parts:
                if p.is_loaded():
                    n += p.size_bytes() or 0
        for c in op.children:
            n += pinned(c)
        return n

    try:
        return (8192
                + 24 * (len(optimized.display_tree())
                        + len(physical.display_tree()))
                + pinned(physical))
    except Exception:
        return 65536


def _fresh_slot(slot, memo: dict):
    """Per-query-fresh copy of a JoinFilterSlot; the SAME slot object is
    shared by its feed and probe exchanges, so the copy must be too."""
    import copy

    ns = memo.get(id(slot))
    if ns is None:
        ns = copy.copy(slot)
        ns._builder = None
        ns._filter = None
        ns._sealed = False
        memo[id(slot)] = ns
    return ns


def clone_plan(op, _memo: Optional[dict] = None):
    """Rehydrate a cached physical tree for one execution: structural
    clone (fresh op objects + children lists; expressions, schemas,
    FusedPrograms, and scan tasks are immutable and shared) with every
    per-query latch reset. Cached trees are never executed directly —
    concurrent serving queries each get their own clone."""
    import copy

    from ..fuse.compile import FusedMapOp
    from ..fuse.segment import DeviceSegmentOp

    if _memo is None:
        _memo = {}
    new = copy.copy(op)
    new.children = [clone_plan(c, _memo) for c in op.children]
    if isinstance(new, FusedMapOp):
        # the once-per-query chain-counter latch (the program itself is
        # immutable and shared)
        new._recorded = False
        new._record_lock = threading.Lock()
    if isinstance(new, DeviceSegmentOp):
        # same contract for the resident-segment op: fusion-counter latch,
        # first-resident-success latch; the SegmentProgram is immutable and
        # shared — a warm hit performs ZERO segment compiles
        new._recorded = False
        new._resident_recorded = False
        new._record_lock = threading.Lock()
    ff = getattr(new, "filter_feed", None)
    if ff is not None:
        new.filter_feed = _fresh_slot(ff, _memo)
    pf = getattr(new, "probe_filter", None)
    if pf is not None:
        new.probe_filter = _fresh_slot(pf, _memo)
    return new


def _cfg_key(cfg) -> str:
    """The FULL ExecutionConfig as a deterministic string: ANY knob change
    invalidates (conservative by design — a missed planning-relevant field
    could serve a stale plan; an extra field only costs a re-plan)."""
    import dataclasses

    return ";".join(f"{f.name}={getattr(cfg, f.name)!r}"
                    for f in dataclasses.fields(cfg))


def _sans_generation(cfg_key: str) -> list:
    """A config key less its generation: a key ends
    ``|v<version>|g<generation>|r<runner>`` (``_plan_query``)."""
    parts = cfg_key.rsplit("|", 3)
    del parts[2:3]
    return parts


class PlanCache:
    """Bounded, thread-safe plan/program cache (see module docstring)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[Tuple[str, str], _Entry]" = OrderedDict()
        self._inflight: Dict[tuple, threading.Event] = {}
        self._bytes = 0
        self._generation = 0
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0
        self.demotions = 0
        self.errors = 0

    # ------------------------------------------------------------ ledger
    def _charge(self, delta: int) -> None:
        if not delta:
            return
        try:
            from ..spill import MEMORY_LEDGER

            MEMORY_LEDGER.cache_account("plan_cache_bytes", delta)
        except Exception as e:  # ledger unavailable during teardown
            logger.warning("plan_cache_ledger_charge_failed",
                           error=repr(e))

    # ------------------------------------------------------------ lookup
    def miss_reason(self, canonical_fp: str, cfg_key: str) -> Tuple[str, bool]:
        """Why a lookup of this shape under ``cfg_key`` found no plan:
        ``("binding", False)`` where the entry exists (a new binding:
        literals or a source's mtime), ``("config", gen_only)`` where the
        shape has entries under other config keys only (``gen_only``: one
        of them differs from ``cfg_key`` in ``PLAN_CACHE.generation`` alone),
        else ``("shape", False)``."""
        with self._lock:
            if (canonical_fp, cfg_key) in self._entries:
                return "binding", False
            others = [k for fp, k in self._entries if fp == canonical_fp]
        if not others:
            return "shape", False
        mine = _sans_generation(cfg_key)
        return "config", any(_sans_generation(k) == mine for k in others)

    def lookup(self, canonical_fp: str, cfg_key: str,
               binding: str) -> Optional[CompiledPlan]:
        with self._lock:
            entry = self._entries.get((canonical_fp, cfg_key))
            if entry is None:
                self.misses += 1
                return None
            cp = entry.bindings.get(binding)
            if cp is None:
                self.misses += 1
                return None
            entry.bindings.move_to_end(binding)
            self._entries.move_to_end((canonical_fp, cfg_key))
            entry.last_used = time.monotonic()
            entry.hits += 1
            self.hits += 1
            return cp

    def store(self, canonical_fp: str, cfg_key: str, binding: str,
              cp: CompiledPlan, cap_bytes: int) -> None:
        if cp.nbytes > max(cap_bytes, 0):
            return  # one oversized plan must not evict the whole cache
        with self._lock:
            key = (canonical_fp, cfg_key)
            entry = self._entries.get(key)
            if entry is None:
                entry = self._entries[key] = _Entry(canonical_fp, cfg_key)
            old = entry.bindings.pop(binding, None)
            if old is not None:
                entry.nbytes -= old.nbytes
                self._bytes -= old.nbytes
            entry.bindings[binding] = cp
            entry.nbytes += cp.nbytes
            self._bytes += cp.nbytes
            self.inserts += 1
            delta = cp.nbytes - (old.nbytes if old is not None else 0)
            while len(entry.bindings) > _BINDINGS_PER_ENTRY:
                _, shed = entry.bindings.popitem(last=False)
                entry.nbytes -= shed.nbytes
                self._bytes -= shed.nbytes
                delta -= shed.nbytes
                self.evictions += 1
            self._entries.move_to_end(key)
            entry.last_used = time.monotonic()
            while self._bytes > cap_bytes and len(self._entries) > 1:
                k, shed_e = self._entries.popitem(last=False)
                if k == key:  # never shed the entry just stored
                    self._entries[k] = shed_e
                    self._entries.move_to_end(k, last=False)
                    break
                self._bytes -= shed_e.nbytes
                delta -= shed_e.nbytes
                self.evictions += 1
            # the cap binds within one entry too: literal variants of a
            # single hot shape must not hold unbounded plan bytes
            while self._bytes > cap_bytes and len(entry.bindings) > 1:
                bk = next(iter(entry.bindings))
                if bk == binding:
                    break  # never shed the binding just stored
                shed = entry.bindings.pop(bk)
                entry.nbytes -= shed.nbytes
                self._bytes -= shed.nbytes
                delta -= shed.nbytes
                self.evictions += 1
        self._charge(delta)

    # -------------------------------------------------------- invalidation
    def demote(self, canonical_fp: str) -> None:
        """Drop every entry of this shape (runtime mispredict: the cached
        plan's FDO decision was wrong — the next run re-plans uncached-
        fresh and re-caches from the corrected history)."""
        freed = 0
        with self._lock:
            for key in [k for k in self._entries if k[0] == canonical_fp]:
                e = self._entries.pop(key)
                freed += e.nbytes
                self._bytes -= e.nbytes
                self.demotions += 1
        if freed:
            self._charge(-freed)
            logger.info("plan_cache_demoted", fingerprint=canonical_fp,
                        freed_bytes=freed)

    def revalidate(self, site_fps) -> None:
        """Drop BINDINGS whose baked FDO expectations consulted any of
        the just-updated sites and no longer re-derive (fresh history
        would now plan differently — e.g. a build side crossed below the
        broadcast threshold). Per binding, not per entry: an older
        literal binding compiled under different history must not hide
        behind a newer sibling's still-valid decisions."""
        from . import fdo

        stale: List[Tuple[Tuple[str, str], str]] = []
        with self._lock:
            items = [(key, list(e.bindings.items()))
                     for key, e in self._entries.items()]
        for key, bindings in items:
            for bk, cp in bindings:
                for exp in cp.fdo_expect:
                    if exp.get("site") not in site_fps:
                        continue
                    try:
                        ok = fdo.still_valid(exp)
                    except Exception:
                        ok = False
                    if not ok:
                        stale.append((key, bk))
                        break
        if not stale:
            return
        freed = 0
        with self._lock:
            for key, bk in stale:
                e = self._entries.get(key)
                if e is None:
                    continue
                cp = e.bindings.pop(bk, None)
                if cp is None:
                    continue
                e.nbytes -= cp.nbytes
                self._bytes -= cp.nbytes
                freed += cp.nbytes
                self.demotions += 1
                if not e.bindings:
                    self._entries.pop(key, None)
        if freed:
            self._charge(-freed)
            logger.info("plan_cache_revalidated", dropped=len(stale))

    def bump_generation(self) -> None:
        """Invalidate everything (the runtime analog of a CACHE_VERSION
        bump; ``clear`` for tests)."""
        self.clear()
        with self._lock:
            self._generation += 1

    def clear(self) -> None:
        """Drop every entry AND reset the stat counters (a cleared cache
        reads as a fresh one — hit rates measured after a clear start
        from zero). In-flight single-flight events are SIGNALLED before
        being dropped: a waiter must fail open to an uncached plan now,
        not sit out the full wait timeout."""
        with self._lock:
            freed = self._bytes
            inflight = list(self._inflight.values())
            self._entries.clear()
            self._inflight.clear()
            self._bytes = 0
            self.hits = self.misses = self.inserts = 0
            self.evictions = self.demotions = self.errors = 0
        for ev in inflight:
            ev.set()
        self._charge(-freed)

    # ------------------------------------------------------ single flight
    def begin_build(self, full_key) -> Optional[threading.Event]:
        """Returns None when THIS caller owns the build; otherwise the
        event to wait on (another thread is already planning this key)."""
        with self._lock:
            ev = self._inflight.get(full_key)
            if ev is not None:
                return ev
            self._inflight[full_key] = threading.Event()
            return None

    def end_build(self, full_key) -> None:
        with self._lock:
            ev = self._inflight.pop(full_key, None)
        if ev is not None:
            ev.set()

    # -------------------------------------------------- persist artifacts
    def export_artifact(self) -> list:
        """The persist/ serialization view:
        ``[(canonical_fp, cfg_key, [(binding, pickled-CompiledPlan)])]``.
        Per-binding blobs, so one unpicklable plan (exotic closures)
        skips alone; ``mem#`` bindings (process-local in-memory source
        tokens) never persist — a fresh process can't hold their data."""
        import pickle as _pickle

        with self._lock:
            items = [(key, list(e.bindings.items()))
                     for key, e in self._entries.items()]
        out = []
        for (fp, cfg_key), bindings in items:
            blobs = []
            for bk, cp in bindings:
                if "mem#" in bk:
                    continue
                try:
                    blobs.append((bk, _pickle.dumps(
                        cp, protocol=_pickle.HIGHEST_PROTOCOL)))
                except Exception:
                    continue  # fail open: this binding stays process-only
            if blobs:
                out.append((fp, cfg_key, blobs))
        return out

    def import_artifact(self, entries, cap_bytes: int) -> int:
        """Merge an artifact's entries; LIVE bindings win (the running
        process's plans are newer than any file). Lookup counters are NOT
        touched — hit rates must reflect real query traffic, not the
        load. Returns bindings merged."""
        import pickle as _pickle

        n = 0
        for fp, cfg_key, blobs in entries:
            for bk, blob in blobs:
                with self._lock:
                    e = self._entries.get((fp, cfg_key))
                    if e is not None and bk in e.bindings:
                        continue
                try:
                    cp = _pickle.loads(blob)
                except Exception:
                    continue  # one bad blob is one cold binding
                self.store(fp, cfg_key, bk, cp, cap_bytes)
                n += 1
        return n

    # ------------------------------------------------------------- admin
    @property
    def generation(self) -> int:
        with self._lock:
            return self._generation

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bindings": sum(len(e.bindings)
                                for e in self._entries.values()),
                "bytes": self._bytes,
                "hits": self.hits,
                "misses": self.misses,
                "inserts": self.inserts,
                "evictions": self.evictions,
                "demotions": self.demotions,
                "errors": self.errors,
            }


PLAN_CACHE = PlanCache()


def _event(stats, kind: str, **fields) -> None:
    p = stats.profiler
    if p.armed:
        p.event("plancache", kind=kind, **fields)


def _has_write(plan) -> bool:
    from ..logical import Write

    if isinstance(plan, Write):
        return True
    return any(_has_write(c) for c in plan.children())


def plan_query(plan, cfg, stats=None, optimized: bool = False,
               runner: str = "native"):
    """``_plan_query`` inside the ``plan`` frame: ``planning_wall_ns``, and
    a ``plan`` span when the query's profiler is armed. A frame, so an
    enclosing one (``entry.setup``) takes planning off its own time."""
    if stats is None:
        return _plan_query(plan, cfg, stats, optimized, runner)
    from ..profile.timeline import DeviceFrame

    with DeviceFrame(stats, "plan", "planning_wall_ns"):
        return _plan_query(plan, cfg, stats, optimized, runner)


def _plan_query(plan, cfg, stats, optimized: bool, runner: str):
    """The runners' one planning entry point: FDO-informed optimize +
    translate + fuse, served from the plan cache when possible.

    Returns ``(optimized_plan, physical_plan, run_cfg)`` — ``run_cfg`` is
    ``cfg`` unless a history-driven per-query hint (e.g. streaming-off)
    replaced a knob for this execution only.

    Timing lands in ``stats``: ``planning_wall_ns`` (``plan_query``'s
    frame) covers this whole call (cold planning or warm lookup+rehydrate),
    ``compile_wall_ns`` the fuse-compile share inside ``translate`` — the
    very costs the cache removes stay measurable either way. A miss bumps
    ``plan_cache_misses`` and one ``plan_cache_miss_<reason>``
    (``PlanCache.miss_reason``; ``uncached`` where the cache stood down or
    failed)."""
    from . import fdo
    from .fingerprint import canonical_fingerprint

    canonical = ""
    try:
        canonical = canonical_fingerprint(plan)
    except Exception as e:
        # an unfingerprintable plan only loses cache/FDO eligibility
        logger.warning("canonical_fingerprint_failed", error=repr(e))

    def _finish(opt, phys, run_cfg, from_cache: bool):
        if canonical:
            phys._canonical_fp = canonical
        run_cfg = fdo.apply_query_hints(canonical, run_cfg, stats)
        return opt, phys, run_cfg

    def _cold(record_fdo: bool):
        from ..optimizer import optimize
        from ..physical import fuse_for_device, translate

        # fan-out resizes decline for: mesh plans (the device collective
        # yields its own partition count — a reduce-side fan-in would
        # desynchronize translate's counts) and Write-bearing plans (one
        # output file per partition: an identical write query must not
        # change its file count/layout with process history)
        fanout_ok = runner != "mesh" and not _has_write(plan)
        with fdo.collecting(cfg, stats, enabled=record_fdo,
                            fanout_ok=fanout_ok) as coll:
            opt = plan if optimized else optimize(plan)
            phys = translate(opt, cfg, stats=stats)
            phys = fuse_for_device(phys, cfg)
        return opt, phys, coll

    use_cache = (getattr(cfg, "plan_cache", True) and not optimized
                 and canonical)
    binding = cfg_key = None
    if use_cache:
        try:
            from .. import faults
            from ..runners import plan_cache_key

            faults.check("plancache.lookup", stats)
            # warm-start: merge any on-disk artifacts before the first
            # lookup (latched per process; inert without cfg.cache_dir).
            # Sits BEFORE the any_armed stand-down so an armed
            # persist.load plan reaches its site and cold-misses there.
            if getattr(cfg, "cache_dir", None) is not None:
                from .. import persist

                persist.ensure_loaded(cfg, stats)
            # an armed fault registry stands the cache down entirely: a
            # cached plan would let an armed site (fuse.compile, ...)
            # silently never fire — chaos runs must plan for real
            binding = None if faults.any_armed() else plan_cache_key(plan)
            # the runner is part of the key: mesh plans decline FDO
            # fan-out decisions, so a native-planned tree must never
            # serve a mesh execution (and vice versa)
            cfg_key = _cfg_key(cfg) + f"|v{CACHE_VERSION}" \
                + f"|g{PLAN_CACHE.generation}|r{runner}"
        except Exception as e:
            PLAN_CACHE.errors += 1
            if stats is not None:
                stats.bump("plan_cache_errors")
            logger.warning("plan_cache_lookup_failed", error=repr(e))
            binding = None
    if not use_cache or binding is None:
        if use_cache and stats is not None:
            # stood down (faults armed, an uncacheable plan) or failed
            stats.bump_many({"plan_cache_misses": 1,
                             "plan_cache_miss_uncached": 1})
        opt, phys, _ = _cold(record_fdo=not optimized)
        return _finish(opt, phys, cfg, from_cache=False)

    full_key = (canonical, cfg_key, binding)
    waited = False
    while True:
        try:
            cp = PLAN_CACHE.lookup(canonical, cfg_key, binding)
        except Exception:
            PLAN_CACHE.errors += 1
            cp = None
        if cp is not None:
            if stats is not None:
                stats.bump("plan_cache_hits")
                _event(stats, "hit", fingerprint=canonical)
            try:
                phys = clone_plan(cp.physical)
            except Exception as e:
                # rehydration defect: fail open to a fresh plan
                PLAN_CACHE.errors += 1
                if stats is not None:
                    stats.bump("plan_cache_errors")
                logger.warning("plan_cache_rehydrate_failed",
                               error=repr(e))
                break
            return _finish(cp.optimized, phys, cfg, from_cache=True)
        if waited:
            break  # builder failed or evicted underneath us: plan uncached
        ev = PLAN_CACHE.begin_build(full_key)
        if ev is not None:
            # someone else is planning this exact binding: wait, re-check
            waited = True
            if not ev.wait(_SINGLE_FLIGHT_WAIT_S):
                break
            continue
        # we own the build
        try:
            if stats is not None:
                reason, gen_only = PLAN_CACHE.miss_reason(canonical, cfg_key)
            opt, phys, coll = _cold(record_fdo=True)
            if stats is not None:
                stats.bump_many({"plan_cache_misses": 1,
                                 f"plan_cache_miss_{reason}": 1})
                _event(stats, "miss", fingerprint=canonical, reason=reason,
                       generation_only=gen_only)
            try:
                cp = CompiledPlan(opt, phys,
                                  _estimate_plan_bytes(opt, phys),
                                  fdo_expect=coll.expects)
                PLAN_CACHE.store(canonical, cfg_key, binding, cp,
                                 getattr(cfg, "plan_cache_bytes",
                                         64 * 1024 * 1024))
            except Exception as e:
                PLAN_CACHE.errors += 1
                if stats is not None:
                    stats.bump("plan_cache_errors")
                logger.warning("plan_cache_store_failed", error=repr(e))
            return _finish(opt, phys, cfg, from_cache=False)
        finally:
            PLAN_CACHE.end_build(full_key)
    # fail-open tail: plan uncached (still FDO-informed)
    opt, phys, _ = _cold(record_fdo=True)
    if stats is not None:
        stats.bump_many({"plan_cache_misses": 1,
                         "plan_cache_miss_uncached": 1})
    return _finish(opt, phys, cfg, from_cache=False)
