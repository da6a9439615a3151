# daftlint: migrated
"""Sub-plan result cache: scan+project/filter prefixes memoize their
materialized partitions across queries.

Two different queries often share a prefix — ``scan.filter(x)`` feeding a
groupby in one and a sort in another. The whole-plan PartitionSetCache
(runners.py) only helps when the ENTIRE plan repeats; this cache
memoizes at the prefix boundary instead, hooked into
``execution.execute_plan``'s builder: when a maximal chain of map-class
ops (Project/Filter/FusedMap) bottoms out at a ScanOp, its output
partitions are teed into the cache on first execution and replayed on
the next query that plans the same prefix.

Keying follows the ``_PARTITION_SET_CACHE`` discipline exactly — the
exact structural key of every scan task (``runners._scan_task_key``:
path + MTIME/SIZE + format + pushdowns + schema + storage options) plus
each chain op's literal-bearing expression keys — so an overwritten
source file can never serve stale rows, and UDF-bearing chains decline
(non-deterministic, id-reused). Float-affecting device knobs are part of
the key; every other knob is covered by the engine's byte-identity
invariants (fusion/streaming/prefetch on or off produce identical bytes).

Entries hold detached Table references (never the query's own
MicroPartition objects, which downstream spill may unload) and each hit
serves FRESH MicroPartition wrappers, so one query spilling its copy
can never corrupt another's. Bytes are LRU-shed under
``cfg.subplan_cache_bytes`` and charged to the MemoryLedger's
``subplan_cache_bytes`` account. Fails open (armed
``resultcache.lookup`` fault included): any defect degrades to plain
execution.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Iterator, List, Optional, Tuple

from ..obs.log import get_logger

__all__ = ["SubplanResultCache", "RESULT_CACHE", "try_result_cache"]

logger = get_logger("resultcache")


class _Entry:
    __slots__ = ("tables", "nbytes", "hits", "created")

    def __init__(self, tables, nbytes: int):
        self.tables = tables
        self.nbytes = nbytes
        self.hits = 0
        self.created = time.monotonic()


class SubplanResultCache:
    """Bounded, thread-safe table cache keyed by exact prefix keys."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: "OrderedDict[str, _Entry]" = OrderedDict()
        self._bytes = 0
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0
        self.errors = 0

    def _charge(self, delta: int) -> None:
        if not delta:
            return
        try:
            from ..spill import MEMORY_LEDGER

            MEMORY_LEDGER.cache_account("subplan_cache_bytes", delta)
        except Exception as e:  # ledger unavailable during teardown
            logger.warning("subplan_cache_ledger_charge_failed",
                           error=repr(e))

    def get(self, key: str):
        with self._lock:
            e = self._entries.get(key)
            if e is None:
                self.misses += 1
                return None
            self._entries.move_to_end(key)
            e.hits += 1
            self.hits += 1
            return list(e.tables)

    def put(self, key: str, tables, nbytes: int, cap_bytes: int) -> None:
        if nbytes > max(cap_bytes, 0):
            return  # one oversized prefix must not evict everything else
        delta = 0
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
                delta -= old.nbytes
            self._entries[key] = _Entry(tables, nbytes)
            self._bytes += nbytes
            delta += nbytes
            self.inserts += 1
            while self._bytes > cap_bytes and len(self._entries) > 1:
                k, shed = self._entries.popitem(last=False)
                if k == key:
                    self._entries[k] = shed
                    self._entries.move_to_end(k, last=False)
                    break
                self._bytes -= shed.nbytes
                delta -= shed.nbytes
                self.evictions += 1
        self._charge(delta)

    def clear(self) -> None:
        """Drop every entry AND reset the stat counters (a cleared cache
        reads as a fresh one)."""
        with self._lock:
            freed = self._bytes
            self._entries.clear()
            self._bytes = 0
            self.hits = self.misses = 0
            self.inserts = self.evictions = self.errors = 0
        self._charge(-freed)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "bytes": self._bytes,
                "hits": self.hits,
                "misses": self.misses,
                "inserts": self.inserts,
                "evictions": self.evictions,
                "errors": self.errors,
            }


RESULT_CACHE = SubplanResultCache()


# float-affecting knobs: the only config under which "byte-identical at
# every knob setting" does not hold (reduced-precision device sums)
_CFG_KEY_FIELDS = ("use_device_kernels", "device_reduced_precision",
                   "use_pallas_segment_sums")


def _chain_over_scan(op) -> Optional[Tuple[list, object]]:
    """(map-op chain top-down, scan op) when `op` roots a pure
    Project/Filter/FusedMap chain over a ScanOp; None otherwise."""
    from ..fuse.compile import FusedMapOp
    from ..physical import FilterOp, ProjectOp, ScanOp

    chain = []
    cur = op
    while isinstance(cur, (ProjectOp, FilterOp, FusedMapOp)):
        chain.append(cur)
        cur = cur.children[0]
    if not chain or not isinstance(cur, ScanOp):
        return None
    return chain, cur


def _op_key(op) -> str:
    from ..expressions import expr_has_udf
    from ..fuse.compile import FusedMapOp
    from ..physical import FilterOp

    exprs = list(op._map_exprs())
    if any(expr_has_udf(e) for e in exprs):
        raise _Decline
    kind = ("fused" if isinstance(op, FusedMapOp)
            else "filter" if isinstance(op, FilterOp) else "project")
    return f"{kind}[{';'.join(repr(e._node._key()) for e in exprs)}]"


class _Decline(Exception):
    pass


def _prefix_key(chain, scan, cfg) -> str:
    from ..runners import _Uncacheable, _scan_task_key

    try:
        scan_part = ";".join(_scan_task_key(t) for t in scan.tasks)
    except _Uncacheable:
        raise _Decline from None
    ops_part = "|".join(_op_key(o) for o in chain)
    cfg_part = ",".join(f"{k}={getattr(cfg, k, None)!r}"
                        for k in _CFG_KEY_FIELDS)
    return f"{scan_part}||{ops_part}||{cfg_part}"


def try_result_cache(op, ctx, build, trace) -> Optional[Iterator]:
    """The execute_plan builder hook: replay a cached prefix, or tee this
    prefix's output into the cache. None = not applicable (caller builds
    normally). Fails open on every path."""
    cfg = ctx.cfg
    if not getattr(cfg, "subplan_result_cache", True):
        return None
    if ctx.memory_budget is not None:
        # spill-aware: a budgeted query's working set is governed by the
        # ledger/spill machinery — replaying a process-pinned prefix (or
        # pinning this query's output in one) would silently rewrite the
        # bounded-memory execution profile the budget asked for
        return None
    if getattr(ctx, "try_device_shuffle", None) is not None \
            or getattr(ctx, "scan_owner", None) is not None:
        return None  # mesh/multi-host: partitions may be foreign-owned
    if getattr(ctx, "dist_backend", None) is not None:
        # distributed runner: workers read scan tasks themselves (scan
        # locality) — replaying a driver-pinned prefix would pull the
        # whole scan back onto the driver
        return None
    skip = getattr(ctx, "_rc_inner_ops", None)
    if skip is not None and id(op) in skip:
        return None  # an op inside a prefix already being teed above
    found = _chain_over_scan(op)
    if found is None:
        return None
    chain, scan = found
    try:
        from .. import faults

        faults.check("resultcache.lookup", ctx.stats)
        if faults.any_armed():
            # a replayed prefix would let an armed site (scan.read, ...)
            # silently never fire: fault-injection runs execute for real
            return None
        key = _prefix_key(chain, scan, cfg)
    except _Decline:
        return None
    except Exception as e:
        RESULT_CACHE.errors += 1
        ctx.stats.bump("subplan_cache_errors")
        logger.warning("subplan_cache_key_failed", error=repr(e))
        return None
    cap = getattr(cfg, "subplan_cache_bytes", 64 * 1024 * 1024)
    tables = RESULT_CACHE.get(key)
    if tables is not None:
        ctx.stats.bump("subplan_cache_hits")
        p = ctx.stats.profiler
        if p.armed:
            p.event("resultcache", kind="hit", parts=len(tables))
        return _replay(tables)
    # memory miss: the persistent disk tier (exact replay or incremental
    # refresh), which also re-populates the memory tier on a hit. pmeta
    # is None whenever the tier is off/ineligible — everything below
    # stays byte-for-byte the PR 13 path.
    pmeta = None
    try:
        from ..persist import resultstore

        pmeta = resultstore.prefix_meta(chain, scan, cfg)
        if pmeta is not None:
            tables = resultstore.disk_lookup(pmeta, chain, scan, ctx)
            if tables is not None:
                nbytes = sum(t.size_bytes() or 0 for t in tables)
                RESULT_CACHE.put(key, tables, nbytes, cap)
                return _replay(tables)
    except Exception as e:
        ctx.stats.bump("persist_load_failures")
        logger.warning("persist_tier_failed", error=repr(e))
        pmeta = None
    ctx.stats.bump("subplan_cache_misses")
    # build the real stream. The whole chain (op itself included — the
    # recursive build() below re-enters this hook) is marked so neither
    # the re-entry nor nested sub-prefixes tee duplicate entries.
    if skip is None:
        skip = ctx._rc_inner_ops = set()
    for inner in chain:
        skip.add(id(inner))
    inner_stream = build(op)
    return _teeing(inner_stream, key, cap, ctx, pmeta)


def _replay(tables) -> Iterator:
    from ..micropartition import MicroPartition

    for t in tables:
        yield MicroPartition.from_table(t)


def _teeing(inner, key: str, cap_bytes: int, ctx,
            pmeta: Optional[dict] = None) -> Iterator:
    """Pass-through that stores the prefix's output on CLEAN exhaustion
    (a limit short-circuit or error never stores a partial prefix).
    Accumulation is byte-bounded: once the running total passes the cap
    the tee abandons immediately — it must never RETAIN a giant prefix
    only for put() to reject it at the end. Close propagates promptly so
    limit early-stop semantics survive."""
    acc: List = []
    acc_bytes = 0
    abandon = False
    try:
        for p in inner:
            if not abandon:
                if p.is_loaded():
                    acc.append(p)
                    acc_bytes += p.size_bytes() or 0
                    if acc_bytes > cap_bytes:
                        # oversized prefix: stop holding references now
                        abandon = True
                        acc.clear()
                else:
                    abandon = True  # foreign/unloaded output: don't cache
                    acc.clear()
            yield p
    finally:
        close = getattr(inner, "close", None)
        if close is not None:
            try:
                close()
            except Exception as e:
                # inner teardown failing must not mask the tee's exit
                logger.warning("subplan_cache_close_failed",
                               error=repr(e))
    if abandon:
        return
    try:
        tables = [p.table() for p in acc]
        nbytes = sum(p.size_bytes() or 0 for p in acc)
        RESULT_CACHE.put(key, tables, nbytes, cap_bytes)
        p = ctx.stats.profiler
        if p.armed:
            p.event("resultcache", kind="insert", parts=len(tables),
                    nbytes=nbytes)
    except Exception as e:
        RESULT_CACHE.errors += 1
        ctx.stats.bump("subplan_cache_errors")
        logger.warning("subplan_cache_store_failed", error=repr(e))
        return
    if pmeta is not None:
        # commit to the durable tier too (its own fault site + fail-open
        # path live inside disk_store — a persist defect never surfaces)
        from ..persist import resultstore

        resultstore.disk_store(pmeta, tables, nbytes, ctx)
