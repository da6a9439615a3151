"""ServingRuntime: N queries concurrently over the shared pool and mesh.

One runtime owns one AdmissionController and one SharedExecutorPool. Every
submitted query runs on its own driver thread through the admission gate:

    handle = runtime.submit(df)            # sheds DaftOverloadedError when
                                           # the bounded queue is full
    result_df = handle.result(timeout)     # or raises the query's error
    handle.record()                        # its flight-recorder QueryRecord

Robustness headline, per the ISSUE: admitted queries get a QueryContext —
their own RuntimeStats, breakers, deadline, cancellation handle, and a
MemoryLedger share carved from the global budget
(``memory_budget_bytes / max_concurrent_queries``) — so one heavy or
poisoned query spills, trips, times out, and dies ALONE. Shed queries get
a "shed" QueryRecord so the flight recorder sees every outcome, not just
executions.

``runtime.shutdown(timeout_s)`` is drain-mode: stop admitting (queued and
new queries shed), finish in-flight queries within the timeout, cancel and
report stragglers, then tear the shared pool down. The module-level
``shutdown()`` does that for every live runtime plus the actor pools —
``daft_tpu.shutdown()`` re-exports it and an atexit hook runs it with a
short timeout.
"""

from __future__ import annotations

import atexit
import itertools
import threading
import time
import weakref
from typing import List, Optional

from ..context import get_context, resolve_executor_threads
from ..errors import DaftOverloadedError
from ..obs.log import get_logger
from .admission import AdmissionController
from .pool import SharedExecutorPool
from .qcontext import QueryContext

logger = get_logger("serve")

# live runtimes, for engine-wide drain (dt.shutdown / atexit); weak so a
# dropped runtime never outlives its last user reference
_RUNTIMES: "weakref.WeakSet[ServingRuntime]" = weakref.WeakSet()
_runtimes_lock = threading.Lock()

# thread-name prefixes the engine owns; leaked_thread_count() scans these.
# Every spawn site's static name prefix must be covered by an entry here —
# daftlint DTL012 enforces the inventory, so a new subsystem prefix that
# forgets to register itself fails lint instead of leaking invisibly.
_ENGINE_THREAD_PREFIXES = ("daft-serve", "daft-exec", "daft-actor",
                           "daft-spill-writer", "daft-dist", "daft-peer",
                           "daft-mm")


class QueryHandle:
    """Future-like handle for one submitted query."""

    def __init__(self, query_id: str, stats):
        self.query_id = query_id
        self.stats = stats
        # submit -> terminal monotonic timestamps: the caller-visible
        # latency (queue wait included) the serving bench quantiles
        self.submitted_at = time.monotonic()
        self.finished_at: Optional[float] = None
        self._done = threading.Event()
        self._admitted = threading.Event()
        self._result = None
        self._error: Optional[BaseException] = None
        self._qctx: Optional[QueryContext] = None

    # ----------------------------------------------------------- completion
    def _set_result(self, df) -> None:
        self._result = df
        self.finished_at = time.monotonic()
        self._done.set()

    def _set_exception(self, e: BaseException) -> None:
        self._error = e
        self.finished_at = time.monotonic()
        self._done.set()

    def latency_s(self) -> Optional[float]:
        """Submit-to-terminal wall seconds (None until terminal)."""
        if self.finished_at is None:
            return None
        return self.finished_at - self.submitted_at

    def done(self) -> bool:
        return self._done.is_set()

    def wait_admitted(self, timeout: Optional[float] = None) -> bool:
        """True once the query holds an execution slot (shed/failed queries
        also return via ``done``)."""
        return self._admitted.wait(timeout)

    def result(self, timeout: Optional[float] = None):
        """The materialized DataFrame, or raises the query's terminal error
        (DaftOverloadedError when shed, DaftTimeoutError on deadline, ...)."""
        if not self._done.wait(timeout):
            from ..errors import DaftTimeoutError

            raise DaftTimeoutError(
                f"{self.query_id}: no terminal state within {timeout}s")
        if self._error is not None:
            raise self._error
        return self._result

    def exception(self, timeout: Optional[float] = None):
        self._done.wait(timeout)
        return self._error

    def record(self):
        """This query's flight-recorder QueryRecord (None until terminal)."""
        return self.stats.last_record

    def progress(self):
        """Live progress snapshot of this query while it executes (the
        ``dt.health()["queries"]`` entry: ops completed/total, rows/bytes
        flowed, tasks in flight, per-worker dispatch state, streaming
        channel depths). None before admission and after completion —
        a finished query's truth lives in :meth:`record`."""
        from ..obs.cluster import query_progress

        return query_progress(self.query_id)

    def cancel(self) -> None:
        """Stop the query at the next partition boundary; queued-but-
        unstarted work on the shared pool is cancelled too."""
        qctx = self._qctx
        if qctx is not None:
            qctx.cancel()
        else:
            self.stats.cancel()


_UNSET = object()


class ServingRuntime:
    def __init__(self, max_concurrent_queries: Optional[int] = None,
                 queue_depth: Optional[int] = None,
                 admission_timeout_s=_UNSET):
        cfg = get_context().execution_config
        slots = (max_concurrent_queries if max_concurrent_queries is not None
                 else cfg.max_concurrent_queries)
        depth = (queue_depth if queue_depth is not None
                 else cfg.admission_queue_depth)
        timeout = (cfg.admission_timeout_s if admission_timeout_s is _UNSET
                   else admission_timeout_s)
        self.admission = AdmissionController(slots, depth, timeout)
        self.pool = SharedExecutorPool(resolve_executor_threads(cfg))
        self._qseq = itertools.count(1)
        self._threads: List[threading.Thread] = []
        self._threads_lock = threading.Lock()
        # query_id -> live handle (weak: a dropped handle's query still
        # finishes, but the runtime never pins results)
        self._handles: "weakref.WeakValueDictionary[str, QueryHandle]" = (
            weakref.WeakValueDictionary())
        self._closed = False
        from ..obs.health import register_admission

        register_admission(self.admission)
        with _runtimes_lock:
            _RUNTIMES.add(self)

    # ---------------------------------------------------------------- submit
    def submit(self, df, timeout_s: Optional[float] = None,
               admission_timeout_s: Optional[float] = None) -> QueryHandle:
        """Submit a DataFrame's plan. Raises DaftOverloadedError HERE when
        the bounded admission queue is already full (deterministic shed at
        the door); queue-timeout sheds surface on the handle.

        ``timeout_s`` is this query's execution deadline (overrides
        ``cfg.execution_timeout_s``); ``admission_timeout_s`` overrides the
        queue-wait limit."""
        from ..execution import RuntimeStats

        if self._closed:
            raise DaftOverloadedError("serving runtime is shut down")
        stats = RuntimeStats()
        query_id = f"serve-q{next(self._qseq)}"
        handle = QueryHandle(query_id, stats)
        submitted_at = time.monotonic()
        try:
            ticket = self.admission.enqueue(query_id)
        except DaftOverloadedError as e:
            self._record_shed(handle, e, submitted_at)
            raise
        t = threading.Thread(
            target=self._run_query,
            args=(handle, ticket, df._plan, timeout_s, admission_timeout_s,
                  submitted_at),
            name=f"daft-serve-{query_id}", daemon=True)
        with self._threads_lock:
            self._threads = [x for x in self._threads if x.is_alive()]
            self._threads.append(t)
            self._handles[query_id] = handle
        t.start()
        return handle

    def _run_query(self, handle: QueryHandle, ticket, plan,
                   timeout_s: Optional[float],
                   admission_timeout_s: Optional[float],
                   submitted_at: float) -> None:
        try:
            self.admission.await_slot(ticket, admission_timeout_s)
        except DaftOverloadedError as e:
            logger.warning("query_shed", query=handle.query_id,
                           error=str(e))
            self._record_shed(handle, e, submitted_at)
            handle._set_exception(e)
            return
        handle._admitted.set()
        ctx = get_context()
        cfg = ctx.execution_config
        qctx = QueryContext.build(
            cfg, stats=handle.stats, query_id=handle.query_id,
            timeout_s=timeout_s, shared_pool=self.pool,
            memory_budget_bytes=self._memory_share(cfg))
        handle._qctx = qctx
        try:
            from ..dataframe import from_partitions
            from ..profile import arm_for_query

            arm_for_query(handle.stats, handle.query_id)

            pset = ctx.runner().run(plan, stats=handle.stats, qctx=qctx)
            out = from_partitions(pset.partitions, pset.schema)
            # the handle's stats carry the QueryRecord; hand them to the
            # result DataFrame so df.last_query_record() works there too
            out.stats = handle.stats
            handle._set_result(out)
        except BaseException as e:
            handle._set_exception(e)
        finally:
            self.admission.release(ticket)
            # a failed/cancelled query may leave queued work behind
            self.pool.cancel_queued(handle.query_id)

    def _memory_share(self, cfg) -> Optional[int]:
        """Each admitted query's MemoryLedger share: the global budget
        split across the execution slots, so all concurrently-admissible
        queries together can never exceed it."""
        if cfg.memory_budget_bytes is None:
            return None
        return max(1, cfg.memory_budget_bytes // self.admission.slots)

    def _record_shed(self, handle: QueryHandle, error: BaseException,
                     submitted_at: float) -> None:
        """Shed queries get a flight-recorder record too (outcome "shed");
        observability must never fail the shed path."""
        cfg = get_context().execution_config
        try:
            from ..obs.querylog import QUERY_LOG, build_record

            wall_ns = int((time.monotonic() - submitted_at) * 1e9)
            rec = build_record(handle.query_id, "unplanned", {}, cfg,
                               handle.stats, wall_ns, "shed", error=error)
            if getattr(cfg, "enable_query_log", True):
                QUERY_LOG.resize(cfg.query_log_depth)
                QUERY_LOG.append(rec)
                handle.stats.last_record = rec
        except Exception as e:
            logger.error("shed_record_failed", error=repr(e))

    # -------------------------------------------------------------- shutdown
    def shutdown(self, timeout_s: float = 30.0) -> dict:
        """Drain-mode shutdown: stop admitting (queued + new queries shed
        with DaftOverloadedError), let in-flight queries finish within the
        timeout, cancel and report stragglers, then stop the shared pool.
        Idempotent."""
        t0 = time.monotonic()
        self._closed = True
        self.admission.begin_drain()
        stragglers = self.admission.wait_drained(timeout_s)
        if stragglers:
            logger.warning("drain_stragglers", queries=stragglers)
            for qid in stragglers:
                # cancellation reaches each straggler's next partition
                # boundary; its queued-but-unstarted pool work dies now
                h = self._handles.get(qid)
                if h is not None:
                    h.cancel()
                else:
                    self.pool.cancel_queued(qid)
        remaining = max(0.0, timeout_s - (time.monotonic() - t0))
        # joining with wait=True would hang on a wedged straggler; bounded
        # join then daemon threads die with the process
        self.pool.shutdown(wait=not stragglers)
        for t in self._live_threads():
            t.join(timeout=max(0.05, remaining / max(
                1, len(self._live_threads()))))
        report = {
            "drained": not stragglers,
            "stragglers": stragglers,
            "waited_s": round(time.monotonic() - t0, 3),
            "shed_total": self.admission.shed_total,
            "admitted_total": self.admission.admitted_total,
        }
        logger.info("serving_shutdown", **{k: v for k, v in report.items()
                                           if k != "stragglers"})
        return report

    def _live_threads(self) -> List[threading.Thread]:
        with self._threads_lock:
            return [t for t in self._threads if t.is_alive()]


# ---------------------------------------------------------------------------
# engine-wide shutdown + leak accounting
# ---------------------------------------------------------------------------

def leaked_thread_count() -> int:
    """Engine-owned threads (daft-serve/exec/actor/spill prefixes) still
    alive — 0 after a clean ``shutdown()``. The serving leak test's
    assertion surface; actor-pool join leaks are also counted by
    ``actor_pool.leaked_thread_count`` with their own warning."""
    me = threading.current_thread()
    return sum(
        1 for t in threading.enumerate()
        if t is not me and t.is_alive()
        and t.name.startswith(_ENGINE_THREAD_PREFIXES))


def shutdown(timeout_s: float = 10.0) -> dict:
    """Graceful engine shutdown: drain every live ServingRuntime, stop the
    actor pools, then wait (bounded) for engine threads to exit. Returns a
    report with any stragglers and the final leaked-thread count.
    Registered atexit with a short timeout; safe to call repeatedly."""
    import gc

    t0 = time.monotonic()
    with _runtimes_lock:
        runtimes = list(_RUNTIMES)
    stragglers: List[str] = []
    for rt in runtimes:
        try:
            rep = rt.shutdown(timeout_s=max(
                0.1, timeout_s - (time.monotonic() - t0)))
            stragglers.extend(rep["stragglers"])
        except Exception as e:
            logger.error("runtime_shutdown_failed", error=repr(e))
    from ..actor_pool import shutdown_all

    shutdown_all()
    try:
        from ..dist.supervisor import shutdown_worker_pool

        # distributed worker PROCESSES die here too: zero leaked workers
        # after dt.shutdown() is part of the kill-a-worker acceptance
        shutdown_worker_pool(timeout_s=max(
            0.5, timeout_s - (time.monotonic() - t0)))
    except Exception as e:
        logger.error("worker_pool_shutdown_failed", error=repr(e))
    # private per-query pools are released by GC (their worker threads exit
    # via the executor's weakref wakeup); collect so the wait below sees it
    gc.collect()
    deadline = t0 + timeout_s
    while leaked_thread_count() and time.monotonic() < deadline:
        time.sleep(0.02)
    report = {
        "stragglers": stragglers,
        "leaked_threads": leaked_thread_count(),
        "waited_s": round(time.monotonic() - t0, 3),
    }
    logger.info("engine_shutdown", **{k: v for k, v in report.items()
                                      if k != "stragglers"})
    return report


def _atexit_shutdown() -> None:
    # bounded: a wedged straggler must not hang interpreter exit; daemon
    # threads die with the process anyway
    with _runtimes_lock:
        live = bool(_RUNTIMES)
    if live:
        shutdown(timeout_s=2.0)
        return
    try:
        import sys

        dist_mod = sys.modules.get("daft_tpu.dist.supervisor")
        if dist_mod is not None:
            # worker PROCESSES are not daemon threads: they must be told
            # to exit even when no serving runtime ever existed
            dist_mod.shutdown_worker_pool(timeout_s=2.0)
    except Exception:
        pass


atexit.register(_atexit_shutdown)
