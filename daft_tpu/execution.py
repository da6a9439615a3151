"""Execution driver: pull-based streaming over the physical operator tree.

Role-equivalent to the reference's src/daft-local-execution/src/run.rs:117
(streaming pipeline executor) + daft/execution/physical_plan.py (the
partition-task generator chain). Each PhysicalOp.execute is a generator;
composing them yields a fully streaming pipeline with early-stop (limit) and
bounded buffering at pipeline breakers.

The ExecutionContext also owns the device-kernel routing decision, once for
every shape: an operator declares a physical.DeviceStep, and
ExecutionContext.launch / run send its partitions through the jit'd XLA
kernels when eligible, its host (pyarrow) kernel otherwise — the TPU analog
of the reference's fused pipeline_instruction execution.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, Iterator, List, Optional

from .context import ExecutionConfig
from .micropartition import MicroPartition
from .physical import PhysicalOp
from .profile import timeline


class QueryCancelledError(RuntimeError):
    """Raised inside a running query after RuntimeStats.cancel()."""


class ResourceRequest:
    """What one task needs while it runs (reference: ResourceRequest,
    src/common/resource-request — num_cpus/num_gpus/memory)."""

    __slots__ = ("num_cpus", "num_gpus", "memory_bytes")

    def __init__(self, num_cpus: float = 0.0, num_gpus: float = 0.0,
                 memory_bytes: int = 0):
        self.num_cpus = num_cpus or 0.0
        self.num_gpus = num_gpus or 0.0
        self.memory_bytes = memory_bytes or 0

    def __bool__(self) -> bool:
        return bool(self.num_cpus or self.num_gpus or self.memory_bytes)

    def __repr__(self):
        return (f"ResourceRequest(cpus={self.num_cpus}, gpus={self.num_gpus}, "
                f"memory={self.memory_bytes})")


def op_resource_request(op) -> ResourceRequest:
    """Sum the resource requests of every UDF an op evaluates (multiple UDFs
    in one projection all run for the same task)."""
    from .expressions import PyUdf

    cpus = gpus = mem = 0

    def walk(node):
        nonlocal cpus, gpus, mem
        if isinstance(node, PyUdf) and node.resource_request:
            c, g, m = node.resource_request
            cpus += c or 0
            gpus += g or 0
            mem += m or 0
        for ch in node.children():
            walk(ch)

    for e in op._map_exprs():
        walk(e._node)
    return ResourceRequest(cpus, gpus, mem)


class ResourceAccountant:
    """Admission control for in-flight tasks (reference: the PyRunner
    admission loop, daft/runners/pyrunner.py:352-370): a task dispatches only
    when its declared cpus/accelerators/memory fit the remaining capacity; an
    impossible request fails fast instead of deadlocking."""

    def __init__(self, cpus: float, gpus, memory_bytes: Optional[int]):
        """gpus may be a float or a zero-arg callable resolved on FIRST use —
        counting accelerators initializes the jax backend, which host-only
        queries must never pay for (and which, in a process that is not
        meant to hold the chip, would take it)."""
        self.total_cpus = cpus
        self._gpu_src = gpus
        self._gpus_resolved: Optional[float] = (
            float(gpus) if not callable(gpus) else None)
        self.total_memory = memory_bytes
        self._cpus = cpus
        self._gpus_used = 0.0
        self._memory = memory_bytes
        self._cond = threading.Condition()

    @property
    def total_gpus(self) -> float:
        if self._gpus_resolved is None:
            self._gpus_resolved = float(self._gpu_src())
        return self._gpus_resolved

    def check(self, req: ResourceRequest) -> None:
        """Raise if the request can NEVER be admitted on this host."""
        from .errors import DaftResourceError

        if req.num_cpus > self.total_cpus:
            raise DaftResourceError(
                f"task requests {req.num_cpus} CPUs but only "
                f"{self.total_cpus} exist")
        if req.num_gpus and req.num_gpus > self.total_gpus:
            raise DaftResourceError(
                f"task requests {req.num_gpus} accelerator(s) but only "
                f"{self.total_gpus} exist")
        if self.total_memory is not None and req.memory_bytes > self.total_memory:
            raise DaftResourceError(
                f"task requests {req.memory_bytes} bytes but the memory "
                f"budget is {self.total_memory}")

    def _fits(self, req: ResourceRequest) -> bool:
        gpu_ok = (not req.num_gpus
                  or req.num_gpus <= self.total_gpus - self._gpus_used + 1e-9)
        return (req.num_cpus <= self._cpus + 1e-9 and gpu_ok
                and (self._memory is None or req.memory_bytes <= self._memory))

    def admit(self, req: ResourceRequest) -> None:
        """Block until the request fits, then reserve it."""
        self.check(req)
        with self._cond:
            while not self._fits(req):
                self._cond.wait()
            self._cpus -= req.num_cpus
            self._gpus_used += req.num_gpus
            if self._memory is not None:
                self._memory -= req.memory_bytes

    def release(self, req: ResourceRequest) -> None:
        with self._cond:
            self._cpus += req.num_cpus
            self._gpus_used -= req.num_gpus
            if self._memory is not None:
                self._memory += req.memory_bytes
            self._cond.notify_all()


def _accelerator_count() -> int:
    """Non-CPU jax devices on this host (0 on a CPU-only test mesh). A
    backend that fails to initialize also counts 0 — the admission check
    then refuses accelerator requests — but says why."""
    try:
        import jax

        return sum(1 for d in jax.devices() if d.platform != "cpu")
    except Exception as e:
        from .obs.log import get_logger

        get_logger("device").warning(
            "accelerator_count_failed", error=f"{type(e).__name__}: {e}")
        return 0


class RuntimeStats:
    """Per-query counters + the cancellation handle (reference: runtime stats
    in daft-local-execution, and driver-side stop_plan/MaterializedResult
    .cancel() — ray_runner.py:489-502, partitioning.py:192)."""

    def __init__(self):
        from .profile.spans import DISARMED

        self._lock = threading.Lock()
        self.counters: Dict[str, int] = {}
        self.op_rows: Dict[str, int] = {}
        self.op_wall_ns: Dict[str, int] = {}
        self.op_bytes: Dict[str, int] = {}
        self._cancelled = threading.Event()
        # the per-query span/event recorder (profile/spans.py). DISARMED is
        # the shared no-op profiler — collect(profile=...) or an armed
        # chrome trace swaps in a live one before execution starts
        self.profiler = DISARMED
        # the QueryRecord of this handle's most recent plan execution
        # (set by execute_plan's completion hook; df.last_query_record())
        self.last_record = None
        # FDO site observations (daft_tpu/adapt/): canonical subtree
        # fingerprint -> [rows, bytes] accumulated by tagged exchanges/
        # joins, folded into the process history at query end
        self.fdo_obs: Dict[str, list] = {}
        # the first "site: Type: message" a device-path attempt raised in
        # this query (note_device_error) — the host path answered, so this
        # is the only place the cause survives
        self.device_error: Optional[str] = None
        self._device_errors_logged: set = set()

    def cancel(self) -> None:
        """Stop the query this handle is attached to at the next partition
        boundary (safe from any thread)."""
        self._cancelled.set()

    def reset_cancel(self) -> None:
        """Re-arm the handle for a fresh run (a cancelled query's DataFrame
        stays usable: retrying clears the previous cancellation)."""
        self._cancelled.clear()

    def is_cancelled(self) -> bool:
        return self._cancelled.is_set()

    def bump(self, key: str, n: int = 1) -> None:
        # counter updates are read-modify-write and arrive concurrently from
        # pool workers, the async spill writer, and prefetch threads — the
        # lock is load-bearing (tests/test_profile.py hammers this)
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n

    def bump_many(self, adds: Dict[str, int]) -> None:
        """Several counters under one lock (a device frame's flush)."""
        with self._lock:
            counters = self.counters
            for key, n in adds.items():
                counters[key] = counters.get(key, 0) + n

    # what a device attempt records inside an operator, by layer
    # (profile/timeline.py); xla_compiles rides along so every finished
    # query reads it, 0 included
    _DEVICE_LAYER_NS = ("stage_ns", "device_dispatch_ns", "device_wait_ns",
                        "gather_ns")

    def fold_op_self_host(self) -> None:
        """Query end: ``op_self_host_ns`` is the operators' self time less what
        their device attempts recorded inside them — the host-operator
        work proper. Set, not added: an AQE query folds once per stage over
        shared stats, and the last fold covers them all. The layer
        counters it reads default to 0, so a finished query always reads
        them (a stage-cache hit records nothing, which is 0, not absent)."""
        with self._lock:
            c = self.counters
            c.setdefault("xla_compiles", 0)
            inside = sum(c.setdefault(k, 0) for k in self._DEVICE_LAYER_NS)
            c["op_self_host_ns"] = max(
                0, sum(self.op_wall_ns.values()) - inside)

    def bump_max(self, key: str, n: int) -> None:
        """Monotonic-max counter (channel high-water marks and the like):
        the stored value only ever ratchets up to ``n``."""
        with self._lock:
            if n > self.counters.get(key, 0):
                self.counters[key] = n

    def note_device_error(self, site: str, exc: BaseException) -> None:
        """A device-path attempt raised and the host path is about to
        answer in its place: count it (``device_attempt_errors``), keep the
        first ``site: Type: message`` for explain_analyze / the QueryRecord,
        and log one structured line per distinct error of this query."""
        msg = f"{type(exc).__name__}: {exc}"
        with self._lock:
            self.counters["device_attempt_errors"] = (
                self.counters.get("device_attempt_errors", 0) + 1)
            if self.device_error is None:
                self.device_error = f"{site}: {msg}"[:400]
            first = (site, msg) not in self._device_errors_logged
            if first:
                self._device_errors_logged.add((site, msg))
        if first:
            from .obs.log import get_logger

            get_logger("device").warning("device_attempt_error", site=site,
                                         error=msg[:2000])

    def fdo_observe(self, site_fp: str, rows: int, nbytes: int) -> None:
        """Accumulate one FDO site observation (what actually flowed
        through a tagged plan subtree this query)."""
        with self._lock:
            cur = self.fdo_obs.get(site_fp)
            if cur is None:
                self.fdo_obs[site_fp] = [rows, nbytes]
            else:
                cur[0] += rows
                cur[1] += nbytes

    def take_fdo_obs(self) -> Dict[str, tuple]:
        """Drain the accumulated observations (history fold consumes them
        exactly once per execution)."""
        with self._lock:
            out = {k: (v[0], v[1]) for k, v in self.fdo_obs.items()}
            self.fdo_obs.clear()
        return out

    def io_wait(self, ns: int) -> None:
        """Record consumer-thread blocked IO time: the counter AND the
        io_wait phase of the innermost open profiler span, so per-op
        io_wait in a QueryProfile reconciles with the io_wait_ns total."""
        self.bump("io_wait_ns", ns)
        p = self.profiler
        if p.armed:
            p.phase("io_wait", ns)

    def dispatch_wait(self, ns: int) -> None:
        """Head-of-line blocked time in the dispatch loop (queue_wait phase
        on the pulling op's span)."""
        self.bump("dispatch_wait_ns", ns)
        p = self.profiler
        if p.armed:
            p.phase("queue_wait", ns)

    def record_op(self, name: str, rows: int, wall_ns: int,
                  bytes_out: int = 0) -> None:
        with self._lock:
            self.op_rows[name] = self.op_rows.get(name, 0) + rows
            self.op_wall_ns[name] = self.op_wall_ns.get(name, 0) + wall_ns
            if bytes_out:
                self.op_bytes[name] = self.op_bytes.get(name, 0) + bytes_out

    def io_wait_share(self) -> float:
        """Fraction of accumulated operator wall time the execution threads
        spent BLOCKED on IO (scan-prefetch waits and sync scan reads, spill
        read-backs on the consumer thread, sync spill writes, writer-queue
        backpressure). Background prefetch/readahead reads that overlapped
        compute are excluded — this is the residual serialization the
        pipelined-IO layer exists to shrink."""
        with self._lock:
            wait = self.counters.get("io_wait_ns", 0)
            total = sum(self.op_wall_ns.values())
        if wait <= 0:
            return 0.0
        return min(1.0, wait / max(total, wait))

    def io_breakdown(self) -> Dict[str, float]:
        """The io_wait-vs-compute split plus prefetch hit/miss and spill
        write/read throughput — the explain_analyze / bench view
        of the pipelined IO layer."""
        with self._lock:
            c = dict(self.counters)

        def mbps(b, ns):
            return (b / 2**20) / (ns / 1e9) if ns > 0 else 0.0

        return {
            "io_wait_share": round(self.io_wait_share(), 4),
            "io_wait_ms": round(c.get("io_wait_ns", 0) / 1e6, 1),
            "prefetch_hits": c.get("prefetch_hits", 0),
            "prefetch_misses": c.get("prefetch_misses", 0),
            "prefetch_throttled": c.get("prefetch_throttled", 0),
            "unspill_readahead_hits": c.get("unspill_readahead_hits", 0),
            "spill_write_mbps": round(
                mbps(c.get("spill_write_bytes", 0),
                     c.get("spill_write_ns", 0)), 1),
            "spill_read_mbps": round(
                mbps(c.get("spill_read_bytes", 0),
                     c.get("spill_read_ns", 0)), 1),
        }

    def op_throughput(self) -> Dict[str, Dict[str, float]]:
        """Per-operator rows/sec and bytes/sec over accumulated wall time —
        the explain_analyze / bench throughput view."""
        with self._lock:
            out: Dict[str, Dict[str, float]] = {}
            for name, ns in self.op_wall_ns.items():
                secs = ns / 1e9
                if secs <= 0:
                    continue
                out[name] = {
                    "rows_per_sec": self.op_rows.get(name, 0) / secs,
                    "bytes_per_sec": self.op_bytes.get(name, 0) / secs,
                }
            return out

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "counters": dict(self.counters),
                "op_rows": dict(self.op_rows),
                "op_wall_ns": dict(self.op_wall_ns),
                "op_bytes": dict(self.op_bytes),
            }


class DeviceHealth:
    """Circuit breaker for one accelerator resource (device kernels, mesh
    collectives). Closed = normal; after `threshold` CONSECUTIVE failures it
    opens and allow() answers False — callers route straight to the host
    path instead of re-paying the failure per partition. After
    `cooldown_s` the breaker goes half-open and
    lets exactly ONE probe attempt through: success re-closes it, failure
    re-opens it for another cooldown.

    Counter names are prefixed by `kind` ("device" → device_breaker_trips,
    device_breaker_probes, device_breaker_recoveries, ...)."""

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 kind: str = "device"):
        self.threshold = max(1, int(threshold))
        self.cooldown_s = float(cooldown_s)
        self.kind = kind
        self._lock = threading.Lock()
        self._state = self.CLOSED
        self._consecutive = 0
        self._opened_at = 0.0
        self._probe_inflight = False
        self._probe_started = 0.0

    @property
    def state(self) -> str:
        with self._lock:
            return self._state

    def allow(self, stats: Optional[RuntimeStats] = None) -> bool:
        """May an attempt use the resource right now? Open → False; open
        past the cooldown → half-open, admitting one probe."""
        with self._lock:
            if self._state == self.CLOSED:
                return True
            now = time.monotonic()
            if (self._state == self.OPEN
                    and now - self._opened_at >= self.cooldown_s):
                self._state = self.HALF_OPEN
            if self._state == self.HALF_OPEN and (
                    not self._probe_inflight
                    # a probe whose resolver was abandoned (limit early-stop
                    # closed the stream before the deferred result resolved)
                    # must not wedge the breaker open forever: reclaim the
                    # slot after one cooldown and let a new probe through
                    or now - self._probe_started >= self.cooldown_s):
                self._probe_inflight = True
                self._probe_started = now
                if stats is not None:
                    stats.bump(f"{self.kind}_breaker_probes")
                    self._emit(stats, "probe")
                return True
            return False

    def _emit(self, stats: Optional["RuntimeStats"], transition: str) -> None:
        """Breaker state transitions are typed events on the profile
        timeline (kind `breaker`) AND structured log lines, so both a trace
        and the always-on flight recorder show exactly when the device path
        opened/recovered relative to the pipeline."""
        from .obs.log import get_logger

        get_logger("breaker").info(f"breaker_{transition}", breaker=self.kind,
                                   state=self._state)
        if stats is not None and stats.profiler.armed:
            stats.profiler.event("breaker", kind=self.kind,
                                 transition=transition, state=self._state)

    def record_success(self, stats: Optional[RuntimeStats] = None) -> None:
        with self._lock:
            self._consecutive = 0
            if self._state == self.HALF_OPEN:
                # only the probe path re-closes the breaker: a straggler
                # async success that launched BEFORE the trip must not close
                # an OPEN breaker and route new work back to a dead device
                self._state = self.CLOSED
                self._probe_inflight = False
                if stats is not None:
                    stats.bump(f"{self.kind}_breaker_recoveries")
                    self._emit(stats, "recovery")

    def record_failure(self, stats: Optional[RuntimeStats] = None) -> None:
        with self._lock:
            self._consecutive += 1
            if self._state == self.HALF_OPEN:
                # probe failed: straight back to open for another cooldown
                self._state = self.OPEN
                self._opened_at = time.monotonic()
                self._probe_inflight = False
                if stats is not None:
                    stats.bump(f"{self.kind}_breaker_reopens")
                    self._emit(stats, "reopen")
            elif (self._state == self.CLOSED
                    and self._consecutive >= self.threshold):
                self._state = self.OPEN
                self._opened_at = time.monotonic()
                if stats is not None:
                    stats.bump(f"{self.kind}_breaker_trips")
                    self._emit(stats, "trip")

    def release_probe(self) -> None:
        """An admitted attempt DECLINED (no failure, no success — e.g. the
        kernel layer judged the data ineligible): free the probe slot so the
        half-open breaker isn't wedged waiting on a result that never comes."""
        with self._lock:
            self._probe_inflight = False


class ExecutionContext:
    def __init__(self, cfg: ExecutionConfig, stats: Optional[RuntimeStats] = None,
                 deadline: Optional[float] = None,
                 device_health: Optional[DeviceHealth] = None,
                 qctx=None):
        self.cfg = cfg
        # the per-query mutable state — stats, deadline, breakers, ledger
        # share, cancellation — lives on a QueryContext (serve/qcontext.py).
        # Runners/the serving runtime build one per query so AQE stages
        # share a single time budget, breaker, and memory share; a context
        # built directly (tests) assembles an implicit solo one from the
        # legacy keyword arguments.
        if qctx is None:
            from .serve.qcontext import QueryContext

            qctx = QueryContext.build(cfg, stats=stats, deadline=deadline,
                                      device_health=device_health)
        self.qctx = qctx
        self.stats = qctx.stats
        self.deadline = qctx.deadline
        self.device_health = qctx.device_health
        # this query's MemoryLedger (a child share of the process root
        # under the serving runtime) and byte budget: every buffer,
        # prefetcher, and the accountant charge/read THESE, never the
        # process-global account
        self.ledger = qctx.ledger
        self.memory_budget = qctx.memory_budget_bytes
        self._pool = None
        # dispatch backend for map-class partition tasks (scheduler.
        # DispatchBackend): None = the in-process pool; the
        # DistributedRunner attaches the supervised WorkerPool here so
        # eligible tasks execute in worker processes
        self.dist_backend = None
        # live-progress tracker (obs/cluster.QueryProgress), set by
        # execute_plan for the execution's lifetime; None for direct op
        # execution in tests — every hook guards on it
        self.progress = None
        # terminal once the query's stream closed: unspill readahead stops
        # submitting (its buffers are settled by finish_query anyway); the
        # scan prefetcher MAY still recreate the pool for late reads — see
        # pool() below
        self._pool_finished = False
        self._spill_scope = None
        self._lineage = None
        self._buffers: List = []
        self._accountant: Optional[ResourceAccountant] = None
        # live streaming segments (stream/pipeline.py): each registers its
        # shutdown so query teardown can close the stream tree even when
        # the pipeline generator is unreachable by close() — an op ABOVE
        # the segment raising leaves the pipeline suspended at a yield,
        # and the exception traceback keeps its frame (and its parked
        # producers) alive until the exception object dies
        self._active_streams: dict = {}
        # shuffle ids whose pieces live on PEER workers (dist/peerplane.py):
        # finish_query tells the pool to drop them fleet-wide — by then
        # every root output has been forced local (see rooted())
        self._peer_shuffles: set = set()

    def register_peer_shuffle(self, sid: int) -> None:
        """Record a peer-hosted shuffle for drop at query finish."""
        self._peer_shuffles.add(sid)

    def check_deadline(self) -> None:
        """Cooperative deadline check (morsel loop, pipeline breakers):
        raises DaftTimeoutError carrying the partial stats accumulated so
        far when execution_timeout_s has been exceeded. Doubles as the
        barrier where async-spill writer-internal errors surface on the
        query thread instead of dying with the writer."""
        if self._spill_scope is not None:
            self._spill_scope.raise_async_errors()
        if self.deadline is not None and time.monotonic() > self.deadline:
            from .errors import DaftTimeoutError
            from .obs.log import get_logger

            limit = (self.qctx.timeout_s if self.qctx.timeout_s is not None
                     else self.cfg.execution_timeout_s)
            self.stats.bump("deadline_expired")
            get_logger("scheduler").warning(
                "deadline_expired", timeout_s=limit)
            raise DaftTimeoutError(
                f"query exceeded execution_timeout_s={limit}",
                stats=self.stats.snapshot())

    @property
    def spill_scope(self):
        """Per-query spill directory (lazily created; removed at query end)."""
        if self._spill_scope is None:
            from .spill import SpillScope

            self._spill_scope = SpillScope()
        return self._spill_scope

    @property
    def lineage(self):
        """This query's bounded LineageLog (integrity/lineage.py), or None
        when lineage recomputation is off. Spilled partitions record how
        they were produced here so a corrupted/missing spill artifact
        recomputes instead of failing the query."""
        if not getattr(self.cfg, "lineage_recomputation", True):
            return None
        if self._lineage is None:
            from .integrity.lineage import LineageLog

            self._lineage = LineageLog(
                getattr(self.cfg, "lineage_log_depth", 4096))
        return self._lineage

    def partition_buffer(self):
        """A spillable PartitionBuffer bound to this query's budget, stats,
        and spill directory. Tracked so abandoned queries (limit early-stop,
        cancellation, errors) still return their held bytes to the ledger."""
        # pipeline breakers are the other cooperative deadline checkpoint
        # (besides the morsel loop): a breaker about to buffer its whole
        # input first proves the query still has time budget
        self.check_deadline()
        from .spill import PartitionBuffer

        buf = PartitionBuffer(
            self.memory_budget, self.stats,
            scope=self.spill_scope,
            async_spill=self.cfg.async_spill_writes,
            readahead=(self._bg_submit if self.cfg.unspill_readahead
                       else None),
            ledger=self.ledger,
            integrity=getattr(self.cfg, "partition_integrity", True),
            lineage=self.lineage)
        self._buffers.append(buf)
        return buf

    def _bg_submit(self, fn):
        """Submit background IO (unspill readahead) onto the shared worker
        pool; raises RuntimeError after shutdown (callers degrade to
        synchronous reads)."""
        if self._pool_finished:
            raise RuntimeError("worker pool already shut down")
        return self.pool().submit(fn)

    @property
    def accountant(self) -> ResourceAccountant:
        """Per-query admission control, sized from host cores, accelerator
        count, and the configured memory budget."""
        if self._accountant is None:
            import os as _os

            try:
                cores = len(_os.sched_getaffinity(0))
            except AttributeError:
                cores = _os.cpu_count() or 1
            self._accountant = ResourceAccountant(
                cpus=float(max(cores, self.num_workers)),
                gpus=_accelerator_count,  # resolved only if a task asks
                memory_bytes=self.memory_budget)
        return self._accountant

    def register_stream(self, shutdown) -> object:
        """Track a running streaming segment's shutdown for teardown;
        returns a token for :meth:`unregister_stream`."""
        token = object()
        self._active_streams[token] = shutdown
        return token

    def unregister_stream(self, token) -> None:
        self._active_streams.pop(token, None)

    def close_streams(self, short_circuit: bool) -> None:
        """Shut down every still-registered streaming segment (idempotent
        per segment). ``short_circuit`` says whether abandoned work counts
        as ``morsels_short_circuited`` (deliberate early stop) or not
        (error/cancel/deadline teardown — a failed query's record must not
        read as if a limit fired)."""
        while self._active_streams:
            _, shutdown = self._active_streams.popitem()
            try:
                shutdown(short_circuit=short_circuit)
            except BaseException as e:
                from .obs.log import get_logger

                get_logger("execution").warning(
                    "stream_shutdown_failed", error=repr(e))

    def finish_query(self) -> None:
        """Release buffer accounting and delete this query's spill files."""
        for b in self._buffers:
            b.release()
        self._buffers.clear()
        if self._spill_scope is not None:
            self._spill_scope.cleanup()
            self._spill_scope = None
        if self._peer_shuffles:
            sids, self._peer_shuffles = list(self._peer_shuffles), set()
            backend = self.dist_backend
            drop = getattr(backend, "drop_shuffles", None)
            if drop is not None:
                try:
                    drop(sids)
                except Exception:
                    pass  # pool mid-teardown: workers clear on exit anyway

    @property
    def num_workers(self) -> int:
        from .context import resolve_executor_threads

        n = resolve_executor_threads(self.cfg)
        if self.dist_backend is not None:
            # a remote-dispatched task occupies a LOCAL pool thread for the
            # round trip, so the local pool must cover the whole worker
            # fleet (plus one driver-side slot) or the cluster idles
            n = max(n, self.dist_backend.capacity() + 1)
        return n

    def pool(self):
        """Lazily-created worker pool; shut down by execute_plan. Under the
        serving runtime this is a per-query CLIENT of the shared
        SharedExecutorPool (fair FIFO across admitted queries) instead of a
        private executor. A post-shutdown call (scan-prefetch serving late
        reads, e.g. to_pydict over an unforced collect) recreates a private
        pool; the recreated pool is released by GC when the last partition
        referencing the prefetcher loads or dies."""
        if self._pool is None:
            shared = self.qctx.shared_pool
            if shared is not None and not self._pool_finished:
                self._pool = shared.client(
                    self.qctx.query_id or f"ctx-{id(self):x}")
            else:
                from concurrent.futures import ThreadPoolExecutor

                self._pool = ThreadPoolExecutor(
                    max_workers=self.num_workers,
                    thread_name_prefix="daft-exec")
        return self._pool

    def shutdown_pool(self) -> None:
        self._pool_finished = True
        if self._pool is not None:
            # a shared-pool client interprets this as close(): the SHARED
            # executor outlives the query; only its queue is torn down
            self._pool.shutdown(wait=False)
            self._pool = None

    def _device_allowed(self) -> bool:
        """Breaker gate for work that IS device-eligible: an open breaker
        sends it to the host path and counts the degraded completion."""
        if self.device_health.allow(self.stats):
            return True
        self.stats.bump("degraded_completions")
        return False

    def _device_eligible(self, part: MicroPartition) -> bool:
        if not self.device_path_on():
            return False
        n = part.num_rows_or_none()
        if n is None and not self.foreign_owned(part):
            # a scan partition with a pushed-down filter has no count until
            # it is read — which the device and the host path both do next.
            # Treating "unknown" as 0 sent every such scan to the host.
            n = len(part.table())
        return (n or 0) >= self.cfg.device_min_rows and self._device_allowed()

    def _device_attempt(self, fn, launch: bool = False):
        """Run one device-path attempt under the fault registry + breaker.
        An exception is reported (RuntimeStats.note_device_error), records
        a breaker failure and returns None (the device layer's decline
        convention); a None result is a decline (probe slot released,
        breaker untouched). A non-None result records success —
        unless `launch` is set, in which case the caller owns the outcome
        (a step's launch succeeding says nothing about the computation it
        started, whose finisher records for real)."""
        from . import faults
        from .kernels.compile_cache import configure_compile_cache

        configure_compile_cache()
        # device_dispatch_ns: this attempt's wall less the staging (and, for
        # the whole-in-one sort and distinct attempts, the waits and copies)
        # recorded inside it
        with timeline.DeviceFrame(self.stats, "dispatch",
                                  "device_dispatch_ns"):
            try:
                faults.check("device.kernel", self.stats)
                out = fn()
            except Exception as e:
                self._device_failed("device.attempt", e)
                return None
        if out is None:
            self.device_health.release_probe()
        elif not launch:
            self.device_health.record_success(self.stats)
        return out

    def _device_resolve(self, resolve):
        """Run a launched attempt's resolver: waiting for the device
        (``device_wait_ns``, recorded by ``kernels.device.fetch``) apart
        from copying back and assembling (``gather_ns``, the rest of this
        wall). Exceptions pass through to the caller's fallback."""
        with timeline.DeviceFrame(self.stats, "gather", "gather_ns"):
            return resolve()

    def _device_failed(self, site: str, exc: BaseException) -> None:
        """A device attempt (launch or resolve) raised: report the
        exception and inform the breaker. The caller falls back to the host
        path — the answer stays right, the cause stays visible."""
        self.stats.note_device_error(site, exc)
        self.device_health.record_failure(self.stats)

    def foreign_owned(self, part: MicroPartition) -> bool:
        """True when this process must not materialize `part` (another host
        of a multi-process run owns its rows). Single-process: never."""
        return False

    # ------------------------------------------------------------------
    # the device path: ONE launch/resolve contract for every DeviceStep
    # (physical.DeviceStep: projection, filter, fused map, aggregate,
    # sketch build, resident segment, join probe). A step says what differs
    # between shapes; everything below is the policy, written once.
    # ------------------------------------------------------------------

    def device_path_on(self) -> bool:
        """Is the device path switched on? The operators' one question:
        no execution-time code outside this class reads the knob."""
        return self.cfg.use_device_kernels

    def _collective_answer(self, step, parts) -> Optional[MicroPartition]:
        """Hook for runners with a device mesh: a step answered by one
        collective across the mesh, asked before the per-partition device
        path. Single-host base context: never."""
        return None

    def launch(self, step, *parts):
        """Launch `step` over `parts` on the device without blocking.
        Returns a zero-arg finisher yielding the output partition (it falls
        back to the step's host kernel itself, counters truthful), or None
        when nothing was launched: the caller answers with ``step.host``.

        The only place that asks eligibility, runs the attempt (one
        ``dispatch`` frame, ``faults.check("device.kernel")`` once) and
        bumps the step's counters. ``dispatches`` counts launches, whether
        the caller resolves at once (``run``) or later."""
        part = parts[0]
        if self.foreign_owned(part) and not part.is_loaded():
            # per-host scan locality: never read another process's file
            deferred = step.defer(part)
            if deferred is not None:
                return lambda: deferred
        done = self._collective_answer(step, parts)
        if done is not None:
            return lambda: done
        if not step.has_program or not (
                self._join_eligible(step, *parts) if len(parts) == 2
                else self._device_eligible(part)):
            return None
        resolve = self._device_attempt(
            lambda: step.launch(self, *parts), launch=True)
        if resolve is None:
            # failed (reported, breaker informed) or declined (probe slot
            # released) before anything was launched
            if step.counts_failed_launch:
                return lambda: step.fall_back(self, *parts)
            return None
        step.count(self.stats, 1)
        if step.dispatches is not None:
            self.stats.bump(step.dispatches)
        # the one finisher: it holds the step, the resolver and the
        # partitions, never itself or a staged `env`, so the attempt's
        # device arrays die with it (no wait for the cyclic collector)
        return lambda: self._finish_step(step, resolve, parts)

    def run(self, step, *parts) -> MicroPartition:
        """`step` over `parts`, blocking: launched and finished at once
        when eligible, else the step's host kernel. One code path for the
        synchronous callers (worker pool, dist workers, streaming, lineage
        recompute) and the pipelined ones."""
        fin = self.launch(step, *parts)
        return fin() if fin is not None else step.host(self, *parts)

    def _finish_step(self, step, resolve, parts) -> MicroPartition:
        """A launched attempt's second half: resolve (one ``gather``
        frame), then the step's ``finish``. A raised exception is a device
        failure (reported at the step's site, breaker informed); a None
        result a decline (the aggregate's overflow guard, a segment
        decline: probe slot released). Either way the partition was NOT
        computed on the device after all: the counters say so and the host
        kernel answers. All of it under the step's phase span, if it names
        one (the segment's ``fuse.segment``)."""
        from .profile.spans import _NOOP

        with (self.stats.profiler.span(step.span, kind="phase")
              if step.span is not None else _NOOP):
            try:
                out = self._device_resolve(resolve)
                if out is not None and step.finish_falls_back:
                    out = step.finish(self, out, *parts)
            except Exception as e:
                out = None
                self._device_failed(step.site, e)
            else:
                if out is None:
                    self.device_health.release_probe()
            if out is None:
                step.count(self.stats, -1)
                return step.fall_back(self, *parts)
            self.device_health.record_success(self.stats)
            if not step.finish_falls_back:
                out = step.finish(self, out, *parts)
            return out

    def _take_by_device_index(self, part: MicroPartition, attempt,
                              counter: str) -> Optional[MicroPartition]:
        """The rows of `part` at the indices one whole-in-one device
        attempt computes (launch and resolve inside the attempt's frame),
        or None when the host must answer."""
        if self._device_eligible(part):
            idx = self._device_attempt(attempt)
            if idx is not None:
                import numpy as np

                from .series import Series

                self.stats.bump(counter)
                return MicroPartition.from_table(part.table().take(
                    Series.from_numpy(idx.astype(np.uint64), "indices")))
        return None

    def eval_sort(self, part: MicroPartition, sort_by, descending=None,
                  nulls_first=None) -> MicroPartition:
        """Route a per-partition sort through the device argsort when
        eligible: keys compile + sort on device, only the payload take runs
        on host. Host pyarrow sort otherwise."""
        def _run():
            from .kernels.device import device_table_argsort

            return device_table_argsort(
                part.table(), sort_by, descending, nulls_first,
                stage_cache=part.device_stage_cache())

        out = self._take_by_device_index(part, _run, "device_sorts")
        if out is not None:
            return out
        self.stats.bump("host_sorts")
        return part.sort(sort_by, descending, nulls_first)

    def eval_distinct(self, part: MicroPartition, subset) -> MicroPartition:
        """Route distinct through the device group-codes kernel when the keys
        are device-eligible; host dictionary encode otherwise."""
        def _run():
            from .expressions import col
            from .kernels.device_agg import device_distinct_indices

            keys = list(subset) if subset else [
                col(n) for n in part.column_names]
            return device_distinct_indices(
                part.table(), keys, part.device_stage_cache(),
                len(part.table()))

        out = self._take_by_device_index(part, _run, "device_distincts")
        if out is not None:
            return out
        self.stats.bump("host_distincts")
        return part.distinct(subset)

    def prepare_broadcast(self, part: MicroPartition, on_exprs,
                          how: str = "inner") -> MicroPartition:
        """Hook for runners with a device mesh: replicate a broadcast-join
        build side into every device's HBM once, so per-partition probes use
        a local replica instead of re-shipping the build keys. Single-host
        base context: no-op."""
        return part

    def _join_eligible(self, probe, lpart, rpart) -> bool:
        """The two-input eligibility test of a join pair (`probe` is the
        operator's physical.JoinProbe)."""
        return (self.device_path_on()
                and probe.how in ("inner", "left", "semi", "anti")
                and 1 <= len(probe.left_on) == len(probe.right_on) <= 4
                and max(lpart.num_rows_or_none() or 0,
                        rpart.num_rows_or_none() or 0)
                >= self.cfg.device_min_rows
                and self._device_allowed())


_QUERY_SEQ = itertools.count(1)
_DONE = object()  # stream-exhausted sentinel for the per-pull context loop


def _classify_outcome(e: BaseException) -> str:
    from .errors import DaftTimeoutError

    if isinstance(e, DaftTimeoutError):
        return "timeout"
    if isinstance(e, QueryCancelledError):
        return "cancelled"
    return "error"


def _record_query(root: PhysicalOp, ctx: ExecutionContext, query_id: str,
                  fingerprint: str, plan_ops: Dict[str, int], wall_ns: int,
                  outcome: str, error, rows_emitted: int) -> None:
    """Completion hook: append the QueryRecord (every outcome, including
    the error/timeout paths — this runs in execute_plan's ``finally``) and
    hand it to the slow/failed-query auto-capture, fold the history and
    persist (three parts of ``entry.finish``: ``finish.record``,
    ``finish.history``, ``finish.persist``). ``enable_query_log``
    gates only the ring (and ``last_query_record``); the diagnostics
    capture contract — errored/deadline-killed queries always bundle when
    ``diagnostics_dir`` is set — survives a disabled log. Observability
    must never fail the query: any defect here degrades to an error log."""
    cfg = ctx.cfg
    canonical = getattr(root, "_canonical_fp", "")
    want_log = getattr(cfg, "enable_query_log", True)
    want_capture = (getattr(cfg, "diagnostics_dir", None)
                    or getattr(cfg, "slow_query_threshold_s", None)
                    is not None)
    rec = None
    if want_log or want_capture:
        try:
            from .obs import capture as obs_capture
            from .obs.querylog import QUERY_LOG, build_record

            prof = ctx.stats.profiler
            with timeline.part("finish.record", "entry_finish_record_ns"):
                rec = build_record(query_id, fingerprint, plan_ops, cfg,
                                   ctx.stats, wall_ns, outcome, error=error,
                                   profiled=prof.armed,
                                   rows_emitted=rows_emitted,
                                   canonical=canonical)
                if want_log:
                    QUERY_LOG.resize(cfg.query_log_depth)
                    QUERY_LOG.append(rec)
                    ctx.stats.last_record = rec
                obs_capture.maybe_capture(rec, cfg, ctx.stats, prof)
        except Exception as e:
            from .obs.log import get_logger

            get_logger("obs").error("query_record_failed", error=repr(e))
    if getattr(cfg, "history_fdo", True):
        # fold this execution's FDO observations + profile into the
        # process history (daft_tpu/adapt/history.py) — the input of the
        # next plan of this shape. Never fails the query.
        try:
            from .adapt.history import HISTORY

            with timeline.part("finish.history", "entry_finish_history_ns"):
                HISTORY.fold(canonical, ctx.stats, rec if rec is not None
                             else {"outcome": outcome,
                                   "wall_s": wall_ns / 1e9,
                                   "counters":
                                       ctx.stats.snapshot()["counters"]})
        except Exception as e:
            from .obs.log import get_logger

            get_logger("obs").error("history_fold_failed", error=repr(e))
    if getattr(cfg, "cache_dir", None) is not None:
        # warm-start artifact leg (daft_tpu/persist/): snapshot the plan
        # cache + history to disk when they moved this query. maybe_save
        # is fail-open by contract; the guard here is belt-and-braces.
        try:
            from . import persist

            with timeline.part("finish.persist", "entry_finish_persist_ns"):
                persist.maybe_save(cfg, ctx.stats)
        except Exception as e:
            from .obs.log import get_logger

            get_logger("obs").error("persist_save_failed", error=repr(e))


def execute_plan(root: PhysicalOp, ctx: ExecutionContext,
                 trace: bool = True) -> Iterator[MicroPartition]:
    """Wire up the generator tree and return the root partition stream.

    Every op is wrapped with per-partition accounting (rows + wall time into
    RuntimeStats, feeding explain_analyze) and — when the query's profiler
    is armed — with profiler spans. Queries are armed where they begin,
    before planning (``profile.arm_for_query`` in ``DataFrame.collect`` and
    the serving runtime); a plan that reaches here unarmed (iter_partitions,
    a bare runner) gets the same decision now, and the slow-query
    auto-capture arms here because it needs the plan's fingerprint: a
    previous run of it crossed ``cfg.slow_query_threshold_s``. The chrome
    output is rendered FROM the span tree at query end (one consolidated
    writer, re-armed per query).

    The flight recorder (daft_tpu/obs/) hooks both ends: the query id is
    bound as structured-log context for the query's lifetime, and EVERY
    completion — success, error, deadline kill, cancel, abandoned stream —
    appends a QueryRecord to the process query log."""
    from . import tracing
    from .obs import log as obs_log
    from .obs.querylog import plan_signature

    fingerprint, plan_ops = plan_signature(root)
    # the query's canonical (literal-masked) shape fingerprint, stamped by
    # the planner (adapt/plancache.plan_query); ops consult it for FDO
    # mispredict demotion, the completion hook for the QueryRecord
    ctx.canonical_fp = getattr(root, "_canonical_fp", "")
    prof = ctx.stats.profiler
    if prof.armed:
        query_id = prof.query_id
    else:
        # serving-runtime queries carry their admission-visible id through
        # the whole observability stack (records, logs, health)
        query_id = ctx.qctx.query_id or f"q-{next(_QUERY_SEQ)}"
        from .obs import capture as obs_capture
        from .profile import Profiler, arm_for_query

        arm_for_query(ctx.stats, query_id, profile=False)
        # slow-query auto-arm is part of the capture contract, which
        # survives a disabled query log
        if (not ctx.stats.profiler.armed
                and obs_capture.take_arm(fingerprint)):
            ctx.stats.profiler = Profiler(query_id=query_id)
    parallel = ctx.num_workers > 1

    def build(op: PhysicalOp) -> Iterator[MicroPartition]:
        # sub-plan result cache (daft_tpu/adapt/resultcache.py): a
        # scan+project/filter prefix another query already materialized
        # replays its cached partitions (or tees its output in on this
        # first execution). Declines (knob off, mesh/multi-host, UDFs,
        # unstattable sources) fall through; fails open.
        from .adapt.resultcache import try_result_cache

        served = try_result_cache(op, ctx, build, trace)
        if served is not None:
            return served
        # morsel-driven streaming (daft_tpu/stream/): a streamable segment
        # rooted here replaces its whole op chain with one pipelined
        # stream — bounded channels, producer stages on the worker pool,
        # byte-identical re-chunked output. Declines (device path, mesh,
        # UDFs, no streamable chain) fall through to the normal build.
        from .stream.pipeline import try_stream

        pipe = try_stream(op, ctx, build, trace)
        if pipe is not None:
            return pipe
        child_streams = [build(c) for c in op.children]
        if _unsplit(op, ctx):
            child_streams = [_stage_views(child_streams[0], ctx)]
        if getattr(op, "batch_declared", False) and ctx.dist_backend is None:
            # dynamic-batching UDFs (physical.BatchedUdfOp): the op's own
            # execute() coalesces across partitions — thread fan-out would
            # re-pin batch size to partition size. Under a distributed
            # backend we fall through instead: workers run map_partition
            # and host the pinned model actors process-locally.
            stream = op.execute(child_streams, ctx)
            return _traced(op, stream, ctx) if trace else stream
        if (parallel and op.map_partition is not None and len(child_streams) == 1
                and op.parallel_safe()):
            if op.device_pipelinable(ctx) and not op_resource_request(op):
                # device compute serializes on one chip: prefer the
                # double-buffered sequential driver — but fall back to thread
                # fan-out if the first partition declines the device path
                return _adaptive_device_map(op, child_streams[0], ctx, trace)
            # instrumentation happens inside the workers (the consumer-side
            # wrapper would only measure blocked-wait time)
            return _parallel_map(op, child_streams[0], ctx)
        stream = op.execute(child_streams, ctx)
        if trace:
            return _traced(op, stream, ctx)
        return stream

    built = build(root)

    def rooted():
        t0 = time.perf_counter_ns()
        outcome, error = "ok", None
        rows_out = 0
        saw_first_rows = False
        it = iter(built)
        # live query progress (obs/cluster.py): registered while this
        # execution runs, snapshotted by dt.health()["queries"] /
        # QueryHandle.progress(); last-wins per query id across AQE stages
        from .obs.cluster import (QueryProgress, register_progress,
                                  unregister_progress)

        progress = QueryProgress(query_id, ctx.stats, plan_ops)
        ctx.progress = progress
        register_progress(progress)
        # the plan stream's first pull: DataFrame.collect's entry.setup ends
        timeline.end_frame(ctx.stats, "entry_setup_ns")
        try:
            # the query id binds per PULL, never across a yield: two lazily
            # interleaved streams on one thread would otherwise cross-
            # attribute (and unbind) each other's log context
            while True:
                with obs_log.query_context(query_id):
                    part = next(it, _DONE)
                if part is _DONE:
                    break
                if ctx._peer_shuffles:
                    # a root output backed by peer-hosted shuffle pieces
                    # must not outlive them: force it local BEFORE the
                    # finally-block's finish_query drops the shuffles
                    from .dist.peerplane import ensure_local

                    with obs_log.query_context(query_id):
                        ensure_local(part)
                # exact root output count for the QueryRecord (the op-name
                # rollup can't distinguish a root op from same-class
                # upstream ops); metadata-only, never forces a load
                n = part.num_rows_or_none()
                if n:
                    rows_out += n
                    progress.add_rows(n)
                    if not saw_first_rows:
                        # time-to-first-row: how long the first non-empty
                        # partition took to surface (the streaming
                        # executor's first-row latency metric; rendered by
                        # the explain_analyze "streaming:" line and the
                        # bench ttfr rung)
                        saw_first_rows = True
                        ctx.stats.bump("time_to_first_row_ns",
                                       time.perf_counter_ns() - t0)
                yield part
        except GeneratorExit:
            # consumer closed the stream early (limit/abandoned iterator):
            # not a failure, but the record says the plan never finished
            outcome = "abandoned"
            raise
        except BaseException as e:
            outcome, error = _classify_outcome(e), e
            raise
        finally:
            # teardown (and the record/capture hooks it runs) still logs
            # under this query's id. The progress entry unregisters in the
            # inner finally: a teardown step raising must not leak a
            # phantom "running" query into the process registry forever.
            try:
                # entry.finish: this whole block, each hook a part of it
                with obs_log.query_context(query_id), timeline.DeviceFrame(
                        ctx.stats, "entry.finish", "entry_finish_ns"):
                    with timeline.part("finish.teardown",
                                       "entry_finish_teardown_ns"):
                        # close the stream tree BEFORE the pool goes away: a
                        # streaming pipeline's producers may be blocked on
                        # their channels, and generator close is what shuts
                        # the channels and unblocks them (GC would get there
                        # eventually; an abandoned/erroring query must not
                        # leave pool workers parked until then)
                        close = getattr(it, "close", None)
                        if close is not None:
                            try:
                                close()
                            except BaseException as e:
                                # a generator's own teardown raising must
                                # not skip pool shutdown or the record-on-
                                # every-completion contract (and must not
                                # mask the query's error)
                                obs_log.get_logger("execution").warning(
                                    "stream_close_failed", error=repr(e))
                        # close(it) cannot reach a pipeline suspended below
                        # an op whose raise terminated the chain above it
                        # (the traceback keeps those frames alive — see
                        # register_stream): shut down the stragglers
                        # directly. Only a deliberate early stop (success/
                        # abandoned consumer) counts short-circuits.
                        ctx.close_streams(
                            short_circuit=outcome in ("ok", "abandoned"))
                        ctx.shutdown_pool()
                        ctx.finish_query()
                        ctx.stats.fold_op_self_host()
                    prof = ctx.stats.profiler
                    prof.finish()
                    if tracing.active() and prof.armed:
                        # span tree -> chrome events, then rewrite the
                        # armed trace file (buffer kept: the next query
                        # appends to the same consolidated writer)
                        tracing.add_span_events(prof)
                        tracing.flush_query()
                    from .profile.metrics import record_query_metrics

                    wall_ns = time.perf_counter_ns() - t0
                    with timeline.part("finish.metrics",
                                       "entry_finish_metrics_ns"):
                        record_query_metrics(ctx.stats, wall_ns)
                    _record_query(root, ctx, query_id, fingerprint,
                                  plan_ops, wall_ns, outcome, error,
                                  rows_out)
                    tracing.query_finished()
            finally:
                unregister_progress(progress)
                ctx.progress = None

    return rooted()


def _unsplit(op: PhysicalOp, ctx: ExecutionContext) -> bool:
    """Does the device map ``op`` meet its in-memory source through stage
    views (``_stage_views``)? It does where a partition of the source is
    larger than ``morsel_size_rows``: each partition is then one launch that
    reads the lanes the source holds and leaves it no new ones, where a
    partition at or under a morsel keeps what its maps stage. Only row-local
    maps (Project, Filter, FusedMap): an aggregate or a segment keeps its
    lanes for the next query. With streaming or device residency off, or
    partitions pinned to a mesh, a host or a worker process, every map
    keeps the partition path."""
    from .physical import InMemoryOp
    from .stream.pipeline import pinned_partitions

    cfg = ctx.cfg
    src = op.children[0] if len(op.children) == 1 else None
    if not (isinstance(src, InMemoryOp) and op.morsel_streamable
            and cfg.streaming_execution and cfg.device_residency
            and not pinned_partitions(ctx)):
        return False
    morsel = max(1, int(cfg.morsel_size_rows))
    return (any((p.num_rows_or_none() or 0) > morsel for p in src.parts)
            and op.device_pipelinable(ctx))


def _stage_views(parts: Iterator[MicroPartition],
                 ctx: ExecutionContext) -> Iterator[MicroPartition]:
    """Each partition as a stage view (``MicroPartition.stage_view``). A
    view's own lanes are dropped when the next view is asked for, or the
    stream ends: by then the map has launched over it (its program holds
    what it reads until it is done), and its output partition carries only
    lanes the source held. So they never meet the consumer's staging."""
    view = None
    try:
        for part in parts:
            if view is not None:
                view.drop_staged()
            ctx.stats.bump("device_maps_unsplit")
            view = part.stage_view()
            yield view
    finally:
        if view is not None:
            view.drop_staged()


def _adaptive_device_map(op: PhysicalOp, child: Iterator[MicroPartition],
                         ctx: ExecutionContext,
                         trace: bool) -> Iterator[MicroPartition]:
    """Peek at the first partition: if it accepts the device dispatch, run the
    whole stream through the double-buffered sequential driver (the launched
    finisher is handed over as `_primed`, nothing recomputes); if it declines
    (below device_min_rows, staging failure, ...), thread fan-out would have
    been the better strategy after all — delegate the stream, first partition
    included, to the worker pool.

    The accepted branch wraps in _traced like every other sequential stream
    (per-partition stats, chrome-trace events, cancellation checks); the
    declined branch's _parallel_map instruments inside its workers."""
    import itertools

    it = iter(child)
    first = next(it, None)
    if first is None:
        yield from op.execute([iter(())], ctx)
        return
    dispatch = op.map_partition_dispatch(first, ctx)
    if dispatch is None:
        yield from _parallel_map(op, itertools.chain([first], it), ctx)
        return
    stream = op._map_execute([it], ctx, _primed=dispatch)
    # the driver's pending slot owns the finisher now: held here too, its
    # device arrays would outlive its call by the whole stream
    del dispatch, first
    if trace:
        stream = _traced(op, stream, ctx)
    yield from stream


def _parallel_map(op: PhysicalOp, child: Iterator[MicroPartition],
                  ctx: ExecutionContext) -> Iterator[MicroPartition]:
    """Morsel-parallel per-partition map with bounded in-flight window and
    order-preserving output (reference: worker-per-core IntermediateOps with
    round-robin morsel dispatch, intermediate_op.rs:71).

    Stats are recorded around the worker-side call, so explain_analyze sees
    real work time, not the consumer's blocked waits. The worker-side op
    SPAN (queue-wait phase included) is opened by scheduler.dispatch, which
    also carries the dispatching thread's span context across the hop —
    run_one only annotates it with the row count."""
    from . import tracing
    from .scheduler import PartitionTask, dispatch, run_map_task

    name = op.name()
    req = op_resource_request(op)

    def run_one(part, seq=0):
        out, rows_hint, dt = run_map_task(op, part, ctx, name, seq)
        if rows_hint is not None:
            rows = rows_hint
        else:
            n = out.num_rows_or_none()
            rows = n if n is not None else 0
        ctx.stats.record_op(name, rows, dt, _part_bytes(out))
        prof = ctx.stats.profiler
        if prof.armed:
            sp = prof.current()
            if sp is not None:
                sp.set_attr("rows", rows)
        return out

    saw_any = False

    def tasks():
        nonlocal saw_any
        for i, part in enumerate(child):
            saw_any = True
            yield PartitionTask(part, lambda p, _i=i: run_one(p, _i),
                                req, name, i)

    for out in dispatch(tasks(), ctx):
        n = out.num_rows_or_none()
        tracing.report_progress(name, n if n is not None else 0)
        yield out
    if not saw_any:
        yield from op.map_empty(ctx)
    progress = getattr(ctx, "progress", None)
    if progress is not None:
        progress.op_done(name)


def _part_bytes(part: MicroPartition) -> int:
    """Output bytes for throughput accounting — loaded partitions only, so
    instrumentation never triggers IO or forces a deferred op."""
    if not part.is_loaded():
        return 0
    b = part.size_bytes()
    return b if b is not None else 0


_tl = threading.local()


def _traced(op: PhysicalOp, stream: Iterator[MicroPartition],
            ctx: ExecutionContext) -> Iterator[MicroPartition]:
    from . import tracing

    name = op.name()
    stats = ctx.stats
    seq = 0
    while True:
        if stats.is_cancelled():
            raise QueryCancelledError(f"query cancelled (at {name})")
        ctx.check_deadline()
        # Self-time accounting: pulling next(stream) recursively runs the
        # child wrappers on this same thread, so each wrapper pushes a frame,
        # accumulates its INCLUSIVE time into the parent frame, and reports
        # inclusive - children as its own wall time. explain_analyze then
        # ranks operators by where time is actually spent, not by depth.
        # The profiler span covers the same interval (kind "op"): its export
        # self-time subtracts the same same-thread child op spans, so the
        # QueryProfile reconciles with RuntimeStats by construction.
        stack = getattr(_tl, "stack", None)
        if stack is None:
            stack = _tl.stack = []
        stack.append(0)
        prof = stats.profiler
        sp = prof.begin(name, op=name, part=seq) if prof.armed else None
        t0 = time.perf_counter_ns()
        pulled = False
        try:
            part = next(stream)
            pulled = True
        except StopIteration:
            progress = getattr(ctx, "progress", None)
            if progress is not None:
                progress.op_done(name)
            return
        finally:
            dt = time.perf_counter_ns() - t0
            child_ns = stack.pop()
            if stack:
                stack[-1] += dt
            if sp is not None:
                # the final StopIteration pull is not a partition: close
                # its span unrecorded so per-op partition counts stay exact
                (prof.end if pulled else prof.cancel)(sp)
        n = part.num_rows_or_none()
        rows = n if n is not None else 0
        stats.record_op(name, rows, max(dt - child_ns, 0),
                        _part_bytes(part))
        if sp is not None:
            sp.set_attr("rows", rows)
        seq += 1
        tracing.report_progress(name, rows)
        yield part
