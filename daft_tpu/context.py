"""Global context + config system.

Role-equivalent to the reference's daft/context.py:295-351
(set_planning_config / set_execution_config, ~19 knobs backed by
common/daft-config) and the runner-selection logic of DaftContext. Config is a
frozen-ish dataclass swapped atomically on the singleton context; readers grab
a snapshot at plan/execute time.
"""

from __future__ import annotations

import dataclasses
import os
import threading
from typing import Optional


@dataclasses.dataclass
class PlanningConfig:
    """Knobs consulted while building/optimizing logical plans
    (reference: DaftPlanningConfig)."""

    default_io_num_retries: int = 3
    enable_strict_filter_pushdown: bool = False


@dataclasses.dataclass
class ExecutionConfig:
    """Knobs consulted at physical planning / execution time
    (reference: DaftExecutionConfig, common/daft-config/src/lib.rs)."""

    scan_tasks_min_size_bytes: int = 96 * 1024 * 1024
    scan_tasks_max_size_bytes: int = 384 * 1024 * 1024
    broadcast_join_size_bytes_threshold: int = 10 * 1024 * 1024
    sort_merge_join_sort_with_aligned_boundaries: bool = False
    sample_size_for_sort: int = 20
    num_preview_rows: int = 8
    parquet_target_filesize: int = 512 * 1024 * 1024
    parquet_target_row_group_size: int = 128 * 1024 * 1024
    parquet_inflation_factor: float = 3.0
    csv_target_filesize: int = 512 * 1024 * 1024
    csv_inflation_factor: float = 0.5
    shuffle_aggregation_default_partitions: int = 200
    default_morsel_size: int = 128 * 1024
    # adaptive query execution: materialize join-input stages and re-plan with
    # real sizes (reference: AdaptivePlanner, planner.rs:288)
    enable_aqe: bool = False
    # AQE shuffle-count adaptation: a shuffle over a source of KNOWN size is
    # re-sized to ceil(bytes / this target) partitions (shrink-only), so a
    # 2KB input never fans out 200 ways (reference: stage-boundary re-planning
    # with materialized stats, planner.rs:288-351)
    shuffle_target_partition_bytes: int = 64 * 1024 * 1024
    # transient-IO retry at scan-task granularity (reference: s3_like.rs retry)
    scan_retry_attempts: int = 3
    scan_retry_backoff_s: float = 0.1
    # pipelined IO (README "Pipelined IO"): consumption-driven scan
    # readahead — materializing scan partition i issues the reads of the
    # next N tasks on the shared executor pool (io/prefetch.py), charged
    # against the MemoryLedger so readahead never blows memory_budget_bytes.
    # 0 disables (fully synchronous reads); results are byte-identical at
    # every depth.
    scan_prefetch_depth: int = 2
    # pipeline breakers hand spill IPC writes to a bounded background writer
    # thread instead of stalling on disk (spill.AsyncSpillWriter); write
    # failures keep the partition in memory exactly like the sync path, and
    # writer-internal errors surface at the next check_deadline barrier
    async_spill_writes: bool = True
    # draining a spilled buffer issues the NEXT unloaded partition's
    # read-back on the pool before the consumer needs it (double buffering);
    # the shuffle reduce side preloads bucket i+1 while bucket i is consumed
    unspill_readahead: bool = True
    # map-side shuffle fanout (decode + hash/split) runs as order-preserving
    # partition tasks on the worker pool — window min(4, workers) for
    # streams that may carry unloaded (out-of-core) partitions, the normal
    # workers+backlog window for resident ones — instead of inline on the
    # consumer thread (reference: FanoutInstruction partition tasks)
    parallel_shuffle_fanout: bool = True
    # morsel-parallel execution (reference: worker-per-core intermediate ops,
    # intermediate_op.rs:71): 0 = auto (one worker per core when the host has
    # >= 4 cores; sequential below that — oversubscription on tiny hosts
    # costs more than it buys), 1 = sequential, N = exactly N workers
    executor_threads: int = 0
    # extra tasks queued beyond the worker count in the dispatch loop
    # (reference: RayRunner's cores + max_task_backlog dynamic bound,
    # ray_runner.py:504-685); -1 = auto (one backlog slot per worker)
    max_task_backlog: int = -1
    # expression-pipeline fusion (daft_tpu/fuse/): maximal Project/Filter
    # chains collapse into single-pass FusedMapOp programs (one composed
    # host projection per partition; one jit program on the device path)
    # with hash-consing CSE and dead-column elimination. Results are
    # byte-identical with fusion on or off; False restores the per-op
    # interpreted chain (the bench.py laion fusion A/B axis).
    expr_fusion: bool = True
    # two-phase approximate aggregations (daft_tpu/sketch/): multi-partition
    # approx_count_distinct / approx_percentiles plan as sketch->merge stages
    # whose exchange ships serialized sketch bytes, O(sketch_size x
    # partitions). False restores the raw-row shuffle/gather path (the
    # before/after axis bench.py's sketch_exchange rung measures).
    sketch_aggregations: bool = True
    # --- exchange v2 (daft_tpu/exchange/, README "Exchange") --------------
    # runtime join filters (sideways information passing): the join build
    # side's exchange builds a Bloom + min-max filter from its keys and the
    # probe side's exchange (or the broadcast-join probe stream) prunes
    # non-qualifying rows BEFORE bucketing, spill, and merge. Semantics
    # gated per join type (inner/semi: either side; left: right side only;
    # right/anti/outer: decline); false-positive tolerant — the join
    # re-checks every surviving row, so results are byte-identical off.
    runtime_join_filters: bool = True
    # dictionary-encode low-cardinality columns of fanout bucket pieces
    # before they enter the spillable PartitionBuffer (per-column
    # cardinality sampling skips hostile columns; spilled exchange bytes
    # shrink too); decode happens once, at reduce-merge. Byte-identical off.
    exchange_payload_encoding: bool = True
    # hierarchical exchange: two-stage aggregations fold map-side pieces
    # headed to the same destination through the stage-2 combine BEFORE
    # the exchange buffers them (intra-host combine -> inter-host
    # all_to_all; mirrored on the mesh path ahead of the ICI collective).
    # Only schema-closed decomposable merges fold; byte-identical off.
    hierarchical_exchange_combine: bool = True
    # --- morsel-driven streaming executor (daft_tpu/stream/, README
    # "Streaming execution") ----------------------------------------------
    # streamable chains (Scan/InMemory -> Project/Filter/FusedMap ->
    # optional Limit) pull fixed-size morsels through bounded channels with
    # backpressure instead of materializing whole partitions between steps:
    # bounded working-set memory, first-row latency for limit/interactive
    # queries, and upstream early-termination when a limit is satisfied.
    # Results are byte-identical with streaming off (pipeline breakers keep
    # their partition-granular contract behind the driver's re-chunk
    # boundary). Declines automatically on the device-kernel and
    # mesh/multi-host paths.
    streaming_execution: bool = True
    # rows per morsel (the streaming unit; morsels never span reader-chunk
    # boundaries, so the effective size is min(this, chunk rows))
    morsel_size_rows: int = 128 * 1024
    # bounded-channel capacity in morsels, per in-flight source partition;
    # producers block (backpressure) past it
    stream_channel_capacity: int = 4
    # producer stages concurrently in flight; 0 = auto (one per worker —
    # the streaming path replaces _parallel_map's full worker fan-out and
    # must not cap map parallelism below it)
    stream_producer_window: int = 0
    # TPU-specific: route eligible projections/aggregations through the jax
    # device kernel layer (kernels/device.py); host pyarrow path otherwise.
    use_device_kernels: bool = False
    device_min_rows: int = 4096
    # whole-plan device residency (fuse/segment.py): compile eligible
    # project->filter->agg plan segments into one HBM-resident pipeline —
    # the map program's intermediate columns feed the fused aggregation as
    # DeviceArrays (one host->device stage at segment entry, one gather at
    # exit, zero Arrow materialization between). Results are byte-identical
    # with this off; any segment-compile or resident-run failure degrades
    # to the staged per-op device path. No effect without
    # use_device_kernels.
    device_residency: bool = True
    # result cache (PartitionSetCache): off when benchmarking so repeated runs
    # measure execution, not cache lookups
    enable_result_cache: bool = True
    # bounded-memory execution: pipeline breakers (shuffle buckets, join
    # builds) spill partitions to parquet past this engine-held byte budget;
    # None = unbounded (reference: the 16x data-to-memory SF1000 single-node
    # run, benchmarks.rst:111-124)
    memory_budget_bytes: Optional[int] = None
    # With x64 off (real TPUs are 32-bit), allow float64 data to execute as
    # float32 on device. Sums stay accurate: per-partition partials are
    # combined in float64 on the host. Set False to force exact float64
    # expressions onto the host path.
    device_reduced_precision: bool = True
    # 32-bit mode only: batch all float segment-SUMS of a fused grouped agg
    # through ONE pallas one-hot matmul on the MXU (kernels/pallas_ops.py)
    # instead of K scatter-based segment_sum lowerings. Same float32
    # accumulation contract as device_reduced_precision.
    use_pallas_segment_sums: bool = True
    # query deadline: the runner converts this to an absolute deadline at
    # run start (ONE deadline across all AQE stages), checked cooperatively
    # in the morsel loop and at pipeline breakers; expiry raises
    # DaftTimeoutError carrying the partial RuntimeStats. None = no limit.
    execution_timeout_s: Optional[float] = None
    # structured query profiler (daft_tpu/profile/): arm span/event
    # recording for every query without passing collect(profile=True) each
    # time. Off by default — the disarmed hot path is a single flag check
    # (guard-tested zero-allocation), so q1 wall is unaffected.
    enable_profiling: bool = False
    # always-on flight recorder (daft_tpu/obs/): every completed plan
    # execution appends a QueryRecord to the bounded process query log
    # (dt.query_log() / df.last_query_record()). Built only from state the
    # stats stack already collects — one dict build per query, guard-tested
    # like the DISARMED profiler — so it stays on even in production.
    # False disables ONLY the ring/last_query_record; the diagnostics
    # capture below keeps working.
    enable_query_log: bool = True
    query_log_depth: int = 256
    # slow/failed-query auto-capture: a query slower than this (seconds)
    # counts as slow — it arms the profiler for the NEXT run of the same
    # plan fingerprint, and (with diagnostics_dir set) dumps a diagnostics
    # bundle. None disables the slow path; errored/deadline-killed queries
    # always capture when diagnostics_dir is set.
    slow_query_threshold_s: Optional[float] = None
    # where diagnostics bundles land (record.json + stats.txt + profile
    # when armed + log/trace tails); None = no bundles. Retention is
    # bounded: only the newest diagnostics_keep_last bundles survive.
    diagnostics_dir: Optional[str] = None
    diagnostics_keep_last: int = 20
    # --- serving runtime (daft_tpu/serve/) ---------------------------------
    # query-level admission control: how many queries may EXECUTE at once in
    # a ServingRuntime (per-task admission via ResourceAccountant still
    # applies inside each query)
    max_concurrent_queries: int = 4
    # queries allowed to WAIT for a slot beyond the active set; a submit
    # past (active slots + this queue) sheds immediately with
    # DaftOverloadedError instead of piling up unboundedly
    admission_queue_depth: int = 16
    # a queued query that cannot get a slot within this window is shed with
    # DaftOverloadedError; None = wait forever (not recommended for serving)
    admission_timeout_s: Optional[float] = 30.0
    # scheduler partition tasks that raise DaftTransientError (including
    # injected io.get/scan.read faults that exhausted the IO-layer retries)
    # are re-run through the shared RetryPolicy this many EXTRA times
    # before failing the query; 0 disables task-level retry
    task_retry_attempts: int = 2
    task_retry_backoff_s: float = 0.05
    # --- distributed runner (daft_tpu/dist/, README "Distributed
    # execution") -------------------------------------------------------
    # supervised worker PROCESSES the DistributedRunner ships map-class
    # partition tasks to over the length-prefixed socket transport.
    # 0 = off (single-process execution, the default); N > 0 spawns N
    # workers, each with a carved child memory budget
    # (memory_budget_bytes // (N + 1); the driver keeps one share).
    # Results are byte-identical to the local runner at every N.
    distributed_workers: int = 0
    # supervision cadence: the driver pings every worker at this interval
    # and declares a worker dead when no pong (or result) arrived within
    # the timeout — its in-flight tasks re-dispatch to surviving workers
    worker_heartbeat_interval_s: float = 0.5
    worker_heartbeat_timeout_s: float = 5.0
    # spawn-to-handshake deadline for one worker process
    worker_spawn_timeout_s: float = 60.0
    # total worker RESPAWNS the pool may spend across its lifetime
    # (initial spawns are free); exhausted = the pool degrades to local
    # in-process execution instead of cycling forever
    worker_restart_budget: int = 8
    # dispatch attempts per task across worker losses: a poison task that
    # kills every worker it touches fails the QUERY with a DaftError
    # naming the task once it exhausts this budget (or has excluded every
    # worker slot), instead of re-dispatching forever
    dist_task_max_attempts: int = 4
    # cluster-wide observability plane (daft_tpu/obs/cluster.py): workers
    # piggyback a bounded, versioned telemetry fragment (span subtree,
    # RuntimeStats delta, typed events, log tail) on every task reply;
    # the driver merges it into the query's span tree, counter rollups,
    # and log ring, so one query produces ONE truthful trace regardless
    # of how many processes ran it. Strictly fail-open: a dropped or
    # corrupt fragment costs a telemetry_dropped counter, never a task
    # failure. Off = replies carry result/error only (the bench
    # dist_telemetry_overhead_pct A/B axis).
    cluster_telemetry: bool = True
    # peer-to-peer shuffle data plane (daft_tpu/dist/peerplane.py, README
    # "Peer-to-peer shuffle & elasticity"): hash/random shuffles dispatch
    # fanout tasks that park their pieces ON the workers, and reduce
    # buckets carry only a piece-location map — whoever materializes a
    # bucket pulls its pieces straight from the hosting peers over the
    # token-authenticated crc-framed transport, so driver payload bytes
    # stay flat as the worker count grows. Results are byte-identical
    # with this off and at every N; a dead/corrupt/stale peer degrades to
    # lineage recompute of just the lost pieces (peer_refetches), never a
    # failed query.
    peer_shuffle: bool = True
    # elastic worker pool: when BOTH bounds are set, the supervisor scales
    # the live worker count inside [min, max] — up under pressure
    # (admission queue depth + dispatch waiters; warm FDO history jumps
    # straight toward max, a cold pool steps by one), down by gracefully
    # DRAINING an idle worker after elastic_idle_scale_down_s of fleet
    # idleness. Unset (the default) keeps the fixed-size pool semantics.
    distributed_workers_min: Optional[int] = None
    distributed_workers_max: Optional[int] = None
    elastic_scale_interval_s: float = 0.5
    elastic_idle_scale_down_s: float = 10.0
    # drain_worker()/SIGTERM grace: a draining worker stops taking tasks
    # but keeps serving hosted shuffle pieces for this window, so spot
    # preemption costs bounded recompute, never a failed query; a worker
    # whose in-flight task outlives drain_timeout is killed and the task
    # re-dispatches through the normal loss path
    worker_drain_grace_s: float = 2.0
    worker_drain_timeout_s: float = 10.0
    # --- self-healing data plane (daft_tpu/integrity/, README "Data
    # integrity & speculation") ----------------------------------------
    # end-to-end partition integrity: payloads leaving compute (spill IPC
    # files, transport frames, encoded exchange pieces) carry a crc32
    # recorded at production and verified at re-entry; a mismatch raises
    # DaftCorruptionError (transient — lineage recompute / task re-dispatch
    # own recovery) instead of a garbled table. Results are byte-identical
    # with this off; off also skips the checksum computation (the bench
    # integrity_overhead_pct A/B axis).
    partition_integrity: bool = True
    # lineage-based recomputation: a bounded per-query LineageLog records
    # how spilled partitions were produced (scan task ref, or fanout op +
    # source partition ref); a corrupted or missing spill artifact is
    # recomputed from its recipe (partitions_recomputed) instead of
    # failing the query, degrading to a query-level DaftError only when
    # lineage is truncated or the recompute itself fails
    lineage_recomputation: bool = True
    lineage_log_depth: int = 4096
    # speculative straggler mitigation (distributed runner): a remote task
    # exceeding speculation_quantile_factor x the running p75 task wall
    # for its op (floor speculation_min_s) gets a duplicate dispatched to
    # a different worker; first result wins through the exactly-once ack
    # ledger, the loser is cancelled, and concurrent duplicates are
    # bounded by speculation_max_inflight so a sick fleet cannot double
    # its own load (tasks_speculated / speculation_wins counters)
    speculative_execution: bool = True
    speculation_quantile_factor: float = 3.0
    speculation_min_s: float = 1.0
    speculation_max_inflight: int = 2
    # --- query-velocity subsystem (daft_tpu/adapt/, README "Plan &
    # program cache") ---------------------------------------------------
    # plan/program cache: repeated plan shapes serve their optimized
    # logical plan, translated physical plan, and compiled FusedPrograms
    # from a bounded process cache keyed by a canonical fingerprint
    # (literals parameterized out) — warm traffic performs zero
    # optimize()/translate()/fuse-compile calls, byte-identical to a
    # cold plan. Invalidated on any config change, source mtime change,
    # cache-version bump, or FDO revalidation/demotion; fails open.
    plan_cache: bool = True
    # total estimated plan bytes held before LRU shedding (charged to the
    # MemoryLedger's plan_cache_bytes account)
    plan_cache_bytes: int = 64 * 1024 * 1024
    # feedback-directed optimization: the planner consults the recorded
    # history of this plan shape (flight-recorder rollups folded per
    # canonical fingerprint) — broadcast-vs-hash join flips, aggregate-
    # exchange fan-out resizes, and streaming-segment hints land on the
    # FIRST run of a repeated shape instead of after an AQE
    # materialization. Decisions are typed profiler events; a runtime
    # mispredict demotes the cached plan and reverts the decision.
    history_fdo: bool = True
    # sub-plan result cache: scan+project/filter prefixes shared across
    # queries memoize their materialized partitions, keyed by the exact
    # prefix fingerprint + source mtime (the _PARTITION_SET_CACHE
    # invalidation discipline); bytes LRU-shed under the cap below and
    # charged to the ledger's subplan_cache_bytes account
    subplan_result_cache: bool = True
    subplan_cache_bytes: int = 64 * 1024 * 1024
    # --- dynamic-batching UDF executor (daft_tpu/batch/, README "Batched
    # inference") --------------------------------------------------------
    # batch-declared UDFs (@daft_tpu.batch_udf / udf(..., batching=...))
    # route through the BatchingExecutor: morsels/partitions coalesce
    # across their boundaries into device-friendly batches under the
    # row/byte budget below, results re-split to exact source boundaries.
    # Results are byte-identical with this off (per-partition UDF path) —
    # the standing hard invariant, and the bench laion batching A/B axis.
    dynamic_batching: bool = True
    # per-batch coalesce budget: a batch closes when EITHER bound is
    # reached (declaration-site values override per UDF)
    batch_max_rows: int = 4096
    batch_max_bytes: int = 32 * 1024 * 1024
    # max-latency flush: a batch older than this flushes even when under
    # budget, so sparse streams never stall behind the coalescer
    batch_flush_ms: float = 25.0
    # batch shape policy: "ragged" concatenates as-is (row-offset vector
    # kept for the re-split); "padded" pads to the next power-of-two
    # bucket (repeating the last valid row; pad rows are sliced away
    # after the apply) so a jit'd apply sees few distinct shapes
    batch_padding: str = "ragged"
    # pinned-model LRU cap (batch/actors.ModelActorPool): resident weight
    # bytes across all pinned actor pools, charged to the ledger's
    # model_cache_bytes account; least-recently-used pools evict past it
    model_cache_bytes: int = 512 * 1024 * 1024
    # device circuit breaker (execution.DeviceHealth): after this many
    # CONSECUTIVE device-kernel failures the breaker opens and every
    # device-eligible partition routes straight to the host path (one trip,
    # not one failure tax per partition) ...
    device_breaker_threshold: int = 3
    # ... until the cooldown elapses, after which ONE probe partition tries
    # the device again: success re-closes the breaker, failure re-opens it.
    device_breaker_cooldown_s: float = 30.0
    # --- persistent cache store (daft_tpu/persist/) ------------------------
    # Directory for durable, cluster-shared cache artifacts. None (the
    # default) disables ALL persistence — the three legs below only engage
    # once a cache_dir is set, so the in-process cold/warm contracts stay
    # exactly as they were. Every leg fails open: any artifact defect
    # reads as a cold miss, never a query failure.
    cache_dir: Optional[str] = None
    # leg 1 — warm-start artifacts: the plan/program cache + FDO history
    # serialize to versioned, crc-verified files (written on query
    # completion / dt.shutdown(), loaded lazily at first planning), so a
    # fresh process serves warm plan-cache hits with zero optimize/
    # translate/fuse-compile calls
    persist_artifacts: bool = True
    # leg 2 — cluster-shared result tier: the sub-plan result cache gains
    # a spill-IPC on-disk tier (addressed by scan-task key + chain
    # fingerprint) served worker-to-worker through the PieceServer plane
    persist_result_store: bool = True
    # leg 3 — incremental refresh: when a source file's mtime/size moves,
    # recompute ONLY the affected partitions of a disk-tier entry and
    # splice them in, instead of discarding the whole entry
    persist_refresh: bool = True
    # artifact-directory hygiene: keep only the newest K artifact files
    # per family (concurrent drivers append, the pruner bounds the dir)
    persist_keep_last: int = 3
    # disk-tier byte cap (results/ subdirectory; oldest entries pruned
    # past it, counted as persist evictions)
    persist_result_bytes: int = 256 * 1024 * 1024


def resolve_executor_threads(cfg: "ExecutionConfig") -> int:
    n = cfg.executor_threads
    if n == 0:
        try:  # cgroup/affinity-aware, not raw host cores
            cores = len(os.sched_getaffinity(0))
        except AttributeError:
            cores = os.cpu_count() or 1
        n = cores if cores >= 4 else 1
    return max(1, n)


class DaftContext:
    """Process-global context: configs + runner (reference: daft/context.py)."""

    _instance: Optional["DaftContext"] = None
    _lock = threading.Lock()

    def __init__(self):
        self.planning_config = PlanningConfig()
        self.execution_config = ExecutionConfig()
        self._runner = None
        # most recent QueryProfile built by a profiled collect()
        self._last_profile = None
        self._runner_name = os.environ.get("DAFT_TPU_RUNNER", "native")
        if os.environ.get("DAFT_TPU_PROGRESS") == "1":
            from . import tracing

            tracing.progress_bars(True)

    @classmethod
    def get(cls) -> "DaftContext":
        with cls._lock:
            if cls._instance is None:
                cls._instance = DaftContext()
            return cls._instance

    def runner(self):
        if self._runner is None:
            from .runners import MeshRunner, NativeRunner

            if self._runner_name == "mesh":
                self._runner = MeshRunner()
            elif self._runner_name == "distributed":
                from .dist.runner import DistributedRunner

                self._runner = DistributedRunner()
            else:
                self._runner = NativeRunner()
        if self._runner_name == "native":
            # cfg.distributed_workers alone turns the multi-process runner
            # on/off; an explicitly-installed runner (mesh, or a test's
            # hand-built MeshRunner) is never clobbered
            from .runners import NativeRunner

            dw = self.execution_config.distributed_workers
            if dw > 0 and type(self._runner) is NativeRunner:
                from .dist.runner import DistributedRunner

                self._runner = DistributedRunner()
            elif dw == 0 and type(self._runner).__name__ == "DistributedRunner":
                self._runner = NativeRunner()
        return self._runner

    def last_profile(self):
        """The QueryProfile of the most recent profiled query in this
        process (``df.collect(profile=True)`` / cfg ``enable_profiling``),
        or None."""
        return self._last_profile

    def set_runner(self, name: str) -> None:
        from .errors import DaftValueError

        if name not in ("native", "mesh", "distributed"):
            raise DaftValueError(f"unknown runner {name!r}")
        self._runner_name = name
        self._runner = None


def get_context() -> DaftContext:
    return DaftContext.get()


def set_planning_config(**kwargs) -> DaftContext:
    ctx = get_context()
    cfg = dataclasses.replace(ctx.planning_config, **kwargs)
    ctx.planning_config = cfg
    return ctx


def set_execution_config(**kwargs) -> DaftContext:
    ctx = get_context()
    cfg = dataclasses.replace(ctx.execution_config, **kwargs)
    ctx.execution_config = cfg
    return ctx


def set_runner_native() -> DaftContext:
    ctx = get_context()
    ctx.set_runner("native")
    return ctx


def set_runner_mesh() -> DaftContext:
    ctx = get_context()
    ctx.set_runner("mesh")
    return ctx
