"""Physical plan: executable operators over streams of MicroPartitions.

Role-equivalent to the reference's src/daft-plan/src/physical_plan.rs +
physical_planner/translate.rs (notably the two-stage aggregation decomposition
at translate.rs:761) and the partition-task generators of
daft/execution/physical_plan.py (fanout/reduce at :1365, sort at :1414).

Execution model: each operator is a generator over MicroPartitions — streaming
ops (scan/project/filter/limit) never hold more than one partition; pipeline
breakers (sort/shuffle/agg-final/join-build) buffer what they must. The same
operator tree executes single-chip today and maps onto a device mesh via the
parallel/ shuffle kernels (partition i ↔ mesh slot i).
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from .expressions import AggExpr, Alias, Expression, col, expr_has_udf, lit
from .logical import (
    Aggregate,
    Concat,
    Distinct,
    Explode,
    Filter,
    InMemorySource,
    Join,
    Limit,
    LogicalPlan,
    MonotonicallyIncreasingId,
    Pivot,
    Project,
    Repartition,
    Sample,
    ScanSource,
    Sort,
    Unpivot,
    Write,
)
from .micropartition import MicroPartition
from .schema import Schema

PartStream = Iterator[MicroPartition]


def summarize_exprs(exprs, limit: int = 120) -> str:
    """Compact expression-list rendering for plan dumps: full displays up to
    `limit` chars, then a count of what was elided — a 40-column projection
    must not dump hundreds of chars into every explain line."""
    parts = []
    used = 0
    for i, e in enumerate(exprs):
        d = e._node.display()
        if parts and used + len(d) + 2 > limit:
            return ", ".join(parts) + f", ... (+{len(exprs) - i} more)"
        if not parts and len(d) > limit:
            d = d[:limit] + "…"
        parts.append(d)
        used += len(d) + 2
    return ", ".join(parts)


class DeviceStep:
    """What ONE device-able shape tells ``ExecutionContext.launch`` / ``run``:
    only what differs between shapes. The policy (who is eligible, the
    breaker, the ``device.kernel`` fault site, the counters' arithmetic, the
    fallback) is the driver's, written once there.

    A per-partition map operator declares a step by inheriting this beside
    PhysicalOp: the step is then built with the plan, cloned and pickled
    with it, keeps the operator's per-query latches, and nothing is built
    per partition. A join operator holds one (``JoinProbe``). ``parts`` is
    one partition, or the two sides of a join pair."""

    counter: Optional[str] = None     # answered by the device: +1 a launch,
    #                                   -1 when the host answered after all
    dispatches: Optional[str] = None  # launches, resolved at once or later
    fallbacks: Optional[str] = None   # launches the host answered after all
    site = "device.attempt"           # where a failed resolve is reported
    span: Optional[str] = None        # phase span round the finisher
    has_program = True        # False: never compiles; the breaker is not asked
    finish_falls_back = True  # False: a defect in ``finish`` raises
    counts_failed_launch = False  # True: a failed launch is a fallback too

    def compilable(self) -> bool:
        """Static check against the child schema (no data, no staging):
        does the shape compile for the device? Says whether the
        double-buffered driver is worth preferring over thread fan-out."""
        return False

    def launch(self, ctx, *parts):
        """The kernel call: stage and dispatch now, return a zero-arg
        resolver of the device output, or None to decline."""
        raise NotImplementedError

    def finish(self, ctx, out, *parts) -> MicroPartition:
        """The resolved device output -> the output partition."""
        return MicroPartition.from_table(out)

    def host(self, ctx, *parts) -> MicroPartition:
        """The host kernel, with its own ``host_*`` counters."""
        raise NotImplementedError

    def defer(self, part: MicroPartition) -> Optional[MicroPartition]:
        """A foreign-owned unloaded partition with this step appended to
        its pending chain (the owner evaluates for real), or None when the
        shape cannot be deferred."""
        return None

    def count(self, stats, n: int) -> None:
        if self.counter is not None:
            stats.bump(self.counter, n)

    def fall_back(self, ctx, *parts) -> MicroPartition:
        """The host's answer to an attempt the device did not answer."""
        if self.fallbacks is not None:
            ctx.stats.bump(self.fallbacks)
        return self.host(ctx, *parts)


def _exprs_compile(exprs, schema: Schema) -> bool:
    try:
        from .kernels.device import normalize_and_check

        return normalize_and_check(exprs, schema) is not None
    except Exception:
        return False


def _launch_exprs(part: MicroPartition, exprs):
    from .kernels.device import eval_projection_device_async

    return eval_projection_device_async(
        part.table(), exprs, stage_cache=part.device_stage_cache())


def _selected_column(e) -> Optional[str]:
    """The input column a projection expression passes through unchanged
    (a bare column, aliased or not), or None."""
    from .expressions import Column

    node = e._node
    while isinstance(node, Alias):
        node = node.child
    return node.cname if isinstance(node, Column) else None


def _passed_through_lanes(part: MicroPartition, exprs) -> dict:
    """The staged lanes of the columns a projection passes through
    unchanged, keyed for its output: a bare column, aliased or not, keeps
    its rows, so its lanes over the input are its lanes over the output. A
    consumer of the output that stages such a column (the runtime join
    filter, a join probe) then finds a resident input's lanes in place of
    staging them again."""
    cache = part.device_stage_cache()
    if not cache:
        return {}  # (and jax stays unimported on a host-only worker)
    from .kernels.device import carry_staged

    renames: Dict[str, List[str]] = {}
    for e in exprs:
        src = _selected_column(e)
        if src is not None:
            renames.setdefault(src, []).append(e.name())
    return carry_staged(cache, renames)


def _with_lanes(part: MicroPartition, lanes: dict) -> MicroPartition:
    part.device_stage_cache().update(lanes)
    return part


class PhysicalOp:
    """Base: children + a generator-producing execute().

    Ops that are pure per-partition maps have a `map_partition` ((part, ctx)
    -> part: their own method, or the one below when they declare a
    DeviceStep); the executor then runs them morsel-parallel across a worker
    pool (reference: worker-per-core IntermediateOps,
    intermediate_op.rs:71) instead of calling execute()."""

    @property
    def map_partition(self):
        """None for an operator that is no per-partition map. One that
        declares a DeviceStep is: its partitions go through the context's
        one driver (device when eligible, else the step's host kernel)."""
        return self._run_step if isinstance(self, DeviceStep) else None

    def _run_step(self, part, ctx):
        self._record(ctx)
        return ctx.run(self, part)

    def _record(self, ctx) -> None:
        """Once-a-query counters of the operator's plan-time work (the
        fused operators'), bumped where its partitions start to arrive:
        by the sequential driver, or by the first `map_partition`."""

    # The morsel contract (daft_tpu/stream/, README "Streaming execution"):
    # True declares map_partition ROW-LOCAL — applying it per fixed-size
    # morsel and re-chunking equals applying it per partition, byte for
    # byte — so the streaming executor may pull this op's work through
    # bounded channels. Ops that aggregate, reorder, or depend on partition
    # position must leave this False; daftlint DTL006 pins that a claiming
    # op implements map_partition (no silent whole-partition
    # materialization inside a streaming stage).
    morsel_streamable = False

    def map_empty(self, ctx):
        """Partitions to emit when the (parallel-mapped) input is empty."""
        return iter(())

    def parallel_safe(self) -> bool:
        """Whether map_partition may run concurrently across morsels.
        Function UDFs (and bare class UDFs sharing one instance) carry
        arbitrary user state with no thread-safety contract, so they force
        sequential order; class UDFs on an actor pool (concurrency > 1)
        serialize per instance and stay morsel-parallel."""
        from .expressions import expr_udfs_parallel_safe

        return all(expr_udfs_parallel_safe(e) for e in self._map_exprs())

    def _map_exprs(self):
        return ()

    def _map_execute(self, inputs, ctx, _primed=None):
        """Sequential driver over map_partition (the parallel executor has its
        own worker-pool driver over the same map_partition; device-pipelinable
        ops are routed HERE instead — see execute_plan). Honors UDF resource
        requests (fail-fast on impossible ones; reference: pyrunner.py:352-370).
        `_primed` is the already-launched finisher of a first partition the
        caller consumed while deciding the execution strategy.

        Device double-buffering: an operator with a DeviceStep launches
        partition i+1's staging + compute BEFORE partition i's result
        is pulled back from the device, overlapping host↔HBM transfer with
        device compute (reference role: the channelled pipeline of
        daft-local-execution intermediate_op.rs:71+). Output order is
        preserved; a host-path partition first drains the pending device one.
        The one pending slot is cleared as its finisher is called, so a
        resolved partition's device arrays die before its output is consumed.
        """
        from .execution import op_resource_request

        self._record(ctx)
        req = op_resource_request(self)
        if req:
            ctx.accountant.check(req)
        saw = _primed is not None
        # finisher of the previous device partition, in ONE slot
        pending, _primed = _primed, None
        for part in inputs[0]:
            saw = True
            if req:
                ctx.accountant.admit(req)
            try:
                # resource-requested ops never defer: the finisher would run
                # outside the accountant's admission window
                dispatch = None if req else self.map_partition_dispatch(part, ctx)
                if pending is not None:
                    out, pending = pending(), None
                    yield out
                if dispatch is not None:
                    pending = dispatch
                    continue
                out = self.map_partition_declined(part, ctx)
            finally:
                if req:
                    ctx.accountant.release(req)
            yield out
        if pending is not None:
            out, pending = pending(), None
            yield out
        if not saw:
            yield from self.map_empty(ctx)

    def map_partition_dispatch(self, part, ctx):
        """Non-blocking launch of one partition: a zero-arg finisher, or
        None to take the synchronous path (always, without a DeviceStep)."""
        if not isinstance(self, DeviceStep):
            return None
        return ctx.launch(self, part)

    def map_partition_declined(self, part, ctx):
        """Synchronous evaluation AFTER map_partition_dispatch returned None:
        a step's host kernel at once, never a doomed second device attempt."""
        if isinstance(self, DeviceStep):
            return self.host(ctx, part)
        return self.map_partition(part, ctx)

    def device_pipelinable(self, ctx) -> bool:
        """True when this op's step compiles for the device against its
        child schema — execute_plan then prefers the double-buffered
        sequential driver over thread fan-out (device compute serializes on
        one chip; the pipeline keeps the host link busy instead)."""
        return (isinstance(self, DeviceStep) and ctx.device_path_on()
                and self.compilable())

    def __init__(self, children: List["PhysicalOp"], schema: Schema, num_partitions: int):
        self.children = children
        self.schema = schema
        self.num_partitions = num_partitions

    def name(self) -> str:
        return type(self).__name__

    def execute(self, inputs: List[PartStream], ctx) -> PartStream:
        """A per-partition map runs the sequential driver; every other
        operator has its own."""
        if self.map_partition is None:
            raise NotImplementedError
        return self._map_execute(inputs, ctx)

    def display_tree(self, indent: str = "") -> str:
        out = [indent + ("* " if indent else "") + self.describe()]
        for c in self.children:
            out.append(c.display_tree(indent + "  "))
        return "\n".join(out)

    def describe(self) -> str:
        return f"{self.name()} [{self.num_partitions} parts]"

    def __repr__(self) -> str:
        return self.display_tree()


# ---------------------------------------------------------------------------
# sources
# ---------------------------------------------------------------------------

class ScanOp(PhysicalOp):
    def __init__(self, tasks: List[Any], schema: Schema):
        super().__init__([], schema, max(len(tasks), 1))
        self.tasks = tasks

    def plan_parts(self, ctx) -> List[MicroPartition]:
        """Prune + emit the scan's unloaded partitions (shared by the
        generator path below and the streaming pipeline driver, so both
        see identical pruning, counters, and multi-host ownership). The
        caller owns the ``scan.plan`` phase span."""
        scan_owner = getattr(ctx, "scan_owner", None)
        parts = []
        for i, task in enumerate(self.tasks):
            if task.can_prune():
                ctx.stats.bump("scan_tasks_pruned")
                continue
            ctx.stats.bump("scan_tasks_emitted")
            part = MicroPartition.from_scan_task(task)
            if scan_owner is not None:
                # multi-host: the task index over the globally-consistent
                # list assigns which process materializes (and READS) it
                part.owner_process = scan_owner(i)
            parts.append(part)
        return parts

    def execute(self, inputs, ctx) -> PartStream:
        from .io.prefetch import pipeline_scan_parts

        with ctx.stats.profiler.span("scan.plan", kind="phase"):
            parts = self.plan_parts(ctx)
        # bounded readahead: reading partition i triggers the background
        # fetch of i+1..i+depth (locally-owned tasks only); byte-identical
        # with prefetch off, order preserved by this very loop. (The
        # streaming executor bypasses this wrapper: its producer window IS
        # the readahead, reading chunk-wise on the pool.)
        yield from pipeline_scan_parts(parts, ctx)

    def describe(self):
        return f"Scan [{len(self.tasks)} tasks]"


class InMemoryOp(PhysicalOp):
    def __init__(self, parts: List[MicroPartition], schema: Schema):
        super().__init__([], schema, max(len(parts), 1))
        self.parts = parts

    def execute(self, inputs, ctx) -> PartStream:
        yield from self.parts


# ---------------------------------------------------------------------------
# streaming unary ops
# ---------------------------------------------------------------------------

class ProjectOp(DeviceStep, PhysicalOp):
    # row-local projection: per-morsel evaluation + re-chunk is
    # byte-identical to per-partition evaluation (the streaming driver
    # still declines UDF-bearing instances — a batch-dependent UDF sees
    # whole partitions on the partition-granular path)
    morsel_streamable = True

    counter = "device_projections"
    dispatches = "device_projection_dispatches"
    fallbacks = "device_projection_fallbacks"
    site = "device.projection"

    def __init__(self, child: PhysicalOp, exprs: List[Expression], schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.exprs = exprs

    @property
    def has_program(self) -> bool:
        # one that only selects and renames columns computes nothing: the
        # host's select is zero-copy and hands the input's staged lanes on,
        # where a program would stage, copy and fetch them back (and, over
        # a partition larger than a morsel, stream it as morsels whose
        # lanes die with them)
        return any(_selected_column(e) is None for e in self.exprs)

    def compilable(self) -> bool:
        return self.has_program and _exprs_compile(self.exprs,
                                                   self.children[0].schema)

    def launch(self, ctx, part):
        # taken before the launch stages anything: only what the input
        # held already is shared, so no staged array outlives its partition
        kept = _passed_through_lanes(part, self.exprs)
        resolve = _launch_exprs(part, list(self.exprs))
        return None if resolve is None else (lambda: (resolve(), kept))

    def finish(self, ctx, out, part):
        tbl, kept = out
        return _with_lanes(part._wrap(tbl), kept)

    def host(self, ctx, part):
        ctx.stats.bump("host_projections")
        return _with_lanes(part.eval_expression_list(self.exprs),
                           _passed_through_lanes(part, self.exprs))

    def defer(self, part):
        exprs = list(self.exprs)
        schema = Schema([e._node.to_field(part.schema) for e in exprs])
        return part.with_pending_op(
            lambda t: t.eval_expression_list(exprs), schema,
            count_preserving=True)

    def _map_exprs(self):
        return self.exprs

    def describe(self):
        return "Project: " + summarize_exprs(self.exprs)


class BatchedUdfOp(PhysicalOp):
    """A projection containing batch-declared UDFs (daft_tpu/batch/),
    routed through the dynamic-batching executor instead of the
    per-partition UDF path.

    Deliberately NOT a ProjectOp subclass: both fuse passes match
    ``isinstance(op, (ProjectOp, FilterOp))``, so this op is a fusion
    barrier by construction — batch-declared UDFs must keep their own op
    (the batching driver owns their evaluation), while chains above and
    below still fuse normally.

    Three entry points, all byte-identical:
      execute()       — local non-streaming driver: coalesces whole
                        partitions under the budget, re-splits to source
                        partition boundaries
      map_partition() — one-partition batched apply; the degrade target,
                        AND the worker-side entry under the distributed
                        runner (the op pickles like any map op, so workers
                        host the pinned model actors process-locally)
      stream adapter  — stream/pipeline.py builds a BatchingExecutor per
                        producer and re-splits to morsel boundaries
    """

    # the batch declaration IS a row-locality contract (see batch_udf),
    # which is exactly the morsel contract
    morsel_streamable = True
    # routing marker: execute_plan sends this op to its own execute()
    # locally; stream/pipeline.py lifts the UDF decline for it
    batch_declared = True

    def __init__(self, child: PhysicalOp, exprs: List[Expression], schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.exprs = exprs

    def _map_exprs(self):
        return self.exprs

    def _settings(self, ctx):
        from .batch.executor import BatchSettings
        from .expressions import expr_batch_udfs

        decl = None
        for e in self.exprs:
            udfs = expr_batch_udfs(e)
            if udfs:
                decl = udfs[0].batching  # first declaration wins
                break
        return BatchSettings.resolve(decl, ctx.cfg)

    def map_partition(self, part, ctx):
        # whole-partition batched apply: the degrade path and the
        # distributed worker entry (pinned actors live in the worker)
        from .batch.device import exec_ctx_scope

        ctx.stats.bump("host_projections")
        with exec_ctx_scope(ctx):
            return part.eval_expression_list(self.exprs)

    def execute(self, inputs, ctx) -> PartStream:
        from .execution import op_resource_request

        if op_resource_request(self):
            # resource-requested UDFs run under the accountant's admission
            # window, which is per-partition — skip cross-partition
            # coalescing rather than hold admission across a batch
            yield from self._map_execute(inputs, ctx)
            return
        from .batch.executor import BatchingExecutor

        bx = BatchingExecutor(self.name(), self.exprs, ctx,
                              settings=self._settings(ctx))
        try:
            for part in inputs[0]:
                yield from bx.feed(part)
            yield from bx.finish()
        finally:
            # abandoned stream (limit/error above): settle buffered charges
            bx.abort()

    def describe(self):
        return "BatchedUdf: " + summarize_exprs(self.exprs)


def _route_batched_udfs(op: PhysicalOp) -> PhysicalOp:
    """Pre-fusion pass: rewrite ProjectOps whose expressions carry a
    batching declaration into BatchedUdfOp. Runs BEFORE fuse_for_device /
    fuse_map_chains (which would otherwise fold the projection into a
    fused map and strand the declaration)."""
    from .expressions import expr_has_batch_udf

    op.children = [_route_batched_udfs(c) for c in op.children]
    if type(op) is ProjectOp and any(expr_has_batch_udf(e) for e in op.exprs):
        return BatchedUdfOp(op.children[0], op.exprs, op.schema)
    return op


class FilterOp(DeviceStep, PhysicalOp):
    # row-local predicate: a row's fate depends only on its own values, so
    # morsel-wise compaction concatenates to the partition-granular result
    morsel_streamable = True

    counter = "device_filters"
    dispatches = "device_filter_dispatches"
    fallbacks = "device_filter_fallbacks"
    site = "device.filter"

    def __init__(self, child: PhysicalOp, predicate: Expression):
        super().__init__([child], child.schema, child.num_partitions)
        self.predicate = predicate

    def compilable(self) -> bool:
        return _exprs_compile([self.predicate], self.children[0].schema)

    def launch(self, ctx, part):
        return _launch_exprs(part, [self.predicate])

    def finish(self, ctx, out, part):
        # the mask was computed on the device; the compaction is the host's
        return part._wrap(part.table().filter_with_mask(out._columns[0]))

    def host(self, ctx, part):
        ctx.stats.bump("host_filters")
        return part.filter([self.predicate])

    def defer(self, part):
        predicate = self.predicate
        return part.with_pending_op(
            lambda t: t.filter([predicate]), part.schema,
            count_preserving=False)

    def _map_exprs(self):
        return (self.predicate,)

    def describe(self):
        return f"Filter: {self.predicate._node.display()}"


class LimitOp(PhysicalOp):
    """Streaming global limit with early stop (reference: global_limit,
    physical_plan.py — iterative partition takes).

    Upstream early-termination: once the limit is satisfied the child
    stream is CLOSED, not merely abandoned — a streaming pipeline below
    (daft_tpu/stream/) tears down its channels and producers immediately
    (they stop scanning/decoding partitions nobody will read, counted in
    ``morsels_short_circuited``) instead of waiting for end-of-query GC.
    When the limit sits directly atop a streamable chain the driver
    absorbs it as a morsel-consuming sink instead, and this op never
    executes."""

    def __init__(self, child: PhysicalOp, limit: int):
        super().__init__([child], child.schema, child.num_partitions)
        self.limit = limit

    def execute(self, inputs, ctx) -> PartStream:
        remaining = self.limit
        src = inputs[0]
        if remaining > 0:
            for part in src:
                n = part.num_rows_or_none()
                if n is None or n > remaining:
                    part = part.head(remaining)
                remaining -= len(part)
                yield part
                if remaining <= 0:
                    break
        close = getattr(src, "close", None)
        if close is not None:
            close()

    def describe(self):
        return f"Limit: {self.limit}"


class ExplodeOp(PhysicalOp):
    """Map-class since the DTL006 burn-down: per-partition explode runs
    through the instrumented _map_execute driver (driver/worker op spans,
    morsel parallelism) instead of a blind streaming loop."""

    def __init__(self, child: PhysicalOp, exprs: List[Expression], schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.exprs = exprs

    def map_partition(self, part, ctx):
        return part.explode(self.exprs)

    def _map_exprs(self):
        return list(self.exprs)


class UnpivotOp(PhysicalOp):
    """Map-class since the DTL006 burn-down (same driver instrumentation
    as ExplodeOp)."""

    def __init__(self, child: PhysicalOp, ids, values, variable_name, value_name, schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.ids = ids
        self.values = values
        self.variable_name = variable_name
        self.value_name = value_name

    def map_partition(self, part, ctx):
        return part.unpivot(self.ids, self.values, self.variable_name,
                            self.value_name)

    def _map_exprs(self):
        return list(self.ids) + list(self.values)


class SampleOp(PhysicalOp):
    def __init__(self, child: PhysicalOp, fraction: float, with_replacement: bool, seed):
        super().__init__([child], child.schema, child.num_partitions)
        self.fraction = fraction
        self.with_replacement = with_replacement
        self.seed = seed

    def execute(self, inputs, ctx) -> PartStream:
        for i, part in enumerate(inputs[0]):
            seed = None if self.seed is None else self.seed + i
            yield part.sample(fraction=self.fraction, with_replacement=self.with_replacement,
                              seed=seed)


class MonotonicIdOp(PhysicalOp):
    """Per-partition ids offset by partition_index << 36 (reference:
    monotonically_increasing_id partition encoding)."""

    def __init__(self, child: PhysicalOp, column_name: str, schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.column_name = column_name

    def execute(self, inputs, ctx) -> PartStream:
        for i, part in enumerate(inputs[0]):
            yield part.add_monotonic_id(i << 36, self.column_name)


class WriteOp(PhysicalOp):
    def __init__(self, child: PhysicalOp, root_dir: str, format: str,
                 compression, partition_cols, schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.root_dir = root_dir
        self.format = format
        self.compression = compression
        self.partition_cols = partition_cols

    def execute(self, inputs, ctx) -> PartStream:
        wrote = False
        for part in inputs[0]:
            wrote = True
            with ctx.stats.profiler.span("write.sink", kind="phase"):
                out = part.write_tabular(self.root_dir, self.format,
                                         self.compression,
                                         self.partition_cols)
            yield out
        if not wrote:
            yield MicroPartition.empty(self.schema)


# ---------------------------------------------------------------------------
# pipeline breakers
# ---------------------------------------------------------------------------

class CoalesceOp(PhysicalOp):
    """N partitions -> M partitions without a shuffle ('into_partitions')."""

    def __init__(self, child: PhysicalOp, num: int):
        super().__init__([child], child.schema, num)
        self.num = num

    def execute(self, inputs, ctx) -> PartStream:
        with ctx.stats.profiler.span("coalesce.gather", kind="phase"):
            parts = [p for p in inputs[0]]
        if not parts:
            return
        total = sum(len(p) for p in parts)
        if self.num >= len(parts):
            # split: rebalance rows evenly
            big = MicroPartition.concat(parts) if len(parts) > 1 else parts[0]
            per = (total + self.num - 1) // self.num if self.num else total
            for i in range(self.num):
                lo = min(i * per, total)
                hi = min((i + 1) * per, total)
                yield big.slice(lo, hi)
        else:
            # merge adjacent chunks
            per = (len(parts) + self.num - 1) // self.num
            for i in range(0, len(parts), per):
                group = parts[i:i + per]
                yield MicroPartition.concat(group) if len(group) > 1 else group[0]


class ShuffleOp(PhysicalOp):
    """Fanout+reduce all-to-all exchange (reference: FanoutInstruction +
    ReduceMerge, physical_plan.py:1365). scheme: hash | random | range.

    Exchange v2 (daft_tpu/exchange/, README "Exchange"): the translate
    wiring may attach a runtime-join-filter slot this exchange FEEDS
    (build side) or PRUNES WITH (probe side), and/or a stage-2 combine
    spec that folds map-side pieces per destination before they buffer.
    Bucket pieces additionally dictionary-encode before entering the
    spillable PartitionBuffer. Every leg is knob-gated and byte-identical
    off."""

    # exchange v2 attachments (class-level defaults keep every other
    # construction site unchanged)
    filter_feed = None    # JoinFilterSlot this build-side exchange populates
    probe_filter = None   # JoinFilterSlot whose sealed filter prunes here
    combine = None        # (stage2_aggs, key_cols) pre-exchange fold spec
    # FDO observation key (daft_tpu/adapt/): when set, the payload that
    # actually crossed this exchange is recorded under this canonical
    # subtree fingerprint at query end — the history future plans read
    fdo_obs_key = None
    # FDO fan-out resize (daft_tpu/adapt/fdo.py): emit this many output
    # partitions by concatenating ADJACENT hash buckets at reduce time.
    # Hashing stays modulo `num`, so group co-location, combine folds,
    # and — because per-bucket group sets are disjoint and
    # first-occurrence order composes — the OUTPUT ROW ORDER are all
    # byte-identical to the unresized exchange; only the partition count
    # (stage-2 invocations, downstream fan-in) shrinks. None = off.
    reduce_to = None

    def __init__(self, child: PhysicalOp, scheme: str, num: int,
                 by: Optional[List[Expression]] = None,
                 descending: Optional[List[bool]] = None,
                 nulls_first: Optional[List[Optional[bool]]] = None):
        super().__init__([child], child.schema, num)
        self.scheme = scheme
        self.num = num
        self.by = by or []
        self.descending = descending or [False] * len(self.by)
        self.nulls_first = nulls_first if nulls_first is not None else [None] * len(self.by)

    def _feed_filter(self, stream, ctx) -> PartStream:
        """Build-side pass-through: fold every streamed partition's join
        keys into the slot's builder; seal at stream end (the join op
        drains this side fully before the probe side's exchange runs).
        Any failure — including the ``join.filter`` fault site — abandons
        the filter; the exchange itself is untouched (fail-open)."""
        from . import faults

        slot = self.filter_feed
        if not getattr(ctx.cfg, "runtime_join_filters", True) \
                or not slot.eligible:
            slot.abandon()
            yield from stream
            return
        slot.begin()
        for p in stream:
            if ctx.foreign_owned(p):
                # multi-host scan locality: this process must not read the
                # partition, and a locally-built filter would miss foreign
                # build keys (a WRONG prune) — abandon entirely
                slot.abandon()
            else:
                try:
                    faults.check("join.filter", ctx.stats)
                    for t in p.chunk_tables():
                        slot.feed(t)
                except Exception:
                    slot.abandon()
                    ctx.stats.bump("join_filter_errors")
            yield p
        try:
            slot.seal()
        except Exception:
            slot.abandon()
            ctx.stats.bump("join_filter_errors")
        if slot.filter() is not None:
            ctx.stats.bump("join_filter_built")

    def _prune_stream(self, stream, ctx, obs=None) -> PartStream:
        """Probe-side pass-through: prune each partition with the sealed
        build-side filter BEFORE bucketing/spill/merge. The slot is
        consulted per partition (None — unsealed, abandoned, disabled —
        passes rows through untouched).

        ``obs`` is the shuffle's FDO observation accumulator: what
        pruning removed is added BACK there, so the side's recorded size
        is the pre-prune truth — a broadcast flip seeded from post-prune
        bytes would materialize the side UNPRUNED and mispredict."""
        from .exchange.joinfilter import prune_partition

        slot = self.probe_filter
        for p in stream:
            jf = slot.filter()
            if jf is None or (ctx.foreign_owned(p) and not p.is_loaded()):
                # foreign-owned (multi-host scan locality): pruning would
                # force this process to read a partition another host owns
                # — the mesh exchange skips it by owner instead
                yield p
            else:
                out = prune_partition(p, jf, self.by, ctx)
                if obs is not None and p.is_loaded():
                    # prune_partition forced the load; both sizes are free
                    pre_r = p.num_rows_or_none() or 0
                    pre_b = p.size_bytes() or 0
                    post_r = out.num_rows_or_none() or 0
                    post_b = out.size_bytes() or 0
                    obs[0] += max(0, pre_r - post_r)
                    obs[1] += max(0, pre_b - post_b)
                yield out

    def _peer_execute(self, stream, ctx, n, fdo_obs, backend) -> PartStream:
        """Peer-to-peer exchange: each source partition ships to a worker
        as a FANOUT task (split happens there, pieces stay hosted on that
        worker's piece-server) and each reduce output is a PeerPieceTask —
        an unloaded scan task whose payload is a piece-LOCATION map, pulled
        peer-to-peer by whichever worker lands the downstream task. The
        driver moves plan metadata and location maps only, so its payload
        bytes stay flat as the pool grows.

        Robustness contract: a worker declining a fanout (pool busy,
        ineligible partition, unroutable result) degrades THAT source to a
        driver-side split with inline pieces — mixed buckets are fine, the
        reader concatenates entries in source order either way. A peer
        dying after fanout is the reader's problem: PeerPieceTask fails
        over to the captured source task and recomputes just the lost
        piece (see peerplane.PeerPieceTask._recompute)."""
        from .dist.peerplane import PeerPieceTask, PieceRef
        from .integrity.lineage import fanout_piece_recipe, unwrap_source_task

        lineage_on = getattr(ctx.cfg, "lineage_recomputation", True)
        integrity = getattr(ctx.cfg, "partition_integrity", True)
        sid = backend.new_shuffle_id()
        ctx.register_peer_shuffle(sid)
        token = backend.peer_token()
        sources: Dict[int, Any] = {}
        entries: List[List[Any]] = [[] for _ in range(n)]
        saw = False

        def account(rows, nbytes):
            if rows:
                ctx.stats.bump("exchange_rows", rows)
            if nbytes:
                ctx.stats.bump("exchange_bytes", nbytes)
            if fdo_obs is not None:
                fdo_obs[0] += rows or 0
                fdo_obs[1] += nbytes or 0

        with ctx.stats.profiler.span("shuffle.fanout", kind="phase"):
            pool = ctx.pool()
            pending = []
            for pi, p in enumerate(stream):
                saw = True
                # capture BEFORE shipping: the recipe is the failover path
                # for every piece this source produces. A source WITHOUT a
                # recipe (loaded/derived partition, or lineage off) never
                # fans out remotely — a peer hosting unrecomputable pieces
                # would turn its death into a failed query, and the driver
                # already holds these bytes anyway.
                src_task = unwrap_source_task(p) if lineage_on else None
                if src_task is not None:
                    sources[pi] = src_task
                    spec = {"sid": sid, "src": pi, "scheme": self.scheme,
                            "num": n, "seed": pi, "by": self.by,
                            "crc": integrity}
                    pending.append((pi, p, pool.submit(
                        backend.execute_fanout, p, spec, ctx,
                        f"shuffle.{self.scheme}", pi)))
                else:
                    pending.append((pi, p, None))
            for pi, p, fut in pending:
                res = fut.result() if fut is not None else None
                if res is None:
                    # declined: split here, pieces ride inline in the map
                    if self.scheme == "hash":
                        pieces = p.partition_by_hash(self.by, n)
                    else:
                        pieces = p.partition_by_random(n, seed=pi)
                    src_task = sources.get(pi)
                    for i, piece in enumerate(pieces):
                        nrows = piece.num_rows_or_none() or 0
                        if not nrows:
                            continue
                        if src_task is not None:
                            piece.lineage_recipe = fanout_piece_recipe(
                                src_task, self.by, self.scheme, n, pi, i)
                        account(nrows, piece.size_bytes() or 0)
                        entries[i].append(piece)
                else:
                    wid, (host, port), metas = res
                    for (i, rows, nbytes, crc) in metas:
                        account(rows, nbytes)
                        entries[i].append(PieceRef(
                            wid, host, port, sid, i, pi, rows, nbytes, crc))
        if fdo_obs is not None and saw:
            ctx.stats.fdo_observe(self.fdo_obs_key, fdo_obs[0], fdo_obs[1])
        if not saw:
            return
        ctx.stats.bump("shuffles")
        split = (self.by, self.scheme, n)

        def emit(bucket_entries):
            refs = bucket_entries
            if not refs:
                return MicroPartition.empty(self.schema)
            # only the sources actually referenced by THIS bucket's remote
            # pieces ride along (inline pieces carry their own recipe)
            need = {e.src for e in refs if isinstance(e, PieceRef)}
            task = PeerPieceTask(
                self.schema, refs, token, split,
                {s: sources[s] for s in need if s in sources},
                checksum=integrity, stats=ctx.stats)
            return MicroPartition.from_scan_task(task)

        k = (self.reduce_to
             if self.reduce_to is not None and 0 < self.reduce_to < n
             else None)
        if k is None:
            for i in range(n):
                yield emit(entries[i])
            return
        groups: List[List[int]] = [[] for _ in range(k)]
        for i in range(n):
            groups[i * k // n].append(i)
        ctx.stats.bump("fdo_reduced_partitions", n - k)
        for idxs in groups:
            merged: List[Any] = []
            for i in idxs:
                merged.extend(entries[i])
            yield emit(merged)

    def execute(self, inputs, ctx) -> PartStream:
        n = self.num
        src = inputs[0]
        fdo_obs = [0, 0] if self.fdo_obs_key is not None else None
        if self.filter_feed is not None:
            src = self._feed_filter(src, ctx)
        if self.probe_filter is not None \
                and getattr(ctx.cfg, "runtime_join_filters", True):
            src = self._prune_stream(src, ctx, obs=fdo_obs)
        combine = (self.combine if self.combine is not None and
                   getattr(ctx.cfg, "hierarchical_exchange_combine", True)
                   else None)
        # Mesh path: one all_to_all collective over ICI instead of host fanout
        # (parallel/mesh_exec.py); falls through to host on ineligibility.
        # Range shuffles sample their boundaries host-side first (reference:
        # ReduceToQuantiles, execution_step.py:878) — the payload still rides
        # ICI, making device range-shuffle + per-device sort a global sort.
        dev_shuffle = getattr(ctx, "try_device_shuffle", None)
        pre_boundaries = None
        if dev_shuffle is not None and self.scheme in ("hash", "random", "range"):
            parts = [p for p in src]
            if not parts:
                return
            if self.scheme == "range":
                # cheap dtype-eligibility gate BEFORE the sampling work; the
                # sampled boundaries are reused by the host fallback below
                from .parallel.mesh_exec import exchangeable_dtype

                if all(exchangeable_dtype(f.dtype) for f in parts[0].schema):
                    samples = [sample_partition_keys(p, self.by, n,
                                                     ctx.cfg.sample_size_for_sort)
                               for p in parts]
                    pre_boundaries = boundaries_from_samples(
                        samples, self.by, n, self.descending, self.nulls_first)
            # exchange_rows/exchange_bytes are counted INSIDE the mesh
            # exchange (actual staged payload, post pre-combine) so the
            # device and host paths report the same thing
            out = dev_shuffle(parts, self.by, n, self.scheme, self.descending,
                              self.nulls_first, pre_boundaries,
                              combine=combine)
            if out is not None:
                yield from out
                return
            stream = iter(parts)
        else:
            stream = src
        # Peer-to-peer path (daft_tpu/dist/peerplane.py): hash/random
        # exchanges on a peer-capable worker pool fan out ON the workers
        # and reduce buckets become piece-location maps — payload bytes
        # never transit the driver. Exchange v2 attachments (join-filter
        # feed/prune, pre-combine) and range schemes keep the star path:
        # each is defined over driver-resident pieces, and p2p must be
        # byte-identical off, not approximately off.
        if (self.scheme in ("hash", "random")
                and self.filter_feed is None
                and self.probe_filter is None
                and combine is None
                and getattr(ctx.cfg, "peer_shuffle", True)):
            backend = getattr(ctx, "dist_backend", None)
            if (backend is not None
                    and getattr(backend, "execute_fanout", None) is not None
                    and backend.peer_ready()):
                yield from self._peer_execute(stream, ctx, n, fdo_obs,
                                              backend)
                return
        buckets = [ctx.partition_buffer() for _ in range(n)]
        # payload encoding engages on BUDGETED queries only: that is where
        # exchanged bytes gate throughput (ledger pressure -> spill IO, and
        # spilled encoded buckets stay encoded on disk). On an unbudgeted
        # in-memory exchange the encode/decode pass is pure overhead
        # (measured ~1.6x on the bench exchange rung), so it stands down.
        encode = (getattr(ctx.cfg, "exchange_payload_encoding", True)
                  and ctx.memory_budget is not None)
        comb = None
        if combine is not None:
            from .exchange.combine import BucketCombiner

            comb = BucketCombiner(combine[0], combine[1], ctx.stats,
                                  ledger=ctx.ledger,
                                  budget=ctx.memory_budget)

        def exchange_append(i: int, piece: MicroPartition) -> None:
            # every row/byte ACTUALLY crossing the exchange is counted here
            # — post filter-prune and pre-combine fold, so the counters are
            # the real exchanged payload on both the host and mesh paths
            # (the sketch subsystem's acceptance metric reads these)
            nrows = piece.num_rows_or_none()
            if nrows:
                ctx.stats.bump("exchange_rows", nrows)
            raw = piece.size_bytes() or 0
            if raw:
                ctx.stats.bump("exchange_bytes", raw)
            if fdo_obs is not None:
                fdo_obs[0] += nrows or 0
                fdo_obs[1] += raw
            if encode:
                enc_bytes = raw
                try:
                    from .exchange.encode import encode_exchange_partition

                    enc = encode_exchange_partition(
                        piece, ctx.stats,
                        integrity=getattr(ctx.cfg, "partition_integrity",
                                          True))
                except Exception:
                    enc = None
                    ctx.stats.bump("exchange_encode_failures")
                if enc is not None:
                    piece = enc
                    enc_bytes = piece.size_bytes() or raw
                    ctx.stats.bump("exchange_pieces_encoded")
                # the encoded-vs-raw ratio needs a denominator covering the
                # SAME pieces (exchange_bytes also counts gathers and
                # encode-disabled shuffles)
                if raw:
                    ctx.stats.bump("exchange_bytes_encodable", raw)
                if enc_bytes:
                    ctx.stats.bump("exchange_bytes_encoded", enc_bytes)
            buckets[i].append(piece)

        saw = False
        lineage_on = getattr(ctx.cfg, "lineage_recomputation", True)
        # the whole map-side fanout (decode + hash/split + bucket appends)
        # runs inside the FIRST pull of this op: make it a named phase on
        # the span timeline so the exchange's two halves are separable
        with ctx.stats.profiler.span("shuffle.fanout", kind="phase"):
            if self.scheme == "range":
                # Boundaries need all inputs, so partitions are buffered
                # (spillable); keys are SAMPLED AS PARTITIONS STREAM IN so a
                # spilled partition is never re-materialized for sampling,
                # and drain() drops each ref after fanout — out-of-core
                # inputs are resident once at a time.
                in_buf = ctx.partition_buffer()
                samples = []
                src_tasks = []
                for p in stream:
                    if pre_boundaries is None:
                        samples.append(sample_partition_keys(
                            p, self.by, n, ctx.cfg.sample_size_for_sort))
                    if lineage_on:
                        # scan-backed sources make every range piece
                        # recomputable (integrity/lineage.py): capture the
                        # task BEFORE the buffer/fanout materializes p
                        from .integrity.lineage import unwrap_source_task

                        src_tasks.append(unwrap_source_task(p))
                    else:
                        src_tasks.append(None)
                    in_buf.append(p)
                saw = len(in_buf) > 0
                if not saw:
                    boundaries = None
                elif pre_boundaries is not None:
                    boundaries = pre_boundaries  # sampled for device attempt
                else:
                    boundaries = boundaries_from_samples(
                        samples, self.by, n, self.descending, self.nulls_first)
                for pi, p in enumerate(in_buf.drain()):
                    pieces = p.partition_by_range(self.by, boundaries,
                                                  self.descending,
                                                  self.nulls_first)
                    for i, piece in enumerate(pieces):
                        if src_tasks[pi] is not None:
                            from .integrity.lineage import \
                                range_piece_recipe

                            piece.lineage_recipe = range_piece_recipe(
                                src_tasks[pi], self.by, boundaries,
                                self.descending, self.nulls_first, i)
                        exchange_append(min(i, n - 1), piece)
            else:
                def fanout(p, pi):
                    # lineage (integrity/lineage.py): when the SOURCE
                    # partition is scan-backed, every piece of this
                    # deterministic split can be recomputed by re-reading
                    # the source — capture the recipe BEFORE the split
                    # materializes p, so a piece spilled later survives a
                    # corrupted/missing spill file. Loaded/pruned sources
                    # decline (capturing them would pin memory): their
                    # pieces carry truncated lineage by design.
                    src_task = None
                    if lineage_on:
                        from .integrity.lineage import unwrap_source_task

                        src_task = unwrap_source_task(p)
                    if self.scheme == "hash":
                        pieces = p.partition_by_hash(self.by, n)
                    else:
                        pieces = p.partition_by_random(n, seed=pi)
                    if src_task is not None:
                        from .integrity.lineage import fanout_piece_recipe

                        for i, piece in enumerate(pieces):
                            piece.lineage_recipe = fanout_piece_recipe(
                                src_task, self.by, self.scheme, n, pi, i)
                    return pieces

                for pieces in _fanout_stream(stream, fanout, ctx,
                                             _subtree_may_yield_unloaded(self)):
                    saw = True
                    for i, piece in enumerate(pieces):
                        if comb is not None and not comb.failed:
                            flushed = comb.add(i, piece)
                            if flushed is not None:
                                # fold failed: everything staged so far is
                                # appended raw, combining stops for this
                                # shuffle (results stay correct — stage 2
                                # merges partials of any granularity)
                                for b, part in flushed:
                                    exchange_append(b, part)
                        else:
                            exchange_append(i, piece)
                if comb is not None:
                    for b, part in comb.finish():
                        exchange_append(b, part)
        if fdo_obs is not None and saw:
            ctx.stats.fdo_observe(self.fdo_obs_key, fdo_obs[0], fdo_obs[1])
        if not saw:
            return
        ctx.stats.bump("shuffles")
        k = (self.reduce_to
             if self.reduce_to is not None and 0 < self.reduce_to < n
             else None)
        if k is None:
            for i in range(n):
                if i + 1 < n:
                    # unspill readahead across the reduce side: bucket
                    # i+1's spilled pieces re-materialize on the pool
                    # while the consumer works on bucket i
                    buckets[i + 1].preload()
                if len(buckets[i]):
                    with ctx.stats.profiler.span("shuffle.merge",
                                                 kind="phase"):
                        merged = MicroPartition.concat(buckets[i].parts())
                    yield merged
                else:
                    yield MicroPartition.empty(self.schema)
                buckets[i].release()
            return
        # FDO reduce-side fan-in: adjacent buckets merge into k outputs
        # (bucket i -> output i*k//n), in bucket order — byte-identical
        # rows AND row order vs the k=None loop's concatenated outputs
        groups: List[List[int]] = [[] for _ in range(k)]
        for i in range(n):
            groups[i * k // n].append(i)
        ctx.stats.bump("fdo_reduced_partitions", n - k)
        for g, idxs in enumerate(groups):
            if g + 1 < k:
                for j in groups[g + 1]:
                    buckets[j].preload()
            parts: List[MicroPartition] = []
            for i in idxs:
                if len(buckets[i]):
                    parts.extend(buckets[i].parts())
            if parts:
                with ctx.stats.profiler.span("shuffle.merge",
                                             kind="phase"):
                    merged = (MicroPartition.concat(parts)
                              if len(parts) > 1 else parts[0])
                yield merged
            else:
                yield MicroPartition.empty(self.schema)
            for i in idxs:
                buckets[i].release()

    def describe(self):
        by = ", ".join(e._node.display() for e in self.by)
        tags = []
        if self.filter_feed is not None:
            tags.append("join-filter-feed")
        if self.probe_filter is not None:
            tags.append("join-filter-probe")
        if self.combine is not None:
            tags.append("combine")
        if self.reduce_to is not None:
            tags.append(f"fdo-reduce {self.reduce_to}")
        tag = f" <{'+'.join(tags)}>" if tags else ""
        return (f"Shuffle[{self.scheme}] -> {self.num}"
                + (f" by [{by}]" if by else "") + tag)


def _subtree_may_yield_unloaded(op: PhysicalOp) -> bool:
    """True when `op`'s stream can contain UNLOADED partitions: a ScanOp
    anywhere below it (streaming ops like Limit/Project pass scan
    partitions through un-forced). Pipeline breakers always yield loaded
    partitions, but they cannot appear BETWEEN a scan and this op without
    forcing it, so the presence test stays sound and conservative."""
    if isinstance(op, ScanOp):
        return True
    return any(_subtree_may_yield_unloaded(c) for c in op.children)


def _fanout_stream(stream: PartStream, fn, ctx, may_be_unloaded: bool):
    """Map-side shuffle fanout, yielding each partition's piece list IN
    INPUT ORDER. With parallel_shuffle_fanout on (and a real worker pool),
    the decode + hash/split of partition i+1 runs on the pool while
    partition i's pieces append to their buckets — the reference runs
    fanout as parallel partition tasks (FanoutInstruction,
    physical_plan.py:1365); inline-serial otherwise. Streams that may
    carry unloaded (out-of-core) partitions get an in-flight window of
    min(4, workers) so only a few decoded partitions exist beyond the
    buckets (4 ≈ double-buffering per core-pair; measured: window 2 left
    the SF10 fanout decode-bound, 4 closed it); fully-resident streams
    use the normal workers+backlog window. Order-preserving dispatch
    keeps bucket contents byte-identical with the inline path."""
    if not getattr(ctx.cfg, "parallel_shuffle_fanout", False) \
            or ctx.num_workers <= 1:
        for pi, p in enumerate(stream):
            yield fn(p, pi)
        return
    from .scheduler import PartitionTask, dispatch

    window = min(4, ctx.num_workers) if may_be_unloaded else None

    def tasks():
        for pi, p in enumerate(stream):
            yield PartitionTask(p, (lambda part, _pi=pi: fn(part, _pi)),
                                None, "shuffle-fanout", pi)

    yield from dispatch(tasks(), ctx, window=window)


def _counted(stream: PartStream, ctx, counter: str) -> PartStream:
    """Pass-through that counts rows AND bytes entering an exchange
    boundary (rows alone can't see payload inflation: a sketch row is
    16 KiB where a raw row is a few bytes — exchange_bytes keeps the
    before/after metric honest)."""
    bytes_counter = counter.replace("_rows", "_bytes")
    for p in stream:
        n = p.num_rows_or_none()
        if n:
            ctx.stats.bump(counter, n)
        if p.is_loaded():
            b = p.size_bytes()
            if b:
                ctx.stats.bump(bytes_counter, b)
        yield p


def sample_partition_keys(p: MicroPartition, by: List[Expression], num: int,
                          sample_size: int = 20):
    """Sampled sort-key rows of ONE partition (possibly an empty Table).
    Called while partitions stream into a spillable buffer, so boundary
    estimation never re-materializes a spilled partition (reference: sort
    sampling in physical_plan.py:1414)."""
    keys = p.table().eval_expression_list(by)
    if len(keys) == 0:
        return keys
    k = min(len(keys), max(sample_size, sample_size * num))
    return keys.sample(size=k, seed=0) if k < len(keys) else keys


def boundaries_from_samples(samples, by: List[Expression], num: int,
                            descending: List[bool],
                            nulls_first: Optional[List[Optional[bool]]] = None):
    """num-1 quantile boundary rows from per-partition key samples."""
    import pyarrow as pa

    from .series import Series
    from .table import Table

    key_tables = [s for s in samples if s is not None and len(s) > 0]
    if not key_tables:
        return next(s for s in samples if s is not None).slice(0, 0)
    allk = Table.concat(key_tables)
    skeys = [col(n) for n in allk.column_names]
    allk = allk.sort(skeys, descending=descending, nulls_first=nulls_first)
    m = len(allk)
    idxs = [int(np.floor(m * (i + 1) / num)) for i in range(num - 1)]
    idxs = [min(max(i, 0), m - 1) for i in idxs]
    return allk.take(Series.from_arrow(pa.array(np.asarray(idxs, dtype=np.uint64)), "i"))


def sample_boundaries(parts: List[MicroPartition], by: List[Expression], num: int,
                      descending: List[bool],
                      nulls_first: Optional[List[Optional[bool]]] = None,
                      sample_size: int = 20):
    """Boundary rows for already-resident partitions (mesh/host sort paths
    that never spill). Streaming consumers should sample incrementally via
    sample_partition_keys + boundaries_from_samples instead."""
    samples = [sample_partition_keys(p, by, num, sample_size) for p in parts]
    return boundaries_from_samples(samples, by, num, descending, nulls_first)


def aligned_boundaries_from_samples(sides_samples, num: int):
    """Quantile boundaries over the COMBINED per-partition key samples of
    several inputs, so all sides range-partition identically — bucket i on
    every side covers the same key interval (reference: Boundaries
    intersection, daft/runners/partitioning.py:110-166). Samples are
    collected while partitions stream into their spillable buffers."""
    import pyarrow as pa

    from .series import Series
    from .table import Table

    key_tables = []
    first_empty = None
    for samples in sides_samples:
        for keys in samples:
            if keys is None:
                continue
            if first_empty is None:
                first_empty = keys.slice(0, 0)
            if len(keys) == 0:
                continue
            # align names AND dtypes to the first side so samples concat
            if keys.schema != first_empty.schema:
                keys = Table(first_empty.schema,
                             [c.cast(f.dtype).rename(f.name)
                              for c, f in zip(keys._columns, first_empty.schema)])
            key_tables.append(keys)
    if not key_tables:
        return first_empty
    allk = Table.concat(key_tables)
    skeys = [col(n) for n in allk.column_names]
    allk = allk.sort(skeys)
    m = len(allk)
    idxs = [min(max(int(np.floor(m * (i + 1) / num)), 0), m - 1) for i in range(num - 1)]
    return allk.take(Series.from_arrow(pa.array(np.asarray(idxs, dtype=np.uint64)), "i"))


def sample_aligned_boundaries(sides, num: int, sample_size: int = 20):
    """Aligned boundaries for already-resident inputs (each `(parts,
    key_exprs)`); streaming consumers sample incrementally instead."""
    return aligned_boundaries_from_samples(
        [[sample_partition_keys(p, by, num, sample_size) for p in parts]
         for parts, by in sides], num)


class SortOp(PhysicalOp):
    """Per-partition sort; upstream ShuffleOp(range) makes it a global sort."""

    def __init__(self, child: PhysicalOp, sort_by, descending, nulls_first):
        super().__init__([child], child.schema, child.num_partitions)
        self.sort_by = sort_by
        self.descending = descending
        self.nulls_first = nulls_first

    def execute(self, inputs, ctx) -> PartStream:
        # sequential by design: the per-partition sort may route through
        # the device argsort, and device compute serializes on one chip.
        # The kernel interval gets its own phase span (DTL006) so profiles
        # split sort time from pull overhead.
        prof = ctx.stats.profiler
        for part in inputs[0]:
            with prof.span("sort.partition", kind="phase"):
                out = ctx.eval_sort(part, self.sort_by, self.descending,
                                    self.nulls_first)
            yield out

    def describe(self):
        return "Sort: " + ", ".join(e._node.display() for e in self.sort_by)


class _AggStep(DeviceStep):
    """The step of a (filter-fused) per-partition aggregation: the fused
    grouped-aggregate program, or, for a stage-1 list of nothing but
    ``sketch_hll``, the register scatter of the sketch build. Which of the
    two is fixed by the aggregate list, so it is decided once, at plan time,
    before any breaker or fault site is touched."""

    counter = "device_aggregations"
    dispatches = "device_agg_dispatches"
    fallbacks = "device_agg_fallbacks"
    site = "device.agg"
    predicate: Optional[Expression] = None
    sketch_build = False

    def _declare_step(self) -> None:
        from .sketch.device import aggs_all_sketch_hll

        if self.predicate is None and aggs_all_sketch_hll(self.aggregations):
            self.sketch_build = True
            self.counter, self.dispatches = "device_sketch_builds", None
            self.fallbacks, self.site = "device_sketch_fallbacks", "device.sketch"

    def compilable(self) -> bool:
        try:
            from .kernels.device_agg import agg_plan_device_compilable
        except Exception:
            return False
        return agg_plan_device_compilable(self.aggregations,
                                          self.children[0].schema,
                                          predicate=self.predicate)

    def launch(self, ctx, part):
        if self.sketch_build:
            from .sketch.device import hll_build_table_device_launch

            return hll_build_table_device_launch(
                part.table(), list(self.aggregations), list(self.groupby))
        from .kernels.device_agg import device_grouped_agg_async

        return device_grouped_agg_async(
            part.table(), list(self.aggregations), list(self.groupby),
            stage_cache=part.device_stage_cache(), predicate=self.predicate,
            stats=ctx.stats)

    def host(self, ctx, part):
        """Host aggregation (applies the predicate first when one was
        fused)."""
        ctx.stats.bump("host_aggregations")
        predicate, groupby = self.predicate, self.groupby
        if predicate is not None:
            tbl = part.table()
            # acero single-pass pays off when the hash-agg subsumes the
            # filtered-table materialization; ungrouped reductions are faster
            # through the pruned filter+agg below (measured on TPC-H Q6)
            out = tbl.acero_fused_agg(list(self.aggregations), list(groupby),
                                      predicate) if groupby else None
            if out is not None:
                ctx.stats.bump("fused_host_aggregations")
                return MicroPartition.from_table(out)
            # unfused fallback: prune to referenced columns before filtering
            # so the compaction doesn't copy payload the agg never reads
            from .expressions import required_columns

            need = set()
            for e in list(self.aggregations) + list(groupby) + [predicate]:
                need.update(required_columns(e))
            if need and need < set(part.column_names):
                keep = [n for n in part.column_names if n in need]
                part = MicroPartition.from_table(tbl.select_columns(keep))
            part = part.filter([predicate])
        return part.agg(self.aggregations, groupby or None)

    def map_empty(self, ctx):
        # global agg over zero partitions still yields one row (count=0 etc.)
        if not self.groupby:
            yield MicroPartition.empty(self.children[0].schema).agg(self.aggregations, None)

    def _map_exprs(self):
        pred = [] if self.predicate is None else [self.predicate]
        return pred + list(self.aggregations) + list(self.groupby)

    def _describe_aggs(self) -> str:
        a = ", ".join(e._node.display() for e in self.aggregations)
        g = ", ".join(e._node.display() for e in self.groupby)
        return a + (f" by [{g}]" if g else "")


class AggregateOp(_AggStep, PhysicalOp):
    """Full aggregation per partition (single-partition finals and stage
    executions both use this)."""

    def __init__(self, child: PhysicalOp, aggregations: List[Expression],
                 groupby: List[Expression], schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.aggregations = aggregations
        self.groupby = groupby
        self._declare_step()

    def describe(self):
        return "Aggregate: " + self._describe_aggs()


class FusedFilterAggregateOp(_AggStep, PhysicalOp):
    """Filter fused into a grouped aggregation: on the device path the
    predicate stays a mask feeding masked segment reductions — no host
    compaction or intermediate materialization (the TPU analog of the
    reference's fused streaming pipeline, pipeline.rs:141-211). The host
    fallback applies filter-then-agg per partition."""

    def __init__(self, child: PhysicalOp, predicate: Expression,
                 aggregations: List[Expression], groupby: List[Expression],
                 schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.predicate = predicate
        self.aggregations = aggregations
        self.groupby = groupby

    def describe(self):
        return (f"FusedFilterAggregate: where {self.predicate._node.display()}"
                f" agg {self._describe_aggs()}")


class GatherOp(PhysicalOp):
    """All partitions -> one (global agg finals, small sorts, sort_merge)."""

    def __init__(self, child: PhysicalOp):
        super().__init__([child], child.schema, 1)

    def execute(self, inputs, ctx) -> PartStream:
        with ctx.stats.profiler.span("gather.merge", kind="phase"):
            parts = [p for p in _counted(inputs[0], ctx, "exchange_rows")]
            out = (MicroPartition.empty(self.schema) if not parts
                   else parts[0] if len(parts) == 1
                   else MicroPartition.concat(parts))
        yield out


class DistinctOp(PhysicalOp):
    def __init__(self, child: PhysicalOp, subset: Optional[List[Expression]]):
        super().__init__([child], child.schema, child.num_partitions)
        self.subset = subset

    def execute(self, inputs, ctx) -> PartStream:
        # sequential like SortOp (the distinct may use the device group-
        # codes kernel); the kernel interval is a phase span (DTL006)
        prof = ctx.stats.profiler
        for part in inputs[0]:
            with prof.span("distinct.partition", kind="phase"):
                out = ctx.eval_distinct(part, self.subset)
            yield out


class PivotOp(PhysicalOp):
    def __init__(self, child: PhysicalOp, groupby, pivot_col, value_col, agg_fn, names,
                 schema: Schema):
        super().__init__([child], schema, 1)
        self.groupby = groupby
        self.pivot_col = pivot_col
        self.value_col = value_col
        self.agg_fn = agg_fn
        self.names = names

    def execute(self, inputs, ctx) -> PartStream:
        # the gather is this op's blocking phase (DTL006): it buffers the
        # whole input before the single-partition pivot can run
        with ctx.stats.profiler.span("pivot.gather", kind="phase"):
            parts = [p for p in inputs[0]]
            part = MicroPartition.concat(parts) if len(parts) > 1 else (
                parts[0] if parts else MicroPartition.empty(self.children[0].schema))
        out = part.pivot(self.groupby, self.pivot_col, self.value_col, self.names, self.agg_fn)
        yield out.cast_to_schema(self.schema)


class ConcatOp(PhysicalOp):
    def __init__(self, left: PhysicalOp, right: PhysicalOp, schema: Schema):
        super().__init__([left, right], schema, left.num_partitions + right.num_partitions)

    def execute(self, inputs, ctx) -> PartStream:
        for part in inputs[0]:
            yield part.cast_to_schema(self.schema)
        for part in inputs[1]:
            yield part.cast_to_schema(self.schema)


class JoinProbe(DeviceStep):
    """The step of one join pair: the right-build range probe of
    kernels/device_join.py on the device, acero's hash join on the host.
    Held by the join operators (a pair is two partitions, so the operator
    is no per-partition map); stateless, shared by a cached plan's clones."""

    dispatches = "device_join_dispatches"
    fallbacks = "device_join_fallbacks"
    site = "device.join"
    # assembly runs OUTSIDE the driver's catch-all: a defect there must
    # crash loudly, not silently recompute on host
    finish_falls_back = False
    # the logical join's ``origin`` ("sql_subquery"), set by translate: its
    # pairs count in ``<origin>_joins`` and ``<origin>_joins_device``
    origin: Optional[str] = None

    def __init__(self, left_on, right_on, how: str, suffix: str):
        self.left_on = left_on
        self.right_on = right_on
        self.how = how
        self.suffix = suffix

    def launch(self, ctx, lpart, rpart):
        from .kernels.device_join import device_join_launch, join_key_replicas

        left_on, right_on = self.left_on, self.right_on
        single = len(left_on) == 1
        return device_join_launch(
            lpart.table(), rpart.table(), list(left_on), list(right_on),
            lpart.device_stage_cache(), rpart.device_stage_cache(), self.how,
            left_replicas=(join_key_replicas(lpart, left_on[0])
                           if single else None),
            right_replicas=(join_key_replicas(rpart, right_on[0])
                            if single else None))

    def finish(self, ctx, res, lpart, rpart):
        """(side, hit, bidx) probe result -> output partition."""
        with ctx.stats.profiler.span("join.assemble", kind="phase"):
            out = self._assemble(res, lpart.table(), rpart.table())
        ctx.stats.bump("device_join_probes")
        if self.origin is not None:
            ctx.stats.bump_many({f"{self.origin}_joins": 1,
                                 f"{self.origin}_joins_device": 1})
        return MicroPartition.from_table(out)

    def _assemble(self, res, ltbl, rtbl):
        from .series import Series

        side, hit, bidx = res
        how, args = self.how, (self.left_on, self.right_on, self.suffix)
        if side == "expanded":
            # N:M range join: (lidx, ridx) pairs already expanded on
            # host from the device range probe (-1 = left-outer miss)
            return ltbl.join_from_indices(rtbl, hit, bidx, *args)
        if side == "right_build":
            if how == "semi":
                return ltbl.filter_with_mask(Series.from_numpy(hit, "m"))
            if how == "anti":
                return ltbl.filter_with_mask(Series.from_numpy(~hit, "m"))
            if how == "inner":
                lidx = np.nonzero(hit)[0]
                return ltbl.join_from_indices(rtbl, lidx, bidx[hit], *args)
            # left outer: every left row, -1 -> null right
            lidx = np.arange(len(ltbl), dtype=np.int64)
            ridx = np.where(hit, bidx, -1)
            return ltbl.join_from_indices(rtbl, lidx, ridx, *args)
        # left_build (inner only): re-sort to host (lidx, ridx) order
        ridx = np.nonzero(hit)[0]
        lidx = bidx[hit]
        order = np.argsort(lidx, kind="stable")
        return ltbl.join_from_indices(rtbl, lidx[order], ridx[order], *args)

    def host(self, ctx, lpart, rpart):
        ctx.stats.bump("host_joins")
        if self.origin is not None:
            ctx.stats.bump(f"{self.origin}_joins")
        return lpart.hash_join(rpart, self.left_on, self.right_on, self.how,
                               self.suffix)


def _pipelined_join(ctx, pairs, probe: JoinProbe):
    """Shared double-buffered join driver: for each (l, r) pair, pair i+1's
    keys stage and its probe LAUNCHES while pair i's result resolves (one
    pending slot, cleared as its finisher is called, bounds the extra HBM
    to one in-flight pair). A declined launch goes straight to the host
    join — never re-staging the attempt the launch just proved doomed."""
    pending = None
    for l, r in pairs:
        fin = ctx.launch(probe, l, r)
        if pending is not None:
            out, pending = pending(), None
            yield out
        if fin is not None:
            pending = fin
        else:
            yield probe.host(ctx, l, r)
    if pending is not None:
        out, pending = pending(), None
        yield out


class HashJoinOp(PhysicalOp):
    """Partition-aligned join: bucket i of left joins bucket i of right.
    Upstream ShuffleOps co-partition both sides."""

    def __init__(self, left: PhysicalOp, right: PhysicalOp, left_on, right_on,
                 how: str, schema: Schema, suffix: str = "right."):
        super().__init__([left, right], schema, max(left.num_partitions, right.num_partitions))
        self.how = how
        self.probe = JoinProbe(left_on, right_on, how, suffix)

    def execute(self, inputs, ctx) -> PartStream:
        lbuf = ctx.partition_buffer()
        rbuf = ctx.partition_buffer()
        with ctx.stats.profiler.span("join.build", kind="phase"):
            for p in inputs[0]:
                lbuf.append(p)
            for p in inputs[1]:
                rbuf.append(p)
        n = max(len(lbuf), len(rbuf))
        lschema = self.children[0].schema
        rschema = self.children[1].schema
        # drain() is lazy: a partition's held bytes leave the ledger only when
        # its pair is consumed, and the ref drops right after the join.
        liter = lbuf.drain()
        riter = rbuf.drain()

        def pairs():
            for _ in range(n):
                l = next(liter, None)
                r = next(riter, None)
                if l is None:
                    l = MicroPartition.empty(lschema)
                if r is None:
                    r = MicroPartition.empty(rschema)
                yield l, r

        yield from _pipelined_join(ctx, pairs(), self.probe)

    def describe(self):
        return f"HashJoin[{self.how}]"


class BroadcastJoinOp(PhysicalOp):
    """Collect the small side fully, stream the large side (reference:
    broadcast join strategy, translate.rs join planning)."""

    # set by _translate_join when FDO history (not a static estimate)
    # chose this strategy: (site_fp, max_bytes) mispredict guard + the
    # observation key that keeps the side's history current
    fdo_guard = None
    fdo_obs_key = None

    def __init__(self, big: PhysicalOp, small: PhysicalOp, big_on, small_on,
                 how: str, schema: Schema, small_is_left: bool, suffix: str = "right."):
        super().__init__([big, small], schema, big.num_partitions)
        self.big_on = big_on
        self.small_on = small_on
        self.how = how
        self.small_is_left = small_is_left
        self.probe = (JoinProbe(small_on, big_on, how, suffix) if small_is_left
                      else JoinProbe(big_on, small_on, how, suffix))

    def _filter_prunable(self) -> bool:
        """Whether the streamed (big) side may be pruned by a filter built
        from the replicated side's keys — the shared per-join-type gate
        (exchange.joinfilter.PRUNABLE); the probe here is the big side,
        which is the RIGHT side exactly when the small side is left."""
        from .exchange.joinfilter import prunable

        return prunable(self.how, probe_is_right=self.small_is_left)

    def _build_small_filter(self, small: MicroPartition, ctx):
        """Bloom + min-max filter over the collected small side's keys, or
        None (knob off, ineligible dtypes, any failure — fail-open; the
        ``join.filter`` fault site fires per build attempt)."""
        from . import faults

        if not getattr(ctx.cfg, "runtime_join_filters", True) \
                or not self._filter_prunable():
            return None
        from .exchange.joinfilter import JoinFilterSlot

        slot = JoinFilterSlot(self.small_on, self.big_on,
                              self.children[1].schema,
                              self.children[0].schema, self.how)
        if not slot.eligible:
            return None
        try:
            faults.check("join.filter", ctx.stats)
            slot.begin()
            for t in small.chunk_tables():
                slot.feed(t)
            slot.seal()
        except Exception:
            ctx.stats.bump("join_filter_errors")
            return None
        jf = slot.filter()
        if jf is not None:
            ctx.stats.bump("join_filter_built")
        return jf

    def execute(self, inputs, ctx) -> PartStream:
        with ctx.stats.profiler.span("join.build", kind="phase"):
            small_parts = [p for p in inputs[1]]
            small = (MicroPartition.concat(small_parts) if len(small_parts) > 1
                     else (small_parts[0] if small_parts else MicroPartition.empty(self.children[1].schema)))
            # mesh runners replicate the build keys into every device's HBM
            # here (one ICI broadcast); per-partition probes stay device-local
            small = ctx.prepare_broadcast(small, self.small_on, self.how)
            # runtime join filter: the small side IS the build side — prune
            # each streamed big partition before its probe (fewer rows into
            # the per-pair join; semantics gated per join type)
            jf = self._build_small_filter(small, ctx)
        ctx.stats.bump("broadcast_joins")
        small_bytes = small.size_bytes() or 0
        if self.fdo_obs_key is not None:
            # keep the side's history current even while the broadcast
            # plan serves — a grown side reverts the decision next plan
            ctx.stats.fdo_observe(self.fdo_obs_key, len(small), small_bytes)
        if self.fdo_guard is not None and small_bytes > self.fdo_guard[1]:
            # history said broadcast; the side arrived big. The query
            # completes on this (correct, merely slower) plan — the entry
            # is demoted and the next plan degrades to the uncached hash
            # strategy from the fresh observation above.
            from .adapt.fdo import note_broadcast_mispredict

            note_broadcast_mispredict(self.fdo_guard, small_bytes, ctx,
                                      getattr(ctx, "canonical_fp", ""))

        def pairs():
            from .exchange.joinfilter import prune_partition

            for part in inputs[0]:
                if jf is not None:
                    part = prune_partition(part, jf, self.big_on, ctx)
                yield (small, part) if self.small_is_left else (part, small)

        yield from _pipelined_join(ctx, pairs(), self.probe)

    def describe(self):
        return f"BroadcastJoin[{self.how}]"


class SortMergeJoinOp(PhysicalOp):
    """Distributed sort-merge join with ALIGNED range boundaries: both sides
    sample their join keys into one combined quantile set, range-partition by
    the same boundaries (bucket i of left joins exactly bucket i of right),
    and merge per bucket — no single-partition gather. Reference:
    physical_plan.py:860 (sort_merge_join_aligned_boundaries) + Boundaries
    intersection (daft/runners/partitioning.py:110-166). Per-bucket sorted
    outputs concatenate to a globally key-sorted result, preserving the
    sort-merge contract."""

    def __init__(self, left: PhysicalOp, right: PhysicalOp, left_on, right_on,
                 how: str, schema: Schema, suffix: str = "right."):
        super().__init__([left, right], schema,
                         max(left.num_partitions, right.num_partitions))
        self.left_on = left_on
        self.right_on = right_on
        self.how = how
        self.suffix = suffix

    def execute(self, inputs, ctx) -> PartStream:
        lbuf = ctx.partition_buffer()
        rbuf = ctx.partition_buffer()
        lsamples, rsamples = [], []
        n = self.num_partitions
        ssize = ctx.cfg.sample_size_for_sort
        # keys sampled as partitions stream in: spilled inputs are never
        # re-materialized for boundary estimation
        with ctx.stats.profiler.span("join.build", kind="phase"):
            for p in inputs[0]:
                lsamples.append(sample_partition_keys(p, self.left_on, n,
                                                      ssize))
                lbuf.append(p)
            for p in inputs[1]:
                rsamples.append(sample_partition_keys(p, self.right_on, n,
                                                      ssize))
                rbuf.append(p)
        lschema = self.children[0].schema
        rschema = self.children[1].schema
        if n <= 1 or (len(lbuf) <= 1 and len(rbuf) <= 1):
            # concat needs every partition resident at once (the documented
            # single-partition merge); keep ledger accounting until after
            lparts = lbuf.parts()
            rparts = rbuf.parts()
            l = MicroPartition.concat(lparts) if len(lparts) > 1 else (
                lparts[0] if lparts else MicroPartition.empty(lschema))
            r = MicroPartition.concat(rparts) if len(rparts) > 1 else (
                rparts[0] if rparts else MicroPartition.empty(rschema))
            lbuf.release()
            rbuf.release()
            yield l.sort_merge_join(r, self.left_on, self.right_on, self.how, self.suffix)
            return
        k = len(self.left_on)
        bnds = aligned_boundaries_from_samples([lsamples, rsamples], n)
        ctx.stats.bump("aligned_boundary_shuffles")
        # Mesh path: BOTH sides ride the same aligned-boundary range exchange
        # over ICI; bucket i of each side lands co-partitioned on device i % n
        # with its columns left HBM-resident for the per-bucket merge.
        dev_shuffle = getattr(ctx, "try_device_shuffle", None)
        if dev_shuffle is not None:
            from .parallel.mesh_exec import exchangeable_dtype

            lparts = lbuf.parts()
            rparts = rbuf.parts()
            lrows = sum(len(p) for p in lparts)
            rrows = sum(len(p) for p in rparts)
            eligible = (lrows > 0 and rrows > 0  # empty sides: host handles
                        and all(p.is_loaded() for p in lparts + rparts)
                        and all(exchangeable_dtype(f.dtype) for f in lschema)
                        and all(exchangeable_dtype(f.dtype) for f in rschema))
            if eligible:
                zeros, nf = [False] * k, [None] * k
                # exchange the SMALLER side first: a late ineligibility only
                # detectable at staging (e.g. int64 beyond int32 range with
                # x64 off) then wastes the cheaper collective, not both
                small_left = lrows <= rrows
                first = ((lparts, self.left_on) if small_left
                         else (rparts, self.right_on))
                second = ((rparts, self.right_on) if small_left
                          else (lparts, self.left_on))
                out1 = dev_shuffle(first[0], first[1], n, "range", zeros, nf, bnds)
                out2 = (dev_shuffle(second[0], second[1], n, "range", zeros,
                                    nf, bnds) if out1 is not None else None)
                lout, rout = ((out1, out2) if small_left else (out2, out1))
                if lout is not None and rout is not None:
                    lbuf.release()
                    rbuf.release()
                    ctx.stats.bump("device_aligned_smj_exchanges")
                    for l, r in zip(lout, rout):
                        yield l.sort_merge_join(r, self.left_on, self.right_on,
                                                self.how, self.suffix)
                    return
        lbuckets = [ctx.partition_buffer() for _ in range(n)]
        rbuckets = [ctx.partition_buffer() for _ in range(n)]
        for buf, on, buckets in ((lbuf, self.left_on, lbuckets),
                                 (rbuf, self.right_on, rbuckets)):
            for p in buf.drain():
                pieces = p.partition_by_range(on, bnds, [False] * k, [None] * k)
                for i, piece in enumerate(pieces):
                    # the aligned-boundary exchange is a real exchange:
                    # count its payload at bucket append so this fallback
                    # matches the mesh path's staged-payload accounting
                    nrows = piece.num_rows_or_none()
                    if nrows:
                        ctx.stats.bump("exchange_rows", nrows)
                        pb = piece.size_bytes() or 0
                        if pb:
                            ctx.stats.bump("exchange_bytes", pb)
                    buckets[min(i, n - 1)].append(piece)
        for i in range(n):
            l = (MicroPartition.concat(lbuckets[i].parts()) if len(lbuckets[i]) > 1
                 else (lbuckets[i].parts()[0] if len(lbuckets[i]) else MicroPartition.empty(lschema)))
            r = (MicroPartition.concat(rbuckets[i].parts()) if len(rbuckets[i]) > 1
                 else (rbuckets[i].parts()[0] if len(rbuckets[i]) else MicroPartition.empty(rschema)))
            yield l.sort_merge_join(r, self.left_on, self.right_on, self.how, self.suffix)
            lbuckets[i].release()
            rbuckets[i].release()


class CrossJoinOp(PhysicalOp):
    def __init__(self, left: PhysicalOp, right: PhysicalOp, schema: Schema, suffix: str):
        super().__init__([left, right], schema, left.num_partitions)
        self.suffix = suffix

    def execute(self, inputs, ctx) -> PartStream:
        rparts = [p for p in inputs[1]]
        right = (MicroPartition.concat(rparts) if len(rparts) > 1
                 else (rparts[0] if rparts else MicroPartition.empty(self.children[1].schema)))
        key = "__cross_key"
        rk = right.eval_expression_list(
            [col(c) for c in right.column_names] + [lit(1).alias(key)])
        for part in inputs[0]:
            lk = part.eval_expression_list(
                [col(c) for c in part.column_names] + [lit(1).alias(key)])
            joined = lk.hash_join(rk, [col(key)], [col(key)], "inner", self.suffix)
            keep = [c for c in joined.column_names if c != key]
            yield joined.select_columns(keep).cast_to_schema(self.schema)


# ---------------------------------------------------------------------------
# two-stage aggregation decomposition (reference: translate.rs:761
# populate_aggregation_stages)
# ---------------------------------------------------------------------------

DECOMPOSABLE = {"sum", "count", "mean", "min", "max", "list", "concat", "any_value", "stddev"}

# approximate aggregations decompose through the sketch subsystem
# (daft_tpu/sketch/): stage 1 builds a fixed-size mergeable sketch per
# group, the exchange ships serialized sketch BYTES (a Binary column),
# stage 2 merges registers, and the final projection computes the estimate
# (reference: daft-sketch/hyperloglog stages in translate.rs:761+)
SKETCH_DECOMPOSABLE = {"approx_count_distinct", "approx_percentiles"}


def _strip_alias(e: Expression) -> AggExpr:
    n = e._node
    while isinstance(n, Alias):
        n = n.child
    if not isinstance(n, AggExpr):
        raise ValueError(f"expected aggregation expression, got {e!r}")
    return n


def aggs_decomposable(aggs: List[Expression], include_sketch: bool = False) -> bool:
    allowed = DECOMPOSABLE | (SKETCH_DECOMPOSABLE if include_sketch else set())
    try:
        return all(_strip_alias(e).kind in allowed for e in aggs)
    except ValueError:
        return False


def populate_aggregation_stages(
    aggs: List[Expression],
) -> Tuple[List[Expression], List[Expression], List[Expression]]:
    """Split aggregations into (first_stage, second_stage, final_projection).

    first_stage runs per input partition; second_stage merges partials after a
    shuffle on the group keys; final_projection computes derived results
    (mean = sum/count, stddev = sqrt(m2)). Mirrors translate.rs:761.
    """
    stage1: List[Expression] = []
    stage2: List[Expression] = []
    final: List[Expression] = []
    seen_ids: Dict[Tuple, str] = {}

    def s1(kind: str, child_expr: Expression, tag: str, extra=None) -> str:
        key = (kind, child_expr._node._key(), tag)
        if key in seen_ids:
            return seen_ids[key]
        ident = f"__s1_{len(seen_ids)}_{kind}"
        seen_ids[key] = ident
        stage1.append(Expression(AggExpr(kind, child_expr._node, extra)).alias(ident))
        merge_kind = {"sum": "sum", "count": "sum", "min": "min", "max": "max",
                      "list": "concat", "concat": "concat", "any_value": "any_value",
                      "sketch_hll": "merge_sketch_hll",
                      "sketch_quantile": "merge_sketch_quantile"}[kind]
        stage2.append(Expression(AggExpr(merge_kind, col(ident)._node,
                                         extra if kind == "any_value" else None)).alias(ident))
        return ident

    for e in aggs:
        node = _strip_alias(e)
        alias = e.name()
        child = Expression(node.child)
        k = node.kind
        if k in ("sum", "min", "max"):
            ident = s1(k, child, "")
            final.append(col(ident).alias(alias))
        elif k == "count":
            ident = s1("count", child, node.extra.get("mode", "valid"), dict(node.extra))
            final.append(col(ident).alias(alias))
        elif k == "mean":
            sid = s1("sum", child, "")
            cid = s1("count", child, "valid", {"mode": "valid"})
            final.append((col(sid) / col(cid)).alias(alias))
        elif k == "stddev":
            # population stddev via sum / sum-of-squares / count; the sum and
            # count partials are shared with any sum()/mean() of the same child
            sid = s1("sum", child, "")
            qid = s1("sum", child * child, "")
            cid = s1("count", child, "valid", {"mode": "valid"})
            mean = col(sid) / col(cid)
            var = (col(qid) / col(cid)) - (mean * mean)
            # max(var, 0): clamp tiny negative fp error before sqrt
            clamped = (var + abs(var)) / lit(2.0)
            final.append((clamped ** lit(0.5)).alias(alias))
        elif k == "list":
            ident = s1("list", child, "list")
            final.append(col(ident).alias(alias))
        elif k == "concat":
            ident = s1("concat", child, "concat")
            final.append(col(ident).alias(alias))
        elif k == "any_value":
            ident = s1("any_value", child, "any", dict(node.extra))
            final.append(col(ident).alias(alias))
        elif k == "approx_count_distinct":
            # sketch->merge->estimate: the exchange carries HLL register
            # bytes, never the counted rows (daft_tpu/sketch/hll.py)
            from .expressions import Function

            ident = s1("sketch_hll", child, "hll")
            final.append(Expression(Function(
                "sketch.hll_estimate", [col(ident)._node])).alias(alias))
        elif k == "approx_percentiles":
            from .expressions import Function

            ident = s1("sketch_quantile", child, "qsketch")
            final.append(Expression(Function(
                "sketch.quantile_estimate", [col(ident)._node],
                {"percentiles": node.extra.get("percentiles", 0.5)}))
                .alias(alias))
        else:
            raise ValueError(f"aggregation {k!r} is not decomposable")
    return stage1, stage2, final


# ---------------------------------------------------------------------------
# logical -> physical translation
# ---------------------------------------------------------------------------

def _split_morsels(parts: List[MicroPartition], cfg) -> List[MicroPartition]:
    """Split oversized in-memory partitions into morsels so the worker pool
    has parallel units even for a single-partition source (reference: the
    morsel size driving source chunking, default_morsel_size). Zero-copy
    slices; partition count is fixed here at plan time so aggregate staging
    sees the real parallelism."""
    from .context import resolve_executor_threads

    if getattr(cfg, "use_device_kernels", False):
        # the device path wants whole partitions: one fused kernel over one
        # big resident buffer beats many small dispatches, and splitting
        # would mint fresh MicroPartitions each plan — orphaning the HBM
        # residency caches that make warm queries fast
        return parts
    threads = resolve_executor_threads(cfg)
    if threads <= 1:
        return parts
    morsel = max(int(cfg.default_morsel_size), 1)
    out: List[MicroPartition] = []
    for p in parts:
        n = p.num_rows_or_none()
        if n is None or n <= 2 * morsel:
            out.append(p)
            continue
        k = min(-(-n // morsel), threads * 4)
        step = -(-n // k)
        for s in range(0, n, step):
            out.append(p.slice(s, min(s + step, n)))
    return out


def fuse_for_device(op: PhysicalOp, cfg) -> PhysicalOp:
    """Post-translation fusion: Aggregate directly over a Filter becomes
    FusedFilterAggregateOp. On the device path the predicate runs as a device-side
    mask feeding the segment reductions (no host compaction between them);
    on the host path the fused op executes as ONE acero filter+project+agg
    exec plan (Table.acero_fused_agg) so the filtered intermediate is never
    materialized — both are the analog of the reference's fused streaming
    pipeline (pipeline.rs:141-211)."""
    for i, c in enumerate(op.children):
        op.children[i] = fuse_for_device(c, cfg)
    if isinstance(op, AggregateOp):
        # splice out column-pruning Projects (pure selection, no renames or
        # compute) above or below the filter: the agg only touches its own
        # referenced columns and device staging only transfers those, while a
        # materialized prune would mint a fresh partition each query and
        # orphan the HBM residency cache
        child = op.children[0]
        if isinstance(child, ProjectOp) and _is_pure_column_selection(child.exprs):
            child = child.children[0]
        if isinstance(child, FilterOp):
            fchild = child.children[0]
            if isinstance(fchild, ProjectOp) and _is_pure_column_selection(fchild.exprs):
                fchild = fchild.children[0]
            return FusedFilterAggregateOp(fchild, child.predicate,
                                    op.aggregations, op.groupby, op.schema)
        op.children[0] = child
    return op


def _is_pure_column_selection(exprs) -> bool:
    from .expressions import Column as ColNode

    for e in exprs:
        n = e._node
        if not (isinstance(n, ColNode) and n.cname == e.name()):
            return False
    return True


def translate(plan: LogicalPlan, cfg, morsels: bool = False,
              stats=None) -> PhysicalOp:
    """Public entry: recursive translation + device-path fusion + map-chain
    fusion, so every caller (runners, explain, adaptive) sees the tree that
    actually runs. fuse_for_device runs FIRST so a filter feeding an
    aggregation folds into FusedFilterAggregateOp; fuse_map_chains then
    collapses the residual Project/Filter chains (the passes compose).

    ``stats`` (when given) receives ``compile_wall_ns`` — the fuse-compile
    share of planning, the cost the plan cache's warm path removes and
    which must therefore stay measurable (README "Plan & program cache")."""
    import time as _time

    out = _translate(plan, cfg, morsels)
    if getattr(cfg, "dynamic_batching", True):
        # before the fuse passes: a batch-declared projection must become
        # its own op (and a fusion barrier), not fold into a fused map
        out = _route_batched_udfs(out)
    out = fuse_for_device(out, cfg)
    if getattr(cfg, "expr_fusion", True):
        from .fuse import fuse_map_chains

        t0 = _time.perf_counter_ns()
        out = fuse_map_chains(out, cfg)
        if stats is not None:
            stats.bump("compile_wall_ns", _time.perf_counter_ns() - t0)
    if getattr(cfg, "use_device_kernels", False) and getattr(
            cfg, "device_residency", True):
        # LAST: the segment compiler consumes the trees the fuse passes
        # built (Aggregate-over-FusedMap), collapsing each eligible segment
        # into one HBM-resident DeviceSegmentOp (fuse/segment.py). Part of
        # the timed compile share — the plan cache's warm path skips it,
        # which is what pins warm runs at zero segment compiles.
        from .fuse import compile_plan_segments

        t0 = _time.perf_counter_ns()
        out = compile_plan_segments(out, cfg, stats)
        if stats is not None:
            stats.bump("compile_wall_ns", _time.perf_counter_ns() - t0)
    return out


def _translate(plan: LogicalPlan, cfg, morsels: bool = False) -> PhysicalOp:
    """Translate an (optimized) logical plan to a physical operator tree.

    cfg: ExecutionConfig (broadcast threshold, default partitions, etc.)
    morsels: split oversized in-memory sources into parallel morsels; set
    only under aggregate pipelines (where the two-stage decomposition turns
    extra partitions into parallel stage-1 work) and propagated through the
    transparent map ops (Project/Filter). Ops that would pay for higher
    partition counts with extra shuffles (Sort/Distinct/Join) never see it.
    """
    if isinstance(plan, InMemorySource):
        parts = _split_morsels(plan.partitions, cfg) if morsels else plan.partitions
        return InMemoryOp(parts, plan.schema)

    if isinstance(plan, ScanSource):
        return ScanOp(plan.tasks, plan.schema)

    if isinstance(plan, Project):
        return ProjectOp(_translate(plan.input, cfg, morsels), plan.exprs, plan.schema)

    if isinstance(plan, Filter):
        return FilterOp(_translate(plan.input, cfg, morsels), plan.predicate)

    if isinstance(plan, Limit):
        return LimitOp(_translate(plan.input, cfg), plan.limit)

    if isinstance(plan, Explode):
        return ExplodeOp(_translate(plan.input, cfg), plan.to_explode, plan.schema)

    if isinstance(plan, Unpivot):
        return UnpivotOp(_translate(plan.input, cfg), plan.ids, plan.values,
                         plan.variable_name, plan.value_name, plan.schema)

    if isinstance(plan, Sample):
        return SampleOp(_translate(plan.input, cfg), plan.fraction,
                        plan.with_replacement, plan.seed)

    if isinstance(plan, MonotonicallyIncreasingId):
        return MonotonicIdOp(_translate(plan.input, cfg), plan.column_name, plan.schema)

    if isinstance(plan, Write):
        return WriteOp(_translate(plan.input, cfg), plan.root_dir, plan.format,
                       plan.compression, plan.partition_cols, plan.schema)

    if isinstance(plan, Sort):
        child = _translate(plan.input, cfg)
        if child.num_partitions > 1:
            child = ShuffleOp(child, "range", child.num_partitions, plan.sort_by,
                              plan.descending, plan.nulls_first)
        return SortOp(child, plan.sort_by, plan.descending, plan.nulls_first)

    if isinstance(plan, Repartition):
        child = _translate(plan.input, cfg)
        num = plan.num if plan.num is not None else child.num_partitions
        if plan.scheme == "into":
            if num == child.num_partitions:
                return child
            return CoalesceOp(child, num)
        if plan.scheme == "hash":
            return ShuffleOp(child, "hash", num, plan.by)
        if plan.scheme == "range":
            return ShuffleOp(child, "range", num, plan.by, plan.descending)
        return ShuffleOp(child, "random", num)

    if isinstance(plan, Distinct):
        child = _translate(plan.input, cfg)
        subset = plan.subset
        out = DistinctOp(child, subset)
        if child.num_partitions > 1:
            keys = subset if subset else [col(c) for c in plan.schema.field_names()]
            out = DistinctOp(ShuffleOp(out, "hash", child.num_partitions, keys), subset)
        return out

    if isinstance(plan, Aggregate):
        return _translate_aggregate(plan, cfg)

    if isinstance(plan, Pivot):
        child = _translate(plan.input, cfg)
        return PivotOp(child, plan.groupby, plan.pivot_col, plan.value_col,
                       plan.agg_fn, plan.names, plan.schema)

    if isinstance(plan, Concat):
        l = _translate(plan.input, cfg)
        r = _translate(plan.other, cfg)
        return ConcatOp(l, r, plan.schema)

    if isinstance(plan, Join):
        op = _translate_join(plan, cfg)
        if plan.origin is not None and hasattr(op, "probe"):
            op.probe.origin = plan.origin  # counted where the pair is joined
        return op

    raise ValueError(f"cannot translate logical node {plan.name()}")


def _translate_aggregate(plan: Aggregate, cfg) -> PhysicalOp:
    child = _translate(plan.input, cfg, morsels=True)
    nparts = child.num_partitions

    if nparts == 1:
        return AggregateOp(child, plan.aggregations, plan.groupby, plan.schema)

    include_sketch = bool(getattr(cfg, "sketch_aggregations", True))
    if not aggs_decomposable(plan.aggregations, include_sketch):
        # non-decomposable (count_distinct / skew / approx_* with the sketch
        # subsystem disabled): shuffle raw rows by key, then full agg per
        # partition
        if plan.groupby:
            shuffled = ShuffleOp(child, "hash", nparts, plan.groupby)
            return AggregateOp(shuffled, plan.aggregations, plan.groupby, plan.schema)
        cd = _global_count_distinct_plan(plan, child, nparts)
        if cd is not None:
            return cd
        gathered = GatherOp(child)
        return AggregateOp(gathered, plan.aggregations, [], plan.schema)

    stage1, stage2, final = populate_aggregation_stages(plan.aggregations)
    key_cols = [col(e.name()) for e in plan.groupby]

    p1 = AggregateOp(child, stage1, plan.groupby,
                     _stage_schema(plan.input.schema, stage1, plan.groupby))
    if plan.groupby:
        from .adapt import fdo as _fdo

        # feedback-directed fan-out: the internal exchange of a repeated
        # aggregation shape emits only as many partitions as its RECORDED
        # map-side payload needs (shrink-only; engine-chosen counts only).
        # Hash modulus stays nparts and adjacent buckets merge at reduce
        # time, so rows AND row order are byte-identical to the unresized
        # plan — only the partition count (stage-2 invocations,
        # downstream fan-in) shrinks.
        exchanged: PhysicalOp = ShuffleOp(p1, "hash", nparts, key_cols)
        resized = _fdo.agg_shuffle_fanout(plan, nparts)
        if resized:
            exchanged.reduce_to = resized
            exchanged.num_partitions = resized
        okey = _fdo.agg_observation_key(plan)
        if okey:
            exchanged.fdo_obs_key = okey
        # hierarchical exchange: fold map-side pieces headed to the same
        # destination through the stage-2 combine BEFORE they buffer
        # (intra-host combine -> inter-host all_to_all; the mesh path
        # mirrors it ahead of the ICI collective). Only when the fold is
        # schema-closed and every stage-2 kind is a known-safe merge.
        if getattr(cfg, "hierarchical_exchange_combine", True):
            from .exchange.combine import combine_spec_applicable

            if combine_spec_applicable(stage2, key_cols, p1.schema):
                exchanged.combine = (stage2, key_cols)
    else:
        exchanged = GatherOp(p1)
    p2 = AggregateOp(exchanged, stage2, key_cols,
                     _stage_schema(p1.schema, stage2, key_cols))
    final_exprs = key_cols + final
    out = ProjectOp(p2, final_exprs, plan.schema)
    # two-stage float results can drift in dtype (e.g. mean); align to plan schema
    return _cast_to(out, plan.schema)


def _global_count_distinct_plan(plan: Aggregate, child: PhysicalOp,
                                nparts: int) -> Optional[PhysicalOp]:
    """Global count_distinct without gathering raw rows: hash-shuffle rows by
    the counted VALUE (equal values co-locate), count distinct per partition,
    sum the tiny per-partition partials. Applies when every aggregation in
    the list is a count_distinct."""
    from .expressions import Expression

    specs = []
    for e in plan.aggregations:
        node = e._node
        while isinstance(node, Alias):
            node = node.child
        if not (isinstance(node, AggExpr) and node.kind == "count_distinct"):
            return None
        specs.append((e, node))
    if len(specs) != 1:
        return None  # different value columns would need different shuffles
    e, node = specs[0]
    alias = e.name()
    shuffled = ShuffleOp(child, "hash", nparts, [Expression(node.child)])
    p1 = AggregateOp(shuffled, [e], [],
                     _stage_schema(plan.input.schema, [e], []))
    gathered = GatherOp(p1)  # nparts partial counts — rows, not raw data
    p2 = AggregateOp(gathered, [col(alias).sum().alias(alias)], [],
                     _stage_schema(p1.schema, [col(alias).sum().alias(alias)], []))
    return _cast_to(p2, plan.schema)


def _stage_schema(input_schema: Schema, aggs: List[Expression], groupby: List[Expression]) -> Schema:
    from .schema import Field

    fields = []
    for e in groupby:
        f = e._node.to_field(input_schema)
        fields.append(Field(e.name(), f.dtype))
    for e in aggs:
        f = e._node.to_field(input_schema)
        fields.append(Field(e.name(), f.dtype))
    return Schema(fields)


class _CastOp(PhysicalOp):
    def __init__(self, child: PhysicalOp, schema: Schema):
        super().__init__([child], schema, child.num_partitions)

    def execute(self, inputs, ctx) -> PartStream:
        for part in inputs[0]:
            yield part.cast_to_schema(self.schema)

    def describe(self):
        return "CastToSchema"


def _cast_to(op: PhysicalOp, schema: Schema) -> PhysicalOp:
    if op.schema == schema:
        return op
    return _CastOp(op, schema)


def _translate_join(plan: Join, cfg) -> PhysicalOp:
    from .adapt import fdo as _fdo

    left = _translate(plan.left, cfg)
    right = _translate(plan.right, cfg)

    if plan.how == "cross":
        return CrossJoinOp(left, right, plan.schema, plan.suffix)

    strategy = plan.strategy
    fdo_side = None
    if strategy is None:
        # feedback-directed flip (daft_tpu/adapt/fdo.py): a side whose
        # RECORDED size sits safely under the broadcast threshold flips
        # this join on the first run of a repeated shape — no AQE
        # materialization barrier needed. Active only inside a planning
        # collector scope; declines everywhere else.
        fdo_side = _fdo.join_strategy_hint(plan)
        strategy = ("broadcast" if fdo_side is not None
                    else _choose_join_strategy(plan, cfg))
    if strategy == "broadcast" and plan.how == "outer":
        # an outer join preserves both sides; replaying the replicated side per
        # big-side partition would duplicate its unmatched rows
        strategy = "hash"

    if strategy == "broadcast":
        lsize = plan.left.approx_size_bytes()
        rsize = plan.right.approx_size_bytes()
        if fdo_side is not None:
            broadcast_left = fdo_side == "left"
        else:
            broadcast_left = _broadcast_side(plan, lsize, rsize) == "left"
        if broadcast_left:
            op = BroadcastJoinOp(right, left, plan.right_on, plan.left_on,
                                 plan.how, plan.schema, small_is_left=True,
                                 suffix=plan.suffix)
        else:
            op = BroadcastJoinOp(left, right, plan.left_on, plan.right_on,
                                 plan.how, plan.schema, small_is_left=False,
                                 suffix=plan.suffix)
        if fdo_side is not None:
            # runtime mispredict detector: the materialized small side is
            # checked against the guard; history keeps observing it so a
            # grown side reverts the decision on the next plan
            op.fdo_guard = _fdo.broadcast_guard(plan, fdo_side)
            op.fdo_obs_key = _fdo.observation_key(
                plan.left if fdo_side == "left" else plan.right)
        return op

    if strategy == "sort_merge":
        return SortMergeJoinOp(left, right, plan.left_on, plan.right_on,
                               plan.how, plan.schema, plan.suffix)

    # hash: co-partition both sides when >1 partition
    nparts = max(left.num_partitions, right.num_partitions)
    if nparts > 1:
        lshuf = ShuffleOp(left, "hash", nparts, plan.left_on)
        rshuf = ShuffleOp(right, "hash", nparts, plan.right_on)
        # FDO observation: each side's exchange records the rows/bytes
        # that actually crossed it, keyed by the side's canonical subtree
        # fingerprint — the history a future plan's broadcast flip reads
        lkey = _fdo.observation_key(plan.left)
        if lkey:
            lshuf.fdo_obs_key = lkey
        rkey = _fdo.observation_key(plan.right)
        if rkey:
            rshuf.fdo_obs_key = rkey
        # runtime join filter (sideways information passing): the left
        # exchange — drained first by HashJoinOp — builds a Bloom+min-max
        # filter from its keys; the right exchange prunes with it before
        # bucketing/spill/merge. Gated per join type: inner/semi — either
        # side prunable (we prune the one whose exchange runs second);
        # left — right side only; right/anti/outer — decline (the probe
        # side's unmatched rows are output).
        from .exchange.joinfilter import JoinFilterSlot, prunable

        # the probe side is the RIGHT exchange (drained second)
        if getattr(cfg, "runtime_join_filters", True) \
                and prunable(plan.how, probe_is_right=True):
            slot = JoinFilterSlot(plan.left_on, plan.right_on,
                                  left.schema, right.schema, plan.how)
            if slot.eligible:
                lshuf.filter_feed = slot
                rshuf.probe_filter = slot
        left, right = lshuf, rshuf
    return HashJoinOp(left, right, plan.left_on, plan.right_on, plan.how,
                      plan.schema, plan.suffix)


def _broadcast_side(plan: Join, lsize, rsize) -> str:
    """Which side to replicate. The preserved side of an outer join can't be
    broadcast (its unmatched rows must appear exactly once)."""
    if plan.how in ("left", "semi", "anti"):
        return "right"
    if plan.how == "right":
        return "left"
    # inner: smaller side
    if lsize is not None and (rsize is None or lsize <= rsize):
        return "left"
    return "right"


def _choose_join_strategy(plan: Join, cfg) -> str:
    lsize = plan.left.approx_size_bytes()
    rsize = plan.right.approx_size_bytes()
    threshold = cfg.broadcast_join_size_bytes_threshold
    if plan.how == "outer":
        return "hash"
    side = _broadcast_side(plan, lsize, rsize)
    size = lsize if side == "left" else rsize
    if size is not None and size <= threshold:
        return "broadcast"
    return "hash"
