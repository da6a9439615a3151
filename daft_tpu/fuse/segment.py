"""Plan-segment compiler: whole project→filter→agg segments stay HBM-resident.

``compile_plan_segments`` (wired into ``physical.translate`` after
``fuse_for_device``/``fuse_map_chains``, behind ``cfg.device_residency``)
finds maximal device-eligible segments — an Aggregate (plain or
filter-fused) whose child is a fused map chain (or a single Project/Filter)
— and collapses each into one ``DeviceSegmentOp``. At runtime the segment
executes as a resident pipeline (``run_segment_async``):

- ONE host→device stage at segment entry (the map program's input columns,
  reused from the partition's HBM residency cache);
- the map program's outputs — every mask lane and every intermediate
  column the aggregation reads — stay on device as DeviceArrays and feed
  the fused aggregation program directly (``env2``), with the mask
  conjunction acting as the aggregation predicate;
- ONE device→host gather at segment exit (the aggregated partials).

Zero Arrow materialization happens between the map and the aggregation:
the ``FusedMapOp → Aggregate`` handoff that previously round-tripped
Arrow↔DeviceArray is elided (counted as ``device_handoffs_elided``).

Sharding contract: consecutive programs run on the same default device
with identical size buckets, so the map outputs are consumed by the
aggregation with no resharding. The intermediates are not donated: the
aggregation's outputs are group-length and its inputs row-length, so XLA
has no output to alias a donated input to (on the v5e the donation was
reported "not usable" for every buffer and did nothing).

Invariants (tests/test_segment.py): results are byte-identical with
``cfg.device_residency`` off; ANY segment-compile or resident-run failure
— including an armed ``fuse.segment`` fault — degrades to the staged
per-op path, never a query failure; the whole leg sits behind the existing
DeviceHealth breaker; warm plan-cache runs perform zero segment compiles
(the pass runs inside ``translate``, which a warm hit skips entirely).
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

from .. import faults
from ..datatypes import DataType
from ..expressions import Alias, BinaryOp, Column, Expression
from ..micropartition import MicroPartition
from ..physical import (
    AggregateOp,
    DeviceStep,
    FilterOp,
    FusedFilterAggregateOp,
    PhysicalOp,
    ProjectOp,
)
from ..schema import Field, Schema
from .compile import (FusedMapOp, FusedProgram, QueryLatches, compile_chain,
                      record_fusion)
from .graph import MASK_PREFIX

__all__ = ["DeviceSegmentOp", "SegmentProgram", "compile_plan_segments",
           "run_segment_async", "process_counters"]


# ---------------------------------------------------------------------------
# process-level counters (the dt.health() "device" section mirrors these —
# health snapshots are engine-wide, RuntimeStats is per-query)
# ---------------------------------------------------------------------------

_PROC_LOCK = threading.Lock()
_PROC_COUNTERS = {
    "resident_segments": 0,
    "handoffs_elided": 0,
    "segment_fallbacks": 0,
    "segment_compiles": 0,
    "hbm_resident_bytes_high_water": 0,
}


def _proc_bump(key: str, n: int = 1) -> None:
    with _PROC_LOCK:
        _PROC_COUNTERS[key] += n


def _proc_max(key: str, n: int) -> None:
    with _PROC_LOCK:
        if n > _PROC_COUNTERS[key]:
            _PROC_COUNTERS[key] = n


def process_counters() -> dict:
    """Snapshot of the process-wide residency counters (obs/health.py)."""
    with _PROC_LOCK:
        return dict(_PROC_COUNTERS)


def reset_process_counters() -> None:
    """Test hook: zero the process-wide residency counters."""
    with _PROC_LOCK:
        for k in _PROC_COUNTERS:
            _PROC_COUNTERS[k] = 0


# ---------------------------------------------------------------------------
# compile-time artifact
# ---------------------------------------------------------------------------

def _peel(node):
    while isinstance(node, Alias):
        node = node.child
    return node


class SegmentProgram:
    """Everything the resident runtime needs, planned once at translate:

    - ``seg_exprs``: the pruned device map program (mask aliases + only the
      intermediate columns the aggregation actually reads);
    - ``inter_schema``: the schema those outputs form (mask lanes as bool
      fields, so the aggregation's predicate/children normalize against it);
    - ``specs``/``child_nodes``/``pred_node``/``kinds``/``modes``: the
      planned aggregation (``_plan_agg_specs`` over ``inter_schema``, the
      mask conjunction folded into the predicate);
    - ``gb_inputs``: group keys remapped to the INPUT table's columns —
      group codes compute over the unfiltered input (rows stay aligned with
      the mask lanes; the pruning output restores filtered-first-occurrence
      group order, exactly the staged FusedFilterAggregate semantics).

    The per-binding sharding key of a compiled segment is
    (nodes, inter_schema, input_names, kinds, modes, segment bucket,
    x64 mode) — ``_compile_agg``'s cache key — so repeat traffic
    with the same shape and size bucket reuses ONE XLA executable, and the
    plan cache (adapt/plancache.py) serves the whole SegmentProgram warm
    with zero translate/segment-compile calls."""

    __slots__ = ("seg_exprs", "input_schema", "inter_schema", "specs",
                 "child_nodes", "pred_node", "input_names", "kinds", "modes",
                 "gb_inputs", "has_groupby", "n_masks")

    def __init__(self, seg_exprs, input_schema, inter_schema, specs,
                 child_nodes, pred_node, input_names, kinds, modes,
                 gb_inputs, n_masks):
        self.seg_exprs = seg_exprs
        self.input_schema = input_schema
        self.inter_schema = inter_schema
        self.specs = specs
        self.child_nodes = tuple(child_nodes)
        self.pred_node = pred_node
        self.input_names = tuple(input_names)
        self.kinds = tuple(kinds)
        self.modes = tuple(modes)
        self.gb_inputs = list(gb_inputs)
        self.has_groupby = bool(gb_inputs)
        self.n_masks = n_masks


def _map_program_for(child: PhysicalOp) -> Optional[FusedProgram]:
    """The device map program of the segment's map stage: a FusedMapOp
    carries one already; a lone Project/Filter (below the 2-op fusion
    threshold) compiles through the same ``compile_chain`` machinery."""
    if isinstance(child, FusedMapOp):
        return child.program
    base = child.children[0]
    if isinstance(child, ProjectOp):
        stages: List[Tuple] = [("project", list(child.exprs))]
    elif isinstance(child, FilterOp):
        stages = [("filter", child.predicate)]
    else:
        return None
    return compile_chain(stages, base.schema, child.schema)


def _try_compile_segment(op, child, cfg) -> Optional[SegmentProgram]:
    """One segment compile, or None to keep the staged ops. EVERY failure
    mode lands here — including an armed ``fuse.segment`` fault — and
    degrades to the per-op plan, never a query failure."""
    from ..kernels.device import (device_required_columns, epoch_cmps_for,
                                  normalize_and_check)
    from ..kernels.device_agg import _ExprView, _plan_agg_specs

    try:
        faults.check("fuse.segment")
        program = _map_program_for(child)
        if program is None or program.device_exprs is None:
            return None
        input_schema = child.children[0].schema
        if normalize_and_check(program.device_exprs, input_schema) is None:
            return None

        # the intermediate schema the aggregation normalizes against:
        # mask lanes first (bool), then the map chain's output columns
        inter_fields = [Field(f"{MASK_PREFIX}{i}", DataType.bool())
                        for i in range(program.n_masks)]
        inter_fields += [Field(f.name, f.dtype) for f in child.schema]
        inter_schema = Schema(inter_fields)

        # group keys must be bare passthroughs of input columns: codes are
        # computed over the UNFILTERED input table, so the key values must
        # exist there unchanged (computed keys would need the intermediate
        # gathered back to host — exactly the handoff this pass deletes)
        out_nodes = dict(program.graph.device_outputs)
        gb_inputs: List[Expression] = []
        for e in (getattr(op, "groupby", None) or []):
            node = _peel(e._node)
            if not isinstance(node, Column):
                return None
            mapped = out_nodes.get(node.cname)
            if mapped is None:
                return None
            mapped = _peel(mapped)
            if not isinstance(mapped, Column):
                return None
            gb_inputs.append(
                Expression(Alias(Column(mapped.cname), e._node.name())))

        # mask conjunction (+ a fused filter's predicate) becomes the
        # aggregation predicate: masked segment reductions + the pruning
        # output replace the staged path's host compaction
        pred = None
        for i in range(program.n_masks):
            m = Column(f"{MASK_PREFIX}{i}")
            pred = m if pred is None else BinaryOp("&", pred, m)
        if isinstance(op, FusedFilterAggregateOp):
            pnode = op.predicate._node
            pred = pnode if pred is None else BinaryOp("&", pred, pnode)

        planned = _plan_agg_specs(
            list(op.aggregations), inter_schema,
            predicate=_ExprView(pred) if pred is not None else None)
        if planned is None:
            return None
        specs, child_nodes, pred_nodes = planned
        pred_node = pred_nodes[0] if pred_nodes else None

        # residency gates: the aggregation env is built purely from the map
        # program's on-device outputs — no dictionaries, no host-evaluated
        # epoch lanes — so anything needing those declines here
        check_nodes = list(child_nodes) + (
            [pred_node] if pred_node is not None else [])
        if epoch_cmps_for(check_nodes, inter_schema):
            return None
        needed = sorted(device_required_columns(check_nodes, inter_schema))
        if not needed:
            return None  # nothing resident to hand off: no segment to win
        for nm in needed:
            if inter_schema[nm].dtype.is_string():
                return None  # string lanes need the dictionaries host-side
        needed_set = set(needed)
        seg_exprs = [e for e in program.device_exprs
                     if e.name() in needed_set]
        if not seg_exprs:
            return None

        kinds = tuple(s[1] for s in specs)
        modes = tuple(s[3] for s in specs)
        return SegmentProgram(seg_exprs, input_schema, inter_schema, specs,
                              child_nodes, pred_node, tuple(needed), kinds,
                              modes, gb_inputs, program.n_masks)
    except Exception:
        return None


def compile_plan_segments(op: PhysicalOp, cfg, stats=None) -> PhysicalOp:
    """Planner pass (physical.translate, after fuse_for_device +
    fuse_map_chains): collapse each eligible Aggregate-over-map-chain into
    one DeviceSegmentOp. ``segment_compiles`` counts real compiles — a warm
    plan-cache hit skips translate entirely, so warm runs pin at zero."""
    for i, c in enumerate(op.children):
        op.children[i] = compile_plan_segments(c, cfg, stats)
    if isinstance(op, (AggregateOp, FusedFilterAggregateOp)):
        child = op.children[0]
        if isinstance(child, (FusedMapOp, ProjectOp, FilterOp)):
            prog = _try_compile_segment(op, child, cfg)
            if prog is not None:
                if stats is not None:
                    stats.bump("segment_compiles")
                _proc_bump("segment_compiles")
                return DeviceSegmentOp(child, op, prog)
    return op


# ---------------------------------------------------------------------------
# the physical operator
# ---------------------------------------------------------------------------

class DeviceSegmentOp(QueryLatches, DeviceStep, PhysicalOp):
    """A project→filter→agg plan segment compiled for whole-segment device
    residency. Its DeviceStep is the resident pipeline when the partition
    is device-eligible, the retained staged ops (``map_op`` then
    ``agg_op``) otherwise — byte-identical either way. NOT
    morsel-streamable: the aggregation is a pipeline breaker, and it keeps
    the lanes it stages in its input partition's cache for the next
    query."""

    morsel_streamable = False

    def __init__(self, map_op: PhysicalOp, agg_op: PhysicalOp,
                 program: SegmentProgram):
        super().__init__([map_op.children[0]], agg_op.schema,
                         map_op.children[0].num_partitions)
        self.map_op = map_op
        self.agg_op = agg_op
        self.program = program
        self._recorded = False
        self._resident_recorded = False
        self._record_lock = threading.Lock()

    def _record(self, ctx) -> None:
        """Once per query: the fusion counters the staged plan would have
        bumped (the chain IS still fused — residency only changes where its
        outputs live), so counter-level dashboards read identically with
        residency on or off."""
        if isinstance(self.map_op, FusedMapOp) and self._first("_recorded"):
            record_fusion(ctx, self.map_op.program.graph, True)

    def _record_resident(self, ctx) -> None:
        """Once per query, on the FIRST successful resident execution."""
        if self._first("_resident_recorded"):
            ctx.stats.bump("device_resident_segments")
            _proc_bump("resident_segments")

    # ------------------------------------------------------ the device step
    # the whole leg sits behind the DeviceHealth breaker: a launch exception
    # (an armed ``fuse.segment`` fault included) records a breaker failure,
    # a decline releases the probe slot, and either is answered by the
    # staged ops and counted a fallback, like a failed resolve
    counter = "device_aggregations"
    dispatches = "segment_dispatches"
    fallbacks = "segment_fallbacks"
    site = "fuse.segment"
    span = "fuse.segment"
    counts_failed_launch = True
    # no static `compilable` check is declared, so `device_pipelinable` is
    # False: execute_plan runs segments on the worker pool, through
    # `ExecutionContext.run` (ROADMAP D3 carries the question)

    def launch(self, ctx, part):
        return run_segment_async(part.table(), self.program,
                                 part.device_stage_cache(),
                                 stats=ctx.stats, cfg=ctx.cfg)

    def finish(self, ctx, out, part):
        # ONE boundary crossed resident: the map→agg Arrow round-trip of
        # the staged plan did not happen
        ctx.stats.bump("device_handoffs_elided")
        self._record_resident(ctx)
        _proc_bump("handoffs_elided")
        return MicroPartition.from_table(out)

    def host(self, ctx, part):
        """The segment as its retained staged ops: the fused map chain,
        Arrow materialization, then the (filter-fused) aggregation, EXACTLY
        the plan the segment pass collapsed, so results are byte-identical.
        Through the driver and not the ops' ``map_partition``: the fusion
        counters are this op's ``_record`` to bump, once."""
        return ctx.run(self.agg_op, ctx.run(self.map_op, part))

    def fall_back(self, ctx, part):
        _proc_bump("segment_fallbacks")
        return super().fall_back(ctx, part)

    def map_empty(self, ctx):
        # same contract as the staged AggregateOp: a global agg over zero
        # partitions still yields one row (count=0, sum=null, ...)
        if not (getattr(self.agg_op, "groupby", None) or []):
            yield MicroPartition.empty(self.map_op.schema).agg(
                self.agg_op.aggregations, None)

    def _map_exprs(self):
        return list(self.map_op._map_exprs()) + list(self.agg_op._map_exprs())

    def describe(self) -> str:
        p = self.program
        return (f"DeviceSegment[{len(p.seg_exprs)} resident col(s), "
                f"{p.n_masks} mask(s)]: {self.map_op.describe()} => "
                f"{self.agg_op.describe()}")


# ---------------------------------------------------------------------------
# the resident runtime
# ---------------------------------------------------------------------------

def run_segment_async(table, prog: SegmentProgram,
                      stage_cache: Optional[dict], stats=None, cfg=None):
    """Dispatch one partition through the resident segment pipeline:
    stage inputs → launch the map program → feed its on-device outputs
    straight into the fused aggregation program → return a zero-arg
    resolver for the ONE result fetch. Returns None when this partition is
    resident-ineligible (the caller degrades to the staged per-op path);
    raises only for real device failures (the breaker's concern)."""
    from ..kernels.device import (_stage_and_run, fetch, int64_wrap_safe,
                                  size_bucket)
    from ..kernels.device_agg import (_finish_agg, group_codes_cached,
                                      launch_agg)

    # runtime firing point of the fuse.segment fault site: the resident
    # handoff (the compile-time firing point is _try_compile_segment)
    faults.check("fuse.segment", stats)

    n = len(table)
    if n == 0:
        return None

    staged = _stage_and_run(table, prog.seg_exprs, stage_cache)
    if staged is None:
        return None
    outs, _dts, _nodes, _dcs, _aux = staged  # async: device computes already
    env2 = {e.name(): out for e, out in zip(prog.seg_exprs, outs)}

    b = size_bucket(n)
    check_nodes = list(prog.child_nodes) + (
        [prog.pred_node] if prog.pred_node is not None else [])
    # the wrap guard runs over the INTERMEDIATE env (stage_cache=None: these
    # lanes are fresh compute, not cacheable staged columns — and must not
    # collide cache keys with same-named input columns)
    if not int64_wrap_safe(check_nodes, prog.inter_schema, env2, None, b):
        return None

    # group codes over the INPUT table: rows stay aligned with the mask
    # lanes (no compaction happened); the pruning output below restores the
    # filtered first-occurrence group order the host path produces
    codes_dev, uniq, num_groups = group_codes_cached(
        table, prog.gb_inputs, stage_cache, n, b, stats)

    hbm = sum(int(v.nbytes) + int(m.nbytes) for v, m in env2.values())
    if stats is not None:
        stats.bump_max("hbm_resident_bytes_high_water", hbm)
    _proc_max("hbm_resident_bytes_high_water", hbm)

    outs_dev = launch_agg(
        prog.child_nodes, prog.pred_node, prog.inter_schema, prog.input_names,
        prog.kinds, prog.modes, num_groups,
        bool(getattr(cfg, "use_pallas_segment_sums", False)),
        env2, codes_dev, n, stage_cache)  # async: device computes from here

    def resolve():
        import numpy as np

        from ..schema import Field as _Field
        from ..schema import Schema as _Schema
        from ..series import Series
        from ..table import Table

        got = fetch(outs_dev)
        out_cols = list(uniq._columns) if uniq is not None else []
        out_fields = list(uniq.schema) if uniq is not None else []
        agg_outs = got[:len(prog.specs)]
        for (alias, kind, agg_node, _mode), out in zip(prog.specs, agg_outs):
            expected_dt = agg_node.to_field(prog.inter_schema).dtype
            if expected_dt.is_string():
                return None  # unreachable: string intermediates declined
            merged = _finish_agg(kind, out, num_groups, expected_dt, n,
                                 dictionary=None)
            if merged is None:
                return None  # overflow guard tripped: staged path recomputes
            out_cols.append(merged.rename(alias))
            out_fields.append(_Field(alias, expected_dt))
        result = Table(_Schema(out_fields), out_cols)
        if prog.pred_node is not None and prog.has_groupby:
            # prune filtered-away groups; order survivors like the host
            # path (first occurrence within the filtered rows)
            sel_cnt, first_idx = (np.asarray(a)[:num_groups]
                                  for a in got[-1])
            surv = np.nonzero(sel_cnt > 0)[0]
            order = surv[np.argsort(first_idx[surv], kind="stable")]
            if len(order) != num_groups \
                    or (order != np.arange(num_groups)).any():
                import pyarrow as pa

                result = result.take(Series.from_arrow(
                    pa.array(order.astype(np.uint64)), "idx"))
        return result

    return resolve
