"""Fused-program emission + the `FusedMapOp` physical operator.

`compile_chain` turns a Project/Filter op chain into a `FusedProgram`:

- **host path**: one pass per partition — per segment, scratch columns
  (pinned UDFs + cross-segment CSE carries) append to the working set, the
  segment mask compacts it, and the final projection evaluates every output
  in ONE `eval_expression_list` (the table-level structural memo makes the
  hash-consed shared subtrees evaluate exactly once). No intermediate
  partition is ever materialized.
- **device path**: the WHOLE DAG — every mask and every computed output —
  goes through `kernels/device.normalize_and_check` and runs as ONE jit
  program behind the existing device breaker; the host then ANDs the mask
  columns and compacts once, taking the input columns the chain passes
  through unchanged from the input table. N staged dispatches and N
  intermediate materializations become one XLA-fused kernel over the
  resident buffer.

The planner pass `fuse_map_chains` (called from `physical.translate` behind
``cfg.expr_fusion``) replaces each maximal chain with a `FusedMapOp`. Any
compile-time failure — including an armed ``fuse.compile`` fault — falls
back to the unfused op chain, never a query failure. The hard invariant is
that results are byte-identical with fusion on or off.
"""

from __future__ import annotations

import threading
from typing import List, Optional, Tuple

from .. import faults
from ..expressions import Alias, Expression, col, required_columns
from ..physical import (DeviceStep, PhysicalOp, _exprs_compile,
                        _launch_exprs, _selected_column, summarize_exprs)
from ..schema import Field, Schema
from ..table import Table
from .graph import (
    MASK_PREFIX,
    FusedGraph,
    FuseDecline,
    build_fused_graph,
)


class FusedProgram:
    """Executable form of a fused map chain (host + optional device plan)."""

    def __init__(self, graph: FusedGraph, out_schema: Schema):
        self.graph = graph
        self.out_schema = out_schema
        self.n_masks = len(graph.device_masks)
        self.has_masks = self.n_masks > 0
        # count-preserving chains (no filter) keep exact scan row counts
        # through multi-host deferral
        self.count_preserving = not self.has_masks

        aug_fields = list(graph.input_schema)
        host_segments: List[Tuple[List[Expression], Optional[Expression]]] = []
        for seg in graph.segments:
            lets: List[Expression] = []
            for name, body in seg.lets:
                dt = body.to_field(Schema(aug_fields)).dtype
                aug_fields.append(Field(name, dt))
                lets.append(Expression(Alias(body, name)))
            mask_expr = None
            if seg.mask is not None:
                mdt = seg.mask.to_field(Schema(aug_fields)).dtype
                if not (mdt.is_boolean() or mdt.is_null()):
                    raise FuseDecline(f"mask resolves to {mdt}, not bool")
                mask_expr = Expression(seg.mask)
            host_segments.append((lets, mask_expr))
        self._host_segments = host_segments

        aug = Schema(aug_fields)
        out_names = [n for n, _ in graph.outputs]
        if out_names != out_schema.field_names():
            raise FuseDecline("fused outputs do not match the chain schema")
        self.output_exprs: List[Expression] = []
        for (name, node), field in zip(graph.outputs, out_schema):
            dt = node.to_field(aug).dtype
            if dt != field.dtype:
                # inlining changed type resolution (e.g. a weak literal
                # adopting a different operand dtype across a stage
                # boundary): byte-identity cannot be guaranteed — decline
                raise FuseDecline(
                    f"output {name!r} resolves to {dt} fused vs "
                    f"{field.dtype} unfused")
            self.output_exprs.append(Expression(Alias(node, name)))

        # input columns the fused pass actually reads (dead-column
        # elimination: everything else never leaves the source partition)
        req = set()
        input_names = set(graph.input_schema.field_names())
        for _lets, _mask in host_segments:
            for e in _lets:
                req.update(required_columns(e))
            if _mask is not None:
                req.update(required_columns(_mask))
        for e in self.output_exprs:
            req.update(required_columns(e))
        self.required_input_columns = req & input_names

        # one-program device plan: masks first, then outputs. Pinned UDFs
        # never compile for the device, so pin-bearing programs stay
        # host-only; carries are host-only too (XLA CSEs the shared DAG
        # itself), so the device sees the pre-carry roots.
        if graph.has_pins:
            self.device_exprs = None
        else:
            self.device_exprs = (
                [Expression(Alias(m, f"{MASK_PREFIX}{i}"))
                 for i, m in enumerate(graph.device_masks)]
                + [Expression(Alias(node, name))
                   for name, node in graph.device_outputs])
        # an output that is an input column unchanged is the host's: the
        # map's own launch neither stages nor returns it, and holds only
        # the lanes its masks and computed outputs read (over a partition
        # of millions of rows the copies were most of its device memory
        # and of its gather). A resident segment still takes every output
        # of device_exprs as a lane.
        self.passthrough = {}
        for name, node in graph.device_outputs:
            src = _selected_column(Expression(node))
            if src in input_names:
                self.passthrough[name] = src
        self.launch_exprs = None if self.device_exprs is None else [
            e for e in self.device_exprs if e.name() not in self.passthrough]

    # ------------------------------------------------------------- host
    def run_host(self, table):
        """Single-pass host evaluation: segments of scratch-eval + mask
        compaction over a pruned working set, then one fused projection."""
        cols = table.column_names
        needed = [c for c in cols if c in self.required_input_columns]
        if not needed and cols:
            needed = cols[:1]  # literal-only outputs still broadcast to n
        work = table if needed == cols else table.select_columns(needed)
        for lets, mask_expr in self._host_segments:
            for let_e in lets:
                work = work.eval_expression_list(
                    [col(c) for c in work.column_names] + [let_e])
            if mask_expr is not None:
                work = work.filter([mask_expr])
        return work.eval_expression_list(self.output_exprs)

    # ----------------------------------------------------------- device
    def assemble_device(self, result_table, table):
        """The launch's result over ``table`` -> output table: each output
        from the program, or from ``table`` where it passes an input column
        through; then AND the mask columns (kleene, same null semantics as
        sequential filters) and compact the output columns once."""
        cols = [table.get_column(self.passthrough[name]).rename(name)
                if name in self.passthrough
                else result_table.get_column(name)
                for name in self.out_schema.field_names()]
        out = Table(Schema([Field(c.name, c.dtype) for c in cols]), cols)
        if not self.n_masks:
            return out
        mask_cols = result_table._columns[:self.n_masks]
        mask = mask_cols[0]
        for m in mask_cols[1:]:
            mask = mask & m
        return out.filter_with_mask(mask)


def compile_chain(stages, input_schema: Schema,
                  out_schema: Schema) -> FusedProgram:
    """stages (bottom-up ``("project", exprs) | ("filter", pred)``) ->
    FusedProgram. Raises FuseDecline when fusion is unsafe."""
    graph = build_fused_graph(stages, input_schema)
    return FusedProgram(graph, out_schema)


def record_fusion(ctx, g: FusedGraph, device_program: bool) -> None:
    """The chain-level counters of one fused chain, and its compile outcome
    as a typed profile event: what fused, how much it collapsed, and
    whether a one-program device plan exists."""
    ctx.stats.bump("fused_chains")
    ctx.stats.bump("fused_ops_eliminated", g.n_ops - 1)
    if g.cse_hits:
        ctx.stats.bump("cse_hits", g.cse_hits)
    if ctx.stats.profiler.armed:
        ctx.stats.profiler.event("fusion", ops=g.n_ops, cse_hits=g.cse_hits,
                                 device_program=device_program)


class QueryLatches:
    """Once-a-query latches of an operator: plain bool attributes (the op
    tree is rebuilt per translate and adapt/plancache.clone_plan resets
    them, so instance state is query-scoped) behind one lock. The lock is
    per-process coordination state, not program identity: it is dropped
    when the operator ships over the dist/ worker transport (the receiving
    process records against ITS stats)."""

    def __getstate__(self):
        state = dict(self.__dict__)
        state.pop("_record_lock", None)
        return state

    def __setstate__(self, state):
        self.__dict__.update(state)
        self._record_lock = threading.Lock()

    def _first(self, latch: str) -> bool:
        """True for exactly one caller a query."""
        if getattr(self, latch):
            return False
        with self._record_lock:
            if getattr(self, latch):
                return False
            setattr(self, latch, True)
        return True


class FusedMapOp(QueryLatches, DeviceStep, PhysicalOp):
    """A maximal Project/Filter chain collapsed to one single-pass operator.

    Its DeviceStep is the whole chain as ONE jit program when eligible, the
    segmented host pass otherwise, driven like ProjectOp's and FilterOp's.
    Byte-identical to the chain it replaced; `fused_chains` /
    `fused_ops_eliminated` / `cse_hits` counters make the collapse visible
    in every plan dump."""

    # the fused program is a composition of row-local projections and
    # filters, so the chain streams morsel-wise exactly like its
    # constituent ops would (pin-bearing programs are declined by the
    # driver's UDF gate via _map_exprs)
    morsel_streamable = True

    def __init__(self, child: PhysicalOp, program: FusedProgram,
                 schema: Schema):
        super().__init__([child], schema, child.num_partitions)
        self.program = program
        self._recorded = False
        self._record_lock = threading.Lock()

    def _record(self, ctx) -> None:
        """Chain-level counters, once per query."""
        if self._first("_recorded"):
            record_fusion(ctx, self.program.graph, self.has_program)

    # ------------------------------------------------------ the device step
    dispatches = "device_fused_map_dispatches"
    fallbacks = "device_fused_map_fallbacks"
    site = "device.fused_map"

    @property
    def has_program(self) -> bool:
        # a chain that only selects and renames columns computes nothing
        return bool(self.program.launch_exprs)

    def compilable(self) -> bool:
        return self.has_program and _exprs_compile(
            self.program.launch_exprs, self.children[0].schema)

    def count(self, stats, n: int) -> None:
        # the legacy per-op class counters advance by the chain's op counts
        # so per-path attribution stays comparable with the unfused engine
        g = self.program.graph
        stats.bump("device_fused_maps", n)
        if g.n_project_ops:
            stats.bump("device_projections", n * g.n_project_ops)
        if g.n_filter_ops:
            stats.bump("device_filters", n * g.n_filter_ops)

    def launch(self, ctx, part):
        return _launch_exprs(part, self.program.launch_exprs)

    def finish(self, ctx, out, part):
        # the chain's host half (mask compaction): the operator's own time
        with ctx.stats.profiler.span("fuse.assemble", kind="phase"):
            return part._wrap(self.program.assemble_device(out,
                                                          part.table()))

    def host(self, ctx, part):
        g = self.program.graph
        ctx.stats.bump("host_fused_maps")
        if g.n_project_ops:
            ctx.stats.bump("host_projections", g.n_project_ops)
        if g.n_filter_ops:
            ctx.stats.bump("host_filters", g.n_filter_ops)
        return part._wrap(self.program.run_host(part.table()))

    def defer(self, part):
        # the whole fused program joins the pending op chain (one deferred
        # single-pass map), preserving per-host scan locality exactly like
        # the unfused chain's deferred Project/Filter ops would
        program = self.program
        return part.with_pending_op(
            lambda t: program.run_host(t), program.out_schema,
            count_preserving=program.count_preserving)

    def _map_exprs(self):
        # the ORIGINAL chain expressions: UDF parallel-safety and resource
        # accounting see exactly what the unfused chain declared
        return self.program.graph.source_exprs

    def describe(self) -> str:
        g = self.program.graph
        n_exprs = self.n_exprs
        body = summarize_exprs(self.program.output_exprs)
        # masks (and scratch lets) are part of the chain's identity: the
        # plan fingerprint hashes this display, so `where x > 5` and
        # `where x > 9` must not collide just because fusion folded the
        # filter out of the op list
        segs = []
        for lets, mask in self.program._host_segments:
            if lets:
                segs.append("let " + summarize_exprs(lets))
            if mask is not None:
                segs.append("where " + summarize_exprs([mask]))
        tail = (" | " + " | ".join(segs)) if segs else ""
        return (f"FusedMap[{g.n_ops} ops, {n_exprs} exprs, "
                f"{g.cse_hits} cse]: {body}{tail}")

    @property
    def n_exprs(self) -> int:
        return (len(self.program.output_exprs) + self.program.n_masks
                + sum(len(lets) for lets, _ in self.program._host_segments))


def fuse_map_chains(op: PhysicalOp, cfg) -> PhysicalOp:
    """Planner pass: collapse every maximal chain of >= 2 map-class ops
    (ProjectOp/FilterOp) into one FusedMapOp. Runs inside
    physical.translate() AFTER fuse_for_device, so a filter feeding an
    aggregation has already folded into FusedFilterAggregateOp and only the
    residual map chain fuses here (the two passes compose). Chains that
    decline — UDF resource requests, aggregations, type-resolution drift,
    an armed ``fuse.compile`` fault — stay as the unfused op chain."""
    from ..physical import FilterOp, ProjectOp

    if isinstance(op, (ProjectOp, FilterOp)):
        chain = [op]
        cur = op
        while isinstance(cur.children[0], (ProjectOp, FilterOp)):
            cur = cur.children[0]
            chain.append(cur)
        base = fuse_map_chains(cur.children[0], cfg)
        cur.children[0] = base
        if len(chain) >= 2:
            fused = _try_fuse_chain(chain, base)
            if fused is not None:
                return fused
        return op
    for i, c in enumerate(op.children):
        op.children[i] = fuse_map_chains(c, cfg)
    return op


def _try_fuse_chain(chain: List[PhysicalOp],
                    base: PhysicalOp) -> Optional[FusedMapOp]:
    """Compile one top-down chain, or None to keep it unfused. EVERY
    failure mode lands here — a fusion-compiler defect degrades to the
    pre-fusion plan instead of failing the query (proven by the armed
    ``fuse.compile`` fault-site test)."""
    from ..physical import ProjectOp

    try:
        faults.check("fuse.compile")
        stages = []
        for op in reversed(chain):
            if isinstance(op, ProjectOp):
                stages.append(("project", list(op.exprs)))
            else:
                stages.append(("filter", op.predicate))
        program = compile_chain(stages, base.schema, chain[0].schema)
    except Exception:
        return None
    return FusedMapOp(base, program, chain[0].schema)
