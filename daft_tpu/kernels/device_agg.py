"""Fused device groupby-aggregation.

ONE jitted program per plan shape evaluates every aggregation input projection
and its masked segment reduction on device, with an optional fused filter
predicate that stays a mask (no host compaction) — the TPU analog of the
reference's fused streaming pipeline (src/daft-local-execution/src/pipeline.rs:141-211
and the grouped-agg sinks in src/daft-table/src/ops/agg.rs).

Division of labor (SURVEY §7): group keys compute their dense codes ON
DEVICE (_group_codes_kernel: sort + boundary scan + first-occurrence
remap) for 1-4 stageable keys — integer/date values, plain string columns
via their sorted dictionary codes, multi-key via mixed-radix packing
(null-free); anything else falls back to the host dictionary encode
(Table._group_codes). Either way the VPU does the O(rows) work:
projections fused into masked `segment_sum/min/max` reductions with
static segment counts (padded to a power of two so XLA compiles once per
bucket, not once per cardinality).

32-bit mode (real TPUs, x64 off): float64 inputs compute as float32; per-call
partials return to the host which combines across partitions in float64, so
multi-partition totals keep ~1e-7 relative accuracy. Integer sums narrow to
int32 and are overflow-guarded: the kernel also returns max|v| and the masked
row count, and the host re-runs that aggregate on the host path if
n * max|v| could exceed int32 (rare; correctness over speed).
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..datatypes import DataType
from ..profile import timeline
from .device import (
    _ONEHOT_MAX_SEGMENTS,
    DENSE_MAX_SEGMENTS,
    compile_projection,
    fetch,
    segment_first_index,
    segment_reduce,
    size_bucket,
    stage_table_columns,
    x64_enabled,
)

# agg kinds with a device segment reduction. mean decomposes to sum+count.
_DEVICE_AGG_KINDS = {"sum", "count", "min", "max", "mean"}

_AGG_CACHE: Dict = {}


def _unwrap(expr):
    from ..expressions import AggExpr, Alias

    node = expr._node
    while isinstance(node, Alias):
        node = node.child
    return node if isinstance(node, AggExpr) else None


@functools.partial(jax.jit, static_argnames=())
def _group_codes_kernel(vals, valid, n):
    """Dense group codes for ONE integer key column, fully on device:
    sort -> boundary detect -> scan -> scatter, then remap codes to
    FIRST-OCCURRENCE order so the output group order matches the host
    dictionary-encode exactly (including the SQL rule that null keys form
    one group). Returns (codes [b] int32, num_groups, first_rows [b],
    uniq_vals [b], uniq_valid [b]) — the uniq arrays are meaningful for the
    first num_groups lanes, ordered by first occurrence."""
    b = vals.shape[0]
    idx = jnp.arange(b, dtype=jnp.int32)
    oob = idx >= n                      # padding lanes beyond the real rows
    isnull = (~valid) & (~oob)          # null KEYS group together (SQL)
    big = jnp.iinfo(vals.dtype).max
    k = jnp.where(valid, vals, big)
    perm = jnp.lexsort((k, isnull.astype(jnp.int32), oob.astype(jnp.int32)))
    sk = k[perm]
    snull = isnull[perm]
    soob = oob[perm]
    prev_diff = jnp.concatenate([
        jnp.ones((1,), bool),
        (sk[1:] != sk[:-1]) | (snull[1:] != snull[:-1])])
    boundary = (~soob) & prev_diff
    codes_sorted = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    codes_sorted = jnp.maximum(codes_sorted, 0)  # padding lanes -> group 0
    codes = jnp.zeros(b, jnp.int32).at[perm].set(codes_sorted)
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    # first-occurrence row per group; padding contributes the sentinel b
    first = jnp.full(b, b, jnp.int32).at[codes].min(jnp.where(oob, b, idx))
    order = jnp.argsort(first)          # empty/sentinel groups sort last
    inv = jnp.zeros(b, jnp.int32).at[order].set(jnp.arange(b, dtype=jnp.int32))
    codes = inv[codes]
    first_rows = first[order]
    safe_rows = jnp.minimum(first_rows, b - 1)
    return codes, num_groups, first_rows, vals[safe_rows], valid[safe_rows]


def _stage_group_key(table, key_expr, cache):
    """(vals, valid) int lanes for ONE group key: integer/date expressions
    via the join-key stager; plain STRING columns via their sorted
    dictionary codes (dense ints already — the device kernel neither knows
    nor cares that they decode to text); transformed-string keys
    (upper/substr/length/fill_null chains over one string column) via a
    host transform of the dictionary gathered by code
    (device.dict_transform_lane)."""
    from ..expressions import normalize_literals
    from .device import (_plain_string_column, _rewrite_between,
                         _string_dict_value_shape, dict_transform_lane,
                         size_bucket)
    from .device_join import _stage_key

    staged = _stage_key(table, key_expr, cache)
    if staged is not None:
        return staged
    # normalize ONCE, with the same rewrites normalize_and_check applies
    # (a Between inside a row-local tree must produce the SAME node key
    # string_transform_env caches under, or the lane stages twice)
    try:
        node = _rewrite_between(
            normalize_literals(key_expr._node, table.schema), table.schema)
    except (ValueError, KeyError):
        return None
    cname = _plain_string_column(node, table.schema)
    if cname is not None:
        staged_cols = stage_table_columns(table, [cname],
                                          size_bucket(len(table)), cache)
        if staged_cols is None:
            return None
        _env, dcs = staged_cols
        dc = dcs[cname]
        if dc.dictionary is None:
            return None
        return dc.values, dc.valid
    # transformed-string keys: no projection-compilability gate — the
    # transform evaluates on host over the dictionary. (INT-valued
    # transforms — length/find — never reach here: _stage_key stages them
    # as compiled int expressions through the same transform lane.)
    shape = _string_dict_value_shape(node, table.schema)
    if shape is None:
        return None
    lane = dict_transform_lane(table, shape, size_bucket(len(table)), cache)
    if lane is None:
        return None
    vals, valid, _tuniq = lane
    return vals, valid


def _try_device_group_codes(table, group_by, stage_cache, n: int):
    """(codes_dev, uniq Table, num_groups) via the device kernel for 1-4
    stageable keys — integer/date values, string dictionary codes, packed
    mixed-radix for multi-key (null-free only: packing collapses null
    components). Unique key ROWS are gathered on host by first-occurrence
    index, so the group order matches the host dictionary encode exactly.
    Returns None when ineligible (host _group_codes handles everything)."""
    from ..series import Series

    lanes = _staged_group_lanes(table, group_by, stage_cache, n)
    if lanes is None:
        return None
    vals, valid = lanes
    codes, num_groups, first_rows, _uv, _um = _group_codes_kernel(
        vals, valid, jnp.int32(n))
    num_groups, first_rows = fetch((num_groups, first_rows))
    num_groups = int(num_groups)  # bounds the segment bucket
    first = np.asarray(first_rows)[:num_groups]
    import pyarrow as pa

    # gather the num_groups first-occurrence ROWS first, then evaluate the
    # key expressions over just those — O(groups) host work, not O(rows)
    first_tbl = table.take(Series.from_arrow(
        pa.array(first.astype(np.uint64)), "idx"))
    uniq = first_tbl.eval_expression_list(list(group_by))
    return codes, uniq, num_groups


def _staged_group_lanes(table, keys, stage_cache, n: int):
    """ONE (vals, valid) int lane for 1-4 group/distinct keys: single keys
    stage directly (nulls fine — the kernel groups them); multi-key packs
    mixed-radix, which is only null-faithful when every component is
    null-free (a null component would collapse distinct tuples like
    (1, null)/(2, null) into one packed-null group), so nullable multi-key
    inputs decline. Shared by the groupby and distinct paths."""
    from .device_join import _pack_composite_keys

    staged = [_stage_group_key(table, k, stage_cache) for k in keys]
    if any(s is None for s in staged):
        return None
    if len(staged) == 1:
        return staged[0]
    # ONE fused reduction + sync for the nullability check, not one/key
    all_valid = bool(fetch(
        jnp.all(jnp.stack([jnp.all(m[:n]) for _, m in staged]))))
    if not all_valid:
        return None
    packed = _pack_composite_keys([staged])
    if packed is None:
        return None
    (vals, valid), = packed
    return vals, valid


def device_distinct_indices(table, keys, stage_cache, n: int):
    """First-occurrence row indices of the distinct key tuples, computed on
    device via _group_codes_kernel (row order preserved — same contract as
    Table.distinct's host dictionary encode). Multi-column keys pack through
    the join layer's mixed-radix packing, which is only null-faithful when
    every component is null-free: a null component would collapse distinct
    tuples like (1, null)/(2, null) into one packed-null group, so nullable
    multi-key inputs decline to the host path. Returns np.ndarray or None."""
    lanes = _staged_group_lanes(table, keys, stage_cache, n)
    if lanes is None:
        return None
    vals, valid = lanes
    _, num_groups, first_rows, _, _ = _group_codes_kernel(
        vals, valid, jnp.int32(n))
    num_groups, first_rows = fetch((num_groups, first_rows))
    return np.asarray(first_rows)[:int(num_groups)]


def group_codes_cached(table, group_by, stage_cache: Optional[dict], n: int,
                       b: int, stats=None):
    """(codes_dev, uniq Table|None, num_groups) for ``group_by`` over
    ``table``, cached with the partition under the stage cache (the
    dictionary encode over string keys is the dominant per-query host cost
    on resident data). Device kernel for 1-4 stageable keys, host
    ``Table._group_codes`` otherwise; ungrouped degenerates to one group.
    Shared by the staged aggregation path and the resident segment runtime
    (fuse/segment.py) so both key the SAME cache entries — a staged run
    warms the resident run and vice versa."""
    from ..table import _group_codes

    codes_key = ("groupcodes", tuple(e._node._key() for e in group_by), b)
    cached = stage_cache.get(codes_key) if stage_cache is not None else None
    if cached is None:
        if 1 <= len(group_by) <= 4:
            # stageable keys (int/date values, string dictionary codes,
            # packed for multi-key): codes computed ON DEVICE (sort +
            # boundary scan), keeping the O(rows) bookkeeping off the host
            try:
                cached = _try_device_group_codes(table, group_by,
                                                 stage_cache, n)
            except Exception as e:
                cached = None  # the host dictionary encode below answers
                if stats is None:
                    raise  # no query to report to: the caller's attempt does
                stats.note_device_error("device.group_codes", e)
            if cached is not None and stats is not None:
                stats.bump("device_group_codes")
        if cached is None:
            if group_by:
                key_tbl = table.eval_expression_list(list(group_by))
                codes_np, uniq = _group_codes(key_tbl)
                num_groups = len(uniq)
            else:
                codes_np = np.zeros(n, dtype=np.int64)
                uniq = None
                num_groups = 1
            codes_dev = jnp.asarray(np.pad(codes_np.astype(np.int32), (0, b - n)))
            cached = (codes_dev, uniq, num_groups)
        if stage_cache is not None:
            stage_cache[codes_key] = cached
    return cached


def _plan_agg_specs(to_agg, schema, predicate=None):
    """Shared eligibility prologue for the async kernel and the planner's
    static check — ONE implementation so the two can never drift. Returns
    (specs, child_nodes, pred_nodes) or None when any aggregation kind,
    count mode, child expression, or predicate is device-ineligible."""
    from .device import normalize_and_check

    specs = []  # (alias, kind, AggExpr node, count_mode)
    child_exprs = []
    for e in to_agg:
        node = _unwrap(e)
        if node is None or node.kind not in _DEVICE_AGG_KINDS:
            return None
        if node.kind == "count" and node.extra.get("mode", "valid") not in (
                "valid", "all", "null"):
            return None
        specs.append((e.name(), node.kind, node, node.extra.get("mode", "valid")))
        child_exprs.append(_ExprView(node.child))
    child_nodes = normalize_and_check(child_exprs, schema)
    if child_nodes is None:
        return None
    pred_nodes = None
    if predicate is not None:
        pred_nodes = normalize_and_check([predicate], schema)
        if pred_nodes is None:
            return None
    return specs, child_nodes, pred_nodes


def agg_plan_device_compilable(to_agg, schema, predicate=None) -> bool:
    """Static shape check (no data, no staging): used by the executor to
    choose the double-buffered driver before any partition exists."""
    try:
        return _plan_agg_specs(to_agg, schema, predicate) is not None
    except Exception:
        return False


def device_grouped_agg_async(table, to_agg, group_by,
                             stage_cache: Optional[dict] = None,
                             predicate=None, stats=None):
    """Fused grouped aggregation for one partition on device, split into a
    dispatch (staging + the jitted launch happen now) and a deferred resolver
    (ONE result fetch + host assembly when called) — the executor stages
    partition i+1 while the device reduces partition i. Honest caveat: on a
    COLD stage cache the dispatch itself still pays small device syncs (the
    group-count fetch bounding the segment bucket, and the wrap-guard's
    min/max when int64 arithmetic is present), which queue behind the
    previous partition's compute; warm partitions dispatch sync-free.

    `to_agg`: aggregation Expressions (kinds sum/count/min/max/mean);
    `group_by`: key Expressions — 1-4 stageable keys (int/date values,
    plain string columns via dictionary codes, multi-key packed null-free)
    code on device, anything else on host; `predicate`: optional filter
    fused as a mask.

    Returns a zero-arg resolver yielding a host Table (keys + aggregates,
    first-occurrence group order, matching the host path) — the resolver
    returns None if the int-sum overflow guard trips at materialization —
    or None immediately when ineligible.
    """
    from ..schema import Field, Schema
    from ..table import Table

    n = len(table)
    if n == 0:
        return None
    schema = table.schema

    # --- plan the aggregate list (shared with the planner's static check) --
    planned = _plan_agg_specs(to_agg, schema, predicate)
    if planned is None:
        return None
    specs, child_nodes, pred_nodes = planned

    # --- host bookkeeping: group codes (cached with the partition — the
    # dictionary encode over string keys is the dominant per-query host cost
    # on resident data) ----------------------------------------------------
    b = size_bucket(n)
    codes_dev, uniq, num_groups = group_codes_cached(table, group_by,
                                                     stage_cache, n, b, stats)

    # --- stage inputs -----------------------------------------------------
    from .device import (device_required_columns, epoch_cmp_env,
                         epoch_cmps_for, int64_wrap_safe, string_joint_env,
                         string_literal_env, string_lut_env,
                         string_transform_env)

    check_nodes = list(child_nodes) + (list(pred_nodes) if pred_nodes else [])
    epoch_cmps = epoch_cmps_for(check_nodes, schema)
    needed = device_required_columns(check_nodes, schema)
    staged = stage_table_columns(table, sorted(needed), b, stage_cache)
    if staged is None:
        return None
    env, dcs = staged
    if not int64_wrap_safe(check_nodes, schema, env, stage_cache, b):
        return None  # int64 arithmetic could wrap in int32 lanes
    env = string_literal_env(check_nodes, schema, dcs, env)
    if env is None:
        return None  # a string comparison lost its dictionary
    env = epoch_cmp_env(epoch_cmps, schema, table, b, stage_cache, env)
    if env is None:
        return None  # an epoch literal failed to convert
    env = string_lut_env(check_nodes, schema, dcs, env)
    if env is None:
        return None  # a LUT predicate lost its dictionary
    joint_aux: dict = {}
    env = string_joint_env(check_nodes, schema, dcs, env, joint_aux)
    if env is None:
        return None  # a joint-group column lost its dictionary
    env = string_transform_env(check_nodes, schema, table, b, stage_cache,
                               env, joint_aux)
    if env is None:
        return None  # a transformed-string lane failed to stage
    from .device import transform_cmp_env

    env = transform_cmp_env(check_nodes, schema, table, b, stage_cache, dcs,
                            env, joint_aux)
    if env is None:
        return None  # a cross-column transform compare lost a dictionary

    # --- compile + run ONE fused program ---------------------------------
    from ..context import get_context

    outs_dev = launch_agg(
        tuple(child_nodes), pred_nodes[0] if pred_nodes else None, schema,
        tuple(sorted(needed)), tuple(s[1] for s in specs),
        tuple(s[3] for s in specs), num_groups,
        bool(get_context().execution_config.use_pallas_segment_sums),
        env, codes_dev, n, stage_cache)  # async: device computes from here

    def resolve():
        outs = fetch(outs_dev)

        # --- assemble host result ----------------------------------------
        from ..series import Series

        out_cols: List[Series] = list(uniq._columns) if uniq is not None else []
        out_fields: List[Field] = list(uniq.schema) if uniq is not None else []
        agg_outs = outs[:len(specs)]
        for (alias, kind, agg_node, _mode), child_nd, out in zip(
                specs, child_nodes, agg_outs):
            expected_dt = agg_node.to_field(schema).dtype
            dictionary = None
            if expected_dt.is_string():
                # string min/max reduce over sorted-dictionary CODES (order-
                # isomorphic): the result must decode through the child
                # column's dictionary — or, for a fill_null/if_else child,
                # its joint-group dictionary — or it would silently return
                # code digits
                from .device import string_output_dictionary

                dictionary = string_output_dictionary(child_nd, schema, dcs,
                                                      joint_aux)
                if dictionary is None:
                    return None  # cannot decode: host path recomputes
            merged = _finish_agg(kind, out, num_groups, expected_dt, n,
                                 dictionary=dictionary)
            if merged is None:
                return None  # overflow guard tripped: host path recomputes
            out_cols.append(merged.rename(alias))
            out_fields.append(Field(alias, expected_dt))
        result = Table(Schema(out_fields), out_cols)
        if pred_nodes is not None:
            # prune filtered-away groups; order survivors like the host path
            # (first occurrence within the filtered rows)
            sel_cnt, first_idx = (np.asarray(a)[:num_groups] for a in outs[-1])
            if group_by:
                surv = np.nonzero(sel_cnt > 0)[0]
                order = surv[np.argsort(first_idx[surv], kind="stable")]
                if len(order) != num_groups or (order != np.arange(num_groups)).any():
                    import pyarrow as pa

                    result = result.take(Series.from_arrow(
                        pa.array(order.astype(np.uint64)), "idx"))
        return result

    return resolve


class _ExprView:
    """Minimal Expression-shaped wrapper so helper APIs taking Expressions
    can accept bare nodes."""

    __slots__ = ("_node",)

    def __init__(self, node):
        self._node = node

    def name(self):
        return self._node.name()


def _sum_form(gb: int, use_pallas: bool) -> str:
    """The form an aggregate program's float sums take over a bucket of
    ``gb`` segments: "dense" (per-group masked reductions, every small
    bucket), "sorted" (segment_reduce's sorted-segment sums: every bucket
    over its one-hot cap, past which even a one-lane-tile one-hot block
    outgrows VMEM), "kernel" (the batched pallas one-hot matmul: 32-bit
    mode, between the two) or "onehot" (segment_reduce's one-hot form)."""
    if gb <= DENSE_MAX_SEGMENTS:
        return "dense"
    if gb > _ONEHOT_MAX_SEGMENTS:
        return "sorted"
    if use_pallas and not x64_enabled():
        return "kernel"
    return "onehot"


def launch_agg(child_nodes, pred_node, schema, input_names, kinds, modes,
               num_groups: int, use_pallas: bool, env, codes_dev, n: int,
               stage_cache: Optional[dict]):
    """Compile (once a plan shape and segment bucket) and launch the fused
    aggregate program over staged inputs; returns its device outputs
    without waiting. Shared by the staged path and the resident segment
    runtime (fuse/segment.py). Bumps ``agg_reduce_dense``,
    ``agg_reduce_kernel`` or ``agg_reduce_sorted`` by the form the program's
    float sums take (the one-hot form has no counter)."""
    # static segment bucket: the power of two over the groups (seven sum
    # columns over 64M rows: 4.6 ms at 2 and 4, 5.3 at 8, 8.6 at 16, 20.9 at
    # 32; tools/segment_sum_sweep.py), and never one (device._dense_hits)
    gb = max(2, 1 << (num_groups - 1).bit_length())
    with timeline.part("dispatch.lookup", "dispatch_lookup_ns"):
        run = _compile_agg(child_nodes, pred_node, schema, input_names,
                           kinds, modes, gb, use_pallas)
    form = _sum_form(gb, use_pallas)
    if form == "dense" or (form in ("kernel", "sorted") and any(
            kind in ("sum", "mean") and nd.to_field(schema).dtype.is_floating()
            for nd, kind in zip(child_nodes, kinds))):
        timeline.add(f"agg_reduce_{form}", 1)
    # the row-count scalar lives on device with the partition, so a warm
    # query makes zero uploads and ONE result fetch
    nkey = ("nrows", n)
    n_dev = stage_cache.get(nkey) if stage_cache is not None else None
    if n_dev is None:
        n_dev = jnp.int32(n)
        if stage_cache is not None:
            stage_cache[nkey] = n_dev
    with timeline.part("dispatch.call", "dispatch_call_ns"):
        return run(env, codes_dev, n_dev)


def _compile_agg(child_nodes, pred_node, schema, input_names, kinds, modes, gb,
                 use_pallas: bool = False):
    key = (tuple(n._key() for n in child_nodes),
           pred_node._key() if pred_node is not None else None,
           tuple((f.name, f.dtype) for f in schema), input_names, kinds, modes,
           gb, x64_enabled(), use_pallas)
    if key in _AGG_CACHE:
        return _AGG_CACHE[key]

    child_run, _ = compile_projection(list(child_nodes), schema, input_names)
    pred_run = None
    if pred_node is not None:
        pred_run, _ = compile_projection([pred_node], schema, input_names)

    from .pallas_ops import segment_sums_lanes

    pallas_ok = _sum_form(gb, use_pallas) == "kernel"

    @jax.jit
    def run(env, codes, n):
        inbounds = jnp.arange(codes.shape[0], dtype=jnp.int32) < n
        if pred_run is not None:
            (pv, pm), = pred_run(env)
            sel = pv & pm & inbounds  # invalid predicate rows filter out (SQL WHERE)
        else:
            sel = inbounds
        # In 32-bit mode every float sum accumulates in float32 anyway, so
        # above the dense bound the batched pallas kernel (ALL float-sum
        # columns in ONE one-hot MXU pass, pallas_ops.py) keeps the
        # segment_sum route's accuracy contract; x64 mode keeps exact
        # float64 segment sums.
        fused_sums = []  # (slot in outs, pre-masked float32 column, cnt)
        outs = []
        for (v, m), kind, mode in zip(child_run(env), kinds, modes):
            m = m & sel
            if kind == "count":
                if mode == "all":
                    contrib = sel
                elif mode == "null":
                    contrib = sel & ~m
                else:
                    contrib = m
                cnt, _ = segment_reduce(contrib, contrib, codes, gb, "count")
                outs.append(cnt)
                continue
            if kind in ("sum", "mean"):
                # accumulate in the widest same-class dtype (int8 inputs must
                # not sum in int8)
                if jnp.issubdtype(v.dtype, jnp.floating):
                    acc = v.astype(jnp.float64 if x64_enabled() else jnp.float32)
                elif v.dtype == jnp.bool_:
                    acc = v.astype(jnp.int64 if x64_enabled() else jnp.int32)
                elif jnp.issubdtype(v.dtype, jnp.unsignedinteger):
                    acc = v.astype(jnp.uint64 if x64_enabled() else jnp.uint32)
                else:
                    acc = v.astype(jnp.int64 if x64_enabled() else jnp.int32)
                cnt, _ = segment_reduce(m, m, codes, gb, "count")
                if pallas_ok and jnp.issubdtype(acc.dtype, jnp.floating):
                    fused_sums.append((len(outs),
                                       jnp.where(m, acc, 0.0).astype(jnp.float32),
                                       cnt))
                    outs.append(None)  # back-filled from the batched kernel
                    continue
                vals, valid = segment_reduce(acc, m, codes, gb, "sum")
                if jnp.issubdtype(acc.dtype, jnp.integer) and not x64_enabled():
                    # overflow guard operands: masked max|v| for the host check
                    absv = jnp.where(m, jnp.abs(v.astype(jnp.float32)), 0.0)
                    outs.append((vals, valid, cnt, jnp.max(absv)))
                else:
                    outs.append((vals, valid, cnt, jnp.float32(0)))
                continue
            # min / max
            vals, valid = segment_reduce(v, m, codes, gb, kind)
            outs.append((vals, valid))
        if fused_sums:
            # rows on the lane axis: (K, n) values, (1, n) codes. The
            # columns are already zero wherever the mask or `sel` is false.
            vk = jnp.stack([col for _, col, _ in fused_sums], axis=0)
            sums = segment_sums_lanes(codes[None, :], vk, gb,
                                      jax.default_backend() == "cpu")
            for j, (slot, _col, cnt) in enumerate(fused_sums):
                outs[slot] = (sums[j], cnt > 0, cnt, jnp.float32(0))
        if pred_run is not None:
            # group-survival data: codes/uniq were built from the UNFILTERED
            # table, so the host must drop groups with no selected rows and
            # reorder survivors by first selected row (host semantics:
            # first-occurrence order of the filtered table)
            sel_cnt, _ = segment_reduce(sel, sel, codes, gb, "count")
            outs.append((sel_cnt, segment_first_index(sel, codes, gb)))
        return outs

    _AGG_CACHE[key] = run
    return run


def _finish_agg(kind, out, num_groups, expected_dt: DataType, n,
                dictionary=None):
    """Device partials -> host Series of the expected dtype (or None when the
    int32 overflow guard fired and the host must recompute). `dictionary`
    decodes string min/max code results."""
    import pyarrow as pa

    from ..series import Series
    from .device import DeviceColumn, unstage

    if kind == "count":
        vals = np.asarray(out)[:num_groups]
        return Series.from_arrow(pa.array(vals.astype(np.uint64)), "o", expected_dt)
    if kind in ("sum", "mean"):
        vals, valid, cnt, max_abs = out
        vals = np.asarray(vals)
        valid = np.asarray(valid)
        if np.issubdtype(vals.dtype, np.integer) and not x64_enabled():
            # guards BOTH sum and mean — a wrapped int32 sum poisons either
            if float(n) * float(max_abs) >= 2**31 - 1:
                return None  # could have wrapped: recompute on host
        if kind == "mean":
            cnt = np.asarray(cnt)[:num_groups]
            with np.errstate(invalid="ignore", divide="ignore"):
                mv = vals[:num_groups].astype(np.float64) / cnt.astype(np.float64)
            arr = pa.array(mv, pa.float64())
            if not valid[:num_groups].all():
                arr = pa.compute.if_else(pa.array(valid[:num_groups]), arr,
                                         pa.nulls(num_groups, pa.float64()))
            return Series.from_arrow(arr, "o", expected_dt)
        dc = DeviceColumn(vals, valid, num_groups, expected_dt)
        return unstage(dc)
    # min / max
    vals, valid = out
    dc = DeviceColumn(np.asarray(vals), np.asarray(valid), num_groups,
                      expected_dt, dictionary=dictionary)
    return unstage(dc)
