"""Rule-based logical optimizer.

Role-equivalent to the reference's
src/daft-plan/src/logical_optimization/optimizer.rs:126 rule batches:
PushDownFilter, PushDownProjection (column pruning into sources),
PushDownLimit, DropRepartition, and projection folding. Rules rewrite the
logical tree to a fixed point (bounded passes), then a single column-pruning
pass installs scan pushdowns.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Set, Tuple

from .expressions import Expression, col
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
    expr_has_special,
    expr_input_columns,
    is_trivial_passthrough,
    substitute_columns,
)


def optimize(plan: LogicalPlan, max_passes: int = 8) -> LogicalPlan:
    for _ in range(max_passes):
        new = _apply_once(plan)
        if new is None:
            break
        plan = new
    plan = _prune_columns(plan, None)
    # pruning may introduce Projects that enable further pushdown
    for _ in range(max_passes):
        new = _apply_once(plan)
        if new is None:
            break
        plan = new
    return plan


def _apply_once(plan: LogicalPlan) -> Optional[LogicalPlan]:
    """One top-down rewrite pass; returns None if nothing changed."""
    changed = False

    def rec(p: LogicalPlan) -> LogicalPlan:
        nonlocal changed
        while True:
            q = _rewrite(p)
            if q is None:
                break
            changed = True
            p = q
        kids = p.children()
        if kids:
            new_kids = [rec(k) for k in kids]
            if any(a is not b for a, b in zip(kids, new_kids)):
                p = p.with_children(new_kids)
        return p

    out = rec(plan)
    return out if changed else None


def _rewrite(p: LogicalPlan) -> Optional[LogicalPlan]:
    for rule in (_push_down_filter, _push_down_limit, _drop_repartition, _fold_projections):
        q = rule(p)
        if q is not None:
            return q
    return None


# ---------------------------------------------------------------------------
# filter pushdown
# ---------------------------------------------------------------------------

def _split_conjuncts(e: Expression) -> List[Expression]:
    from .expressions import BinaryOp

    n = e._node
    if isinstance(n, BinaryOp) and n.op == "&":
        return _split_conjuncts(Expression(n.left)) + _split_conjuncts(Expression(n.right))
    return [e]


def _and_all(preds: List[Expression]) -> Expression:
    out = preds[0]
    for p in preds[1:]:
        out = out & p
    return out


def _push_down_filter(p: LogicalPlan) -> Optional[LogicalPlan]:
    if not isinstance(p, Filter):
        return None
    child = p.input
    pred = p.predicate

    if isinstance(child, Filter):
        return Filter(child.input, child.predicate & pred)

    if isinstance(child, Project):
        # a pure column-pruning Project over an in-memory source is there to
        # narrow the filter's working set — swapping the filter below it would
        # re-widen the filter to every source column for no pushdown benefit
        if isinstance(child.input, InMemorySource) and all(
                is_trivial_passthrough(e) is not None for e in child.exprs):
            return None
        # substitute computed columns into the predicate; abort if any referenced
        # projection expr contains an agg/UDF (not freely movable)
        defs: Dict[str, Expression] = {}
        for e in child.exprs:
            src = is_trivial_passthrough(e)
            if src is not None:
                defs[e.name()] = col(src)
            else:
                if expr_has_special(e):
                    defs[e.name()] = None  # type: ignore[assignment]
                else:
                    defs[e.name()] = e
        needed = expr_input_columns(pred)
        if any(defs.get(c, col(c)) is None for c in needed):
            return None
        subst = substitute_columns(pred, {k: v for k, v in defs.items() if v is not None})
        return Project(Filter(child.input, subst), child.exprs)

    if isinstance(child, (Sort, Repartition, MonotonicallyIncreasingId, Distinct)):
        if isinstance(child, MonotonicallyIncreasingId) and child.column_name in expr_input_columns(pred):
            return None
        moved = Filter(child.children()[0], pred)
        return child.with_children([moved] + child.children()[1:])

    if isinstance(child, Concat):
        return Concat(Filter(child.input, pred), Filter(child.other, pred))

    if isinstance(child, Join):
        return _filter_into_join(p, child)

    if isinstance(child, ScanSource):
        pd = child.pushdowns()
        if pd.limit is not None:
            return None  # limit already applied at scan; filter must stay above it
        if expr_has_special(pred):
            return None
        new_filter = pred._node if pd.filters is None else (Expression(pd.filters) & pred)._node
        return child.with_pushdowns(pd.with_filters(new_filter))

    return None


def _filter_into_cross_join(f: Filter, j: Join) -> Optional[LogicalPlan]:
    """A filter over a cross join (SQL's ``FROM a, b WHERE ...``): a
    conjunct ``l == r`` between one column of each side, of one type, becomes
    a key of an inner join, and a one-sided conjunct goes to its side. The
    cross join's schema is kept: the inner join drops its right keys, so a
    projection gives each back as its left key under the old name."""
    from .expressions import BinaryOp, Column

    lschema, rschema = j.left.schema, j.right.schema
    lnames = set(lschema.field_names())
    # output name of every right column -> its name on the right side
    rnames = {(n if n not in lnames else f"{j.suffix}{n}"): n
              for n in rschema.field_names()}
    to_left: List[Expression] = []
    to_right: List[Expression] = []
    keep: List[Expression] = []
    keys: Dict[str, str] = {}  # right output name -> left key
    for c in _split_conjuncts(f.predicate):
        cols = expr_input_columns(c)
        n = c._node
        if expr_has_special(c) or not cols:
            keep.append(c)
        elif all(x in lnames for x in cols):
            to_left.append(c)
        elif all(x in rnames for x in cols):
            to_right.append(substitute_columns(
                c, {out: col(orig) for out, orig in rnames.items()}))
        elif (isinstance(n, BinaryOp) and n.op == "=="
              and isinstance(n.left, Column) and isinstance(n.right, Column)):
            a, b = n.left.cname, n.right.cname
            if a not in lnames:
                a, b = b, a
            if (a in lnames and b in rnames and b not in keys
                    and a not in keys.values()
                    and lschema[a].dtype == rschema[rnames[b]].dtype):
                keys[b] = a
            else:
                keep.append(c)
        else:
            keep.append(c)
    if not (to_left or to_right or keys):
        return None
    left = Filter(j.left, _and_all(to_left)) if to_left else j.left
    right = Filter(j.right, _and_all(to_right)) if to_right else j.right
    if keys:
        inner = Join(left, right, [col(a) for a in keys.values()],
                     [col(rnames[b]) for b in keys], "inner", None, j.suffix)
        out: LogicalPlan = Project(inner, [
            col(keys[n]).alias(n) if n in keys else col(n)
            for n in j.schema.field_names()])
    else:
        out = Join(left, right, [], [], "cross", j.strategy, j.suffix)
    return Filter(out, _and_all(keep)) if keep else out


def _filter_into_join(f: Filter, j: Join) -> Optional[LogicalPlan]:
    if j.how == "cross":
        return _filter_into_cross_join(f, j)
    if j.how not in ("inner", "semi", "anti", "left", "right"):
        return None
    # map join-output column name -> (side, original name)
    lk = [e.name() for e in j.left_on]
    origin: Dict[str, Tuple[str, str]] = {}
    for i, ln in enumerate(lk):
        origin[ln] = ("key", ln)
    for fld in j.left.schema:
        if fld.name not in origin:
            origin[fld.name] = ("left", fld.name)
    lnames = set(j.left.schema.field_names())
    rk = [e.name() for e in j.right_on]
    for fld in j.right.schema:
        if fld.name in rk:
            continue
        out_name = fld.name if fld.name not in lnames else f"{j.suffix}{fld.name}"
        if out_name not in origin:
            origin[out_name] = ("right", fld.name)

    conjuncts = _split_conjuncts(f.predicate)
    to_left: List[Expression] = []
    to_right: List[Expression] = []
    keep: List[Expression] = []
    for c in conjuncts:
        cols = expr_input_columns(c)
        sides = set()
        ok = True
        for cc in cols:
            o = origin.get(cc)
            if o is None:
                ok = False
                break
            sides.add(o[0])
        if not ok or expr_has_special(c):
            keep.append(c)
            continue
        side_set = sides - {"key"}
        if not side_set:
            # references only join keys; output keys coalesce from the preserved
            # side, so treat as that side (left unless it's a right join)
            side_set = {"right"} if j.how == "right" else {"left"}
        if side_set == {"left"} and j.how in ("inner", "left", "semi", "anti"):
            to_left.append(c)
        elif side_set == {"right"} and j.how in ("inner", "right"):
            # rename output cols back to right-side names
            ren = {out: col(orig) for out, (s, orig) in origin.items() if s == "right"}
            to_right.append(substitute_columns(c, ren))
        else:
            keep.append(c)
    if not to_left and not to_right:
        return None
    # keys referenced by right-side pushdown are left names; remap keys for right side
    new_left = j.left
    new_right = j.right
    if to_left:
        new_left = Filter(new_left, _and_all(to_left))
    if to_right:
        key_map = {ln: j.right_on[i] for i, ln in enumerate(lk)}
        to_right = [substitute_columns(c, key_map) for c in to_right]
        new_right = Filter(new_right, _and_all(to_right))
    new_join = Join(new_left, new_right, j.left_on, j.right_on, j.how, j.strategy, j.suffix,
                    j.origin)
    if keep:
        return Filter(new_join, _and_all(keep))
    return new_join


# ---------------------------------------------------------------------------
# limit pushdown
# ---------------------------------------------------------------------------

def _push_down_limit(p: LogicalPlan) -> Optional[LogicalPlan]:
    if not isinstance(p, Limit):
        return None
    child = p.input
    if isinstance(child, Limit):
        return Limit(child.input, min(p.limit, child.limit), p.eager)
    if isinstance(child, Project):
        if any(expr_has_special(e) for e in child.exprs):
            return None
        return Project(Limit(child.input, p.limit, p.eager), child.exprs)
    if isinstance(child, ScanSource):
        pd = child.pushdowns()
        if pd.limit is not None and pd.limit <= p.limit:
            return None
        new_limit = p.limit if pd.limit is None else min(pd.limit, p.limit)
        # keep the Limit node: per-task limits still need a global cap
        return Limit(child.with_pushdowns(pd.with_limit(new_limit)), p.limit, p.eager)
    if isinstance(child, Concat):
        a, b = child.input, child.other
        need = (isinstance(a, Limit) and a.limit <= p.limit) and (
            isinstance(b, Limit) and b.limit <= p.limit)
        if need:
            return None
        return Limit(Concat(Limit(a, p.limit, p.eager), Limit(b, p.limit, p.eager)), p.limit, p.eager)
    return None


# ---------------------------------------------------------------------------
# repartition elision
# ---------------------------------------------------------------------------

def _drop_repartition(p: LogicalPlan) -> Optional[LogicalPlan]:
    if not isinstance(p, Repartition):
        return None
    child = p.input
    if isinstance(child, Repartition):
        return Repartition(child.input, p.scheme, p.num, p.by, p.descending)
    if p.scheme in ("into", "random", "hash") and p.num == 1 and child.num_partitions() == 1:
        return child
    return None


# ---------------------------------------------------------------------------
# projection folding
# ---------------------------------------------------------------------------

def _fold_projections(p: LogicalPlan) -> Optional[LogicalPlan]:
    if not isinstance(p, Project):
        return None
    child = p.input
    if isinstance(child, Project):
        defs: Dict[str, Expression] = {}
        for e in child.exprs:
            if expr_has_special(e):
                return None
            defs[e.name()] = e if is_trivial_passthrough(e) is None else col(is_trivial_passthrough(e))
        # inline each outer expr; bail if any inner def would be duplicated into
        # a non-trivial expression more than once (avoid recompute blowup)
        use_count: Dict[str, int] = {}
        for e in p.exprs:
            for c in expr_input_columns(e):
                use_count[c] = use_count.get(c, 0) + 1
        for name, d in defs.items():
            if is_trivial_passthrough(d) is None and use_count.get(name, 0) > 1:
                return None
        new_exprs = [substitute_columns(e, defs).alias(e.name()) for e in p.exprs]
        return Project(child.input, new_exprs)
    # identity projection over the full child schema -> drop
    names = [e.name() for e in p.exprs]
    if names == child.schema.field_names() and all(
        is_trivial_passthrough(e) == e.name() for e in p.exprs
    ):
        return child
    return None


# ---------------------------------------------------------------------------
# column pruning (single deterministic pass)
# ---------------------------------------------------------------------------

def _restrict(required: Optional[List[str]], schema_names: List[str]) -> List[str]:
    if required is None:
        return list(schema_names)
    return [c for c in schema_names if c in required]


def _prune_columns(p: LogicalPlan, required: Optional[List[str]]) -> LogicalPlan:
    """Push the set of needed columns toward sources; install scan column
    pushdowns. required=None means every column is needed."""
    if isinstance(p, ScanSource):
        pd = p.pushdowns()
        want = _restrict(required, p.schema.field_names())
        if required is not None and want != p.schema.field_names():
            return p.with_pushdowns(pd.with_columns(want))
        return p

    if isinstance(p, InMemorySource):
        want = _restrict(required, p.schema.field_names())
        if required is not None and want != p.schema.field_names():
            return Project(p, [col(c) for c in want])
        return p

    if isinstance(p, Project):
        keep = [e for e in p.exprs if required is None or e.name() in required
                or expr_has_special(e)]
        if not keep:
            keep = p.exprs[:1]
        need: List[str] = []
        for e in keep:
            for c in expr_input_columns(e):
                if c not in need:
                    need.append(c)
        need = [c for c in p.input.schema.field_names() if c in need]
        new_child = _prune_columns(p.input, need)
        return Project(new_child, keep)

    if isinstance(p, Filter):
        need = None if required is None else list(required)
        if need is not None:
            for c in expr_input_columns(p.predicate):
                if c not in need:
                    need.append(c)
        new_child = _prune_columns(p.input, need)
        out: LogicalPlan = Filter(new_child, p.predicate)
        if required is not None and [f for f in out.schema.field_names() if f in required] != out.schema.field_names():
            want = _restrict(required, out.schema.field_names())
            out = Project(out, [col(c) for c in want])
        return out

    if isinstance(p, Aggregate):
        need: List[str] = []
        for e in p.groupby + p.aggregations:
            for c in expr_input_columns(e):
                if c not in need:
                    need.append(c)
        need = [c for c in p.input.schema.field_names() if c in need] or p.input.schema.field_names()[:1]
        return Aggregate(_prune_columns(p.input, need), p.aggregations, p.groupby)

    if isinstance(p, Pivot):
        need = []
        for e in p.groupby + [p.pivot_col, p.value_col]:
            for c in expr_input_columns(e):
                if c not in need:
                    need.append(c)
        need = [c for c in p.input.schema.field_names() if c in need]
        return Pivot(_prune_columns(p.input, need), p.groupby, p.pivot_col, p.value_col,
                     p.agg_fn, p.names)

    if isinstance(p, Join):
        lneed: Optional[List[str]] = None
        rneed: Optional[List[str]] = None
        if required is not None:
            lnames = set(p.left.schema.field_names())
            rk = [e.name() for e in p.right_on]
            lneed = []
            rneed = []
            for e in p.left_on:
                for c in expr_input_columns(e):
                    if c not in lneed:
                        lneed.append(c)
            for e in p.right_on:
                for c in expr_input_columns(e):
                    if c not in rneed:
                        rneed.append(c)
            for fld in p.left.schema:
                if fld.name in required and fld.name not in lneed:
                    lneed.append(fld.name)
            for fld in p.right.schema:
                out_name = fld.name if fld.name not in lnames else f"{p.suffix}{fld.name}"
                if (out_name in required or fld.name in required) and fld.name not in rneed:
                    if fld.name in rk and out_name not in required:
                        continue
                    rneed.append(fld.name)
            lneed = [c for c in p.left.schema.field_names() if c in lneed]
            rneed = [c for c in p.right.schema.field_names() if c in rneed]
        new_left = _prune_columns(p.left, lneed)
        new_right = _prune_columns(p.right, rneed)
        return Join(new_left, new_right, p.left_on, p.right_on, p.how, p.strategy, p.suffix,
                    p.origin)

    if isinstance(p, (Sort, Repartition)):
        need = None if required is None else list(required)
        if need is not None:
            exprs = p.sort_by if isinstance(p, Sort) else p.by
            for e in exprs:
                for c in expr_input_columns(e):
                    if c not in need:
                        need.append(c)
            need = [c for c in p.input.schema.field_names() if c in need]
        return p.with_children([_prune_columns(p.input, need)])

    if isinstance(p, Explode):
        need = None if required is None else list(required)
        if need is not None:
            for e in p.to_explode:
                for c in expr_input_columns(e):
                    if c not in need:
                        need.append(c)
            need = [c for c in p.input.schema.field_names() if c in need]
        return Explode(_prune_columns(p.input, need), p.to_explode)

    if isinstance(p, Unpivot):
        need = []
        for e in p.ids + p.values:
            for c in expr_input_columns(e):
                if c not in need:
                    need.append(c)
        need = [c for c in p.input.schema.field_names() if c in need]
        return Unpivot(_prune_columns(p.input, need), p.ids, p.values,
                       p.variable_name, p.value_name)

    if isinstance(p, Distinct):
        # distinct semantics depend on all visible columns: don't prune below
        return p.with_children([_prune_columns(p.input, None)])

    if isinstance(p, Concat):
        # both sides must keep identical layouts
        need = None if required is None else _restrict(required, p.schema.field_names())
        a = _prune_columns(p.input, need)
        b = _prune_columns(p.other, need)
        if a.schema.field_names() != b.schema.field_names():
            a = _prune_columns(p.input, None)
            b = _prune_columns(p.other, None)
        return Concat(a, b)

    # default: pass full requirement through (Limit, Sample, Write, MonotonicId)
    kids = p.children()
    if not kids:
        return p
    if isinstance(p, (Limit, Sample)):
        return p.with_children([_prune_columns(kids[0], required)])
    return p.with_children([_prune_columns(k, None) for k in kids])
