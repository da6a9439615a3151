"""Decorrelation: a WHERE conjunct that holds a subquery becomes a join.

The SQL planner (``sql.py``) parses ``[NOT] EXISTS (SELECT ...)``,
``expr IN (SELECT ...)`` and ``(SELECT <aggregate> ...)`` into a
:class:`Subquery` and leaves a placeholder column in the predicate. Here
each conjunct with a placeholder is turned into plan nodes the DataFrame API
never produces by itself (role-equivalent to the reference's
``unnest_subquery.rs`` rules, done while the plan is built):

- ``EXISTS``      -> semi join on the correlation equalities; the subquery's
  inner-only conjuncts stay on its side
- ``NOT EXISTS``  -> anti join
- ``x IN (SELECT k ...)`` -> semi join of ``x`` with ``k``
- correlated scalar aggregate -> the aggregate grouped by the correlation
  key, inner-joined on it, then the comparison as a filter (a row with no
  group meets NULL and the comparison is false, as SQL has it)
- uncorrelated scalar aggregate -> a one-row frame, cross-joined, then the
  comparison as a filter: lazy, nothing executes while planning

Every join made here carries ``origin=ORIGIN`` down to its physical
``JoinProbe``, which counts ``sql_subquery_joins`` (probes run) and
``sql_subquery_joins_device`` (those the device answered). What cannot be
rewritten faithfully raises a ``ValueError`` naming the construct.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from .expressions import _CMP_OPS, BinaryOp, Column, Expression, Not, col
from .logical import Join, expr_input_columns

ORIGIN = "sql_subquery"


class Subquery:
    """One parsed subquery of a WHERE clause. ``df`` is its plan, ready to
    be joined: for ``exists`` the inner FROM under its inner-only
    conjuncts, for ``in`` a one-column frame, for ``scalar`` a frame with
    the value column ``name`` (and ``<name>_k<i>``, the correlation keys it
    was grouped by). ``corr`` pairs each inner key with the outer column it
    equals; ``lhs`` is the outer expression of an ``IN``."""

    __slots__ = ("kind", "name", "df", "corr", "lhs")

    def __init__(self, kind: str, name: str, df, corr: List[Tuple[str, str]],
                 lhs: Optional[Expression] = None):
        self.kind = kind
        self.name = name
        self.df = df
        self.corr = corr
        self.lhs = lhs


def correlation(conjunct: Expression, inner: set, outer: set) -> Tuple[str, str]:
    """``(inner column, outer column)`` of a correlated conjunct, which has
    to be one equality between the two (an inner name shadows an outer)."""
    n = conjunct._node
    if isinstance(n, BinaryOp) and n.op == "==" \
            and isinstance(n.left, Column) and isinstance(n.right, Column):
        a, b = n.left.cname, n.right.cname
        if a in inner and b not in inner and b in outer:
            return a, b
        if b in inner and a not in inner and a in outer:
            return b, a
    raise ValueError(
        f"correlated subquery predicate {n.display()} is not supported: "
        "correlation has to be an equality between one inner and one outer "
        "column")


def outer_columns(conjunct: Expression, subs: Dict[str, Subquery]) -> List[str]:
    """The outer query's columns a subquery conjunct needs: what the
    conjunct reads itself, an ``IN``'s left side, the correlation keys."""
    need: List[str] = []
    for c in expr_input_columns(conjunct):
        sq = subs.get(c)
        if sq is None:
            need.append(c)
            continue
        need.extend(o for _, o in sq.corr)
        if sq.lhs is not None:
            need.extend(expr_input_columns(sq.lhs))
    return need


def _join(df, sq: Subquery, left_on, right_on, how: str):
    from .dataframe import DataFrame

    return DataFrame(Join(df._plan, sq.df._plan, left_on, right_on, how,
                          origin=None if how == "cross" else ORIGIN))


def apply_conjunct(df, conjunct: Expression, subs: Dict[str, Subquery]):
    """``df`` restricted by one WHERE conjunct that holds placeholders of
    ``subs``; the result has ``df``'s columns."""
    node = conjunct._node
    negated = False
    while isinstance(node, Not):
        node, negated = node.child, not negated
    sq = subs.get(node.cname) if isinstance(node, Column) else None
    if sq is not None and sq.kind == "exists":
        return _join(df, sq, [col(o) for _, o in sq.corr],
                     [col(i) for i, _ in sq.corr],
                     "anti" if negated else "semi")
    if sq is not None and sq.kind == "in":
        if negated:
            raise not_in_error()
        if isinstance(sq.lhs._node, Column):
            return _join(df, sq, [sq.lhs], [col(sq.df.column_names[0])],
                         "semi")
        names = df.column_names
        tmp = f"{sq.name}_l"  # the left side is an expression: name it
        return _join(df.with_column(tmp, sq.lhs), sq, [col(tmp)],
                     [col(sq.df.column_names[0])], "semi").select(*names)
    held = [subs[c] for c in expr_input_columns(conjunct) if c in subs]
    root = conjunct._node
    if any(s.kind != "scalar" for s in held) or not (
            isinstance(root, BinaryOp) and root.op in _CMP_OPS):
        raise ValueError(
            "a subquery under OR, under NOT(...) or inside another "
            f"predicate is not supported: {root.display()}; write it as a "
            "conjunct of WHERE ([NOT] EXISTS (...), x IN (...), "
            "x <cmp> (SELECT <aggregate> ...))")
    names = df.column_names
    for s in held:
        if s.corr:
            df = _join(df, s, [col(o) for _, o in s.corr],
                       [col(f"{s.name}_k{i}") for i in range(len(s.corr))],
                       "inner")
        else:
            df = _join(df, s, [], [], "cross")
    return df.where(conjunct).select(*names)


def not_in_error() -> ValueError:
    return ValueError(
        "NOT IN (SELECT ...) is not supported: with a NULL on either side "
        "it is not an anti join; write NOT EXISTS (SELECT ... WHERE inner = "
        "outer)")
