"""Table: an eager multi-column batch (schema + equal-length Series).

Role-equivalent to the reference's Table (src/daft-table/src/lib.rs) and its ops/
directory (agg.rs, groups.rs, sort.rs, partition.rs, joins/, explode.rs, pivot.rs,
unpivot.rs). Host kernels are pyarrow/acero + numpy; the executor routes
device-eligible pipelines through the jax kernel layer (kernels/device.py) instead.
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple, Union

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from .errors import DaftValueError
from .datatypes import DataType, TypeKind, try_unify
from .expressions import (
    AggExpr,
    Alias,
    Expression,
    ExpressionsProjection,
    _eval_agg_on_series,
    col,
)
from .kernels.host_hash import hash_table_columns
from .schema import Field, Schema
from .series import Series


def _downcast_key_offsets(arr):
    """large_string/large_binary -> 32-bit-offset variant when the buffer fits
    (< 2GiB): acero's hash table is ~3x slower on 64-bit-offset keys. Shared
    by the join and _acero_grouped_agg; the fused filter+agg path mirrors the
    same rule at the acero-expression level (it casts expressions, not
    arrays)."""
    if arr.nbytes < (1 << 31) - 1:
        if pa.types.is_large_string(arr.type):
            return arr.cast(pa.string())
        if pa.types.is_large_binary(arr.type):
            return arr.cast(pa.binary())
    return arr


def _as_expressions(exprs) -> List[Expression]:
    if isinstance(exprs, Expression):
        return [exprs]
    out = []
    for e in exprs:
        out.append(col(e) if isinstance(e, str) else e)
    return out


class Table:
    __slots__ = ("schema", "_columns", "_memo_by_thread", "__weakref__")

    def __init__(self, schema: Schema, columns: List[Series]):
        if len(schema) != len(columns):
            raise DaftValueError(f"schema has {len(schema)} fields but got {len(columns)} columns")
        n = len(columns[0]) if columns else 0
        for f, c in zip(schema, columns):
            if len(c) != n:
                raise DaftValueError(f"column {f.name!r} length {len(c)} != {n}")
        self.schema = schema
        self._columns = columns
        # per-thread cache of evaluated subexpressions, active only inside
        # _memo_scope (tables are immutable, so hits are always sound; the
        # scope bounds the lifetime of the cached column-sized intermediates).
        # Keyed by thread ident: the same Table may be evaluated concurrently
        # from different worker threads (shared InMemorySource partitions) and
        # the depth counter must not race across them.
        self._memo_by_thread: Dict[int, list] = {}

    @property
    def _eval_memo(self) -> Optional[Dict[Tuple, Series]]:
        state = self._memo_by_thread.get(threading.get_ident())
        return state[0] if state is not None else None

    @contextmanager
    def _memo_scope(self):
        """Share structurally-identical subexpression results across the
        evaluates of one logical pass; dropped when the outermost scope
        exits so intermediates are not pinned for the table's lifetime."""
        tid = threading.get_ident()
        state = self._memo_by_thread.get(tid)
        if state is None:
            state = self._memo_by_thread[tid] = [{}, 0]
        state[1] += 1
        try:
            yield
        finally:
            state[1] -= 1
            if state[1] == 0:
                self._memo_by_thread.pop(tid, None)

    # ------------------------------------------------------------------ ctors
    @staticmethod
    def empty(schema: Optional[Schema] = None) -> "Table":
        schema = schema or Schema.empty()
        return Table(schema, [Series.empty(f.name, f.dtype) for f in schema])

    @staticmethod
    def from_pydict(data: Dict[str, Any]) -> "Table":
        cols: List[Series] = []
        for name, vals in data.items():
            if isinstance(vals, Series):
                cols.append(vals.rename(name))
            elif isinstance(vals, (pa.Array, pa.ChunkedArray)):
                cols.append(Series.from_arrow(vals, name))
            elif isinstance(vals, np.ndarray):
                cols.append(Series.from_numpy(vals, name))
            else:
                cols.append(Series.from_pylist(list(vals), name))
        n = max((len(c) for c in cols), default=0)
        cols = [c if len(c) == n else _broadcast_series(c, n) for c in cols]
        schema = Schema([Field(c.name, c.dtype) for c in cols])
        return Table(schema, cols)

    @staticmethod
    def from_arrow(tbl: Union[pa.Table, pa.RecordBatch]) -> "Table":
        if isinstance(tbl, pa.RecordBatch):
            tbl = pa.Table.from_batches([tbl])
        tbl = tbl.combine_chunks()
        cols = [Series.from_arrow(tbl.column(i), tbl.schema.names[i]) for i in range(tbl.num_columns)]
        schema = Schema([Field(c.name, c.dtype) for c in cols])
        return Table(schema, cols)

    @staticmethod
    def from_pylist(rows: List[dict]) -> "Table":
        keys: List[str] = []
        for r in rows:
            for k in r:
                if k not in keys:
                    keys.append(k)
        return Table.from_pydict({k: [r.get(k) for r in rows] for k in keys})

    # ------------------------------------------------------------------ basics
    def __len__(self) -> int:
        return len(self._columns[0]) if self._columns else 0

    @property
    def column_names(self) -> List[str]:
        return self.schema.field_names()

    def columns(self) -> List[Series]:
        return list(self._columns)

    def get_column(self, name: str) -> Series:
        return self._columns[self.schema.index(name)]

    def num_columns(self) -> int:
        return len(self._columns)

    def size_bytes(self) -> int:
        return sum(c.size_bytes() for c in self._columns)

    def to_arrow(self) -> pa.Table:
        arrays, fields = [], []
        for f, c in zip(self.schema, self._columns):
            if c.is_python():
                raise DaftValueError(f"column {f.name!r} has python dtype; no arrow representation")
            arrays.append(c.to_arrow())
            fields.append(pa.field(f.name, c.to_arrow().type))
        return pa.Table.from_arrays(arrays, schema=pa.schema(fields))

    def to_pydict(self) -> Dict[str, list]:
        return {f.name: c.to_pylist() for f, c in zip(self.schema, self._columns)}

    def to_pylist(self) -> List[dict]:
        d = self.to_pydict()
        names = list(d)
        return [dict(zip(names, vals)) for vals in zip(*d.values())] if names else []

    def to_pandas(self):
        return self.to_arrow().to_pandas()

    def __repr__(self) -> str:
        return f"Table({self.schema!r}, rows={len(self)})"

    def select_columns(self, names: List[str]) -> "Table":
        return Table(self.schema.select(names), [self.get_column(n) for n in names])

    def rename_columns(self, mapping: Dict[str, str]) -> "Table":
        return Table(self.schema.rename(mapping),
                     [c.rename(mapping.get(c.name, c.name)) for c in self._columns])

    def cast_to_schema(self, schema: Schema) -> "Table":
        cols = []
        for f in schema:
            if f.name in self.schema:
                cols.append(self.get_column(f.name).cast(f.dtype))
            else:
                cols.append(Series.full_null(f.name, f.dtype, len(self)))
        return Table(schema, cols)

    # ------------------------------------------------------------------ eval
    def eval_expression_list(self, exprs: Sequence[Expression]) -> "Table":
        exprs = _as_expressions(exprs)
        n = len(self)
        out: List[Series] = []
        names: List[str] = []
        any_agg = any(e._node.is_aggregation() for e in exprs)
        with self._memo_scope():
            for e in exprs:
                s = e._node.evaluate(self)
                out.append(s)
                names.append(e.name())
        if any_agg:
            m = max((len(s) for s in out), default=0)
        else:
            m = n
        out = [_broadcast_series(s, m) if len(s) != m else s for s in out]
        schema = Schema([Field(nm, s.dtype) for nm, s in zip(names, out)])
        return Table(schema, [s.rename(nm) for nm, s in zip(names, out)])

    # ------------------------------------------------------------------ selection
    def filter(self, predicate: Union[Expression, Sequence[Expression]]) -> "Table":
        preds = _as_expressions(predicate)
        mask: Optional[Series] = None
        with self._memo_scope():
            for p in preds:
                s = p._node.evaluate(self)
                if not s.dtype.is_boolean() and not s.dtype.is_null():
                    raise DaftValueError(f"filter predicate must be boolean, got {s.dtype}")
                mask = s if mask is None else (mask & s)
        if mask is None:
            return self
        return self.filter_with_mask(mask)

    def filter_with_mask(self, mask: Series) -> "Table":
        """Compact rows by a precomputed boolean mask (the device filter path
        computes the predicate on the TPU and hands the mask back here)."""
        mask = _broadcast_series(mask, len(self))
        m = mask._arrow
        if m is None:
            return Table(self.schema, [c.filter(mask) for c in self._columns])
        if m.null_count:
            m = pc.fill_null(m, False)
        # one multithreaded arrow-table filter instead of a per-column pass
        arrow_idx = [i for i, c in enumerate(self._columns) if c._arrow is not None]
        ftbl = None
        if arrow_idx:
            ftbl = pa.Table.from_arrays(
                [self._columns[i]._arrow for i in arrow_idx],
                names=[str(i) for i in arrow_idx]).filter(m)
        out: List[Series] = []
        for i, c in enumerate(self._columns):
            if c._arrow is None:
                out.append(c.filter(mask))
            else:
                ch = ftbl.column(str(i))
                arr = ch.chunk(0) if ch.num_chunks == 1 else ch.combine_chunks()
                out.append(Series(c._name, c._dtype, arr))
        return Table(self.schema, out)

    def take(self, indices: Series) -> "Table":
        return Table(self.schema, [c.take(indices) for c in self._columns])

    def slice(self, start: int, end: int) -> "Table":
        return Table(self.schema, [c.slice(start, end) for c in self._columns])

    def head(self, n: int) -> "Table":
        return self.slice(0, min(n, len(self)))

    def sample(self, fraction: Optional[float] = None, size: Optional[int] = None,
               with_replacement: bool = False, seed: Optional[int] = None) -> "Table":
        if fraction is None and size is None:
            raise DaftValueError("sample requires either fraction or size")
        n = len(self)
        k = int(round(n * fraction)) if fraction is not None else int(size)
        rng = np.random.RandomState(seed if seed is not None else None)
        if with_replacement:
            idx = rng.randint(0, max(n, 1), size=k) if n else np.empty(0, np.int64)
        else:
            k = min(k, n)
            idx = rng.permutation(n)[:k]
        return self.take(Series.from_arrow(pa.array(idx.astype(np.uint64)), "idx"))

    @staticmethod
    def concat(tables: List["Table"]) -> "Table":
        if not tables:
            raise DaftValueError("concat of zero tables")
        first = tables[0]
        names = first.column_names
        for t in tables[1:]:
            if t.column_names != names:
                raise DaftValueError(f"concat schema mismatch: {names} vs {t.column_names}")
        cols = []
        for i, name in enumerate(names):
            cols.append(Series.concat([t._columns[i] for t in tables]))
        schema = Schema([Field(c.name, c.dtype) for c in cols])
        return Table(schema, cols)

    # ------------------------------------------------------------------ sort
    def argsort(self, sort_keys: Sequence[Expression], descending=None, nulls_first=None) -> Series:
        sort_keys = _as_expressions(sort_keys)
        k = len(sort_keys)
        descending = _norm_flag(descending, k, False)
        nulls_first = _norm_flag(nulls_first, k, None)
        keys = [e._node.evaluate(self) for e in sort_keys]
        arrs, sort_spec, placements = [], [], []
        for i, (s, d, nf) in enumerate(zip(keys, descending, nulls_first)):
            arrs.append(_broadcast_series(s, len(self)).to_arrow())
            placements.append("at_start" if (nf if nf is not None else d) else "at_end")
            sort_spec.append((f"k{i}", "descending" if d else "ascending"))
        # pyarrow sort_keys are (name, order) pairs with ONE global
        # null_placement (per-key 3-tuples are not part of its API); keys
        # that disagree on placement fall back to a dense-rank lexsort where
        # each key's rank bakes in its own placement
        if len(set(placements)) <= 1:
            tbl = pa.Table.from_arrays(arrs, names=[f"k{i}" for i in range(k)])
            idx = pc.sort_indices(tbl, sort_keys=sort_spec,
                                  null_placement=placements[0] if placements else "at_end")
            return Series.from_arrow(idx.cast(pa.uint64()), "indices")
        ranks = [np.asarray(pc.rank(a, sort_keys="descending" if d else "ascending",
                                    null_placement=p, tiebreaker="dense"))
                 for a, d, p in zip(arrs, descending, placements)]
        idx = np.lexsort(tuple(reversed(ranks)))  # first key = primary
        return Series.from_arrow(pa.array(idx.astype(np.uint64)), "indices")

    def sort(self, sort_keys: Sequence[Expression], descending=None, nulls_first=None) -> "Table":
        return self.take(self.argsort(sort_keys, descending, nulls_first))

    # ------------------------------------------------------------------ hashing / partitioning
    def hash_rows(self, exprs: Optional[Sequence[Expression]] = None, seed: int = 0) -> np.ndarray:
        exprs = _as_expressions(exprs) if exprs is not None else [col(n) for n in self.column_names]
        cols = []
        for e in exprs:
            s = e._node.evaluate(self)
            if s.is_python():
                s = s.cast(DataType.string())
            cols.append(_broadcast_series(s, len(self)).to_arrow())
        return hash_table_columns(cols, seed=seed)

    def partition_by_hash(self, exprs: Sequence[Expression], num_partitions: int) -> List["Table"]:
        if num_partitions <= 0:
            raise DaftValueError("num_partitions must be positive")
        h = self.hash_rows(exprs)
        buckets = (h % np.uint64(num_partitions)).astype(np.int64)
        return self._split_by_buckets(buckets, num_partitions)

    def partition_by_random(self, num_partitions: int, seed: int = 0) -> List["Table"]:
        rng = np.random.RandomState(seed & 0x7FFFFFFF)
        buckets = rng.randint(0, num_partitions, size=len(self))
        return self._split_by_buckets(buckets, num_partitions)

    def partition_by_range(self, exprs: Sequence[Expression], boundaries: "Table",
                           descending: Optional[List[bool]] = None,
                           nulls_first: Optional[List[Optional[bool]]] = None) -> List["Table"]:
        """Split rows by comparing sort keys against per-partition boundary rows.
        nulls_first[i]=None means the sort default (nulls last ascending, first
        descending)."""
        exprs = _as_expressions(exprs)
        k = len(exprs)
        descending = _norm_flag(descending, k, False)
        nulls_first = list(nulls_first) if nulls_first is not None else [None] * k
        nb = len(boundaries)
        if nb == 0:
            return [self]
        keys = [_broadcast_series(e._node.evaluate(self), len(self)) for e in exprs]
        ranks = _composite_rank(keys, [b for b in boundaries._columns], descending, nulls_first)
        return self._split_by_buckets(ranks, nb + 1)

    def partition_by_value(self, exprs: Sequence[Expression]) -> Tuple[List["Table"], "Table"]:
        """Group rows by exact key values; returns (partitions, unique_key_table)."""
        exprs = _as_expressions(exprs)
        keyed = self.eval_expression_list(exprs)
        codes, uniq = _group_codes(keyed)
        parts = self._split_by_buckets(codes, len(uniq))
        return parts, uniq

    def _split_by_buckets(self, buckets: np.ndarray, num: int) -> List["Table"]:
        if len(self) == 0:
            return [self.slice(0, 0) for _ in range(num)]
        from . import native

        if native.available():
            # one O(n) counting pass instead of an O(n log n) argsort
            counts, order = native.bucket_stable_order(buckets, num)
            offs = np.concatenate([[0], np.cumsum(counts)])
        else:
            order = np.argsort(buckets, kind="stable")
            counts = np.bincount(buckets, minlength=num)
            offs = np.concatenate([[0], np.cumsum(counts)])
        sorted_tbl = self.take(Series.from_arrow(pa.array(order.astype(np.uint64)), "idx"))
        return [sorted_tbl.slice(int(offs[i]), int(offs[i + 1])) for i in range(num)]

    # ------------------------------------------------------------------ aggregation
    def agg(self, to_agg: Sequence[Expression], group_by: Optional[Sequence[Expression]] = None) -> "Table":
        group_by = _as_expressions(group_by) if group_by else []
        to_agg = _as_expressions(to_agg)
        if not group_by:
            return self.eval_expression_list(to_agg)
        return self._grouped_agg(to_agg, group_by)

    def _grouped_agg(self, to_agg: List[Expression], group_by: List[Expression]) -> "Table":
        n = len(self)
        with self._memo_scope():
            # keys evaluated inside the scope so subtrees shared between the
            # group-by keys and the agg children are computed once
            key_tbl = self.eval_expression_list(group_by)
            fast = self._acero_grouped_agg(to_agg, key_tbl)
            if fast is not None:
                return fast
            return self._generic_grouped_agg(to_agg, key_tbl, n)

    def _generic_grouped_agg(self, to_agg: List[Expression], key_tbl: "Table", n: int) -> "Table":
        codes, uniq = _group_codes(key_tbl)
        num_groups = len(uniq)

        out_cols: List[Series] = list(uniq._columns)
        out_fields: List[Field] = list(uniq.schema)

        # Lazily sort rows by group code (only aggs that miss every vectorized
        # path need contiguous per-group segments).
        _seg = {}

        def segments():
            if not _seg:
                order = np.argsort(codes, kind="stable")
                counts = np.bincount(codes, minlength=num_groups) if n else np.zeros(num_groups, np.int64)
                offs = np.concatenate([[0], np.cumsum(counts)])
                _seg["order_s"] = Series.from_arrow(pa.array(order.astype(np.uint64)), "o")
                _seg["offs"] = offs
            return _seg["order_s"], _seg["offs"]

        for e in to_agg:
            node = e._node
            alias = e.name()
            while isinstance(node, Alias):
                node = node.child
            if not isinstance(node, AggExpr):
                raise DaftValueError(f"aggregation list contains non-aggregation {e!r}")
            child_s = _broadcast_series(node.child.evaluate(self), n)
            expected_dt = node.to_field(self.schema).dtype
            merged = _sketch_agg_fast(node, child_s, codes, num_groups)
            if merged is None:
                merged = _bincount_agg_fast(node, child_s, codes, num_groups)
            if merged is None:
                merged = _hash_agg_fast(node, child_s, codes, num_groups)
            if merged is None:
                # fallback: contiguous per-group segments after a stable sort by code
                order_s, offs = segments()
                sorted_child = child_s.take(order_s)
                outs = []
                for g in range(num_groups):
                    seg = sorted_child.slice(int(offs[g]), int(offs[g + 1]))
                    outs.append(_eval_agg_on_series(node, seg))
                merged = Series.concat(outs) if outs else _empty_agg_series(node, child_s)
            if merged.dtype != expected_dt:
                merged = merged.cast(expected_dt)
            out_cols.append(merged.rename(alias))
            out_fields.append(Field(alias, expected_dt))
        return Table(Schema(out_fields), out_cols)

    def _acero_grouped_agg(self, to_agg: List[Expression], key_tbl: "Table") -> Optional["Table"]:
        """Single multithreaded C++ hash-agg pass (arrow acero) for the whole
        aggregation list. Returns None when any key/agg needs the generic
        path. Group order (first occurrence) is recovered with a min(row_id)
        side-aggregate so results are deterministic and identical to the
        generic path."""
        n = len(self)
        if n == 0:
            return None
        cols: Dict[str, pa.Array] = {}
        key_names = []
        for i, s in enumerate(key_tbl._columns):
            if s.is_python():
                return None
            arr = s.to_arrow()
            if pa.types.is_nested(arr.type) or pa.types.is_dictionary(arr.type):
                return None
            # acero's hash table is ~3x slower on large_string keys
            cols[f"k{i}"] = _downcast_key_offsets(arr)
            key_names.append(f"k{i}")
        planned = _acero_agg_plans(to_agg)
        if planned is None:
            return None
        plans, nodes, agg_list = planned
        for j, node in enumerate(nodes):
            child_s = _broadcast_series(node.child.evaluate(self), n)
            if child_s.is_python():
                return None
            cols[f"v{j}"] = child_s.to_arrow()
        cols["__row__"] = _rowid_array(n)
        return _acero_run_group(cols, key_names, agg_list,
                                list(key_tbl.schema), plans, self.schema)

    @staticmethod
    def acero_grouped_agg_chunked(tables: List["Table"], to_agg, group_by
                                  ) -> Optional["Table"]:
        """One C++ hash-agg over a MicroPartition's chunk Tables WITHOUT
        concatenating them first: per-chunk expression evaluation feeds
        ChunkedArrays into a single acero group_by, skipping the full-width
        copy Table.concat would make (an 8-bucket SF10 shuffle concatenates
        ~3 GB of pieces just to aggregate them). Semantics identical to
        _acero_grouped_agg — same key-offset downcast, first-occurrence
        order recovery (global row ids continue across chunks in chunk
        order, exactly the concatenated order), same output casts. Returns
        None when ineligible; the caller concats and falls back."""
        tables = [t for t in tables if len(t)]
        if not tables:
            return None
        group_by = _as_expressions(group_by)
        to_agg = _as_expressions(to_agg)
        if not group_by:
            return None
        planned = _acero_agg_plans(to_agg)
        if planned is None:
            return None
        plans, nodes, agg_list = planned
        nk = len(group_by)
        key_chunks: List[List[pa.Array]] = [[] for _ in range(nk)]
        val_chunks: List[List[pa.Array]] = [[] for _ in to_agg]
        row_chunks: List[pa.Array] = []
        key_fields = None
        base = 0
        for t in tables:
            n = len(t)
            with t._memo_scope():
                kt = t.eval_expression_list(group_by)
                if key_fields is None:
                    key_fields = list(kt.schema)
                for i, s in enumerate(kt._columns):
                    if s.is_python():
                        return None
                    arr = s.to_arrow()
                    if pa.types.is_nested(arr.type) or pa.types.is_dictionary(arr.type):
                        return None
                    key_chunks[i].append(arr)
                for j, node in enumerate(nodes):
                    child_s = _broadcast_series(node.child.evaluate(t), n)
                    if child_s.is_python():
                        return None
                    val_chunks[j].append(child_s.to_arrow())
            row_chunks.append(pa.array(np.arange(base, base + n, dtype=np.int64)))
            base += n
        cols: Dict[str, Any] = {}
        for i in range(nk):
            chunks = key_chunks[i]
            # joint downcast decision: a ChunkedArray needs one uniform type
            if all(a.nbytes < (1 << 31) - 1 for a in chunks):
                chunks = [_downcast_key_offsets(a) for a in chunks]
            cols[f"k{i}"] = pa.chunked_array(chunks)
        for j in range(len(to_agg)):
            cols[f"v{j}"] = pa.chunked_array(val_chunks[j])
        cols["__row__"] = pa.chunked_array(row_chunks)
        return _acero_run_group(cols, [f"k{i}" for i in range(nk)], agg_list,
                                key_fields, plans, tables[0].schema)

    def acero_fused_agg(self, to_agg: List[Expression], group_by: List[Expression],
                        predicate: Optional[Expression]) -> Optional["Table"]:
        """Single-pass filter+project+aggregate through one acero Declaration
        (C++ exec plan): the filtered intermediate table is never
        materialized, which is the host-side analog of the reference's fused
        streaming pipeline (src/daft-local-execution/src/pipeline.rs:141-211)
        and of this engine's device-side FusedFilterAggregateOp. Returns None when
        any expression falls outside the translated subset (_to_acero_expr) —
        the caller then runs the unfused filter-then-agg path. Group output
        order is first-occurrence (hash_min row-id side-aggregate), identical
        to _acero_grouped_agg and the generic path."""
        from pyarrow import acero

        from .expressions import normalize_literals, required_columns

        n = len(self)
        if n == 0 or not group_by:
            # ungrouped reductions measure faster through the pruned
            # filter-then-agg path (see physical._AggStep.host); no fused
            # variant exists
            return None
        exprs_all = list(group_by) + list(to_agg) + ([predicate] if predicate is not None else [])
        refs = set()
        for e in exprs_all:
            refs.update(required_columns(e))
        if "__row__" in refs:
            return None  # would collide with the order-recovery column
        by_name = {f.name: s for f, s in zip(self.schema, self._columns)}
        cols: Dict[str, Any] = {}
        for name in refs:
            s = by_name.get(name)
            if s is None or s.is_python():
                return None
            arr = s.to_arrow()
            if pa.types.is_nested(arr.type) or pa.types.is_dictionary(arr.type):
                return None
            cols[name] = arr
        try:
            pred_expr = None
            if predicate is not None:
                pred_expr = _to_acero_expr(
                    normalize_literals(predicate._node, self.schema), self.schema)
            proj_exprs, proj_names = [], []
            key_fields: List[Field] = []
            for i, e in enumerate(group_by):
                kdt = e._node.to_field(self.schema).dtype
                key_expr = _to_acero_expr(
                    normalize_literals(e._node, self.schema), self.schema)
                karrow = kdt.to_arrow()
                # same large_string downcast as _acero_grouped_agg: acero's
                # hash table is ~3x slower on 64-bit-offset keys. Offset
                # width only shrinks safely under 2GiB, which is knowable
                # here only for plain column keys.
                knode = e._node
                while isinstance(knode, Alias):
                    knode = knode.child
                src = cols.get(getattr(knode, "cname", None))
                small = src is not None and src.nbytes < (1 << 31) - 1
                if pa.types.is_large_string(karrow) and small:
                    key_expr = key_expr.cast(pa.string())
                elif pa.types.is_large_binary(karrow) and small:
                    key_expr = key_expr.cast(pa.binary())
                proj_exprs.append(key_expr)
                proj_names.append(f"k{i}")
                key_fields.append(Field(e.name(), kdt))
            plans = []
            agg_list = []
            for j, e in enumerate(to_agg):
                node = e._node
                alias = e.name()
                while isinstance(node, Alias):
                    node = node.child
                if not isinstance(node, AggExpr):
                    raise _AceroUnsupported("non-aggregation in agg list")
                spec = _acero_agg_fn(node, threaded=True)
                if spec is None:
                    raise _AceroUnsupported(f"agg kind {node.kind}")
                fname, opts = spec
                proj_exprs.append(_to_acero_expr(
                    normalize_literals(node.child, self.schema), self.schema))
                proj_names.append(f"v{j}")
                agg_list.append((f"v{j}", "hash_" + fname, opts,
                                 f"v{j}_{fname}"))
                plans.append((f"v{j}", fname, node, alias))
        except _AceroUnsupported:
            return None
        cols["__row__"] = _rowid_array(n)  # recovers first-occurrence order
        decls = [acero.Declaration("table_source",
                                   acero.TableSourceNodeOptions(pa.table(cols)))]
        if pred_expr is not None:
            decls.append(acero.Declaration("filter", acero.FilterNodeOptions(pred_expr)))
        proj_exprs.append(pc.field("__row__"))
        proj_names.append("__row__")
        agg_list.append(("__row__", "hash_min", None, "__row___min"))
        decls.append(acero.Declaration("project",
                                       acero.ProjectNodeOptions(proj_exprs, proj_names)))
        decls.append(acero.Declaration("aggregate", acero.AggregateNodeOptions(
            agg_list, keys=[f"k{i}" for i in range(len(group_by))])))
        try:
            g = acero.Declaration.from_sequence(decls).to_table(use_threads=True)
        except (pa.ArrowNotImplementedError, pa.ArrowInvalid, pa.ArrowTypeError,
                pa.ArrowKeyError):
            return None
        order = np.argsort(np.asarray(g.column("__row___min").combine_chunks()),
                           kind="stable")
        g = g.take(pa.array(order))
        return _assemble_acero_agg_output(g, key_fields, plans, self.schema)

    def distinct(self, subset: Optional[Sequence[Expression]] = None) -> "Table":
        exprs = _as_expressions(subset) if subset else [col(n) for n in self.column_names]
        key_tbl = self.eval_expression_list(exprs)
        codes, _uniq = _group_codes(key_tbl)
        if len(codes) == 0:
            return self
        first_idx = _first_occurrence(codes)
        return self.take(Series.from_arrow(pa.array(first_idx.astype(np.uint64)), "idx"))

    # ------------------------------------------------------------------ joins
    def hash_join(self, right: "Table", left_on: Sequence[Expression],
                  right_on: Sequence[Expression], how: str = "inner",
                  suffix: str = "right.") -> "Table":
        """Hash join with SQL null semantics (null keys never match).

        Output ROW ORDER IS UNSPECIFIED, as in the reference (Rust probe
        tables emit in probe-visit x hash-bucket order, acero in its own
        thread-interleaved order, and the device range probe in left-row-major
        x sorted-build-key order). Callers needing determinism sort after the
        join; tests compare sorted rows. This is the engine-wide join order
        contract — the device/host paths are free to disagree on order while
        agreeing on the multiset of rows."""
        how_map = {
            "inner": "inner", "left": "left outer", "right": "right outer",
            "outer": "full outer", "semi": "left semi", "anti": "left anti",
        }
        if how not in how_map:
            raise DaftValueError(f"unknown join type {how!r}")
        left_on = _as_expressions(left_on)
        right_on = _as_expressions(right_on)
        lk = self.eval_expression_list(left_on)
        rk = right.eval_expression_list(right_on)
        # align key dtypes
        lkc, rkc = [], []
        for a, b in zip(lk._columns, rk._columns):
            u = try_unify(a.dtype, b.dtype)
            if u is None:
                raise DaftValueError(f"cannot join on {a.dtype} vs {b.dtype}")
            lkc.append(a.cast(u))
            rkc.append(b.cast(u))

        # acero's hash table is ~3x slower on large_string keys (same effect
        # as in _acero_grouped_agg). The downcast decision is made JOINTLY per
        # key index: both sides must qualify, or acero would see mismatched
        # string vs large_string key types and raise.
        lka = [s.to_arrow() for s in lkc]
        rka = [s.to_arrow() for s in rkc]
        for i in range(len(lka)):
            la = _downcast_key_offsets(lka[i])
            ra = _downcast_key_offsets(rka[i])
            if la.type == ra.type:
                lka[i], rka[i] = la, ra

        key_names = [f"__k{i}" for i in range(len(lkc))]
        lt = pa.Table.from_arrays(
            lka + [c.to_arrow() for c in self._columns]
            + [pa.array(np.arange(len(self), dtype=np.int64))],
            names=key_names + [f"__l{i}" for i in range(len(self._columns))] + ["__lidx"],
        )
        rt = pa.Table.from_arrays(
            rka + [c.to_arrow() for c in right._columns]
            + [pa.array(np.arange(len(right), dtype=np.int64))],
            names=key_names + [f"__r{i}" for i in range(len(right._columns))] + ["__ridx"],
        )
        # acero builds its hash table on the RIGHT operand: probing 6M rows
        # against a 46k build is ~15x faster than building on the 6M side
        # (measured, TPC-H Q5 SF1). Keep the build on the smaller table by
        # swapping operands and flipping the join type; output assembly is
        # by column NAME (__l*/__r*), so orientation below stays unchanged.
        if len(self) < len(right):
            flip = {"inner": "inner", "left outer": "right outer",
                    "right outer": "left outer", "full outer": "full outer",
                    "left semi": "right semi", "left anti": "right anti"}
            joined = rt.join(lt, keys=key_names, join_type=flip[how_map[how]],
                             use_threads=True)
        else:
            joined = lt.join(rt, keys=key_names, join_type=how_map[how],
                             use_threads=True)
        # deterministic output order: by left index then right index
        sort_keys = [(c, "ascending") for c in ("__lidx", "__ridx") if c in joined.column_names]
        if sort_keys:
            joined = joined.take(pc.sort_indices(joined, sort_keys=sort_keys,
                                                 null_placement="at_end"))
        joined = joined.combine_chunks()

        if how in ("semi", "anti"):
            cols = [Series.from_arrow(joined.column(f"__l{i}"), f.name, f.dtype)
                    for i, f in enumerate(self.schema)]
            return Table(Schema(list(self.schema)), cols)

        out_cols: List[Series] = []
        out_fields: List[Field] = []
        left_names = set(self.column_names)
        # join keys: single merged column named after the left key (reference merges key cols)
        lk_names = [e.name() for e in left_on]
        rk_names = [e.name() for e in right_on]
        for i, kn in enumerate(key_names):
            name = lk_names[i]
            out_cols.append(Series.from_arrow(joined.column(kn), name))
            out_fields.append(Field(name, out_cols[-1].dtype))
        for i, f in enumerate(self.schema):
            if f.name in lk_names:
                continue
            s = Series.from_arrow(joined.column(f"__l{i}"), f.name, f.dtype)
            out_cols.append(s)
            out_fields.append(Field(f.name, s.dtype))
        for i, f in enumerate(right.schema):
            if f.name in rk_names:
                continue
            name = f.name if f.name not in left_names else f"{suffix}{f.name}"
            s = Series.from_arrow(joined.column(f"__r{i}"), name, f.dtype)
            out_cols.append(s)
            out_fields.append(Field(name, s.dtype))
        return Table(Schema(out_fields), out_cols)

    def join_from_indices(self, right: "Table", lidx: np.ndarray, ridx: np.ndarray,
                          left_on, right_on, suffix: str = "right.") -> "Table":
        """Assemble join output from precomputed row-index pairs (the device
        probe path, kernels/device_join.py). `ridx` entries of -1 emit nulls
        (left-outer misses). Output schema/naming matches hash_join exactly:
        merged key columns named after the left keys, then left columns, then
        right columns with `suffix` on collisions."""
        left_on = _as_expressions(left_on)
        right_on = _as_expressions(right_on)
        lk_names = [e.name() for e in left_on]
        rk_names = [e.name() for e in right_on]
        l_take = Series.from_arrow(pa.array(lidx.astype(np.uint64)), "i")
        r_has_null = (ridx < 0).any()
        r_take_arr = pa.array(
            np.where(ridx < 0, 0, ridx).astype(np.int64),
            pa.int64()) if not r_has_null else pa.array(
            [None if i < 0 else int(i) for i in ridx], pa.int64())
        out_cols: List[Series] = []
        out_fields: List[Field] = []
        lkeys = self.eval_expression_list(left_on)
        for i, kn in enumerate(lk_names):
            s = lkeys._columns[i].take(l_take).rename(kn)
            out_cols.append(s)
            out_fields.append(Field(kn, s.dtype))
        left_names = set(self.column_names)
        for f in self.schema:
            if f.name in lk_names:
                continue
            s = self.get_column(f.name).take(l_take)
            out_cols.append(s)
            out_fields.append(Field(f.name, s.dtype))
        for f in right.schema:
            if f.name in rk_names:
                continue
            name = f.name if f.name not in left_names else f"{suffix}{f.name}"
            arr = right.get_column(f.name).to_arrow().take(r_take_arr)
            s = Series.from_arrow(arr, name, right.get_column(f.name).dtype)
            out_cols.append(s)
            out_fields.append(Field(name, s.dtype))
        return Table(Schema(out_fields), out_cols)

    def sort_merge_join(self, right: "Table", left_on, right_on, how: str = "inner",
                        suffix: str = "right.", is_sorted: bool = False) -> "Table":
        """Join pre-sorted (or sorted here) sides; host fallback delegates to hash_join
        after sorting, preserving the sorted output property of the reference."""
        left_on = _as_expressions(left_on)
        right_on = _as_expressions(right_on)
        l = self if is_sorted else self.sort(left_on)
        r = right if is_sorted else right.sort(right_on)
        out = l.hash_join(r, left_on, right_on, how=how, suffix=suffix)
        return out.sort([col(e.name()) for e in left_on])

    # ------------------------------------------------------------------ reshaping
    def explode(self, exprs: Sequence[Expression]) -> "Table":
        exprs = _as_expressions(exprs)
        names = [e.name() for e in exprs]
        list_cols: Dict[str, Series] = {}
        for e in exprs:
            s = e._node.evaluate(self)
            if not s.dtype.is_list():
                raise DaftValueError(f"explode requires list column, got {s.dtype} for {e.name()!r}")
            list_cols[e.name()] = _broadcast_series(s, len(self))
        first = list_cols[names[0]]
        arr0 = first.to_arrow()
        lens = pc.list_value_length(arr0)
        lens_np = np.asarray(pc.fill_null(lens, 0), dtype=np.int64)
        # null/empty lists explode to a single null row (reference semantics)
        out_lens = np.maximum(lens_np, 1)
        for nm, s in list_cols.items():
            ln = np.asarray(pc.fill_null(pc.list_value_length(s.to_arrow()), 0), dtype=np.int64)
            if not np.array_equal(ln, lens_np):
                raise DaftValueError("exploded columns must have equal list lengths per row")
        repeat_idx = np.repeat(np.arange(len(self), dtype=np.int64), out_lens)
        out_cols: List[Series] = []
        out_fields: List[Field] = []
        for f, c in zip(self.schema, self._columns):
            if f.name in list_cols:
                s = list_cols[f.name]
                flat = _explode_series(s, out_lens)
                out_cols.append(flat.rename(f.name))
                out_fields.append(Field(f.name, flat.dtype))
            else:
                taken = c.take(Series.from_arrow(pa.array(repeat_idx), "i"))
                out_cols.append(taken)
                out_fields.append(f)
        return Table(Schema(out_fields), out_cols)

    def unpivot(self, ids: Sequence[Expression], values: Sequence[Expression],
                variable_name: str = "variable", value_name: str = "value") -> "Table":
        ids = _as_expressions(ids)
        values = _as_expressions(values)
        if not values:
            raise DaftValueError("unpivot requires at least one value column")
        id_tbl = self.eval_expression_list(ids) if ids else None
        n = len(self)
        val_series = [e._node.evaluate(self) for e in values]
        vdt = val_series[0].dtype
        for s in val_series[1:]:
            u = try_unify(vdt, s.dtype)
            if u is None:
                raise DaftValueError(f"unpivot value columns have incompatible types {vdt} vs {s.dtype}")
            vdt = u
        out_cols: List[Series] = []
        out_fields: List[Field] = []
        m = len(values)
        if id_tbl is not None:
            tile_idx = np.tile(np.arange(n, dtype=np.int64), m)
            idx_s = Series.from_arrow(pa.array(tile_idx), "i")
            for f, c in zip(id_tbl.schema, id_tbl._columns):
                out_cols.append(c.take(idx_s))
                out_fields.append(f)
        var_vals = np.repeat([e.name() for e in values], n)
        out_cols.append(Series.from_pylist(list(var_vals), variable_name, DataType.string()))
        out_fields.append(Field(variable_name, DataType.string()))
        value_col = Series.concat([s.cast(vdt) for s in val_series]).rename(value_name)
        out_cols.append(value_col)
        out_fields.append(Field(value_name, vdt))
        return Table(Schema(out_fields), out_cols)

    def pivot(self, group_by: Sequence[Expression], pivot_col: Expression,
              value_col: Expression, names: List[str], agg_fn: str = "sum") -> "Table":
        group_by = _as_expressions(group_by)
        pivot_e = _as_expressions(pivot_col)[0]
        value_e = _as_expressions(value_col)[0]
        agg_e = Expression(AggExpr(agg_fn, value_e._node))
        grouped = self.agg([agg_e.alias("__v")], group_by + [pivot_e])
        key_names = [e.name() for e in group_by]
        piv_name = pivot_e.name()
        base = grouped.distinct([col(n) for n in key_names]).select_columns(key_names)
        out = base
        for nm in names:
            sub = grouped.filter(col(piv_name) == nm) if nm is not None else grouped.filter(col(piv_name).is_null())
            sub = sub.select_columns(key_names + ["__v"]).rename_columns({"__v": str(nm)})
            out = out.hash_join(sub, [col(n) for n in key_names], [col(n) for n in key_names], how="left")
        return out

    def add_monotonic_id(self, partition_offset: int = 0, column_name: str = "id") -> "Table":
        ids = np.arange(len(self), dtype=np.uint64) + np.uint64(partition_offset)
        s = Series.from_arrow(pa.array(ids), column_name)
        return Table(Schema([Field(column_name, s.dtype)] + list(self.schema)), [s] + self._columns)


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _broadcast_series(s: Series, n: int) -> Series:
    from .series import _broadcast_to

    return _broadcast_to(s, n)


def _norm_flag(v, k: int, default):
    if v is None:
        return [default] * k
    if isinstance(v, (bool, int)):
        return [bool(v)] * k
    out = list(v)
    if len(out) != k:
        raise DaftValueError(f"expected {k} flags, got {len(out)}")
    return out


def _group_codes(key_tbl: Table) -> Tuple[np.ndarray, Table]:
    """Dense group codes per row + table of unique key rows (nulls form a group)."""
    n = len(key_tbl)
    if n == 0:
        return np.empty(0, dtype=np.int64), key_tbl
    # dictionary-encode each key column, then combine codes by mixed-radix
    combined = np.zeros(n, dtype=np.int64)
    for s in key_tbl._columns:
        arr = s.to_arrow() if not s.is_python() else None
        if arr is None:
            vals = s.to_pylist()
            uniq_map: Dict[Any, int] = {}
            codes = np.empty(n, dtype=np.int64)
            for i, v in enumerate(vals):
                k = repr(v)
                codes[i] = uniq_map.setdefault(k, len(uniq_map))
            card = len(uniq_map)
        else:
            if pa.types.is_nested(arr.type):
                # nested keys: exact repr-based encoding (hash-only grouping could
                # silently merge colliding keys); nested group keys are rare enough
                # that the python path is acceptable
                vals = s.to_pylist()
                uniq_map2: Dict[Any, int] = {}
                codes = np.empty(n, dtype=np.int64)
                for i, v in enumerate(vals):
                    codes[i] = uniq_map2.setdefault(repr(v), len(uniq_map2))
                card = len(uniq_map2)
            else:
                enc = arr.dictionary_encode()
                codes = np.asarray(enc.indices.fill_null(-1)).astype(np.int64)
                codes = codes + 1  # null -> 0
                card = len(enc.dictionary) + 1
        card = max(card, 1)
        if (int(combined.max(initial=0)) + 1) * card >= (1 << 62):
            # overflow guard: re-densify intermediate codes before combining
            _, combined = np.unique(combined, return_inverse=True)
            combined = combined.astype(np.int64)
        combined = combined * np.int64(card) + codes
    # Densify the combined codes without an O(n log n) sort. Preferred: the
    # native open-addressing pass, which emits codes already in
    # first-occurrence order. Fallback: arrow's dictionary_encode (C++ hash
    # pass) + first-occurrence fixup via a reversed fancy-assignment (last
    # write wins, so a reversed index write leaves each slot holding its
    # FIRST occurrence).
    from . import native

    if native.available():
        codes, first_idx = native.dense_codes(combined)
        uniq = key_tbl.take(Series.from_arrow(pa.array(first_idx.astype(np.uint64)), "i"))
        return codes, uniq
    enc = pa.array(combined).dictionary_encode()
    codes = np.asarray(enc.indices).astype(np.int64)
    num = len(enc.dictionary)
    first_per_code = np.empty(num, dtype=np.int64)
    first_per_code[codes[::-1]] = np.arange(n - 1, -1, -1)
    order = np.argsort(first_per_code, kind="stable")
    remap = np.empty(num, dtype=np.int64)
    remap[order] = np.arange(num)
    codes = remap[codes]
    first_idx = first_per_code[order]
    uniq = key_tbl.take(Series.from_arrow(pa.array(first_idx.astype(np.uint64)), "i"))
    return codes, uniq


class _AceroUnsupported(Exception):
    """Expression shape outside the acero-translated subset; callers fall
    back to the per-op Series kernel path."""


def _acero_agg_plans(to_agg: List[Expression]):
    """Shared agg-plan building for the single-chunk and chunked acero
    paths: (plans [(vname, fname, node, alias)], nodes, agg_list) or None
    when any aggregation has no acero mapping."""
    plans, nodes, agg_list = [], [], []
    for j, e in enumerate(to_agg):
        node = e._node
        alias = e.name()
        while isinstance(node, Alias):
            node = node.child
        if not isinstance(node, AggExpr):
            raise DaftValueError(f"aggregation list contains non-aggregation {e!r}")
        spec = _acero_agg_fn(node, threaded=True)
        if spec is None:
            return None
        fname, opts = spec
        nodes.append(node)
        agg_list.append((f"v{j}", fname, opts))
        plans.append((f"v{j}", fname, node, alias))
    return plans, nodes, agg_list


def _acero_run_group(cols: Dict[str, Any], key_names: List[str], agg_list,
                     key_fields: List[Field], plans, schema: Schema
                     ) -> Optional["Table"]:
    """Shared group_by execution + first-occurrence order recovery (min
    row-id side-aggregate) + output assembly. `cols` must already contain
    the `__row__` ids (global across chunks for chunked inputs)."""
    agg_list = list(agg_list) + [("__row__", "min", None)]
    try:
        g = pa.table(cols).group_by(key_names, use_threads=True).aggregate(agg_list)
    except (pa.ArrowNotImplementedError, pa.ArrowInvalid, pa.ArrowTypeError):
        return None
    order = np.argsort(np.asarray(g.column("__row___min").combine_chunks()),
                       kind="stable")
    g = g.take(pa.array(order))
    return _assemble_acero_agg_output(g, key_fields, plans, schema)


def _assemble_acero_agg_output(g: pa.Table, key_fields: List[Field], plans,
                               schema: Schema) -> "Table":
    """Shared output assembly for the TableGroupBy and fused-Declaration agg
    paths: key columns (named k{i}) cast back to engine key dtypes, agg
    outputs (named {vname}_{fname}) cast to the planner's expected dtypes."""
    out_cols: List[Series] = []
    out_fields: List[Field] = []
    for i, f in enumerate(key_fields):
        s = Series.from_arrow(g.column(f"k{i}").combine_chunks(), f.name)
        if s.dtype != f.dtype:
            s = s.cast(f.dtype)
        out_cols.append(s)
        out_fields.append(f)
    for vname, fname, node, alias in plans:
        expected_dt = node.to_field(schema).dtype
        s = Series.from_arrow(g.column(f"{vname}_{fname}").combine_chunks(), alias)
        if s.dtype != expected_dt:
            s = s.cast(expected_dt)
        out_cols.append(s.rename(alias))
        out_fields.append(Field(alias, expected_dt))
    return Table(Schema(out_fields), out_cols)


_ROWID_CACHE: List[Optional[pa.Array]] = [None]
_ROWID_CACHE_MAX = 1 << 26  # don't pin more than 512MB of arange


def _rowid_array(n: int) -> pa.Array:
    """Cached int64 arange (grow-only) for first-occurrence order recovery."""
    cached = _ROWID_CACHE[0]
    if cached is None or len(cached) < n:
        cached = pa.array(np.arange(n, dtype=np.int64))
        if n <= _ROWID_CACHE_MAX:
            _ROWID_CACHE[0] = cached
        return cached
    return cached.slice(0, n)


def _to_acero_expr(node, schema: Schema):
    """ExprNode -> deferred pyarrow.compute Expression with the ENGINE's type
    semantics: operands are cast to the dtypes the Series kernels would unify
    to (series.py _binary_numeric/_cmp), so a fused acero plan computes
    results identical to the per-op host path. The caller must run
    normalize_literals first so weak literals already carry concrete dtypes.
    Raises _AceroUnsupported for anything outside the translated subset."""
    from .expressions import (Between, BinaryOp, Cast, Column, IsNull, Literal,
                              Not)

    if isinstance(node, Alias):
        return _to_acero_expr(node.child, schema)
    if isinstance(node, Column):
        return pc.field(node.cname)
    if isinstance(node, Literal):
        if isinstance(node.value, (list, dict)) or node.dtype.kind == TypeKind.PYTHON:
            raise _AceroUnsupported("complex literal")
        try:
            return pc.scalar(pa.scalar(node.value, node.dtype.to_arrow()))
        except Exception as e:
            raise _AceroUnsupported(f"literal: {e}")
    if isinstance(node, Cast):
        dt = node.dtype
        if not (dt.is_numeric() or dt.is_temporal() or dt.is_boolean()):
            raise _AceroUnsupported(f"cast to {dt}")
        return _to_acero_expr(node.child, schema).cast(dt.to_arrow())
    if isinstance(node, Not):
        return pc.invert(_to_acero_expr(node.child, schema))
    if isinstance(node, IsNull):
        inner = _to_acero_expr(node.child, schema)
        return pc.is_valid(inner) if node.negate else pc.is_null(inner)
    if isinstance(node, Between):
        # Series.between == (child >= lo) & (child <= hi), Kleene logic
        lo = BinaryOp(">=", node.child, node.lower)
        hi = BinaryOp("<=", node.child, node.upper)
        return pc.and_kleene(_to_acero_expr(lo, schema), _to_acero_expr(hi, schema))
    if isinstance(node, BinaryOp):
        op = node.op
        ldt = node.left.to_field(schema).dtype
        rdt = node.right.to_field(schema).dtype
        l = _to_acero_expr(node.left, schema)
        r = _to_acero_expr(node.right, schema)
        if op in ("&", "|"):
            if not (ldt.is_boolean() and rdt.is_boolean()):
                raise _AceroUnsupported("bitwise on non-bool")
            return (pc.and_kleene if op == "&" else pc.or_kleene)(l, r)
        if op in ("==", "!=", "<", "<=", ">", ">="):
            if ldt != rdt:
                sup = try_unify(ldt, rdt)
                if sup is None:
                    raise _AceroUnsupported(f"compare {ldt} vs {rdt}")
                if ldt != sup:
                    l = l.cast(sup.to_arrow())
                if rdt != sup:
                    r = r.cast(sup.to_arrow())
            fn = {"==": pc.equal, "!=": pc.not_equal, "<": pc.less,
                  "<=": pc.less_equal, ">": pc.greater, ">=": pc.greater_equal}[op]
            return fn(l, r)
        if op in ("+", "-", "*", "/"):
            numericish = (ldt.is_numeric() or ldt.is_boolean()) and (
                rdt.is_numeric() or rdt.is_boolean())
            if not numericish:
                raise _AceroUnsupported(f"{op} on {ldt}/{rdt}")
            if op == "/":
                # Series.__truediv__: both sides to float64, unchecked divide
                return pc.divide(l.cast(pa.float64()), r.cast(pa.float64()))
            u = try_unify(ldt, rdt) if ldt != rdt else ldt
            if u is None or not u.is_numeric():
                raise _AceroUnsupported(f"{op} unify {ldt}/{rdt}")
            if ldt != u:
                l = l.cast(u.to_arrow())
            if rdt != u:
                r = r.cast(u.to_arrow())
            fn = {"+": pc.add_checked, "-": pc.subtract_checked,
                  "*": pc.multiply_checked}[op]
            return fn(l, r)
        raise _AceroUnsupported(f"operator {op}")
    raise _AceroUnsupported(type(node).__name__)


def _acero_agg_fn(node: AggExpr, threaded: bool = False):
    """AggExpr -> (acero hash-agg function name, options), or None.

    With threaded=True, order-dependent aggregates (list, any_value/first) are
    rejected: pyarrow guarantees no stable ordering under a threaded exec plan,
    which would break parity with the sequential path."""
    k = node.kind
    if k in ("list", "any_value") and threaded:
        return None
    if k in ("sum", "mean", "min", "max", "count_distinct", "list"):
        return {"count_distinct": "count_distinct"}.get(k, k), None
    if k == "count":
        mode = node.extra.get("mode", "valid")
        if mode not in ("valid", "null", "all"):
            return None
        return "count", pc.CountOptions(
            mode={"valid": "only_valid", "null": "only_null", "all": "all"}[mode])
    if k == "stddev":
        return "stddev", pc.VarianceOptions(ddof=0)
    if k == "any_value":
        return "first", pc.ScalarAggregateOptions(
            skip_nulls=bool(node.extra.get("ignore_nulls", False)))
    return None


def _sketch_agg_fast(node: AggExpr, child: Series, codes: np.ndarray,
                     num_groups: int) -> Optional[Series]:
    """Vectorized grouped kernels of the sketch subsystem (daft_tpu/sketch/):
    the planner-internal stage kinds (sketch_hll/sketch_quantile build one
    Binary sketch per group; merge_sketch_* merges serialized sketches) and
    the single-partition grouped approx_* aggregations, which build+estimate
    in one pass so grouped results match the two-phase plan's estimates.
    Returns None for every other kind."""
    k = node.kind
    if k in ("sketch_hll", "merge_sketch_hll", "approx_count_distinct"):
        from .sketch import hll

        if k == "sketch_hll":
            return hll.build_grouped(child, codes, num_groups)
        if k == "merge_sketch_hll":
            return hll.merge_grouped(child, codes, num_groups)
        est = hll.grouped_estimates(child, codes, num_groups)
        return Series.from_arrow(pa.array(est, type=pa.uint64()), child.name)
    if k in ("sketch_quantile", "merge_sketch_quantile", "approx_percentiles"):
        from .sketch import quantile

        if k == "sketch_quantile":
            return quantile.build_grouped(child, codes, num_groups)
        if k == "merge_sketch_quantile":
            return quantile.merge_grouped(child, codes, num_groups)
        sketches = quantile.build_grouped(child, codes, num_groups)
        return quantile.estimate_series(
            sketches, node.extra.get("percentiles", 0.5))
    return None


def _bincount_agg_fast(node: AggExpr, child: Series, codes: np.ndarray,
                       num_groups: int) -> Optional[Series]:
    """O(n) grouped count/sum/mean via np.bincount (no hash pass, no sort).

    Floats only for sum/mean (bincount accumulates in float64; integer sums
    stay on the exact arrow hash-agg path to avoid 2^53 precision loss).
    Matches arrow hash-agg null semantics: nulls skipped, all-null/empty
    groups yield null, NaN propagates.
    """
    if child.is_python() or num_groups == 0 or len(codes) == 0:
        return None
    k = node.kind
    arr = child.to_arrow()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if k == "count":
        mode = node.extra.get("mode", "valid")
        if mode == "all" or (mode == "valid" and arr.null_count == 0):
            cnt = np.bincount(codes, minlength=num_groups)
        elif mode == "valid":
            cnt = np.bincount(codes[np.asarray(arr.is_valid())], minlength=num_groups)
        elif mode == "null":
            cnt = np.bincount(codes[np.asarray(arr.is_null())], minlength=num_groups)
        else:
            return None
        return Series.from_arrow(pa.array(cnt.astype(np.uint64)), child.name)
    if k not in ("sum", "mean") or not pa.types.is_floating(arr.type):
        return None
    if arr.null_count == 0:
        vals = arr.to_numpy(zero_copy_only=False)
        sums = np.bincount(codes, weights=vals, minlength=num_groups)
        cnt = np.bincount(codes, minlength=num_groups)
    else:
        valid = np.asarray(arr.is_valid())
        vals = np.where(valid, arr.to_numpy(zero_copy_only=False), 0.0)
        sums = np.bincount(codes, weights=vals, minlength=num_groups)
        cnt = np.bincount(codes[valid], minlength=num_groups)
    empty = cnt == 0
    out = sums if k == "sum" else np.divide(sums, cnt, out=np.zeros_like(sums), where=~empty)
    return Series.from_arrow(pa.array(out, type=pa.float64(), mask=empty), child.name)


def _hash_agg_fast(node: AggExpr, child: Series, codes: np.ndarray, num_groups: int) -> Optional[Series]:
    """Vectorized grouped aggregation through arrow's hash-agg engine.

    Returns None when the (kind, dtype) combination needs the segment fallback.
    """
    if child.is_python() or num_groups == 0:
        return None
    k = node.kind
    spec = _acero_agg_fn(node)  # sequential plan: order-dependent aggs allowed
    if spec is None:
        return None
    fname, opts = spec
    arr = child.to_arrow()
    if pa.types.is_nested(arr.type) and k in ("sum", "mean", "min", "max", "stddev", "count_distinct", "list"):
        return None
    try:
        tbl = pa.table({"g": pa.array(codes), "v": arr})
        agg = tbl.group_by("g", use_threads=False).aggregate([("v", fname, opts)])
    except (pa.ArrowNotImplementedError, pa.ArrowInvalid):
        return None
    out_name = [c for c in agg.column_names if c != "g"][0]
    g = np.asarray(agg.column("g").combine_chunks())
    v = agg.column(out_name).combine_chunks()
    if isinstance(v, pa.ChunkedArray):
        v = v.combine_chunks()
    # scatter into group order 0..num_groups-1
    order = np.argsort(g, kind="stable")
    inv = np.empty(num_groups, dtype=np.int64)
    inv[g[order]] = order
    v = v.take(pa.array(inv))
    return Series.from_arrow(v, child.name)


def _first_occurrence(codes: np.ndarray) -> np.ndarray:
    _, first_idx = np.unique(codes, return_index=True)
    return np.sort(first_idx)


def _composite_rank(keys: List[Series], bounds: List[Series], descending: List[bool],
                    nulls_first: Optional[List[Optional[bool]]] = None) -> np.ndarray:
    """For each row, the number of boundary rows at-or-below it in the sort
    order (lexicographic). "Below" honors per-key descending + nulls placement,
    mirroring Table.argsort's ordering so range partitions align with sorts."""
    if nulls_first is None:
        nulls_first = [None] * len(keys)
    n = len(keys[0])
    nb = len(bounds[0])
    ge_all = np.zeros((nb, n), dtype=bool)
    for bi in range(nb):
        cmp_state = np.zeros(n, dtype=np.int8)  # -1 lt, 0 eq, +1 gt (in sort order)
        for s, b, d, nf in zip(keys, bounds, descending, nulls_first):
            bv = b.slice(bi, bi + 1)
            eq_mask = cmp_state == 0
            if not eq_mask.any():
                break
            sv = s.to_arrow()
            bscalar = bv.to_arrow()[0]
            lt = np.asarray(pc.fill_null(pc.less(sv, bscalar), False))
            gt = np.asarray(pc.fill_null(pc.greater(sv, bscalar), False))
            if d:
                lt, gt = gt, lt
            isnull = np.asarray(pc.is_null(sv))
            bnull = not bscalar.is_valid
            # argsort default: nulls at_start iff descending, overridable
            nulls_at_start = nf if nf is not None else d
            if bnull:
                # non-null rows vs a null boundary
                if nulls_at_start:
                    lt2, gt2 = np.zeros(n, dtype=bool), ~isnull
                else:
                    lt2, gt2 = ~isnull, np.zeros(n, dtype=bool)
            else:
                if nulls_at_start:
                    lt2 = np.where(isnull, True, lt)
                    gt2 = np.where(isnull, False, gt)
                else:
                    lt2 = np.where(isnull, False, lt)
                    gt2 = np.where(isnull, True, gt)
            cmp_state = np.where(eq_mask & lt2, -1, cmp_state)
            cmp_state = np.where(eq_mask & gt2, 1, cmp_state)
        ge_all[bi] = cmp_state >= 0
    rank = ge_all.sum(axis=0).astype(np.int64)
    return rank


def _explode_series(s: Series, out_lens: np.ndarray) -> Series:
    arr = s.to_arrow()
    if pa.types.is_fixed_size_list(arr.type):
        arr = arr.cast(pa.large_list(arr.type.value_type))
    offs = np.asarray(arr.offsets).astype(np.int64)
    child = arr.values
    lo = int(offs[0])
    starts, ends = offs[:-1] - lo, offs[1:] - lo
    child = child.slice(lo, int(offs[-1]) - lo)
    n = len(arr)
    idx = np.empty(int(out_lens.sum()), dtype=np.int64)
    valid = np.empty(int(out_lens.sum()), dtype=bool)
    pos = 0
    valid_row = np.asarray(pc.is_valid(arr))
    for i in range(n):
        ln = int(out_lens[i])
        real = int(ends[i] - starts[i]) if valid_row[i] else 0
        if real == 0:
            idx[pos:pos + 1] = 0
            valid[pos:pos + 1] = False
            pos += 1
        else:
            idx[pos:pos + real] = np.arange(starts[i], ends[i])
            valid[pos:pos + real] = True
            pos += real
    if len(child) == 0:
        out = pa.nulls(len(idx), arr.type.value_type)
    else:
        taken = child.take(pa.array(np.clip(idx, 0, len(child) - 1)))
        out = pc.if_else(pa.array(valid), taken, pa.nulls(len(idx), taken.type))
    return Series.from_arrow(out, s.name)


def _empty_agg_series(node: AggExpr, child: Series) -> Series:
    out_field = AggExpr(node.kind, _ConstNode(child.dtype), node.extra).to_field(Schema([]))
    return Series.empty(child.name, out_field.dtype)


class _ConstNode:
    """Internal: an ExprNode-like carrying a fixed dtype for empty-agg typing."""

    def __init__(self, dtype: DataType):
        self._dtype = dtype

    def to_field(self, _schema):
        return Field("x", self._dtype)

    def name(self):
        return "x"
