"""DataFrame: the lazy user-facing API over a LogicalPlan.

Role-equivalent to the reference's daft/dataframe/dataframe.py:71. A DataFrame
wraps a logical plan; transformations build new plans; collect()/show()
optimize + translate + execute through the context's runner. Materialized
results are cached on the DataFrame (reference: _result/_preview discipline).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Iterator, List, Optional, Sequence, Union

from .context import get_context
from .datatypes import DataType
from .execution import RuntimeStats
from .expressions import AggExpr, Expression, col, lit
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
    Sort,
    Unpivot,
    Write,
)
from .micropartition import MicroPartition
from .optimizer import optimize
from .runners import PartitionSet
from .schema import Schema

ColumnInput = Union[str, Expression]


def _to_expr(c: ColumnInput) -> Expression:
    return col(c) if isinstance(c, str) else c


def _to_exprs(cols) -> List[Expression]:
    if isinstance(cols, (str, Expression)):
        return [_to_expr(cols)]
    return [_to_expr(c) for c in cols]


def _norm_bools(v, k: int, default=False):
    if v is None:
        return [default] * k
    if isinstance(v, bool):
        return [v] * k
    out = list(v)
    if len(out) != k:
        raise ValueError(f"expected {k} flags, got {len(out)}")
    return out


class DataFrame:
    def __init__(self, plan: LogicalPlan, result: Optional[PartitionSet] = None):
        self._plan = plan
        self._result = result
        self.stats = RuntimeStats()
        self._profile = None  # QueryProfile from a profiled collect()

    # ------------------------------------------------------------------ metadata
    @property
    def schema(self) -> Schema:
        return self._plan.schema

    @property
    def column_names(self) -> List[str]:
        return self._plan.schema.field_names()

    @property
    def columns(self) -> List[Expression]:
        return [col(n) for n in self.column_names]

    def __getitem__(self, item) -> Expression:
        if isinstance(item, str):
            if item != "*" and item not in self.schema:
                raise ValueError(f"unknown column {item!r}")
            return col(item)
        raise TypeError(f"cannot index DataFrame with {type(item).__name__}")

    def __contains__(self, name: str) -> bool:
        return name in self.schema

    def num_partitions(self) -> int:
        return self._plan.num_partitions()

    def explain(self, show_all: bool = False) -> str:
        """Logical plan (and optimized + physical when show_all)."""
        out = ["== Unoptimized Logical Plan ==", self._plan.display_tree()]
        if show_all:
            ctx = get_context()
            opt = optimize(self._plan)
            out += ["", "== Optimized Logical Plan ==", opt.display_tree()]
            from .physical import translate

            phys = translate(opt, ctx.execution_config)
            out += ["", "== Physical Plan ==", phys.display_tree()]
        text = "\n".join(out)
        print(text)
        return text

    def explain_analyze(self) -> str:
        """Execute (if needed, with the profiler armed) and render
        per-operator rows + wall-time, plus the per-op timeline /
        critical-path section from the QueryProfile.

        Reference: the native executor's explain-analyze output
        (DAFT_DEV_ENABLE_EXPLAIN_ANALYZE, run.rs:106-115) backed by per-node
        RuntimeStatsContext counters (runtime_stats.rs:16-27)."""
        from .obs.capture import render_runtime_stats

        self.collect(profile=True)
        lines = [render_runtime_stats(self.stats)]
        if self._profile is not None and self._profile.ops:
            lines.append("")
            lines.append(self._profile.render_timeline())
        text = "\n".join(lines)
        print(text)
        return text

    # ------------------------------------------------------------------ projection
    def select(self, *columns: ColumnInput) -> "DataFrame":
        exprs = []
        for c in columns:
            if isinstance(c, str) and c == "*":
                exprs.extend(col(n) for n in self.column_names)
            else:
                exprs.append(_to_expr(c))
        return DataFrame(Project(self._plan, exprs))

    def exclude(self, *names: str) -> "DataFrame":
        drop = set(names)
        keep = [col(n) for n in self.column_names if n not in drop]
        return DataFrame(Project(self._plan, keep))

    def with_column(self, name: str, expr: Expression) -> "DataFrame":
        return self.with_columns({name: expr})

    def with_columns(self, columns: Dict[str, Expression]) -> "DataFrame":
        exprs: List[Expression] = []
        for n in self.column_names:
            if n in columns:
                exprs.append(_to_expr(columns[n]).alias(n))
            else:
                exprs.append(col(n))
        for n, e in columns.items():
            if n not in self.schema:
                exprs.append(_to_expr(e).alias(n))
        return DataFrame(Project(self._plan, exprs))

    def with_column_renamed(self, existing: str, new: str) -> "DataFrame":
        return self.with_columns_renamed({existing: new})

    def with_columns_renamed(self, mapping: Dict[str, str]) -> "DataFrame":
        exprs = [col(n).alias(mapping.get(n, n)) for n in self.column_names]
        return DataFrame(Project(self._plan, exprs))

    def transform(self, func: Callable[["DataFrame"], "DataFrame"], *args, **kwargs) -> "DataFrame":
        out = func(self, *args, **kwargs)
        if not isinstance(out, DataFrame):
            raise ValueError(f"transform function must return a DataFrame, got {type(out)}")
        return out

    # ------------------------------------------------------------------ filtering
    def where(self, predicate: Union[Expression, str]) -> "DataFrame":
        if isinstance(predicate, str):
            from .sql import sql_expr

            predicate = sql_expr(predicate)
        return DataFrame(Filter(self._plan, predicate))

    filter = where

    def drop_null(self, *columns: ColumnInput) -> "DataFrame":
        exprs = _to_exprs(columns) if columns else [col(n) for n in self.column_names]
        pred = exprs[0].not_null()
        for e in exprs[1:]:
            pred = pred & e.not_null()
        return self.where(pred)

    def drop_nan(self, *columns: ColumnInput) -> "DataFrame":
        if columns:
            exprs = _to_exprs(columns)
        else:
            exprs = [col(f.name) for f in self.schema if f.dtype.is_floating()]
        if not exprs:
            return self
        pred = None
        for e in exprs:
            p = e.is_null() | e.float.not_nan()
            pred = p if pred is None else (pred & p)
        return self.where(pred)

    def distinct(self, *subset: ColumnInput) -> "DataFrame":
        return DataFrame(Distinct(self._plan, _to_exprs(subset) if subset else None))

    unique = distinct

    def sample(self, fraction: float, with_replacement: bool = False,
               seed: Optional[int] = None) -> "DataFrame":
        if fraction < 0.0 or fraction > 1.0:
            raise ValueError(f"fraction must be in [0, 1], got {fraction}")
        return DataFrame(Sample(self._plan, fraction, with_replacement, seed))

    def limit(self, num: int) -> "DataFrame":
        if num < 0:
            raise ValueError(f"limit must be non-negative, got {num}")
        return DataFrame(Limit(self._plan, num))

    head = limit

    # ------------------------------------------------------------------ ordering
    def sort(self, by, desc: Union[bool, List[bool]] = False,
             nulls_first=None) -> "DataFrame":
        by = _to_exprs(by)
        desc = _norm_bools(desc, len(by))
        nf = _norm_bools(nulls_first, len(by), None) if nulls_first is not None else [None] * len(by)
        return DataFrame(Sort(self._plan, by, desc, nf))

    # ------------------------------------------------------------------ partitioning
    def repartition(self, num: Optional[int], *partition_by: ColumnInput) -> "DataFrame":
        if partition_by:
            return DataFrame(Repartition(self._plan, "hash", num, _to_exprs(partition_by)))
        return DataFrame(Repartition(self._plan, "random", num))

    def into_partitions(self, num: int) -> "DataFrame":
        return DataFrame(Repartition(self._plan, "into", num))

    # ------------------------------------------------------------------ combining
    def join(self, other: "DataFrame", on=None, left_on=None, right_on=None,
             how: str = "inner", strategy: Optional[str] = None,
             suffix: str = "right.") -> "DataFrame":
        if on is not None:
            left_on = right_on = on
        if how != "cross" and (left_on is None or right_on is None):
            raise ValueError("join requires on= or left_on=/right_on=")
        lo = _to_exprs(left_on) if left_on is not None else []
        ro = _to_exprs(right_on) if right_on is not None else []
        return DataFrame(Join(self._plan, other._plan, lo, ro, how, strategy, suffix))

    def concat(self, other: "DataFrame") -> "DataFrame":
        return DataFrame(Concat(self._plan, other._plan))

    # ------------------------------------------------------------------ reshaping
    def explode(self, *columns: ColumnInput) -> "DataFrame":
        return DataFrame(Explode(self._plan, _to_exprs(columns)))

    def unpivot(self, ids, values=None, variable_name: str = "variable",
                value_name: str = "value") -> "DataFrame":
        ids = _to_exprs(ids)
        if values is None:
            id_names = {e.name() for e in ids}
            values = [col(n) for n in self.column_names if n not in id_names]
        else:
            values = _to_exprs(values)
        return DataFrame(Unpivot(self._plan, ids, values, variable_name, value_name))

    melt = unpivot

    def pivot(self, group_by, pivot_col: ColumnInput, value_col: ColumnInput,
              agg_fn: str, names: Optional[List[str]] = None) -> "DataFrame":
        group_by = _to_exprs(group_by)
        pivot_e = _to_expr(pivot_col)
        value_e = _to_expr(value_col)
        if names is None:
            names_df = DataFrame(self._plan).select(pivot_e).distinct().collect()
            names = [v for v in names_df.to_pydict()[pivot_e.name()] if v is not None]
        return DataFrame(Pivot(self._plan, group_by, pivot_e, value_e, agg_fn, names))

    def _add_monotonic_id(self, column_name: str = "id") -> "DataFrame":
        return DataFrame(MonotonicallyIncreasingId(self._plan, column_name))

    with_monotonically_increasing_id = _add_monotonic_id

    # ------------------------------------------------------------------ aggregation
    def _agg_all(self, kind: str, cols, **extra) -> "DataFrame":
        exprs = _to_exprs(cols) if cols else [
            col(f.name) for f in self.schema if f.dtype.is_numeric()]
        aggs = [Expression(AggExpr(kind, e._node, extra or None)).alias(e.name()) for e in exprs]
        return DataFrame(Aggregate(self._plan, aggs, []))

    def sum(self, *cols: ColumnInput) -> "DataFrame":
        return self._agg_all("sum", cols)

    def mean(self, *cols: ColumnInput) -> "DataFrame":
        return self._agg_all("mean", cols)

    def min(self, *cols: ColumnInput) -> "DataFrame":
        return self._agg_all("min", cols)

    def max(self, *cols: ColumnInput) -> "DataFrame":
        return self._agg_all("max", cols)

    def stddev(self, *cols: ColumnInput) -> "DataFrame":
        return self._agg_all("stddev", cols)

    def any_value(self, *cols: ColumnInput) -> "DataFrame":
        return self._agg_all("any_value", cols)

    def count(self, *cols: ColumnInput) -> "DataFrame":
        exprs = _to_exprs(cols) if cols else [col(n) for n in self.column_names]
        aggs = [Expression(AggExpr("count", e._node)).alias(e.name()) for e in exprs]
        return DataFrame(Aggregate(self._plan, aggs, []))

    def agg_list(self, *cols: ColumnInput) -> "DataFrame":
        exprs = _to_exprs(cols) if cols else [col(n) for n in self.column_names]
        aggs = [Expression(AggExpr("list", e._node)).alias(e.name()) for e in exprs]
        return DataFrame(Aggregate(self._plan, aggs, []))

    def agg_concat(self, *cols: ColumnInput) -> "DataFrame":
        exprs = _to_exprs(cols)
        aggs = [Expression(AggExpr("concat", e._node)).alias(e.name()) for e in exprs]
        return DataFrame(Aggregate(self._plan, aggs, []))

    def agg(self, *to_agg) -> "DataFrame":
        aggs = self._normalize_aggs(to_agg)
        return DataFrame(Aggregate(self._plan, aggs, []))

    @staticmethod
    def _normalize_aggs(to_agg) -> List[Expression]:
        flat: List[Any] = []
        for a in to_agg:
            if isinstance(a, (list, tuple)) and not (
                isinstance(a, tuple) and len(a) == 2 and isinstance(a[1], str)
            ):
                flat.extend(a)
            else:
                flat.append(a)
        out: List[Expression] = []
        for a in flat:
            if isinstance(a, tuple):
                e, fn = a
                e = _to_expr(e)
                out.append(getattr(e, {"sum": "sum", "mean": "mean", "min": "min",
                                       "max": "max", "count": "count", "list": "agg_list",
                                       "concat": "agg_concat", "stddev": "stddev"}[fn])())
            else:
                out.append(_to_expr(a))
        for e in out:
            if not e._node.is_aggregation():
                raise ValueError(f"agg() expects aggregation expressions, got {e!r}")
        return out

    def groupby(self, *group_by: ColumnInput) -> "GroupedDataFrame":
        exprs = []
        for g in group_by:
            if isinstance(g, (list, tuple)):
                exprs.extend(_to_exprs(g))
            else:
                exprs.append(_to_expr(g))
        if not exprs:
            raise ValueError("groupby requires at least one column")
        return GroupedDataFrame(self, exprs)

    def count_rows(self) -> int:
        if not self.column_names:
            return 0
        cnt = DataFrame(Aggregate(
            self._plan,
            [Expression(AggExpr("count", col(self.column_names[0])._node,
                                {"mode": "all"})).alias("count")], []))
        return cnt.to_pydict()["count"][0]

    def __len__(self) -> int:
        return self.count_rows()

    # ------------------------------------------------------------------ writes
    def write_parquet(self, root_dir: str, compression: str = "snappy",
                      partition_cols=None) -> "DataFrame":
        pc = _to_exprs(partition_cols) if partition_cols else None
        return DataFrame(Write(self._plan, root_dir, "parquet", compression, pc)).collect()

    def write_csv(self, root_dir: str, partition_cols=None) -> "DataFrame":
        pc = _to_exprs(partition_cols) if partition_cols else None
        return DataFrame(Write(self._plan, root_dir, "csv", None, pc)).collect()

    def write_json(self, root_dir: str, partition_cols=None) -> "DataFrame":
        pc = _to_exprs(partition_cols) if partition_cols else None
        return DataFrame(Write(self._plan, root_dir, "json", None, pc)).collect()

    def write_iceberg(self, table_uri: str, mode: str = "append") -> "DataFrame":
        """Write this DataFrame as an Iceberg v2 snapshot commit (reference:
        daft/dataframe/dataframe.py write_iceberg; no client library — the
        avro manifests are encoded natively by io/avro.py). mode: append |
        overwrite | error. Returns a DataFrame of the added file paths."""
        from .io.catalogs import write_iceberg_table

        self.collect()
        arrow_tables = [p.to_arrow() for p in self._result.partitions]
        added = write_iceberg_table(table_uri, arrow_tables, mode=mode)
        from .api import from_pydict

        return from_pydict({"path": added})

    def write_deltalake(self, table_uri: str, mode: str = "append") -> "DataFrame":
        """Write this DataFrame as a Delta Lake table commit (reference:
        daft/dataframe/dataframe.py write_deltalake). mode: append |
        overwrite | error. The commit is atomic: parquet data files land
        first, then one put-if-absent JSON transaction publishes them.
        Returns a DataFrame of the added file paths."""
        from .io.catalogs import write_deltalake_table

        self.collect()
        arrow_tables = [p.to_arrow() for p in self._result.partitions]
        added = write_deltalake_table(table_uri, arrow_tables, mode=mode)
        from .api import from_pydict

        return from_pydict({"path": added})

    def write_lance(self, table_uri: str, mode: str = "append") -> "DataFrame":
        """Write this DataFrame as a lance dataset (reference:
        daft/dataframe/dataframe.py write_lance via lance.write_dataset —
        requires the optional `lance` package, as in the reference). mode:
        append | overwrite | error. Returns a DataFrame of data-file paths."""
        from .io.catalogs import write_lance_table

        self.collect()
        arrow_tables = [p.to_arrow() for p in self._result.partitions]
        added = write_lance_table(table_uri, arrow_tables, mode=mode)
        from .api import from_pydict

        return from_pydict({"path": added})

    # ------------------------------------------------------------------ execution
    def cancel(self) -> None:
        """Stop this DataFrame's in-flight execution at the next partition
        boundary (reference: stop_plan / MaterializedResult.cancel)."""
        self.stats.cancel()

    def collect(self, profile: Union[bool, str, None] = None) -> "DataFrame":
        """Materialize the plan. ``profile`` arms the structured query
        profiler for this execution: ``True`` records a QueryProfile
        (``df.profile()`` / ``daft_tpu.last_profile()``), a string path
        additionally writes the profile JSON there. ``None`` defers to
        ``ExecutionConfig.enable_profiling``. An already-materialized
        DataFrame cannot re-execute: its existing profile (if any) is
        served — and written to a requested path — instead of silently
        ignoring the argument."""
        if self._result is not None:
            if isinstance(profile, str) and self._profile is not None:
                self._profile.to_json(profile)
            return self
        self.stats.reset_cancel()  # a cancelled DataFrame stays retryable
        from .profile import arm_for_query
        from .profile.timeline import DeviceFrame, end_frame, host_query

        # armed here, where the query begins, so planning is inside a span
        want = arm_for_query(self.stats, f"q-{id(self._plan):x}", profile)
        with host_query(self.stats):
            # entry.setup: from here to the plan stream's first pull
            # (execute_plan), less the planning inside it
            DeviceFrame(self.stats, "entry.setup",
                        "entry_setup_ns").__enter__()
            try:
                self._run()
            finally:
                end_frame(self.stats, "entry_setup_ns")
        if want:
            from .profile import build_profile

            qp = build_profile(self.stats.profiler, self.stats)
            self._profile = qp
            get_context()._last_profile = qp
            if isinstance(want, str):
                qp.to_json(want)
        self._plan = InMemorySource(self._result.schema, self._result.partitions)
        return self

    def _run(self) -> None:
        """Set ``_result``: the result cache's entry for this plan, or the
        runner's run of it."""
        from .runners import partition_set_cache, plan_cache_key

        cfg = get_context().execution_config
        cache = partition_set_cache()
        key = (plan_cache_key(self._plan)
               if cfg.enable_result_cache else None)
        hit = cache.get(key) if key is not None else None
        if hit is not None:
            self.stats.bump("result_cache_hits")
            if self.stats.profiler.armed:
                self.stats.profiler.event("result_cache_hit")
            self._result = hit
        else:
            runner = get_context().runner()
            self._result = runner.run(self._plan, stats=self.stats)
            if key is not None:
                import weakref

                cache.put(key, self._result)
                # the entry lives exactly as long as some DataFrame owns it
                weakref.finalize(self, cache.release, key)

    def profile(self):
        """The QueryProfile recorded by a profiled collect(), or None."""
        return self._profile

    def last_query_record(self):
        """The flight recorder's QueryRecord for this DataFrame's most
        recent plan execution (None before any execution, or when the
        result was served from the plan cache). The same record is in
        ``daft_tpu.query_log()``."""
        return self.stats.last_record

    def iter_partitions(self) -> Iterator[MicroPartition]:
        if self._result is not None:
            yield from self._result.partitions
            return
        self.stats.reset_cancel()
        runner = get_context().runner()
        yield from runner.run_iter(self._plan, stats=self.stats)

    def to_arrow_iter(self):
        for part in self.iter_partitions():
            if len(part):
                yield from part.to_arrow().to_batches()

    def iter_rows(self) -> Iterator[dict]:
        for part in self.iter_partitions():
            yield from part.to_pylist()

    def _materialized(self) -> PartitionSet:
        self.collect()
        return self._result

    def _converted(self, method: Optional[str]):
        """The materialized result as one Table, then its ``method`` (None:
        the Table), in the ``entry.convert`` frame (``entry_convert_ns``)."""
        result = self._materialized()
        from .profile.timeline import DeviceFrame, host_query

        with host_query(self.stats), DeviceFrame(
                self.stats, "entry.convert", "entry_convert_ns"):
            table = result.to_table()
            return table if method is None else getattr(table, method)()

    def to_pydict(self) -> Dict[str, list]:
        return self._converted("to_pydict")

    def to_pylist(self) -> List[dict]:
        return self._converted("to_pylist")

    def to_arrow(self):
        return self._converted("to_arrow")

    def to_pandas(self):
        return self._converted("to_pandas")

    def to_table(self):
        return self._converted(None)

    def to_torch_map_dataset(self):
        from .integrations.torch_data import MapDataset

        return MapDataset(self)

    def to_torch_iter_dataset(self):
        from .integrations.torch_data import IterDataset

        return IterDataset(self)

    def to_ray_dataset(self):
        """Reference: dataframe.py to_ray_dataset — needs the ray runtime,
        which is not part of this image (the mesh runner is the distributed
        backend here)."""
        try:
            import ray.data  # noqa: F401
        except ImportError as e:
            raise ImportError(
                "to_ray_dataset requires ray, which is not installed; "
                "distributed execution here runs on the jax mesh (MeshRunner)") from e
        import ray.data as rd

        return rd.from_arrow(self.to_arrow())

    def to_dask_dataframe(self):
        """Reference: dataframe.py to_dask_dataframe — needs dask."""
        try:
            import dask.dataframe as dd
        except ImportError as e:
            raise ImportError("to_dask_dataframe requires dask, which is not installed") from e
        return dd.from_pandas(self.to_pandas(), npartitions=max(self.num_partitions(), 1))

    # ------------------------------------------------------------------ display
    def show(self, n: int = 8) -> None:
        print(self.limit(n)._preview_str(n))

    def _preview_str(self, n: int) -> str:
        tbl = self.limit(n).to_table()
        d = tbl.to_pydict()
        names = list(d)
        widths = {}
        dtypes = {f.name: repr(f.dtype) for f in tbl.schema}
        for nm in names:
            vals = [_cell(v) for v in d[nm]]
            widths[nm] = min(30, max([len(nm), len(dtypes[nm])] + [len(v) for v in vals] + [4]))
            d[nm] = vals
        def row(cells):
            return "| " + " | ".join(c[:widths[nm]].ljust(widths[nm]) for nm, c in zip(names, cells)) + " |"
        sep = "+" + "+".join("-" * (widths[nm] + 2) for nm in names) + "+"
        lines = [sep, row(names), row([dtypes[nm] for nm in names]), sep]
        nrows = len(d[names[0]]) if names else 0
        for i in range(nrows):
            lines.append(row([d[nm][i] for nm in names]))
        lines.append(sep)
        return "\n".join(lines)

    def __repr__(self) -> str:
        n = get_context().execution_config.num_preview_rows
        if self._result is not None:
            try:
                return self._preview_str(n)
            except Exception:
                pass
        return f"DataFrame({self.schema!r})"

    def _repr_html_(self) -> str:
        """Notebook preview table with registered viz hooks applied to
        Python-object cells (reference: daft/dataframe/display.py +
        daft/viz/html_viz_hooks.py)."""
        import html as _h

        from .viz import html_table

        n = get_context().execution_config.num_preview_rows
        # same discipline as __repr__: never execute the plan at display time,
        # never let a preview error break notebook rendering
        if self._result is not None:
            try:
                total = sum(len(p) for p in self._result.partitions)
                preview = self.limit(n).to_table()
                return html_table(preview.schema, preview.to_pydict(), n, total)
            except Exception:
                pass
        return f"<pre>DataFrame({_h.escape(repr(self.schema))})</pre>"


def _cell(v) -> str:
    if v is None:
        return "None"
    s = str(v)
    return s if len(s) <= 30 else s[:27] + "..."


class GroupedDataFrame:
    """Result of df.groupby(...) (reference: daft/dataframe/dataframe.py
    GroupedDataFrame)."""

    def __init__(self, df: DataFrame, group_by: List[Expression]):
        self.df = df
        self.group_by = group_by

    def _agg_all(self, kind: str, cols, **extra) -> DataFrame:
        keys = {e.name() for e in self.group_by}
        if cols:
            exprs = _to_exprs(cols)
        else:
            exprs = [col(f.name) for f in self.df.schema
                     if f.name not in keys and (f.dtype.is_numeric() or kind in ("count", "any_value"))]
        aggs = [Expression(AggExpr(kind, e._node, extra or None)).alias(e.name()) for e in exprs]
        return DataFrame(Aggregate(self.df._plan, aggs, self.group_by))

    def sum(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("sum", cols)

    def mean(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("mean", cols)

    def min(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("min", cols)

    def max(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("max", cols)

    def stddev(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("stddev", cols)

    def any_value(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("any_value", cols)

    def count(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("count", cols)

    def agg_list(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("list", cols)

    def agg_concat(self, *cols: ColumnInput) -> DataFrame:
        return self._agg_all("concat", cols)

    def agg(self, *to_agg) -> DataFrame:
        aggs = DataFrame._normalize_aggs(to_agg)
        return DataFrame(Aggregate(self.df._plan, aggs, self.group_by))

    def map_groups(self, udf_expr: Expression) -> DataFrame:
        """Run a UDF once per group (reference: GroupedDataFrame.map_groups).
        Executed by materializing group partitions; the UDF sees each group's
        rows as full columns."""
        df = self.df.collect()
        mp = df._result.to_micropartition()
        parts, uniq = mp.partition_by_value(self.group_by)
        from .table import Table

        outs = []
        key_names = uniq.column_names
        for i, part in enumerate(parts):
            res = part.table().eval_expression_list([udf_expr])
            key_row = uniq.slice(i, i + 1)
            n = len(res)
            key_cols = {}
            for kn in key_names:
                v = key_row.get_column(kn).to_pylist()[0]
                key_cols[kn] = [v] * n
            merged = Table.from_pydict({**key_cols, **res.to_pydict()})
            outs.append(merged)
        if not outs:
            schema = Schema(list(uniq.schema))
            out_tbl = Table.empty(schema)
        else:
            out_tbl = Table.concat(outs)
        return from_partitions([MicroPartition.from_table(out_tbl)], out_tbl.schema)


# ---------------------------------------------------------------------------
# constructors (used by api.py)
# ---------------------------------------------------------------------------

def from_partitions(parts: List[MicroPartition], schema: Schema) -> DataFrame:
    ps = PartitionSet(schema, parts)
    return DataFrame(InMemorySource(schema, parts), result=ps)
