"""MicroPartition: the unit of execution — a lazily-materialized batch.

Role-equivalent to the reference's src/daft-micropartition/src/micropartition.rs:35-78:
a partition is either Unloaded (a ScanTask — schema + pushdowns + file metadata,
no bytes decoded yet) or Loaded (one or more concrete Tables). Compute ops force
materialization; metadata ops (len/schema/stats) answer from file footers when
possible so planning never triggers IO. Concat of loaded partitions is O(1)
(tables are chained, not copied) — matching the reference's Vec<Table> design.
"""

from __future__ import annotations

import threading
from typing import (Any, Dict, Iterator, List, Optional, Sequence, Tuple,
                    Union)

from .schema import Schema
from .stats import TableStats
from .table import Table


class MicroPartition:
    __slots__ = ("schema", "_state", "_tables", "_scan_task", "_stats", "_lock",
                 "_device_cache", "owner_process", "_pending",
                 "_count_preserving", "lineage_recipe")

    def __init__(self, schema: Schema, tables: Optional[List[Table]] = None,
                 scan_task=None, stats: Optional[TableStats] = None):
        if (tables is None) == (scan_task is None):
            raise ValueError("MicroPartition needs exactly one of tables / scan_task")
        self.schema = schema
        self._tables = tables
        self._scan_task = scan_task
        self._state = "loaded" if tables is not None else "unloaded"
        self._stats = stats
        self._lock = threading.Lock()
        # HBM residency: staged DeviceColumns keyed by (col, bucket, x64 mode).
        # The host->device link, not compute, bounds device-path throughput, so
        # repeated queries over a cached/collected partition reuse staged
        # columns instead of re-transferring (lifetime == partition lifetime).
        self._device_cache: Dict[Any, Any] = {}
        # Per-host scan locality (reference: per-node dispatch,
        # ray_runner.py:504-685): owner_process marks a scan partition whose
        # rows are CONTRIBUTED by exactly one process of a multi-host run;
        # _pending defers map-op evaluation on foreign-owned unloaded
        # partitions (Table -> Table transforms replayed at materialization)
        # so a projection/filter chain between scan and exchange never forces
        # a foreign read. Any consumer that DOES materialize gets the correct
        # post-op rows — correctness never depends on ownership.
        self.owner_process: Optional[int] = None
        self._pending: Optional[List[Any]] = None
        self._count_preserving = True
        # lineage recipe (integrity/lineage.py): a zero-arg closure that
        # re-derives this partition's exact tables from stable storage.
        # Attached by producers whose derivation is cheap to replay (e.g.
        # shuffle fanout over a scan-backed source); consumed by the spill
        # layer so a corrupted spill file recomputes instead of failing
        # the query. Never pickled (closures are driver-local).
        self.lineage_recipe = None

    def device_stage_cache(self) -> Dict[Any, Any]:
        return self._device_cache

    def stage_view(self) -> "MicroPartition":
        """The same rows, with a stage cache that starts as a copy of this
        one's: a device step over the view reads the lanes this partition
        holds, and what it stages is the view's alone, so this partition
        gains no residency from it."""
        out = self._wrap(self.table())
        out._device_cache = dict(self._device_cache)
        return out

    def drop_staged(self) -> None:
        """Let go of every staged lane: the stage cache starts empty again
        (a launch still reading the old one keeps it until it is done)."""
        self._device_cache = {}

    # ------------------------------------------------------------- pickling
    # Partitions cross process boundaries on the dist/ worker transport.
    # Loaded partitions ship their tables; unloaded ones ship the scan task
    # (the WORKER reads the file — per-worker scan locality). Deferred op
    # chains are closures that cannot cross a process boundary, so they
    # materialize first (the dist backend declines those tasks anyway).
    def __getstate__(self):
        with self._lock:
            if self._state == "loaded":
                return {"schema": self.schema, "tables": list(self._tables),
                        "stats": self._stats, "owner": self.owner_process}
            if not self._pending:
                task = self._scan_task
                # a PrefetchedScanTask wrapper carries driver-local state
                # (queue slot, future): ship the UNDERLYING task — the
                # receiving process performs its own read
                task = getattr(task, "_task", task)
                return {"schema": self.schema, "scan_task": task,
                        "stats": self._stats, "owner": self.owner_process}
        return {"schema": self.schema, "tables": [self.table()],
                "stats": self._stats, "owner": self.owner_process}

    def __setstate__(self, state):
        # a freshly-unpickled partition is visible to exactly one thread:
        # its lock does not exist yet, so lock discipline cannot apply
        self.schema = state["schema"]
        self._tables = state.get("tables")  # daftlint: disable=DTL002
        self._scan_task = state.get("scan_task")  # daftlint: disable=DTL002
        self._state = ("loaded" if self._tables is not None  # daftlint: disable=DTL002
                       else "unloaded")
        self._stats = state.get("stats")
        self._lock = threading.Lock()
        self._device_cache = {}
        self.owner_process = state.get("owner")
        self._pending = None  # daftlint: disable=DTL002
        self._count_preserving = True
        self.lineage_recipe = None

    def with_pending_op(self, fn, schema: Schema,
                        count_preserving: bool) -> "MicroPartition":
        """Deferred map op over an unloaded partition: same scan task, the
        transform replays at table() time. Used only for foreign-owned
        partitions in multi-host mode."""
        out = MicroPartition(schema, scan_task=self._scan_task,
                            stats=None)
        out.owner_process = self.owner_process
        out._pending = list(self._pending or []) + [fn]
        out._count_preserving = self._count_preserving and count_preserving
        return out

    # ------------------------------------------------------------------ ctors
    @staticmethod
    def from_table(tbl: Table) -> "MicroPartition":
        return MicroPartition(tbl.schema, tables=[tbl])

    @staticmethod
    def from_tables(tables: List[Table]) -> "MicroPartition":
        if not tables:
            raise ValueError("from_tables requires at least one table (use empty())")
        return MicroPartition(tables[0].schema, tables=list(tables))

    @staticmethod
    def from_scan_task(task) -> "MicroPartition":
        return MicroPartition(task.materialized_schema, scan_task=task, stats=task.stats)

    @staticmethod
    def empty(schema: Optional[Schema] = None) -> "MicroPartition":
        schema = schema or Schema.empty()
        return MicroPartition.from_table(Table.empty(schema))

    @staticmethod
    def from_pydict(data: Dict[str, Any]) -> "MicroPartition":
        return MicroPartition.from_table(Table.from_pydict(data))

    @staticmethod
    def from_arrow(tbl) -> "MicroPartition":
        return MicroPartition.from_table(Table.from_arrow(tbl))

    # ------------------------------------------------------------------ state
    def is_loaded(self) -> bool:
        return self._state == "loaded"

    def scan_task(self):
        return self._scan_task

    def table(self) -> Table:
        """Materialize to a single concrete Table (loads + concats if needed)."""
        with self._lock:
            if self._state == "unloaded":
                tbl = self._scan_task.read()
                for fn in self._pending or ():
                    tbl = fn(tbl)
                self._pending = None
                self._tables = [tbl]
                self._state = "loaded"
                self._scan_task = None
            if len(self._tables) > 1:
                self._tables = [Table.concat(self._tables)]
            return self._tables[0]

    def chunk_tables(self) -> List[Table]:
        """Materialize preserving the reader's chunk structure (one Table per
        file / reader chunk) instead of collapsing to a single Table. The map
        side of a shuffle hashes and splits each chunk independently, so the
        O(partition-bytes) memcpy that `table()`'s Table.concat pays never
        happens (measured: the concat dominated the out-of-core rung's map
        phase). Falls back to the collapsing path when deferred ops are
        pending — a deferred limit/head chain is defined over the WHOLE
        partition, not per chunk. Reference role: the reference MicroPartition
        is a Vec<Table> whose ops iterate the pieces (micropartition.rs:35-78);
        this surfaces that same contract to row-local consumers."""
        with self._lock:
            if self._state == "loaded":
                return list(self._tables)
            if not self._pending:
                task = self._scan_task
                read_chunks = getattr(task, "read_chunks", None)
                tbls = list(read_chunks()) if read_chunks is not None else [task.read()]
                tbls = [t for t in tbls if len(t)] or [Table.empty(self.schema)]
                self._tables = tbls
                self._state = "loaded"
                self._scan_task = None
                return list(self._tables)
        return [self.table()]

    def iter_chunk_tables(self) -> Iterator[Table]:
        """LAZY counterpart of ``chunk_tables`` for the streaming
        producers (daft_tpu/stream/): a loaded partition yields its
        resident tables; an unloaded one decodes chunk by chunk via
        ``ScanTask.iter_chunks`` (parquet: one row group at a time), so
        the first morsel flows before the rest of the partition is read.
        The load state is NOT mutated — the streaming producer consumes
        the chunks exactly once, and a failed iteration can restart from
        scratch (the partition-level transient-retry contract). Deferred
        pending ops collapse to ``chunk_tables()``: they are defined over
        the whole partition."""
        with self._lock:
            if self._state == "loaded":
                return iter(list(self._tables))
            task = None if self._pending else self._scan_task
        if task is None or not hasattr(task, "iter_chunks"):
            return iter(self.chunk_tables())
        return (t for t in task.iter_chunks() if len(t))

    def __len__(self) -> int:
        n = self.num_rows_or_none()
        if n is not None:
            return n
        return len(self.table())

    def num_rows_or_none(self) -> Optional[int]:
        """Row count without IO, if knowable (loaded, or exact scan metadata)."""
        if self._state == "loaded":
            return sum(len(t) for t in self._tables)
        if not self._count_preserving:
            return None  # a deferred filter changes the count
        return self._scan_task.num_rows()

    def size_bytes(self) -> Optional[int]:
        if self._state == "loaded":
            return sum(t.size_bytes() for t in self._tables)
        if self._pending:
            return None  # deferred ops change the width/count
        return self._scan_task.size_bytes()

    def statistics(self) -> Optional[TableStats]:
        return self._stats

    @property
    def column_names(self) -> List[str]:
        return self.schema.field_names()

    def __repr__(self) -> str:
        if self._state == "unloaded":
            return f"MicroPartition(Unloaded {self._scan_task!r})"
        return f"MicroPartition(Loaded rows={len(self)})"

    # ------------------------------------------------------------------ conversions
    def to_arrow(self):
        return self.table().to_arrow()

    def to_pydict(self) -> Dict[str, list]:
        return self.table().to_pydict()

    def to_pylist(self) -> List[dict]:
        return self.table().to_pylist()

    def to_pandas(self):
        return self.table().to_pandas()

    def get_column(self, name: str):
        return self.table().get_column(name)

    # ------------------------------------------------------------------ compute ops
    # Each materializes and delegates to Table, returning a Loaded partition.

    def _wrap(self, tbl: Table) -> "MicroPartition":
        out = MicroPartition.from_table(tbl)
        # contribution ownership survives per-partition transforms so the
        # multi-host exchange keeps exactly-once semantics by OWNER, not by
        # a fragile stream-index coincidence
        out.owner_process = self.owner_process
        return out

    def eval_expression_list(self, exprs) -> "MicroPartition":
        return self._wrap(self.table().eval_expression_list(exprs))

    def filter(self, predicate) -> "MicroPartition":
        return self._wrap(self.table().filter(predicate))

    def take(self, indices) -> "MicroPartition":
        return self._wrap(self.table().take(indices))

    def slice(self, start: int, end: int) -> "MicroPartition":
        return self._wrap(self.table().slice(start, end))

    def head(self, n: int) -> "MicroPartition":
        if self._state == "unloaded":
            if self._pending:
                # a limit must not push BELOW deferred ops (the deferred
                # filter changes which rows the first n are): defer it too
                return self.with_pending_op(lambda t: t.head(n), self.schema,
                                            count_preserving=False)
            # narrow the scan's limit instead of reading everything
            task = self._scan_task
            pd = task.pushdowns
            new_limit = n if pd.limit is None else min(pd.limit, n)
            narrowed = task.with_pushdowns(pd.with_limit(new_limit))
            out = MicroPartition.from_scan_task(narrowed)
            out.owner_process = self.owner_process
            return out
        return self._wrap(self.table().head(n))

    def sample(self, fraction=None, size=None, with_replacement=False, seed=None) -> "MicroPartition":
        return self._wrap(self.table().sample(fraction, size, with_replacement, seed))

    def sort(self, sort_keys, descending=None, nulls_first=None) -> "MicroPartition":
        return self._wrap(self.table().sort(sort_keys, descending, nulls_first))

    def argsort(self, sort_keys, descending=None, nulls_first=None):
        return self.table().argsort(sort_keys, descending, nulls_first)

    def agg(self, to_agg, group_by=None) -> "MicroPartition":
        if group_by and self._state == "loaded" and len(self._tables) > 1:
            # multi-piece partitions (shuffle buckets) aggregate through ONE
            # chunked acero pass instead of concatenating the pieces first
            out = Table.acero_grouped_agg_chunked(self._tables, to_agg, group_by)
            if out is not None:
                return self._wrap(out)
        return self._wrap(self.table().agg(to_agg, group_by))

    def distinct(self, subset=None) -> "MicroPartition":
        return self._wrap(self.table().distinct(subset))

    def explode(self, exprs) -> "MicroPartition":
        return self._wrap(self.table().explode(exprs))

    def unpivot(self, ids, values, variable_name="variable", value_name="value") -> "MicroPartition":
        return self._wrap(self.table().unpivot(ids, values, variable_name, value_name))

    def pivot(self, group_by, pivot_col, value_col, names, agg_fn="sum") -> "MicroPartition":
        return self._wrap(self.table().pivot(group_by, pivot_col, value_col, names, agg_fn))

    def hash_join(self, right: "MicroPartition", left_on, right_on, how="inner",
                  suffix="right.") -> "MicroPartition":
        return self._wrap(self.table().hash_join(right.table(), left_on, right_on, how, suffix))

    def sort_merge_join(self, right: "MicroPartition", left_on, right_on, how="inner",
                        suffix="right.", is_sorted=False) -> "MicroPartition":
        return self._wrap(self.table().sort_merge_join(right.table(), left_on, right_on,
                                                       how, suffix, is_sorted))

    def add_monotonic_id(self, partition_offset: int = 0, column_name: str = "id") -> "MicroPartition":
        return self._wrap(self.table().add_monotonic_id(partition_offset, column_name))

    def select_columns(self, names: List[str]) -> "MicroPartition":
        if self._state == "unloaded":
            if self._pending:
                # the names may only exist in a deferred projection's output:
                # never push them into the file scan — defer the select
                from .schema import Schema as _S

                return self.with_pending_op(
                    lambda t: t.select_columns(names),
                    _S([self.schema[c] for c in names]),
                    count_preserving=True)
            task = self._scan_task
            pd = task.pushdowns
            cols = [c for c in names]
            narrowed = task.with_pushdowns(pd.with_columns(cols))
            out = MicroPartition.from_scan_task(narrowed)
            out.owner_process = self.owner_process
            return out
        return self._wrap(self.table().select_columns(names))

    def rename_columns(self, mapping: Dict[str, str]) -> "MicroPartition":
        return self._wrap(self.table().rename_columns(mapping))

    def cast_to_schema(self, schema: Schema) -> "MicroPartition":
        return self._wrap(self.table().cast_to_schema(schema))

    def partition_by_hash(self, exprs, num_partitions: int) -> List["MicroPartition"]:
        return self._partition_chunkwise(
            lambda t: t.partition_by_hash(exprs, num_partitions), num_partitions)

    def partition_by_random(self, num_partitions: int, seed: int = 0) -> List["MicroPartition"]:
        # NOT chunk-wise: the assignment is a seeded permutation over row
        # positions, so per-chunk application with the same seed would
        # correlate buckets across chunks instead of matching the collapsed
        # partition's assignment
        return [self._wrap(t) for t in self.table().partition_by_random(num_partitions, seed)]

    def partition_by_range(self, exprs, boundaries: Table, descending=None,
                           nulls_first=None) -> List["MicroPartition"]:
        return self._partition_chunkwise(
            lambda t: t.partition_by_range(exprs, boundaries, descending, nulls_first),
            len(boundaries) + 1)

    def _partition_chunkwise(self, split, num: int) -> List["MicroPartition"]:
        """Row-local partitioners (hash/range: a row's bucket depends only on
        its own values) run per chunk; each bucket chains its per-chunk pieces
        without copying, so a multi-chunk scan partition never pays the full
        concat on the shuffle map side."""
        tabs = self.chunk_tables()
        if len(tabs) == 1:
            return [self._wrap(t) for t in split(tabs[0])]
        buckets: List[List[Table]] = [[] for _ in range(num)]
        for t in tabs:
            for i, bt in enumerate(split(t)):
                if len(bt):
                    buckets[i].append(bt)
        out = []
        for bs in buckets:
            mp = (MicroPartition(self.schema, tables=bs) if bs
                  else MicroPartition.empty(self.schema))
            mp.owner_process = self.owner_process
            out.append(mp)
        return out

    def partition_by_value(self, exprs) -> Tuple[List["MicroPartition"], Table]:
        parts, uniq = self.table().partition_by_value(exprs)
        return [self._wrap(t) for t in parts], uniq

    def hash_rows(self, exprs=None, seed: int = 0):
        return self.table().hash_rows(exprs, seed)

    @staticmethod
    def concat(parts: List["MicroPartition"]) -> "MicroPartition":
        """O(1) concat: chains loaded tables; forces unloaded inputs."""
        if not parts:
            raise ValueError("concat of zero partitions")
        tables: List[Table] = []
        for p in parts:
            if p._state == "loaded":
                tables.extend(p._tables)
            else:
                tables.append(p.table())
        tables = [t for t in tables if len(t) > 0] or [tables[0]]
        return MicroPartition(parts[0].schema, tables=tables)

    def write_tabular(self, root_dir: str, format: str = "parquet",
                      compression: Optional[str] = None, partition_cols=None) -> "MicroPartition":
        from .io.writer import write_tabular

        return self._wrap(write_tabular(self.table(), root_dir, format, compression, partition_cols))
