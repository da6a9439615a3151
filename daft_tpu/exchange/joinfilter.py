# daftlint: migrated
"""Runtime join filters: sideways information passing across the exchange.

The co-partitioned hash join shuffles BOTH sides' full raw rows even when
the build side is selective — q3/q5's worst host-path cost (ROADMAP item
4). This module builds a Bloom + min-max filter from the build side's join
keys while they stream through their own exchange, and the probe side's
ShuffleOp (or the BroadcastJoinOp probe stream) prunes non-qualifying rows
BEFORE bucketing, spill, and merge.

Design contract:

- **False-positive tolerant.** The filter only ever *keeps* extra rows;
  the join itself re-checks every surviving row, so correctness never
  depends on the filter. False *negatives* are engineered away: hashes are
  computed over key columns cast to the SAME unified dtype the join's key
  alignment uses, NaN float keys bypass the filter entirely (bit-pattern
  hashing cannot be trusted for them), and null keys are pruned only for
  join types where a null probe key provably never reaches the output.
- **Byte-identical with the knob off.** Pruning drops whole rows before
  the row-local bucket split; surviving rows keep their relative order,
  and the engine's joins emit deterministic (left-index, right-index)
  order — so query results are identical with ``runtime_join_filters``
  on or off.
- **Fails open.** Any failure while building or probing (including the
  ``join.filter`` fault site) degrades to the unfiltered exchange — never
  a query failure.

The probe has a vectorized host numpy path (min-max, then the Bloom bits
over hashes of the keys). A partition of at least ``device_min_rows`` rows
whose one key is an integer or a date takes the device path instead, behind
the device circuit breaker with the host path as its fallback: one jit
program reads the key's staged lanes (a resident key column is read from
the partition's stage cache, neither hashed nor uploaded) against a bit
table of the build keys addressed directly by ``key - lo``
(``_lane_table``: exact). A build whose keys span more than
``DIRECT_MAX_RANGE`` values keeps the host path.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Tuple

import numpy as np

from ..errors import DaftInternalError
from ..profile import timeline

# Bloom geometry: bits = next_pow2(rows * BITS_PER_KEY) clamped to
# [MIN_BITS, MAX_BITS]; PROBES probes per key via Kirsch-Mitzenmacher
# double hashing (h1 + i*h2). 8 bits/key x 4 probes ~ 2.4% false-positive
# rate — plenty for a pre-exchange prune whose misses the join re-checks.
BLOOM_BITS_PER_KEY = 8
BLOOM_PROBES = 4
BLOOM_MIN_BITS = 1 << 13
BLOOM_MAX_BITS = 1 << 23
# a build side past this many rows abandons the filter: the accumulated
# hash arrays (16 B/row across both seeds) and the prune win both stop
# being worth it when the "small" side is this large
MAX_BUILD_ROWS = 1 << 22

# the device form's bit table is addressed directly by ``key - lo`` (exact:
# only a build key hits); builds whose keys span more than this many values
# (1 MiB of words) keep the host path
DIRECT_MAX_RANGE = 1 << 23

# second hash seed for the probe stride (any odd constant unrelated to the
# bucket hash seed 0 works; splitmix64's increment is conventional)
_H2_SEED = 0x9E3779B97F4A7C15

# join types whose PROBE side may be pruned, by (how, probe_is_right):
# inner/semi — either side is prunable (dropped probe rows can only be
# non-matching, and non-matching probe rows never reach the output);
# left — only the right side (unmatched right rows are dropped anyway);
# right/anti/outer — the probe side's unmatched rows ARE output: decline.
PRUNABLE = {("inner", True), ("inner", False),
            ("semi", True), ("semi", False),
            ("left", True)}


def prunable(how: str, probe_is_right: bool) -> bool:
    """Whether the probe side of a `how` join may be pruned by a filter
    built from the other side's keys (see PRUNABLE)."""
    return (how, probe_is_right) in PRUNABLE


def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _unified_key_dtypes(build_on, probe_on, build_schema, probe_schema):
    """The join's key-alignment dtypes (same unify the hash join applies),
    or None when any pair cannot unify / is python-typed — the filter must
    hash both sides in identical representations or a dtype-width mismatch
    would silently hash the same value to different bits (a false
    negative, i.e. a wrong prune)."""
    from ..datatypes import try_unify

    out = []
    for be, pe in zip(build_on, probe_on):
        try:
            bdt = be._node.to_field(build_schema).dtype
            pdt = pe._node.to_field(probe_schema).dtype
        except Exception:
            return None
        u = try_unify(bdt, pdt)
        if u is None or u.is_python():
            return None
        out.append(u)
    return out


def _key_arrays(tbl, key_exprs, dtypes):
    """Evaluate the key expressions over one table and cast to the unified
    dtypes; returns the arrow arrays (one per key)."""
    cols = []
    for e, dt in zip(key_exprs, dtypes):
        s = e._node.evaluate(tbl)
        if s.is_python():
            raise DaftInternalError("python-typed join key in filter path")
        if len(s) != len(tbl):
            # literal/scalar key: broadcast via the table row count
            from ..table import _broadcast_series

            s = _broadcast_series(s, len(tbl))
        if s.dtype != dt:
            s = s.cast(dt)
        cols.append(s.to_arrow())
    return cols


def _hash_pair(cols) -> Tuple[np.ndarray, np.ndarray]:
    """(h1, h2) uint64 row hashes over the unified key columns — h1 seeds
    from 0 (the same family the bucket hash uses), h2 from an independent
    constant, giving the Kirsch-Mitzenmacher probe stride."""
    from ..kernels.host_hash import hash_table_columns

    return (hash_table_columns(cols, seed=0),
            hash_table_columns(cols, seed=_H2_SEED))


def _lane_keys_dtype(dtypes) -> bool:
    """Whether the device form applies to a filter over keys of ``dtypes``:
    one key, an integer of at most 63 bits' range or a date (what the
    device join stages as lanes)."""
    from ..datatypes import TypeKind

    if dtypes is None or len(dtypes) != 1:
        return False
    dt = dtypes[0]
    return ((dt.is_integer() and dt.kind != TypeKind.UINT64)
            or dt.kind == TypeKind.DATE)


def _lane_table(keys: np.ndarray, wide) -> Tuple[np.ndarray, int, int]:
    """The device form's bit table over the sorted unique build ``keys``
    (int64, spanning at most ``DIRECT_MAX_RANGE`` values) for lanes
    widened to ``wide`` (int32 or int64): ``(words uint32[W], lo, hi)``,
    bit ``key - lo`` set for each key. Keys outside ``wide`` are left out
    (no lane holds them: staging narrows losslessly). W is a power of two
    (at least 32 words), so the program's shape follows a bucket and not
    the data."""
    info = np.iinfo(wide)
    keys = keys[(keys >= info.min) & (keys <= info.max)]
    if not len(keys):
        return np.zeros(32, dtype=np.uint32), 1, 0
    lo, hi = int(keys[0]), int(keys[-1])
    bits = np.zeros(max(_next_pow2(hi - lo + 1), 1 << 10), dtype=bool)
    bits[keys - lo] = True
    return np.packbits(bits, bitorder="little").view("<u4"), lo, hi


@functools.lru_cache(maxsize=1)
def _keep_program():
    """The device keep-mask, jitted once (jax's trace cache is keyed on the
    function object): ``valid & lo <= v <= hi & bit(v - lo)`` over the
    staged lanes, one gather of a table word a lane. Everything is traced,
    so a new build of the same bucket compiles nothing."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def keep(vals, valid, words, lo, hi):
        v = vals.astype(lo.dtype)
        inr = valid & (v >= lo) & (v <= hi)
        pos = jnp.where(inr, v - lo, 0).astype(jnp.uint32)
        word = words.at[(pos >> 5).astype(jnp.int32)].get(
            mode="promise_in_bounds")
        return inr & (((word >> (pos & 31)) & 1) != 0)

    return keep


class RuntimeJoinFilter:
    """A sealed, immutable Bloom + min-max filter over build-side keys,
    with the build's sorted unique keys where the device form applies
    (one integer or date key spanning at most ``DIRECT_MAX_RANGE``
    values)."""

    __slots__ = ("table", "nbits", "minmax", "dtypes", "build_rows", "keys",
                 "_lane_tables")

    def __init__(self, table: np.ndarray, minmax: List[Optional[Tuple[Any, Any]]],
                 dtypes, build_rows: int, keys: Optional[np.ndarray] = None):
        self.table = table  # bool[nbits], nbits a power of two
        self.nbits = len(table)
        self.minmax = minmax  # per key column: (lo, hi) or None
        self.dtypes = dtypes
        self.build_rows = build_rows
        self.keys = keys  # int64 sorted unique build keys, or None
        self._lane_tables: dict = {}  # lane dtype -> staged _lane_table

    # ------------------------------------------------------------- probing
    def keep_mask(self, tbl, key_exprs, ctx=None, cache=None) -> np.ndarray:
        """Boolean keep-mask over ``tbl``'s rows: False rows provably
        cannot match any build-side key (up to the documented NaN bypass).
        ``ctx`` (an ExecutionContext) routes the probe through the device
        path when eligible, reading the key's lanes from ``cache`` (the
        partition's stage cache) where it holds them."""
        import pyarrow as pa
        import pyarrow.compute as pc

        n = len(tbl)
        if n == 0:
            return np.zeros(0, dtype=bool)
        dev = self._device_keep(tbl, key_exprs, ctx, cache)
        if dev is not None:
            return dev
        cols = _key_arrays(tbl, key_exprs, self.dtypes)
        valid = np.ones(n, dtype=bool)
        bypass = np.zeros(n, dtype=bool)
        rng_ok = np.ones(n, dtype=bool)
        for arr, dt, mm in zip(cols, self.dtypes, self.minmax):
            if arr.null_count:
                valid &= np.asarray(pc.is_valid(arr), dtype=bool)
            if pa.types.is_floating(arr.type):
                # NaN keys: bit-pattern hashing can't be trusted (and the
                # join's own NaN semantics are the arbiter) — bypass
                nanmask = pc.is_nan(arr)
                if arr.null_count:
                    nanmask = pc.fill_null(nanmask, False)
                bypass |= np.asarray(nanmask, dtype=bool)
            elif mm is not None:
                lo, hi = mm
                inr = pc.and_kleene(
                    pc.greater_equal(arr, pa.scalar(lo, type=arr.type)),
                    pc.less_equal(arr, pa.scalar(hi, type=arr.type)))
                rng_ok &= np.asarray(pc.fill_null(inr, False), dtype=bool)
        h1, h2 = _hash_pair(cols)
        hit = self._bloom_hits(h1, h2)
        # null keys never match for the prunable join types; NaN bypasses
        return valid & (bypass | (hit & rng_ok))

    def _bloom_hits(self, h1: np.ndarray, h2: np.ndarray) -> np.ndarray:
        mask = np.uint64(self.nbits - 1)
        h = h1.copy()
        out = self.table[(h & mask).astype(np.int64)]
        for _ in range(1, BLOOM_PROBES):
            h += h2
            out &= self.table[(h & mask).astype(np.int64)]
        return out

    def _device_keep(self, tbl, key_exprs, ctx, cache) -> Optional[np.ndarray]:
        """The keep-mask from the device: the key's staged lanes against
        the build's bit table in one program, behind the device circuit
        breaker. None = take the host path (ineligible, breaker open, or
        the attempt failed and was recorded). The key's lanes are read
        from ``cache`` where it holds them (``join_filter_resident_keys``)
        and staged without keeping otherwise: a partition gains no
        residency from its filter. Bumps ``join_filter_device_probes`` a
        mask."""
        if (self.keys is None or ctx is None or not ctx.device_path_on()
                or len(tbl) < ctx.cfg.device_min_rows):
            return None
        from ..datatypes import TypeKind
        from ..expressions import required_columns
        from ..kernels.device import fetch, staged, x64_enabled
        from ..kernels.device_join import _stage_key

        (key,) = key_exprs
        try:
            kdt = key._node.to_field(tbl.schema).dtype
        except Exception:
            return None
        if (kdt.kind == TypeKind.DATE) != (self.dtypes[0].kind == TypeKind.DATE):
            return None
        n = len(tbl)
        resident = staged(cache, required_columns(key), n)

        def _run():
            import jax.numpy as jnp

            wide = np.int64 if x64_enabled() else np.int32
            tab = self._lane_tables.get(wide)
            if tab is None:
                words, lo, hi = _lane_table(self.keys, wide)
                tab = (jnp.asarray(words), jnp.asarray(np.array(lo, wide)),
                       jnp.asarray(np.array(hi, wide)))
                self._lane_tables[wide] = tab
            got = _stage_key(tbl, key, cache if resident else None)
            if got is None:
                return None
            vals, valid = got
            lane = np.dtype(vals.dtype)
            if (lane.itemsize > np.dtype(wide).itemsize
                    or (lane.kind == "u"
                        and lane.itemsize == np.dtype(wide).itemsize)):
                return None  # the lanes do not widen losslessly
            with timeline.part("dispatch.call", "dispatch_call_ns"):
                out = _keep_program()(vals, valid, *tab)
            return np.asarray(fetch(out))[:n]

        out = ctx._device_attempt(_run)
        if out is not None:
            ctx.stats.bump("join_filter_device_probes")
            if resident:
                ctx.stats.bump("join_filter_resident_keys")
        return out


def prune_partition(part, jf: RuntimeJoinFilter, key_exprs, ctx):
    """Prune one probe-side partition with a sealed filter. Fail-open:
    ALWAYS returns a usable partition — the input itself on any failure
    (including the ``join.filter`` fault site). Counters:
    ``join_filter_probe_rows`` (rows inspected) and
    ``join_filter_rows_pruned`` (rows dropped pre-exchange); span
    ``phase:join.filter``. A partition of one table reads a resident key
    column from its own stage cache."""
    with ctx.stats.profiler.span("join.filter", kind="phase"):
        return _prune(part, jf, key_exprs, ctx)


def _prune(part, jf: RuntimeJoinFilter, key_exprs, ctx):
    from .. import faults
    from ..micropartition import MicroPartition
    from ..series import Series

    try:
        faults.check("join.filter", ctx.stats)
        tabs = part.chunk_tables()
        cache = part.device_stage_cache() if len(tabs) == 1 else None
        kept, before, after = [], 0, 0
        for t in tabs:
            nt = len(t)
            before += nt
            if nt == 0:
                continue
            mask = jf.keep_mask(t, key_exprs, ctx, cache)
            if mask.all():
                kept.append(t)
                after += nt
                continue
            ft = t.filter_with_mask(Series.from_numpy(mask, "keep"))
            after += len(ft)
            if len(ft):
                kept.append(ft)
    except Exception:
        ctx.stats.bump("join_filter_errors")
        return part
    ctx.stats.bump("join_filter_probe_rows", before)
    if before != after:
        ctx.stats.bump("join_filter_rows_pruned", before - after)
    if after == before:
        return part
    out = (MicroPartition(part.schema, tables=kept) if kept
           else MicroPartition.empty(part.schema))
    out.owner_process = part.owner_process
    return out


class JoinFilterBuilder:
    """Accumulates build-side key batches; ``seal()`` freezes the filter.

    Hashes are buffered per batch (16 B/row), and for a filter the device
    form may apply to the valid keys as int64 (8 B/row); the bit table is
    sized once the true build row count is known; past MAX_BUILD_ROWS the
    builder abandons (returns None at seal) rather than ballooning."""

    def __init__(self, key_exprs, dtypes):
        self.key_exprs = list(key_exprs)
        self.dtypes = list(dtypes)
        self._h1: List[np.ndarray] = []
        self._h2: List[np.ndarray] = []
        self._keys: Optional[List[np.ndarray]] = (
            [] if _lane_keys_dtype(self.dtypes) else None)
        self._minmax: List[Optional[Tuple[Any, Any]]] = [None] * len(dtypes)
        self._mm_dead: List[bool] = [False] * len(dtypes)
        self._rows = 0
        self._abandoned = False

    def add(self, tbl) -> None:
        """Fold one build-side table's keys into the filter state."""
        import pyarrow as pa
        import pyarrow.compute as pc

        if self._abandoned or len(tbl) == 0:
            return
        self._rows += len(tbl)
        if self._rows > MAX_BUILD_ROWS:
            self._abandoned = True
            self._h1.clear()
            self._h2.clear()
            self._keys = None
            return
        cols = _key_arrays(tbl, self.key_exprs, self.dtypes)
        h1, h2 = _hash_pair(cols)
        self._h1.append(h1)
        self._h2.append(h2)
        if self._keys is not None:
            k = pc.drop_null(cols[0])
            if pa.types.is_date32(k.type):
                k = k.cast(pa.int32())
            self._keys.append(np.asarray(k).astype(np.int64))
        for j, arr in enumerate(cols):
            if self._mm_dead[j] or pa.types.is_floating(arr.type):
                # float min-max would have to reason about NaN ordering;
                # the Bloom leg still covers floats
                self._mm_dead[j] = True
                continue
            if arr.null_count == len(arr):
                continue
            try:
                mm = pc.min_max(arr)
                lo, hi = mm["min"].as_py(), mm["max"].as_py()
            except Exception:
                self._mm_dead[j] = True
                continue
            cur = self._minmax[j]
            if cur is None:
                self._minmax[j] = (lo, hi)
            else:
                self._minmax[j] = (min(cur[0], lo), max(cur[1], hi))

    def seal(self) -> Optional[RuntimeJoinFilter]:
        if self._abandoned:
            return None
        nbits = _next_pow2(max(self._rows * BLOOM_BITS_PER_KEY,
                               BLOOM_MIN_BITS))
        nbits = min(nbits, BLOOM_MAX_BITS)
        table = np.zeros(nbits, dtype=bool)
        mask = np.uint64(nbits - 1)
        for h1, h2 in zip(self._h1, self._h2):
            h = h1.copy()
            for _ in range(BLOOM_PROBES):
                table[(h & mask).astype(np.int64)] = True
                h += h2
        minmax = [None if dead else mm
                  for mm, dead in zip(self._minmax, self._mm_dead)]
        keys = None
        if self._keys is not None:
            keys = np.unique(np.concatenate(self._keys) if self._keys
                             else np.zeros(0, dtype=np.int64))
            if len(keys) and int(keys[-1]) - int(keys[0]) >= DIRECT_MAX_RANGE:
                keys = None  # too wide a span for direct bits: host path
        return RuntimeJoinFilter(table, minmax, self.dtypes, self._rows, keys)


class JoinFilterSlot:
    """Translate-time rendezvous between the build side's exchange and the
    probe side's: the build-side ShuffleOp feeds every streamed partition
    into a builder and seals once its input stream is exhausted (the build
    side is fully drained before the probe side's exchange runs — the
    join op's pull order guarantees it); the probe-side ShuffleOp asks
    ``filter()`` and prunes
    when a sealed filter exists. Unsealed/abandoned/failed -> None -> the
    probe runs unfiltered."""

    def __init__(self, build_on, probe_on, build_schema, probe_schema,
                 how: str):
        self.build_on = list(build_on)
        self.probe_on = list(probe_on)
        self.how = how
        self.dtypes = _unified_key_dtypes(build_on, probe_on,
                                          build_schema, probe_schema)
        self._builder: Optional[JoinFilterBuilder] = None
        self._filter: Optional[RuntimeJoinFilter] = None
        self._sealed = False

    @property
    def eligible(self) -> bool:
        return self.dtypes is not None

    def begin(self) -> None:
        """Reset for a (re-)execution of the build side."""
        self._builder = (JoinFilterBuilder(self.build_on, self.dtypes)
                         if self.eligible else None)
        self._filter = None
        self._sealed = False

    def feed(self, tbl) -> None:
        if self._builder is not None:
            self._builder.add(tbl)

    def abandon(self) -> None:
        self._builder = None
        self._filter = None
        self._sealed = True

    def seal(self) -> None:
        if self._builder is not None:
            self._filter = self._builder.seal()
            self._builder = None
        self._sealed = True

    def filter(self) -> Optional[RuntimeJoinFilter]:
        return self._filter if self._sealed else None
