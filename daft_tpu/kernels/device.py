"""Device (TPU/XLA) kernel layer: Arrow <-> jax staging and jit'd columnar kernels.

This is the TPU-native replacement for the reference's Rust kernel library
(src/daft-core/src/array/ops/, ~60 kernel files). Design principles:

- A device column is a pair of dense jax arrays: `values` (padded to a size bucket so
  XLA compiles once per bucket, not once per row count) and `valid` (bool mask).
  Nulls never use sentinel values in kernels; every kernel threads validity.
- Whole expression trees compile to ONE jitted function per (expr, schema, bucket)
  via `compile_projection` — XLA fuses the elementwise chain into a single kernel,
  the analog of the reference's fused `pipeline_instruction`.
- Aggregations are masked segment reductions (`jax.ops.segment_sum` family) with
  group codes computed host-side by dictionary encoding: the host does the O(groups)
  bookkeeping, the MXU/VPU does the O(rows) FLOPs. Static `num_segments` keeps
  shapes compile-time constant.
- Sorting uses `jax.lax.sort` on bit-transformed keys (total order incl. nulls).
- No data-dependent shapes anywhere: filters for aggregation stay as masks; explicit
  compaction happens host-side only when a materialized filtered table is required.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

import jax
import jax.numpy as jnp

from ..datatypes import DataType, TypeKind
from ..profile import timeline

timeline.listen_for_compiles()  # once a process, before a kernel can compile

# Pad row counts up to one of these buckets (TPU lane width friendly: multiples of
# 8*128). Each bucket compiles once; growth factor 2 bounds waste at 2x.
_MIN_BUCKET = 1024


def size_bucket(n: int) -> int:
    b = _MIN_BUCKET
    while b < n:
        b <<= 1
    return b


_JNP_DTYPES = {
    TypeKind.BOOL: jnp.bool_,
    TypeKind.INT8: jnp.int8, TypeKind.INT16: jnp.int16,
    TypeKind.INT32: jnp.int32, TypeKind.INT64: jnp.int64,
    TypeKind.UINT8: jnp.uint8, TypeKind.UINT16: jnp.uint16,
    TypeKind.UINT32: jnp.uint32, TypeKind.UINT64: jnp.uint64,
    TypeKind.FLOAT32: jnp.float32, TypeKind.FLOAT64: jnp.float64,
}


def x64_enabled() -> bool:
    return bool(jax.config.jax_enable_x64)


def reduced_precision_ok() -> bool:
    """With x64 off (real TPUs), float64 data may run as float32 compute when
    the plan declares reduced precision (ExecutionConfig.device_reduced_precision,
    default on — the TPU-native norm; sums recover accuracy by combining
    per-partition partials in float64 on the host)."""
    from ..context import get_context

    return bool(get_context().execution_config.device_reduced_precision)


# 64-bit logical kinds and their 32-bit compute stand-ins when x64 is off.
# int64/uint64 narrow losslessly (range-checked at stage time); float64 is
# reduced-precision (gated by config); epoch-based temporals cannot fit 32
# bits and stay on the host path.
_NARROW_64 = {TypeKind.INT64: jnp.int32, TypeKind.UINT64: jnp.uint32,
              TypeKind.FLOAT64: jnp.float32}
_EPOCH_KINDS = {TypeKind.TIMESTAMP, TypeKind.DURATION, TypeKind.TIME}


def is_device_dtype(dt: DataType) -> bool:
    """Device-representable under the CURRENT x64 mode. With x64 off (real
    TPUs), int64/uint64 are eligible via lossless int32 narrowing (verified
    per-column at stage time), float64 via reduced-precision float32 compute
    (config-gated), and epoch temporals are host-only."""
    if dt.kind in _EPOCH_KINDS:
        return x64_enabled()
    if dt.kind == TypeKind.FLOAT64:
        return x64_enabled() or reduced_precision_ok()
    if dt.kind in (TypeKind.INT64, TypeKind.UINT64):
        return True
    if dt.kind in _JNP_DTYPES:
        return True
    if dt.kind == TypeKind.DATE:
        return True
    if dt.kind in (TypeKind.EMBEDDING, TypeKind.FIXED_SHAPE_TENSOR, TypeKind.FIXED_SHAPE_IMAGE):
        return is_device_dtype(dt.params[0]) if dt.kind != TypeKind.FIXED_SHAPE_IMAGE else True
    return False


def _physical_np(arr: pa.Array) -> np.ndarray:
    """Dense physical values of a primitive arrow array (nulls filled with 0)."""
    t = arr.type
    if pa.types.is_date32(t):
        arr = arr.cast(pa.int32())
    elif pa.types.is_timestamp(t) or pa.types.is_duration(t) or pa.types.is_time64(t):
        arr = arr.cast(pa.int64())
    elif pa.types.is_time32(t):
        arr = arr.cast(pa.int32())
    if arr.null_count:
        zero = pa.scalar(0, arr.type) if not pa.types.is_boolean(arr.type) else pa.scalar(False)
        arr = pc.fill_null(arr, zero)
    return np.asarray(arr)


class DeviceColumn:
    """values + validity on device, padded to `bucket` rows (valid[n:] == False).

    String columns stage as int32 DICTIONARY CODES against a SORTED
    per-partition dictionary (host-side pa.Array kept on `dictionary`):
    sorted codes are order-isomorphic to the strings, so equality AND
    ordering comparisons, sorts, and group codes all run on device over
    plain int lanes; decode happens at unstage (reference semantics:
    src/daft-core/src/array/ops/groups.rs dictionary grouping)."""

    __slots__ = ("values", "valid", "length", "dtype", "dictionary",
                 "_dict_list")

    def __init__(self, values: jax.Array, valid: jax.Array, length: int,
                 dtype: DataType, dictionary=None):
        self.values = values
        self.valid = valid
        self.length = length
        self.dtype = dtype
        self.dictionary = dictionary  # pa.Array of sorted uniques (strings)
        self._dict_list = None

    def dict_list(self):
        """Python-list view of the dictionary (cached — bisected per query
        for literal code bounds)."""
        if self._dict_list is None and self.dictionary is not None:
            self._dict_list = self.dictionary.to_pylist()
        return self._dict_list

    @property
    def bucket(self) -> int:
        return self.values.shape[0]


def stage_np(s, bucket: Optional[int] = None) -> Tuple[np.ndarray, np.ndarray, int]:
    """Host-side staging core: (values [bucket,*trailing], valid [bucket], n).

    Shared by the single-device path (stage_series) and the mesh shuffle
    (parallel/mesh_exec.py) so padding/fixed-shape/validity logic lives once.
    """
    from ..series import Series

    assert isinstance(s, Series)
    dt = s.dtype
    if not is_device_dtype(dt):
        raise ValueError(f"{dt} is not device-representable")
    n = len(s)
    b = bucket or size_bucket(n)
    arr = s.to_arrow()
    if dt.kind in (TypeKind.EMBEDDING, TypeKind.FIXED_SHAPE_TENSOR, TypeKind.FIXED_SHAPE_IMAGE):
        shape = (dt.params[1],) if dt.kind == TypeKind.EMBEDDING else dt.tensor_shape
        size = int(np.prod(shape))
        child = arr.values.slice(arr.offset * size, n * size)
        vals = _physical_np(child).reshape((n,) + tuple(shape))
        vals = _narrow_staged(vals, dt)
        pad_shape = (b - n,) + tuple(shape)
        vals = np.concatenate([vals, np.zeros(pad_shape, vals.dtype)]) if b > n else vals
    else:
        vals = _narrow_staged(_physical_np(arr), dt)
        if b > n:
            vals = np.concatenate([vals, np.zeros(b - n, dtype=vals.dtype)])
    return vals, _staged_validity(arr, n, b), n


def _staged_validity(arr: pa.Array, n: int, b: int) -> np.ndarray:
    """Validity lane of a staged column, padding lanes False — shared by the
    numeric and string (dictionary-code) staging paths so null/padding
    semantics live once."""
    valid = np.zeros(b, dtype=bool)
    if n:
        valid[:n] = np.asarray(pc.is_valid(arr)) if arr.null_count else True
    return valid


_NARROW_NP = {TypeKind.INT64: np.int32, TypeKind.UINT64: np.uint32,
              TypeKind.FLOAT64: np.float32}


def _narrow_staged(vals: np.ndarray, dt: DataType) -> np.ndarray:
    """32-bit staging when x64 is off: ints narrow only when every value fits
    (lossless — raises otherwise so callers fall back to host); float64
    narrows to float32 (reduced precision, config-gated in is_device_dtype)."""
    inner = dt.params[0] if dt.kind in (TypeKind.EMBEDDING, TypeKind.FIXED_SHAPE_TENSOR) else dt
    if x64_enabled() or inner.kind not in _NARROW_NP:
        return vals
    target = _NARROW_NP[inner.kind]
    if vals.dtype.kind in "iu":
        info = np.iinfo(target)
        if len(vals) and (vals.min() < info.min or vals.max() > info.max):
            raise ValueError(f"{dt} values exceed int32 range; host path")
    return vals.astype(target, copy=False)


def stageable_dtype(dt: DataType) -> bool:
    """Device-stageable: device-representable numerics OR strings (which
    stage as dictionary codes)."""
    return is_device_dtype(dt) or dt.is_string()


def _stage_string_series(s, bucket: Optional[int]) -> DeviceColumn:
    """Stage a string Series as sorted-dictionary codes.

    The dictionary is sorted so code order == lexicographic order (UTF-8
    byte order and codepoint order coincide), which is also pyarrow's
    string ordering — host/device comparison and sort semantics agree."""
    n = len(s)
    b = bucket or size_bucket(n)
    arr = s.to_arrow()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    uniq = pc.unique(arr.drop_null())
    uniq = uniq.take(pc.sort_indices(uniq))
    codes = pc.index_in(arr, value_set=uniq)  # null where arr is null
    vals = np.asarray(pc.fill_null(codes, 0), dtype=np.int32)
    if b > n:
        vals = np.concatenate([vals, np.zeros(b - n, dtype=np.int32)])
    valid = _staged_validity(arr, n, b)
    return DeviceColumn(jnp.asarray(vals), jnp.asarray(valid), n, s.dtype,
                        dictionary=uniq)


def stage_series(s, bucket: Optional[int] = None) -> DeviceColumn:
    """Stage a host Series onto the device (values + validity, padded).
    The one place Arrow columns go to HBM (callers keep the stage cache, so
    a hit never comes here): ``stage`` span, ``stage_ns``, ``stage_bytes``
    (the padded bytes handed over) and ``stage_columns`` of the device
    attempt running on this thread."""
    with timeline.timed("stage", "stage_ns"):
        if s.dtype.is_string():
            dc = _stage_string_series(s, bucket)
        else:
            vals, valid, n = stage_np(s, bucket)
            dc = DeviceColumn(jnp.asarray(vals), jnp.asarray(valid), n,
                              s.dtype)
    timeline.add("stage_bytes", int(dc.values.nbytes) + int(dc.valid.nbytes))
    timeline.add("stage_columns", 1)
    return dc


def fetch(x):
    """Device arrays (any pytree of them) to host numpy. The one place HBM
    goes back to the host, so the wait for the device (``device.wait``
    span, ``device_wait_ns``: the chip is busy) reads apart from the copy
    (``gather`` span, ``gather_ns``, ``gather_bytes``: the chip is idle) in
    the device frame running on this thread; outside one nothing is
    recorded. The copies are queued before the wait, as ``jax.device_get``
    queues them, so they start the moment the outputs exist and not a host
    round trip later."""
    for leaf in jax.tree_util.tree_leaves(x):
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()
    with timeline.timed("device.wait", "device_wait_ns"):
        jax.block_until_ready(x)
    with timeline.timed("gather", "gather_ns"):
        out = jax.device_get(x)
    timeline.add("gather_bytes", sum(
        int(getattr(a, "nbytes", 0)) for a in jax.tree_util.tree_leaves(out)))
    return out


def unstage(col: DeviceColumn):
    """Bring a DeviceColumn back to a host Series."""
    from ..series import Series

    vals, valid = fetch((col.values, col.valid))
    vals = np.asarray(vals)[:col.length]
    valid = np.asarray(valid)[:col.length]
    dt = col.dtype
    if col.dictionary is not None:
        uniq = col.dictionary
        if len(uniq) == 0:
            out = pa.nulls(col.length, pa.large_string())
        else:
            codes = np.clip(vals.astype(np.int64), 0, len(uniq) - 1)
            out = uniq.take(pa.array(codes))
            if not valid.all():
                out = pc.if_else(pa.array(valid), out,
                                 pa.nulls(col.length, out.type))
        return Series.from_arrow(out, "device", dt)
    if dt.kind in (TypeKind.EMBEDDING, TypeKind.FIXED_SHAPE_TENSOR, TypeKind.FIXED_SHAPE_IMAGE):
        shape = (dt.params[1],) if dt.kind == TypeKind.EMBEDDING else dt.tensor_shape
        size = int(np.prod(shape))
        flat = pa.array(vals.reshape(col.length, size).ravel())
        out = pa.FixedSizeListArray.from_arrays(flat, size or 1)
        if not valid.all():
            out = pc.if_else(pa.array(valid), out, pa.nulls(col.length, out.type))
        return Series.from_arrow(out, "device", dt)
    storage = dt.to_arrow()
    out = pa.array(vals)
    if out.type != storage:
        if pa.types.is_timestamp(storage) or pa.types.is_duration(storage) or pa.types.is_time64(storage):
            out = out.cast(pa.int64()).view(storage) if out.type.bit_width == 64 else out.cast(storage)
        elif pa.types.is_date32(storage):
            out = out.cast(pa.int32()).view(storage)
        else:
            out = out.cast(storage)
    if not valid.all():
        out = pc.if_else(pa.array(valid), out, pa.nulls(col.length, out.type))
    return Series.from_arrow(out, "device", dt)


# ---------------------------------------------------------------------------
# Expression -> jax compiler
# ---------------------------------------------------------------------------

_V = Tuple[jax.Array, jax.Array]  # (values, valid)


def _literal_to_physical(value, dt: DataType):
    """Convert a python literal to its device physical value (temporal -> epoch int)."""
    if dt.is_temporal():
        scalar = pa.scalar(value, type=dt.to_arrow())
        if dt.kind == TypeKind.DATE:
            return int(scalar.cast(pa.int32()).as_py())
        return int(scalar.value)
    return value


def _jdt(dt: DataType):
    """COMPUTE dtype for a logical dtype under the current x64 mode: 64-bit
    logical types narrow to their 32-bit stand-ins when x64 is off."""
    if not x64_enabled() and dt.kind in _NARROW_64:
        return _NARROW_64[dt.kind]
    if dt.kind in _JNP_DTYPES:
        return _JNP_DTYPES[dt.kind]
    if dt.kind == TypeKind.DATE:
        return jnp.int32
    if dt.kind in _EPOCH_KINDS:
        if not x64_enabled():
            raise ValueError(f"{dt} needs 64-bit epochs; host path with x64 off")
        return jnp.int64
    raise ValueError(f"{dt} has no device dtype")


def _wf():
    """Widest float compute dtype in the current mode."""
    return jnp.float64 if x64_enabled() else jnp.float32


def _literal_fits_device(lit) -> bool:
    """A literal is device-usable if its dtype has a compute dtype and, for
    int literals narrowing to 32-bit (x64 off), the value fits."""
    if lit.value is None or lit.dtype.is_null():
        return True
    if not is_device_dtype(lit.dtype):
        return False
    try:
        jd = _jdt(lit.dtype)
    except ValueError:
        return False
    if isinstance(lit.value, int) and not isinstance(lit.value, bool) \
            and jnp.issubdtype(jd, jnp.integer):
        info = jnp.iinfo(jd)
        return info.min <= lit.value <= info.max
    return True


_CMP_OPS = ("==", "!=", "<", "<=", ">", ">=")
_CMP_FLIP = {"<": ">", "<=": ">=", ">": "<", ">=": "<=", "==": "==", "!=": "!="}
_CMP_FNS = {
    "==": lambda a, b: a == b, "!=": lambda a, b: a != b,
    "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
    ">": lambda a, b: a > b, ">=": lambda a, b: a >= b,
}


def _plain_column(node, schema, pred) -> Optional[str]:
    """Column name when `node` is a bare Column (through Aliases) whose
    schema dtype satisfies `pred` — shared by the string-dictionary and
    f64-sort-lane paths so 'what counts as a plain column' lives once."""
    from ..expressions import Alias, Column

    while isinstance(node, Alias):
        node = node.child
    if isinstance(node, Column):
        try:
            if pred(schema[node.cname].dtype):
                return node.cname
        except KeyError:
            return None
    return None


def _plain_string_column(node, schema) -> Optional[str]:
    """Bare string Column (through Aliases) — the only string-VALUED shape
    the device supports (codes decode at unstage against that column's
    dictionary)."""
    return _plain_column(node, schema, lambda dt: dt.is_string())


def _plain_epoch_column(node, schema) -> Optional[str]:
    """Bare timestamp/duration/time Column (through Aliases) — 64-bit epoch
    kinds that cannot narrow to int32 but CAN compare/sort exactly via
    order-preserving (hi, lo) uint32 lane splits in 32-bit mode."""
    return _plain_column(node, schema, lambda dt: dt.kind in _EPOCH_KINDS)


def _epoch_lane_side(node, schema):
    """(ident, dtype, side_node_or_None) when `node` is an epoch-typed
    expression whose value can ride host-evaluated (hi, lo) lane pairs:
    a plain Column (ident = colname, shares the column-lane cache;
    side_node None) or ANY computed epoch expression — timestamp
    arithmetic, date truncation — which evaluates once on host in exact
    int64 and splits lanes from the result (ident = expression key)."""
    cname = _plain_epoch_column(node, schema)
    if cname is not None:
        return cname, schema[cname].dtype, None
    try:
        dt = node.to_field(schema).dtype
    except Exception:
        return None
    if dt.kind not in _EPOCH_KINDS:
        return None
    return f"\x00epochexpr\x00{node._key()}", dt, node


def _epoch_cmp_shape(node, schema):
    """(lspec, rspec, op) when `node` is a comparison whose sides are epoch
    lane sides and/or literals (at least one lane side) — compiled in
    32-bit mode as a two-lane unsigned comparison over split epoch bits;
    in x64 mode the generic int64 path handles epochs already. Each spec is
    ("lane", ident, dtype, side_node_or_None) or ("lit", lit_node).
    Lane-vs-lane requires identical dtypes (same epoch kind/unit/tz): the
    raw int64 physicals of different units are not comparable."""
    from ..expressions import BinaryOp, Literal

    if not (isinstance(node, BinaryOp) and node.op in _CMP_OPS):
        return None

    def spec(n):
        if isinstance(n, Literal):
            return ("lit", n)
        side = _epoch_lane_side(n, schema)
        if side is None:
            return None
        return ("lane", *side)

    ls, rs = spec(node.left), spec(node.right)
    if ls is None or rs is None:
        return None
    if ls[0] == "lit" and rs[0] == "lit":
        return None
    if ls[0] == "lane" and rs[0] == "lane" and ls[2] != rs[2]:
        return None
    # a literal compares against the lane side's dtype; reject non-epoch
    # literal-vs-lane pairings where conversion has no target
    return ls, rs, node.op


def _epoch_lane_keys(ident: str) -> Tuple[str, str]:
    return (f"__epochlane__\x00{ident}\x00hi",
            f"__epochlane__\x00{ident}\x00lo")


def _epoch_lit_keys(ident: str, node_key) -> Tuple[str, str]:
    base = f"__epochlit__\x00{ident}\x00{node_key}"
    return base + "\x00hi", base + "\x00lo"


def _two_lane_cmp(op: str, hi, lo, rhi, rlo):
    """Elementwise comparison of (hi, lo) uint32 lane pairs under the
    order-preserving epoch bit encoding (unsigned lexicographic)."""
    eq_hi = hi == rhi
    if op == "==":
        return eq_hi & (lo == rlo)
    if op == "!=":
        return ~(eq_hi & (lo == rlo))
    if op == "<":
        return (hi < rhi) | (eq_hi & (lo < rlo))
    if op == "<=":
        return (hi < rhi) | (eq_hi & (lo <= rlo))
    if op == ">":
        return (hi > rhi) | (eq_hi & (lo > rlo))
    return (hi > rhi) | (eq_hi & (lo >= rlo))  # ">="


def _epoch_bits_np(vals_i64: np.ndarray) -> np.ndarray:
    """Order-preserving uint64 view of int64 epochs (two's-complement ->
    unsigned total order via sign-bit flip)."""
    return vals_i64.astype(np.int64).view(np.uint64) ^ np.uint64(1 << 63)


def _eval_lane_series(table, node):
    """Host-evaluate a lane-staged sort key expression -> Series (length
    broadcast), or None when evaluation fails / yields python storage —
    the caller then declines to the host sort."""
    from ..expressions import Column

    try:
        if isinstance(node, Column):
            s = table.get_column(node.cname)
        else:
            from ..table import _broadcast_series

            s = _broadcast_series(node.evaluate(table), len(table))
    except Exception:
        return None
    if s.is_python():
        return None
    return s


def _peel_alias(node):
    from ..expressions import Alias

    while isinstance(node, Alias):
        node = node.child
    return node


def _stage_epoch_expr_lanes(table, node, bucket: int,
                            stage_cache: Optional[dict]):
    """Lane staging for ANY epoch-typed sort key expression (r4 verdict
    item 6): plain (possibly aliased) columns reuse the shared column-lane
    cache entry; computed epoch expressions (timestamp arithmetic) evaluate
    once on host — exact int64 — and split lanes from the result. UDF-
    containing keys never cache (Expression._memoizable rationale)."""
    from ..expressions import Column

    node = _peel_alias(node)
    if isinstance(node, Column):
        return _stage_epoch_lanes(table, node.cname, bucket, stage_cache)
    cacheable = stage_cache is not None and node._memoizable()
    key = ("__epochlanes__", node._key(), bucket)
    cached = stage_cache.get(key) if cacheable else None
    if cached is not None:
        return cached
    s = _eval_lane_series(table, node)
    if s is None:
        return None
    out = _epoch_lanes_of_series(s, bucket)
    if cacheable:
        stage_cache[key] = out
    return out


def _epoch_lanes_of_series(s, bucket: int):
    n = len(s)
    arr = s.to_arrow()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    vals = _physical_np(arr).astype(np.int64)
    bits = _epoch_bits_np(vals)
    if bucket > n:
        bits = np.concatenate([bits, np.zeros(bucket - n, dtype=np.uint64)])
    hi = (bits >> np.uint64(32)).astype(np.uint32)
    lo = (bits & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    return (jnp.asarray(hi), jnp.asarray(lo),
            jnp.asarray(_staged_validity(arr, n, bucket)))


def _stage_epoch_lanes(table, cname: str, bucket: int,
                       stage_cache: Optional[dict]):
    """(hi u32, lo u32, valid) exact lanes of an epoch column for 32-bit
    mode comparisons and sorts; cached with the partition."""
    key = ("__epochlanes__", cname, bucket)
    cached = stage_cache.get(key) if stage_cache is not None else None
    if cached is not None:
        return cached
    out = _epoch_lanes_of_series(table.get_column(cname), bucket)
    if stage_cache is not None:
        stage_cache[key] = out
    return out


def collect_epoch_cmps(nodes, schema):
    """Every epoch-comparison shape in the trees -> [(lspec, rspec, op)]."""
    from ..expressions import BinaryOp

    out = []

    def walk(n):
        if isinstance(n, BinaryOp):
            shape = _epoch_cmp_shape(n, schema)
            if shape is not None:
                out.append(shape)
                return  # the whole subtree rides lanes; nothing below stages
        for c in n.children():
            walk(c)

    for nd in nodes:
        walk(nd)
    return out


def epoch_cmp_env(cmps, schema, table, bucket: int,
                  stage_cache: Optional[dict], env: dict) -> Optional[dict]:
    """Merge epoch-comparison support into `env` (32-bit mode): each lane
    side's (hi, lo) pair — plain columns through the shared column-lane
    cache, computed sides host-evaluated once in exact int64 — and each
    literal's split bits keyed against its lane side. `cmps` is the list
    from ONE collect_epoch_cmps walk. Returns the (possibly unchanged)
    env, or None when a literal cannot convert or a computed side fails
    host evaluation."""
    if not cmps:
        return env
    merged = dict(env)
    for lspec, rspec, _op in cmps:
        lane_specs = [s for s in (lspec, rspec) if s[0] == "lane"]
        for _tag, ident, _dt, side_node in lane_specs:
            hi_k, lo_k = _epoch_lane_keys(ident)
            if hi_k in merged:
                continue
            if side_node is None:
                lanes = _stage_epoch_lanes(table, ident, bucket, stage_cache)
            else:
                lanes = _stage_epoch_expr_lanes(table, side_node, bucket,
                                                stage_cache)
            if lanes is None:
                return None
            hi, lo, valid = lanes
            merged[hi_k] = (hi, valid)
            merged[lo_k] = (lo, valid)
        lit = lspec[1] if lspec[0] == "lit" else (
            rspec[1] if rspec[0] == "lit" else None)
        if lit is None:
            continue
        _tag, ident, lane_dt, _sn = lane_specs[0]
        lhik, llok = _epoch_lit_keys(ident, lit._key())
        if lhik in merged or lit.value is None:
            continue
        try:
            epoch = _literal_to_physical(lit.value, lane_dt)
        except (ValueError, TypeError, KeyError):
            return None
        bits = int(_epoch_bits_np(np.array([epoch]))[0])
        merged[lhik] = jnp.uint32(bits >> 32)
        merged[llok] = jnp.uint32(bits & 0xFFFFFFFF)
    return merged


def epoch_cmps_for(nodes, schema):
    """ONE walk: the epoch-comparison shapes of `nodes` (empty under x64,
    where the generic int64 path applies)."""
    if x64_enabled():
        return []
    return collect_epoch_cmps(nodes, schema)


def device_required_columns(nodes, schema) -> set:
    """Columns that must stage NORMALLY on device: the plain required-column
    union, minus subtrees that ride host-evaluated epoch lane pairs (their
    inputs never reach the device; staging an epoch column normally would
    fail since 64-bit epochs cannot narrow to int32). A column referenced
    both inside a lane compare and elsewhere still stages."""
    from ..expressions import BinaryOp, Column

    out: set = set()
    in32 = not x64_enabled()

    def walk(n):
        if in32 and isinstance(n, BinaryOp) \
                and _epoch_cmp_shape(n, schema) is not None:
            return
        if isinstance(n, Column):
            out.add(n.cname)
        for c in n.children():
            walk(c)

    for nd in nodes:
        walk(nd)
    return out


def _string_cmp_shape(node, schema):
    """(colname, literal_value, flipped) when `node` is a comparison between
    a string Column and a string Literal (either side); else None. These
    compile to dictionary-code comparisons with the literal's code bounds
    injected per-partition at staging time."""
    from ..expressions import BinaryOp, Literal

    if not (isinstance(node, BinaryOp) and node.op in _CMP_OPS):
        return None

    def lit_str(n):
        return (isinstance(n, Literal)
                and (n.value is None or isinstance(n.value, str))
                and (n.dtype.is_string() or n.dtype.is_null()))

    lcol = _plain_string_column(node.left, schema)
    rcol = _plain_string_column(node.right, schema)
    if lcol is not None and lit_str(node.right):
        return lcol, node.right.value, False
    if rcol is not None and lit_str(node.left):
        return rcol, node.left.value, True
    return None


# LUT-evaluable predicate functions: the per-partition dictionary feeds the
# REGISTERED host implementation, so parity is by construction — including
# regex-backed like/ilike/match, which the device could never run itself
_STR_PRED_FNS = ("utf8.contains", "utf8.startswith", "utf8.endswith",
                 "utf8.like", "utf8.ilike", "utf8.match")


def _string_lut_shape(node, schema):
    """(colname, kind, payload, node_key) for predicates evaluable on the
    per-partition DICTIONARY instead of the rows: utf8.contains/startswith/
    endswith with a literal pattern, and is_in over string literals. The
    host computes the predicate over the O(unique) dictionary values with
    the SAME pyarrow kernels the host path uses (exact parity), producing a
    bool table the device looks up by code (``_dict_bool_lookup``: bit
    tests over packed words up to ``DICT_PACKED_MAX_ENTRIES``, a gather
    above) — O(rows) work stays on the accelerator, O(unique) bookkeeping
    on the host (the division of labor SURVEY §7 prescribes)."""
    from ..expressions import Function, IsIn, Literal

    if isinstance(node, Function) and node.fname in _STR_PRED_FNS:
        if len(node.args) != 2 or node.kwargs:
            return None
        colname = _plain_string_column(node.args[0], schema)
        pat = node.args[1]
        if (colname is None or not isinstance(pat, Literal)
                or not isinstance(pat.value, str)):
            return None
        return colname, node.fname, pat.value, node._key()
    if isinstance(node, IsIn):
        colname = _plain_string_column(node.child, schema)
        items = node.items
        if (colname is None or not isinstance(items, Literal)
                or not isinstance(items.value, (list, tuple))):
            return None
        vals = [v for v in items.value if v is not None]
        if not all(isinstance(v, str) for v in vals):
            return None
        return colname, "is_in", tuple(vals), node._key()
    return None


def _strlut_env_key(node_key) -> str:
    return f"__strlut__\x00{node_key}"


# per-row (row-local) string functions: a predicate built from these over ONE
# string column depends only on that row's value, so it can evaluate over the
# partition dictionary instead of the rows (utf8.tokenize_* excluded: list-
# valued results have no boolean-LUT use and pull in tokenizer state)
_ROWLOCAL_STR_FNS = frozenset(
    f"utf8.{n}" for n in (
        "capitalize", "concat", "contains", "count_matches", "endswith",
        "extract", "find", "ilike", "left", "length", "length_bytes",
        "like", "lower", "lpad", "lstrip", "match", "normalize", "repeat",
        "replace", "reverse", "right", "rpad", "rstrip", "startswith",
        "substr", "upper",
    ))


def _string_dict_pred_shape(node, schema):
    """(colname, node, node_key) when `node` is a BOOLEAN-valued, row-local
    expression whose only column input is ONE plain string column — e.g.
    `upper(s) == "X"`, `strip(s).startswith(p)`, `length(s) > 3`,
    `(s + "-suffix").is_in([...])`. Each row's result depends only on that
    row's string value, so the host evaluates the WHOLE predicate over the
    O(unique) dictionary (+ one null slot for exact null semantics) with
    the registered host kernels, and the device looks (value, validity) up
    by code, the null slot being one more entry (``_dict_bool_lookup``) —
    generalizing the fixed contains/startswith/endswith LUT shapes to
    arbitrary predicate trees over string transforms. Reference semantics:
    fully general utf8 kernels, src/daft-core/src/array/ops/utf8.rs."""
    try:
        if not node.to_field(schema).dtype.is_boolean():
            return None
    except (ValueError, KeyError):
        return None
    colname = _single_string_col_rowlocal(node, schema)
    if colname is None:
        return None
    return colname, node, node._key()


def _single_string_col_rowlocal(node, schema) -> Optional[str]:
    """The one plain string column `node` row-locally depends on, or None.
    Row-local: every applied operation is per-row (whitelisted utf8 fns,
    compares, choices, casts), so a row's result depends only on that
    row's string value — the property that lets the whole subtree evaluate
    over the O(unique) dictionary instead of the rows. Shared by the
    boolean dictionary-predicate shape and the transformed group-key
    lane."""
    from ..expressions import (
        Alias, Between, BinaryOp, Cast, Column, FillNull, IfElse, IsIn,
        IsNull, Literal, Not, Function,
    )

    cols: set = set()

    def rowlocal(n):
        if isinstance(n, (Literal, Column)):
            if isinstance(n, Column):
                cols.add(n.cname)
            return True
        if isinstance(n, (Alias, Not, IsNull, Cast, Between, FillNull,
                          IfElse, BinaryOp)):
            return all(rowlocal(c) for c in n.children())
        if isinstance(n, IsIn):
            return isinstance(n.items, Literal) and rowlocal(n.child)
        if isinstance(n, Function):
            # kwargs are static python config (regex=, index=), never columns
            if n.fname not in _ROWLOCAL_STR_FNS:
                return False
            return all(rowlocal(c) for c in n.args)
        return False

    if not rowlocal(node):
        return None
    if len(cols) != 1:
        return None
    return _plain_string_column_named(next(iter(cols)), schema)


def _plain_string_column_named(colname, schema):
    try:
        return colname if schema[colname].dtype.is_string() else None
    except KeyError:
        return None


def _strdictpred_env_keys(node_key) -> Tuple[str, str, str]:
    base = f"__strdictpred__\x00{node_key}"
    return base + "\x00vals", base + "\x00valid", base + "\x00nullslot"


def _string_dict_value_shape(node, schema):
    """(colname, node, node_key) when `node` is a row-local COMPUTED
    expression of ONE plain string column used as a VALUE (group/distinct
    key, sort key, projection output): `upper(s)`, `s.substr(0, 2)`,
    fill_null chains. Equal source strings produce equal results, so the
    value set computes over the dictionary (+ null slot) and each row's
    dense sorted-order id is a gather. Plain columns are excluded — the
    existing dictionary-code path already handles them without the host
    evaluation."""
    if _plain_string_column(node, schema) is not None:
        return None
    colname = _single_string_col_rowlocal(node, schema)
    if colname is None:
        return None
    return colname, node, node._key()


def _string_value_applies(node, schema):
    """The transformed-string VALUE shape at a compile-claim point: the
    node must be string-VALUED, not a plain column (native codes path) and
    not a choice over plain columns/literals (joint-dictionary path) —
    precedence must match _compile_node's dispatch order."""
    try:
        if not node.to_field(schema).dtype.is_string():
            return None
    except (ValueError, KeyError):
        return None
    if _string_choice_shape(node, schema) is not None:
        return None
    return _string_dict_value_shape(node, schema)


def _int_transform_applies(node, schema):
    """(colname, node, node_key) when `node` is an INTEGER-valued row-local
    expression of ONE string column — `length(s)`, `find(s, p)`,
    `count_matches` — whose values (not recoded ids) gather by source code.
    A bare Function is required at the root: integer ARITHMETIC above the
    transform composes on device through the generic compiler once the
    transform itself is claimed."""
    from ..expressions import Function

    if not isinstance(node, Function):
        return None
    try:
        if not node.to_field(schema).dtype.is_integer():
            return None
    except (ValueError, KeyError):
        return None
    colname = _single_string_col_rowlocal(node, schema)
    if colname is None:
        return None
    return colname, node, node._key()


def _inttrans_env_keys(node_key) -> Tuple[str, str]:
    base = f"__inttransval__\x00{node_key}"
    return base + "\x00vals", base + "\x00valid"


def dict_int_transform_lane(table, shape, bucket: int,
                            stage_cache: Optional[dict]):
    """(vals, valid) integer lanes for an int-valued string transform:
    host evaluates over the dictionary + null slot (shared
    _eval_over_dictionary), the device gathers VALUES by source code. In
    32-bit mode the dictionary values are range-checked exactly on host —
    int64 results that cannot narrow to int32 decline (the wrap-safety
    rule applied at O(unique) cost instead of a device reduction).
    Returns None -> caller declines."""
    colname, node, node_key = shape
    cache_key = ("__inttranslane__", node_key, bucket, x64_enabled())
    cached = stage_cache.get(cache_key) if stage_cache is not None else None
    if cached is not None:
        return cached
    staged = stage_table_columns(table, [colname], bucket, stage_cache)
    if staged is None:
        return None
    _env, dcs = staged
    dc = dcs.get(colname)
    if dc is None or dc.dictionary is None:
        return None
    uniq = dc.dictionary
    arr = _eval_over_dictionary(colname, node, uniq)
    if arr is None:
        return None
    vals_np = np.asarray(pc.fill_null(arr, 0)).astype(np.int64)
    tvalid = np.asarray(pc.is_valid(arr), dtype=bool)
    if not x64_enabled():
        live = vals_np[tvalid]
        if live.size and (live.min() < _INT32_LO or live.max() > _INT32_HI):
            return None
        vals_np = vals_np.astype(np.int32)
    u = len(uniq)
    idx = jnp.where(dc.valid, dc.values, u).astype(jnp.int32)
    vals = jnp.asarray(vals_np)[idx]
    valid = jnp.asarray(tvalid)[idx]
    out = (vals, valid)
    if stage_cache is not None:
        stage_cache[cache_key] = out
    return out


def _strtransval_env_keys(node_key) -> Tuple[str, str]:
    base = f"__strtransval__\x00{node_key}"
    return base + "\x00vals", base + "\x00valid"


def _stroutdict_aux_key(node_key):
    return ("__stroutdict__", node_key)


def _transform_cmp_shape(node, schema):
    """(lside, rside, op) for a comparison whose sides are string-valued
    and column-backed over TWO DIFFERENT columns with at least one side a
    row-local TRANSFORM — `upper(s1) == s2`, `lstrip(a) < rstrip(b)`.
    Plain-vs-plain belongs to the col-vs-col joint-group machinery and
    single-column trees (incl. vs-literal) to the dictionary predicate, so
    this shape claims exactly the residual. Each side is
    ("col", colname, None) or ("trans", colname, side_node); the sides
    recode through a PAIRWISE sorted joint dictionary (transform side: its
    transformed dictionary) and compare as ints — sorted joint codes are
    order-isomorphic, so inequalities hold too."""
    from ..expressions import BinaryOp

    if not (isinstance(node, BinaryOp) and node.op in _CMP_OPS):
        return None

    def side(n):
        c = _plain_string_column(n, schema)
        if c is not None:
            return ("col", c, None)
        vs = _string_value_applies(n, schema)
        if vs is not None:
            return ("trans", vs[0], n)
        return None

    ls, rs = side(node.left), side(node.right)
    if ls is None or rs is None:
        return None
    if ls[0] == "col" and rs[0] == "col":
        return None  # the existing col-vs-col joint group owns this
    if ls[1] == rs[1]:
        return None  # one column: the dictionary predicate owns this
    return ls, rs, node.op


def _transcmp_env_keys(node_key) -> Tuple[str, str]:
    base = f"__transcmp__\x00{node_key}"
    return base + "\x00lremap", base + "\x00rremap"


def transform_cmp_env(nodes, schema, table, bucket: int,
                      stage_cache: Optional[dict], dcs, env: dict,
                      aux: dict) -> Optional[dict]:
    """Merge pairwise joint-dictionary remaps for every cross-column
    transform compare. Runs AFTER string_transform_env: a transform side's
    lane and transformed dictionary are already staged (env/aux); a plain
    side's codes and dictionary are in dcs. Returns env (possibly
    unchanged) or None -> decline to host."""
    from ..expressions import BinaryOp

    merged = env

    def side_dict(s):
        kind, colname, n = s
        if kind == "col":
            dc = dcs.get(colname)
            return None if dc is None or dc.dictionary is None \
                else dc.dictionary
        return aux.get(_stroutdict_aux_key(n._key()))

    # an explicit stack, not a recursive closure: one that named itself
    # would form a reference cycle holding `env` (this attempt's device
    # arrays) until the cyclic collector next ran, and the peak of device
    # memory would follow the collector's timing
    stack = list(reversed(nodes))
    while stack:
        n = stack.pop()
        shape = (_transform_cmp_shape(n, schema)
                 if isinstance(n, BinaryOp) else None)
        if shape is None:
            stack.extend(reversed(n.children()))
            continue
        ls, rs, _op = shape
        lk, rk = _transcmp_env_keys(n._key())
        if lk in merged:
            continue
        cache_key = ("__transcmp__", n._key(), bucket)
        cached = (stage_cache.get(cache_key)
                  if stage_cache is not None else None)
        if cached is None:
            ld, rd = side_dict(ls), side_dict(rs)
            if ld is None or rd is None:
                return None
            joint = pc.unique(pa.concat_arrays(
                [ld.cast(pa.large_string()),
                 rd.cast(pa.large_string())]))
            joint = joint.take(pc.sort_indices(joint))
            cached = (joint_remap(ld, joint), joint_remap(rd, joint))
            if stage_cache is not None:
                stage_cache[cache_key] = cached
        if merged is env:
            merged = dict(env)
        merged[lk], merged[rk] = cached
    return merged


def string_transform_env(nodes, schema, table, bucket: int,
                         stage_cache: Optional[dict], env: dict,
                         aux: dict) -> Optional[dict]:
    """Stage transformed-string VALUE lanes (sorted-order ids + validity)
    into env and their transformed dictionaries into aux for decode at
    unstage. Walks each tree; predicate-LUT subtrees are skipped (their
    env entries come from string_lut_env), and a claimed value subtree is
    not descended (its children evaluate on host over the dictionary).
    Returns env (possibly unchanged), or None when a lane cannot stage —
    the caller declines to the host path."""
    merged = env
    stack = list(reversed(nodes))  # no recursive closure: transform_cmp_env
    while stack:
        n = stack.pop()
        if (_string_lut_shape(n, schema) is not None
                or _string_dict_pred_applies(n, schema) is not None):
            continue  # the LUT env owns this subtree
        vs = _string_value_applies(n, schema)
        if vs is not None:
            lane = dict_transform_lane(table, vs, bucket, stage_cache)
            if lane is None:
                return None
            vals, valid, tuniq = lane
            if merged is env:
                merged = dict(env)
            vk, mk = _strtransval_env_keys(vs[2])
            merged[vk] = vals
            merged[mk] = valid
            aux[_stroutdict_aux_key(vs[2])] = tuniq
            continue
        ivs = _int_transform_applies(n, schema)
        if ivs is not None:
            lane = dict_int_transform_lane(table, ivs, bucket, stage_cache)
            if lane is None:
                return None
            if merged is env:
                merged = dict(env)
            vk, mk = _inttrans_env_keys(ivs[2])
            merged[vk], merged[mk] = lane
            continue
        stack.extend(reversed(n.children()))
    return merged


def dict_transform_lane(table, shape, bucket: int,
                        stage_cache: Optional[dict]):
    """(vals, valid, transformed_dictionary) for a transformed-string
    expression: host evaluates the transform over the dictionary values +
    one null slot (exact null semantics — a fill_null can turn the null
    row into a real group), recodes the results through their SORTED
    distinct values (order-preserving: equal results — 'a' and 'A' under
    lower() — share an id, and id order == value order, so the same lane
    serves group identity AND sorts), and the device gathers ids by source
    code. O(unique log unique) host work, O(rows) on device. The
    transformed dictionary decodes ids back to values for projection
    outputs. Returns None -> caller declines."""
    colname, node, node_key = shape
    cache_key = ("__dicttranslane__", node_key, bucket)
    cached = stage_cache.get(cache_key) if stage_cache is not None else None
    if cached is not None:
        return cached
    staged = stage_table_columns(table, [colname], bucket, stage_cache)
    if staged is None:
        return None
    _env, dcs = staged
    dc = dcs.get(colname)
    if dc is None or dc.dictionary is None:
        return None
    uniq = dc.dictionary
    arr = _eval_over_dictionary(colname, node, uniq)
    if arr is None:
        return None
    try:
        distinct = pc.unique(arr.drop_null())
        tuniq = distinct.take(pc.sort_indices(distinct))
        ids_arr = pc.index_in(arr, value_set=tuniq)  # null -> null id
    except Exception:
        return None
    ids = np.asarray(pc.fill_null(ids_arr, 0), dtype=np.int32)
    tvalid = np.asarray(pc.is_valid(ids_arr), dtype=bool)
    u = len(uniq)
    idx = jnp.where(dc.valid, dc.values, u).astype(jnp.int32)
    vals = jnp.asarray(ids)[idx]
    valid = jnp.asarray(tvalid)[idx]
    out = (vals, valid, tuniq)
    if stage_cache is not None:
        stage_cache[cache_key] = out
    return out


# ---------------------------------------------------------------------------
# Joint-dictionary string groups: col-vs-col compares + string if_else/
# fill_null. Per-column dictionary codes are incomparable across columns, so
# every interacting group of string columns (+ literals) merges into ONE
# sorted joint dictionary at staging time; each column gets a small remap
# array injected into env and the closures compare/select JOINT codes on
# device. Same technique as the cross-table join-key recoding
# (device_join._joint_remaps); reference semantics: fully general utf8
# kernels, src/daft-core/src/array/ops/{utf8.rs,if_else.rs}.
# ---------------------------------------------------------------------------


_CMP_OPS_NULLSAFE = _CMP_OPS + ("<=>",)


def _string_cmp_side(node, schema):
    """One side of a general string compare: ('col', name) for a plain
    string Column, ('choice', _StringChoice) for a string fill_null/if_else,
    ('lit', value) / ('null', None) for string/null literals; else None."""
    from ..expressions import Literal

    c = _plain_string_column(node, schema)
    if c is not None:
        return ("col", c)
    ch = _string_choice_shape(node, schema)
    if ch is not None:
        return ("choice", ch)
    if isinstance(node, Literal):
        if node.value is None:
            return ("null", None)
        if isinstance(node.value, str) and (node.dtype.is_string()
                                            or node.dtype.is_null()):
            return ("lit", node.value)
    return None


def _side_group(side):
    """(cols, lits) a compare side contributes to the joint group."""
    kind, v = side
    if kind == "col":
        return (v,), ()
    if kind == "choice":
        return v.cols, v.lits
    if kind == "lit":
        return (), (v,)
    return (), ()


def _string_colcol_shape(node, schema):
    """(lside, rside) when `node` is a string compare whose sides are plain
    columns, string choice shapes (fill_null/if_else), or literals — with at
    least one non-literal side (pure literal-vs-column compares take the
    cheaper per-column bisect path, _string_cmp_shape, tried first)."""
    from ..expressions import BinaryOp

    if not (isinstance(node, BinaryOp) and node.op in _CMP_OPS_NULLSAFE):
        return None
    try:
        ldt = node.left.to_field(schema).dtype
        rdt = node.right.to_field(schema).dtype
    except (ValueError, KeyError):
        return None
    if not ((ldt.is_string() or ldt.is_null())
            and (rdt.is_string() or rdt.is_null())):
        return None
    lside = _string_cmp_side(node.left, schema)
    rside = _string_cmp_side(node.right, schema)
    if lside is None or rside is None:
        return None
    if lside[0] in ("lit", "null") and rside[0] in ("lit", "null"):
        return None  # constant-folding territory, not worth a device shape
    return lside, rside


class _StringChoice:
    """Shape of a string-producing FillNull/IfElse over plain string columns
    and string literals: `operands` is [('col', name) | ('lit', value) |
    ('null', None)] in positional order (child, fill) / (if_true, if_false);
    `pred` is the IfElse predicate node (None for FillNull)."""

    __slots__ = ("kind", "pred", "operands", "cols", "lits")

    def __init__(self, kind, pred, operands):
        self.kind = kind
        self.pred = pred
        self.operands = operands
        self.cols = tuple(sorted({v for k, v in operands if k == "col"}))
        self.lits = tuple(sorted({v for k, v in operands if k == "lit"}))


def _string_choice_shape(node, schema):
    """_StringChoice for a string-typed FillNull/IfElse whose value operands
    are plain string columns / string literals / null literals; else None."""
    from ..expressions import FillNull, IfElse, Literal

    node = _peel_alias(node)
    if isinstance(node, FillNull):
        kind, pred, vals = "fill_null", None, (node.child, node.fill)
    elif isinstance(node, IfElse):
        kind, pred, vals = "if_else", node.pred, (node.if_true, node.if_false)
    else:
        return None
    try:
        if not node.to_field(schema).dtype.is_string():
            return None
    except (ValueError, KeyError):
        return None
    operands = []
    for v in vals:
        c = _plain_string_column(v, schema)
        if c is not None:
            operands.append(("col", c))
        elif isinstance(v, Literal) and v.value is None:
            operands.append(("null", None))
        elif (isinstance(v, Literal) and isinstance(v.value, str)
              and (v.dtype.is_string() or v.dtype.is_null())):
            operands.append(("lit", v.value))
        else:
            return None
    return _StringChoice(kind, pred, operands)


def string_output_dictionary(node, schema, dcs, aux):
    """THE dictionary a string-producing device output decodes through:
    the column's own dictionary for a bare passthrough, the joint-group
    dictionary for a fill_null/if_else result, None when neither resolves
    (caller declines/errs). Shared by the projection resolver and the
    grouped-agg resolver so the decode rule lives once."""
    cname = _plain_string_column(node, schema)
    src = dcs.get(cname) if cname else None
    if src is not None and src.dictionary is not None:
        return src.dictionary
    ch = _string_choice_shape(node, schema)
    if ch is not None:
        return aux.get(_joint_gkey(ch.cols, ch.lits))
    vs = _string_dict_value_shape(node, schema)
    if vs is not None:
        return aux.get(_stroutdict_aux_key(vs[2]))
    return None


def _cmp_union_group(lside, rside):
    """The ONE definition of a general compare's joint group (union of both
    sides) — group registration and closure compilation must agree on env
    keys byte-for-byte, so both call this."""
    lc, ll = _side_group(lside)
    rc, rl = _side_group(rside)
    return (tuple(sorted(set(lc) | set(rc))),
            tuple(sorted(set(ll) | set(rl))))


def _joint_group_of(node, schema):
    """(cols, lits) joint-dictionary group for a node, or None. A general
    string compare's group unions BOTH sides (a choice side's codes must be
    comparable with the other side's), EXCEPT when the cheap per-column
    literal-bisect shape handles the node — that path uses the column's own
    dictionary, no joint group needed."""
    if _string_cmp_shape(node, schema) is None:
        cc = _string_colcol_shape(node, schema)
        if cc is not None:
            return _cmp_union_group(*cc)
    ch = _string_choice_shape(node, schema)
    if ch is not None:
        return ch.cols, ch.lits
    return None


def joint_remap(dictionary, joint):
    """Device remap array taking one dictionary's codes into a sorted JOINT
    dictionary's code space, padded to a size bucket so the consuming gather
    compiles per bucket — shared by the in-table string groups here and the
    cross-table join-key recoding (device_join._joint_remaps)."""
    if len(dictionary) == 0:
        # all-null side: codes are all 0/masked; remap needs 1 lane
        arr = np.zeros(1, dtype=np.int32)
    else:
        arr = np.asarray(pc.index_in(dictionary.cast(pa.large_string()),
                                     value_set=joint), dtype=np.int32)
    b = size_bucket(len(arr))
    if b > len(arr):
        arr = np.concatenate([arr, np.zeros(b - len(arr), np.int32)])
    return jnp.asarray(arr)


def _joint_gkey(cols, lits) -> str:
    return "\x1f".join(cols) + "\x1e" + "\x1f".join(lits)


def _joint_map_key(gkey: str, col: str) -> str:
    return f"__joint__\x00{gkey}\x00map\x00{col}"


def _joint_lit_key(gkey: str, lit: str) -> str:
    return f"__joint__\x00{gkey}\x00lit\x00{lit}"


def _joint_operand_fn(kind, val, gkey):
    """env -> (joint codes, valid) closure for a col/lit/null operand of a
    joint-dictionary group."""
    if kind == "col":
        mk = _joint_map_key(gkey, val)

        def get(env, _c=val, _mk=mk):
            codes, m = env[_c]
            return env[_mk][codes], m
    elif kind == "lit":
        lk = _joint_lit_key(gkey, val)

        def get(env, _lk=lk):
            n = _env_nrows(env)
            return (jnp.full(n, env[_lk], dtype=jnp.int32),
                    jnp.ones(n, dtype=bool))
    else:  # null literal

        def get(env):
            n = _env_nrows(env)
            return (jnp.zeros(n, dtype=jnp.int32),
                    jnp.zeros(n, dtype=bool))
    return get


def _choice_code_fn(ch, gkey, schema):
    """env -> (joint codes, valid) closure for a string fill_null/if_else,
    emitting codes in the group keyed by `gkey` (the node's OWN group when
    it is a projection output; the enclosing compare's bigger group when
    nested as a compare side)."""
    a = _joint_operand_fn(*ch.operands[0], gkey)
    b = _joint_operand_fn(*ch.operands[1], gkey)
    if ch.kind == "fill_null":
        def run(env, _a=a, _b=b):
            av, am = _a(env)
            bv, bm = _b(env)
            return jnp.where(am, av, bv), am | bm

        return run
    p, _pdt = _compile_node(ch.pred, schema)

    def run(env, _p=p, _a=a, _b=b):
        pv, pm = _p(env)
        av, am = _a(env)
        bv, bm = _b(env)
        out = jnp.where(pv, av, bv)
        return out, pm & jnp.where(pv, am, bm)

    return run


def _side_code_fn(side, gkey, schema):
    """env -> (joint codes, valid) for one side of a general string compare."""
    kind, v = side
    if kind == "choice":
        return _choice_code_fn(v, gkey, schema)
    return _joint_operand_fn(kind, v, gkey)


def _shape_choice_preds(node, schema):
    """The choice-side PREDICATES of a matched joint shape — the only
    subtrees under it that can contain further string shapes (its string
    sides are owned by the shape itself)."""
    ch = _string_choice_shape(node, schema)
    if ch is not None:
        return [ch.pred] if ch.pred is not None else []
    cc = _string_colcol_shape(node, schema)
    preds = []
    if cc is not None:
        for kind, v in cc:
            if kind == "choice" and v.pred is not None:
                preds.append(v.pred)
    return preds


def collect_joint_groups(nodes, schema):
    """Every joint-dictionary group in the trees. A matched shape's string
    sides are not re-walked (a choice nested under a compare emits codes in
    the COMPARE's group; registering its standalone subset group too would
    build a joint dictionary nothing reads) — only choice predicates recurse."""
    out = []

    def walk(n):
        g = _joint_group_of(n, schema)
        if g is not None:
            out.append(g)
            for p in _shape_choice_preds(n, schema):
                walk(p)
            return
        for c in n.children():
            walk(c)

    for nd in nodes:
        walk(nd)
    return out


def string_joint_env(nodes, schema, dcs, env, aux: dict):
    """Merge per-group remap arrays + literal codes into `env`; record each
    group's joint dictionary (pa.Array) into `aux[gkey]` so string-producing
    nodes can decode at unstage. Returns env, or None when a needed
    dictionary is unavailable (caller falls back to host)."""
    groups = collect_joint_groups(nodes, schema)
    if not groups:
        return env
    merged = dict(env)
    for cols, lits in set(groups):
        gkey = _joint_gkey(cols, lits)
        if gkey in aux:
            continue
        parts = []
        for c in cols:
            dc = dcs.get(c)
            if dc is None or dc.dictionary is None:
                return None
            parts.append(dc.dictionary.cast(pa.large_string()))
        if lits:
            parts.append(pa.array(list(lits), pa.large_string()))
        joint = pc.unique(pa.concat_arrays(parts))
        joint = joint.take(pc.sort_indices(joint))
        for c in cols:
            merged[_joint_map_key(gkey, c)] = joint_remap(dcs[c].dictionary,
                                                          joint)
        for lit in lits:
            code = pc.index(joint, pa.scalar(lit, pa.large_string())).as_py()
            merged[_joint_lit_key(gkey, lit)] = jnp.int32(code)
        aux[gkey] = joint
    return merged


def _numeric_isin_items(node, schema):
    """Static per-compile device item values for a numeric/date IsIn, or
    None when ineligible. NaN items decline (arrow's is_in matches NaN,
    jnp equality does not)."""
    import math

    from ..expressions import IsIn, Literal

    if not isinstance(node, IsIn):
        return None
    items = node.items
    if not isinstance(items, Literal) or not isinstance(items.value,
                                                        (list, tuple)):
        return None
    try:
        child_dt = node.child.to_field(schema).dtype
    except (ValueError, KeyError):
        return None
    if not (child_dt.is_numeric() or child_dt.kind == TypeKind.DATE
            or child_dt.kind == TypeKind.BOOL):
        return None
    int_child = not child_dt.is_floating()
    out = []
    for v in items.value:
        if v is None:
            continue  # null items never match (host: pc.is_in + fill_null)
        if isinstance(v, float):
            if math.isnan(v):
                return None  # arrow's is_in matches NaN; jnp equality can't
            if int_child:
                # host unifies int-vs-float to float64 compares, whose
                # rounding the 32-bit device can't reproduce: decline
                return None
        try:
            out.append(_literal_to_physical(v, child_dt))
        except (ValueError, TypeError):
            return None
    if not x64_enabled():
        for v in out:
            if isinstance(v, int) and not (-2**31 <= v <= 2**31 - 1):
                return None
    return tuple(out)


def _string_dict_pred_applies(node, schema):
    """The general dictionary predicate shape, ONLY where no cheaper
    specific shape already handles the node — the precedence must match
    _compile_node's dispatch order exactly, or the env builder and the
    compiled closure would disagree about which path owns a node. Boolean
    connectives and plain pass-throughs are also excluded: each side below
    them gets its own best shape (a bisect compare beats an O(unique)
    dictionary evaluation on high-cardinality columns)."""
    from ..expressions import Alias, BinaryOp, Column, IsNull, Literal, Not

    if isinstance(node, (Alias, Column, Literal, Not)):
        return None
    if isinstance(node, IsNull) and \
            _plain_string_column(node.child, schema) is not None:
        # is_null over a plain column is a native validity-mask op on
        # device; the dictionary evaluation would only add host work
        return None
    if isinstance(node, BinaryOp):
        if node.op in ("&", "|", "^"):
            return None
        if _string_cmp_shape(node, schema) is not None:
            return None
        if _string_colcol_shape(node, schema) is not None:
            return None
        if _epoch_cmp_shape(node, schema) is not None:
            return None
    if _string_lut_shape(node, schema) is not None:
        return None
    return _string_dict_pred_shape(node, schema)


def collect_string_luts(nodes, schema):
    """Every LUT-predicate shape in the trees: the fixed single-function
    shapes, plus general dictionary predicates (tagged "hostpred"); a
    matched general predicate's subtree is skipped — its children evaluate
    on host over the dictionary, never separately on device."""
    out = []

    def walk(n):
        shape = _string_lut_shape(n, schema)
        if shape is not None:
            out.append(shape)
        else:
            gshape = _string_dict_pred_applies(n, schema)
            if gshape is not None:
                out.append((gshape[0], "hostpred", gshape[1], gshape[2]))
                return
        for c in n.children():
            walk(c)

    for nd in nodes:
        walk(nd)
    return out


def _eval_over_dictionary(colname: str, node, uniq):
    """Host-evaluate `node` over the dictionary values PLUS one null slot
    (index len(uniq)) — THE one definition of dictionary-level evaluation,
    shared by the boolean predicate LUT and the transformed group-key lane
    so their null semantics can never diverge. Returns the arrow result
    array of length len(uniq)+1, or None (caller declines to host)."""
    from ..table import Table

    try:
        with_null = pa.concat_arrays(
            [uniq, pa.array([None], type=uniq.type)])
        tbl = Table.from_arrow(pa.table({colname: with_null}))
        got = node.evaluate(tbl)
        arr = got.to_arrow()
        if isinstance(arr, pa.ChunkedArray):
            arr = arr.combine_chunks()
        if len(arr) == 1 and len(with_null) > 1:  # scalar broadcast
            arr = pa.concat_arrays([arr] * len(with_null))
        return arr
    except Exception:
        return None


# A boolean table over at most this many dictionary entries goes to the
# device as packed uint32 words, looked up with selects and one shift that
# fuse into the consumer; a larger one as bool[bucket], gathered by code.
# Fixed from a sweep on one TPU v5e over 64M codes (PERF.md section 6, PR 28;
# `tools/dict_lookup_sweep.py` repeats it): the gather takes 542 ms at every
# size. Up to 1024 entries (32 words) the packed lookup costs what one word
# costs, 1.5 ms, the consumer's own memory time, and its program compiles as
# fast as the gather's (0.5 s). Above, both double with the words (4096
# entries 2.9 ms and 4 s, 131072 entries 99 ms and 62 s): still faster over
# 64M rows, but a program over few rows would pay the compile for nothing.
DICT_PACKED_MAX_ENTRIES = 1024


def _dict_bool_tables(*tables: np.ndarray) -> list:
    """Device form of boolean tables over ONE dictionary's entries (equal
    lengths): at or under ``DICT_PACKED_MAX_ENTRIES`` each packs into
    ``uint32[W]`` (bit ``c & 31`` of word ``c >> 5`` is entry ``c``; W the
    power of two at or above ceil(entries/32), so the program's shape
    follows a bucket and not the data), above it each pads to
    ``bool[size_bucket]``. Either is a traced input, never a constant of
    the program: a new dictionary of the same bucket compiles nothing.
    Bumps ``dict_lookup_packed`` or ``dict_lookup_gather`` once: one lookup
    built for the query."""
    entries = len(tables[0])
    packed = entries <= DICT_PACKED_MAX_ENTRIES
    timeline.add("dict_lookup_packed" if packed else "dict_lookup_gather", 1)
    if packed:
        size = 32 << (max(entries - 1, 0) >> 5).bit_length()
    else:
        size = size_bucket(entries)
    out = []
    for t in tables:
        padded = np.zeros(size, dtype=bool)
        padded[:entries] = t
        if packed:
            padded = np.packbits(padded, bitorder="little").view("<u4")
        out.append(jnp.asarray(padded))
    return out


def _dict_bool_lookup(table, codes):
    """``table[codes]`` for a table ``_dict_bool_tables`` built, in the form
    it finds (a trace-time branch, so jit keeps one program a form and
    bucket). Packed words: bit ``j`` of ``codes >> 5`` picks a half of the
    words at each of log2(W) levels, W-1 selects in all, then one shift
    reads bit ``codes & 31`` — element-wise work the compiler fuses into
    the consumer; a code outside the words reads False. A bool table
    gathers."""
    if table.dtype != jnp.uint32:
        return table[codes]
    words = table.shape[0]
    hi = codes >> 5
    word = jnp.where(hi.astype(jnp.uint32) < words,
                     _packed_word(table, hi, 0, words), jnp.uint32(0))
    return ((word >> (codes & 31).astype(jnp.uint32)) & 1).astype(jnp.bool_)


def _packed_word(table, hi, lo: int, n: int):
    """The word of ``table[lo:lo + n]`` (n a power of two) that the low
    bits of ``hi`` pick: one select a level on bit ``n // 2``."""
    if n == 1:
        return jax.lax.index_in_dim(table, lo, keepdims=False)
    half = n // 2
    return jnp.where((hi & half) != 0, _packed_word(table, hi, lo + half, half),
                     _packed_word(table, hi, lo, half))


def _merge_dict_pred(merged: dict, colname: str, node, node_key, dcs) -> bool:
    """Evaluate a general dictionary predicate over the column's dictionary
    values PLUS one null slot (exact null semantics: whatever the host path
    produces for a null input — is_null, fill_null chains — the device's
    lookup by code produces identically), through the host evaluator itself
    so parity is by construction. The (value, validity) tables go to the
    device in the form ``_dict_bool_tables`` picks for dictionary + null
    slot. False = decline to the host path."""
    vals_k, valid_k, null_k = _strdictpred_env_keys(node_key)
    if vals_k in merged:
        return True
    dc = dcs.get(colname)
    if dc is None or dc.dictionary is None:
        return False
    uniq = dc.dictionary
    arr = _eval_over_dictionary(colname, node, uniq)
    if arr is None:
        return False
    merged[vals_k], merged[valid_k] = _dict_bool_tables(
        np.asarray(pc.fill_null(arr, False), dtype=bool),
        np.asarray(pc.is_valid(arr), dtype=bool))
    merged[null_k] = jnp.int32(len(uniq))
    return True


def string_lut_env(nodes, schema, dcs, env) -> Optional[dict]:
    """Merge per-partition dictionary lookup tables into `env` for every
    LUT-predicate. Returns the (possibly unchanged) env, or None when a
    needed dictionary is unavailable."""
    shapes = collect_string_luts(nodes, schema)
    if not shapes:
        return env
    merged = dict(env)
    for colname, kind, payload, node_key in shapes:
        if kind == "hostpred":
            if not _merge_dict_pred(merged, colname, payload, node_key, dcs):
                return None
            continue
        key = _strlut_env_key(node_key)
        if key in merged:
            continue
        dc = dcs.get(colname)
        if dc is None or dc.dictionary is None:
            return None
        uniq = dc.dictionary
        if kind == "is_in":
            lut = pc.is_in(uniq, value_set=pa.array(list(payload),
                                                    type=uniq.type))
        else:
            # run the REGISTERED host implementation over the dictionary:
            # whatever semantics the host path has (incl. like's regex
            # translation), the LUT has identically
            from ..functions import get_function
            from ..series import Series

            got = get_function(kind).evaluate(
                Series.from_arrow(uniq, "u"),
                Series.from_pylist([payload], "p", DataType.string()))
            lut = got.to_arrow()
        merged[key], = _dict_bool_tables(
            np.asarray(pc.fill_null(lut, False), dtype=bool))
    return merged


def expr_is_device_compilable(node, schema, _normalized: bool = False) -> bool:
    """Can this expression tree run fully on device against `schema`?"""
    from ..expressions import (
        Alias, Between, BinaryOp, Cast, Column, FillNull, Function, IfElse, IsIn,
        IsNull, Literal, Not, normalize_literals,
    )

    if not _normalized:
        try:
            node = normalize_literals(node, schema)
        except (ValueError, KeyError):
            return False
        return expr_is_device_compilable(node, schema, _normalized=True)

    def rec(n):
        return expr_is_device_compilable(n, schema, _normalized=True)

    try:
        out_dt = node.to_field(schema).dtype
    except (ValueError, KeyError):
        return False
    if _string_dict_pred_applies(node, schema) is not None:
        # the whole boolean subtree evaluates over the dictionary on host;
        # nothing below it needs to compile on device
        return True
    if _int_transform_applies(node, schema) is not None:
        # int-valued string transform: values come from a host dictionary
        # evaluation, gathered by code
        return True
    if not (is_device_dtype(out_dt) or out_dt.is_null()):
        # strings ride dictionary codes: bare column passthrough, a
        # fill_null/if_else over string columns/literals whose output codes
        # live in a joint dictionary, or a row-local transform of ONE
        # string column whose sorted-order ids come from a host transform
        # of the dictionary (all decoded at unstage); any OTHER
        # string-producing compute stays host
        if out_dt.is_string():
            if _plain_string_column(node, schema) is not None:
                return True
            ch = _string_choice_shape(node, schema)
            if ch is not None:
                return ch.pred is None or rec(ch.pred)
            if _string_dict_value_shape(node, schema) is not None:
                return True
            return False
        return False
    if isinstance(node, Column):
        return stageable_dtype(schema[node.cname].dtype)
    if isinstance(node, Literal):
        return _literal_fits_device(node)
    if isinstance(node, (Alias, Not, IsNull)):
        return all(rec(c) for c in node.children())
    def any_string_child(n) -> bool:
        """True when any DIRECT child is string-typed (or untyped): its
        device representation would be dictionary codes, which only the
        string-comparison shape knows how to interpret."""
        for c in n.children():
            try:
                if c.to_field(schema).dtype.is_string():
                    return True
            except (ValueError, KeyError):
                return True
        return False

    if isinstance(node, Cast):
        # one level is enough here: casting dictionary CODES themselves is
        # nonsense, but a cast OVER e.g. a bool from a legit string compare
        # is fine — deeper strings are vetted where they are consumed
        if any_string_child(node):
            return False
        return is_device_dtype(node.dtype) and rec(node.child)
    if isinstance(node, BinaryOp):
        if node.op == "+" and out_dt.is_string():
            return False
        if _string_cmp_shape(node, schema) is not None:
            return True
        cc = _string_colcol_shape(node, schema)
        if cc is not None:
            # joint-dictionary recode, compared on device; a choice side's
            # predicate must itself compile
            return all(s[0] != "choice" or s[1].pred is None or rec(s[1].pred)
                       for s in cc)
        # epoch comparisons compile as two-lane splits only in 32-bit mode;
        # under x64 the generic int64 path below handles them
        if not x64_enabled() and _epoch_cmp_shape(node, schema) is not None:
            return True
        # cross-column transform compares recode through a pairwise joint
        # dictionary (transform_cmp_env)
        if _transform_cmp_shape(node, schema) is not None:
            return True
        # any OTHER op touching a string child (col vs col: codes come
        # from different dictionaries) must stay host
        if any_string_child(node):
            return False
        return all(rec(c) for c in node.children())
    if isinstance(node, (FillNull, IfElse, Between)):
        if any_string_child(node):
            return False
        return all(rec(c) for c in node.children())
    if isinstance(node, Function):
        if _string_lut_shape(node, schema) is not None:
            return True  # dictionary-LUT predicate (contains/starts/ends)
        if node.fname in _DEVICE_FNS:
            return all(rec(c) for c in node.children())
        return False
    if isinstance(node, IsIn):
        if _string_lut_shape(node, schema) is not None:
            return True  # string membership via the dictionary LUT
        return (_numeric_isin_items(node, schema) is not None
                and rec(node.child))
    return False


_DEVICE_FNS = {
    "numeric.abs": lambda v: jnp.abs(v),
    "numeric.negate": lambda v: -v,
    "numeric.ceil": lambda v: jnp.ceil(v),
    "numeric.floor": lambda v: jnp.floor(v),
    "numeric.sign": lambda v: jnp.sign(v),
    "numeric.sqrt": lambda v: jnp.sqrt(v.astype(_wf())),
    "numeric.exp": lambda v: jnp.exp(v.astype(_wf())),
    "numeric.log": lambda v: jnp.log(v.astype(_wf())),
    "numeric.log2": lambda v: jnp.log2(v.astype(_wf())),
    "numeric.log10": lambda v: jnp.log10(v.astype(_wf())),
    "numeric.log1p": lambda v: jnp.log1p(v.astype(_wf())),
    "numeric.sin": lambda v: jnp.sin(v.astype(_wf())),
    "numeric.cos": lambda v: jnp.cos(v.astype(_wf())),
    "numeric.tan": lambda v: jnp.tan(v.astype(_wf())),
    "float.is_nan": lambda v: jnp.isnan(v),
    "float.is_inf": lambda v: jnp.isinf(v),
    "float.not_nan": lambda v: ~jnp.isnan(v),
}


def _strlit_keys(colname: str, lit: str) -> Tuple[str, str, str]:
    """Deterministic env keys for a (column, literal) pair's injected code
    bounds: eq code (-1 when absent), bisect-left pos, bisect-right pos."""
    base = f"__strlit__\x00{colname}\x00{lit}"
    return base + "\x00eq", base + "\x00lt", base + "\x00le"


def _env_nrows(env) -> int:
    """Bucket length from the first COLUMN entry (env also carries scalar
    literal-code leaves, which have no row dimension)."""
    for v in env.values():
        if isinstance(v, tuple):
            return v[0].shape[0]
    raise AssertionError("projection env has no column entries")


def collect_string_cmp_literals(nodes, schema):
    """Every (colname, literal) string comparison in the trees (normalized)."""
    from ..expressions import BinaryOp

    out = []

    def walk(n):
        if isinstance(n, BinaryOp):
            shape = _string_cmp_shape(n, schema)
            if shape is not None and shape[1] is not None:
                out.append((shape[0], shape[1]))
        for c in n.children():
            walk(c)

    for nd in nodes:
        walk(nd)
    return out


def string_literal_env(nodes, schema, dcs, env) -> Optional[dict]:
    """Merge the per-partition code bounds for every string-literal
    comparison into `env` ({env_key: int32 scalar} entries). The compiled
    closure is shared across partitions (the literal's CODE varies, the
    program does not). Returns the (possibly unchanged) env, or None when a
    needed dictionary is unavailable (caller falls back to host)."""
    import bisect

    add: Dict[str, jax.Array] = {}
    for colname, lit in collect_string_cmp_literals(nodes, schema):
        keq, klt, kle = _strlit_keys(colname, lit)
        if keq in add:
            continue
        dc = dcs.get(colname)
        if dc is None or dc.dictionary is None:
            return None
        uniq = dc.dict_list()
        i = bisect.bisect_left(uniq, lit)
        j = bisect.bisect_right(uniq, lit)
        eq = i if i < len(uniq) and uniq[i] == lit else -1
        add[keq] = jnp.int32(eq)
        add[klt] = jnp.int32(i)
        add[kle] = jnp.int32(j)
    if not add:
        return env
    merged = dict(env)
    merged.update(add)
    return merged


def _compile_node(node, schema) -> "Tuple[callable, DataType]":
    """Recursively build a python closure over {name: (values, valid)} env.

    The closure is pure jax -> safe to jit; types resolved statically via schema.
    """
    from ..expressions import (
        Alias, Between, BinaryOp, Cast, Column, FillNull, Function, IfElse, IsIn,
        IsNull, Literal, Not,
    )

    out_dt = node.to_field(schema).dtype

    gshape = _string_dict_pred_applies(node, schema)
    if gshape is not None:
        # general dictionary predicate: the WHOLE boolean subtree was
        # host-evaluated over the column's dictionary (+ null slot); the
        # device looks (value, validity) up by code
        colname, _pred, node_key = gshape
        vals_k, valid_k, null_k = _strdictpred_env_keys(node_key)

        def run(env, _c=colname, _vk=vals_k, _mk=valid_k, _nk=null_k):
            codes, m = env[_c]
            idx = jnp.where(m, codes, env[_nk])
            return (_dict_bool_lookup(env[_vk], idx),
                    _dict_bool_lookup(env[_mk], idx))

        return run, out_dt

    vshape = _string_value_applies(node, schema)
    if vshape is not None:
        # transformed-string value: the lane (sorted-order ids + validity)
        # was staged by string_transform_env; decode at unstage goes
        # through the transformed dictionary (string_output_dictionary)
        vk, mk = _strtransval_env_keys(vshape[2])

        def run(env, _vk=vk, _mk=mk):
            return env[_vk], env[_mk]

        return run, out_dt

    ishape = _int_transform_applies(node, schema)
    if ishape is not None:
        # int-valued string transform (length/find/count_matches): the
        # lane carries VALUES gathered by code, no decode needed
        vk, mk = _inttrans_env_keys(ishape[2])

        def run(env, _vk=vk, _mk=mk):
            return env[_vk], env[_mk]

        return run, out_dt

    if isinstance(node, Column):
        name = node.cname

        def run(env):
            return env[name]

        return run, out_dt

    if isinstance(node, Literal):
        if node.value is None:
            def run(env, _dt=out_dt):
                n = _env_nrows(env)
                return jnp.zeros(n, dtype=jnp.int32), jnp.zeros(n, dtype=bool)
        else:
            v = _literal_to_physical(node.value, node.dtype)
            jd = _jdt(node.dtype)

            def run(env, _v=v, _jd=jd):
                n = _env_nrows(env)
                return jnp.full(n, _v, dtype=_jd), jnp.ones(n, dtype=bool)

        return run, out_dt

    if isinstance(node, Alias):
        inner, _ = _compile_node(node.child, schema)
        return inner, out_dt

    if isinstance(node, Cast):
        inner, _ = _compile_node(node.child, schema)
        jd = _jdt(node.dtype)

        def run(env, _inner=inner, _jd=jd):
            v, m = _inner(env)
            return v.astype(_jd), m

        return run, out_dt

    if isinstance(node, Not):
        inner, _ = _compile_node(node.child, schema)

        def run(env, _inner=inner):
            v, m = _inner(env)
            return ~v, m

        return run, out_dt

    if isinstance(node, IsNull):
        inner, _ = _compile_node(node.child, schema)
        neg = node.negate

        def run(env, _inner=inner, _neg=neg):
            v, m = _inner(env)
            out = m if _neg else ~m
            return out, jnp.ones_like(m)

        return run, out_dt

    if isinstance(node, (FillNull, IfElse)) and out_dt.is_string():
        ch = _string_choice_shape(node, schema)
        if ch is None:
            raise ValueError("string choice not device-compilable here")
        return _choice_code_fn(ch, _joint_gkey(ch.cols, ch.lits),
                               schema), out_dt

    if isinstance(node, FillNull):
        a, adt = _compile_node(node.child, schema)
        b, bdt = _compile_node(node.fill, schema)
        jd = _jdt(out_dt)

        def run(env, _a=a, _b=b, _jd=jd):
            av, am = _a(env)
            bv, bm = _b(env)
            out = jnp.where(am, av.astype(_jd), bv.astype(_jd))
            return out, am | bm

        return run, out_dt

    if isinstance(node, IfElse):
        p, _ = _compile_node(node.pred, schema)
        t, _ = _compile_node(node.if_true, schema)
        f, _ = _compile_node(node.if_false, schema)
        jd = _jdt(out_dt)

        def run(env, _p=p, _t=t, _f=f, _jd=jd):
            pv, pm = _p(env)
            tv, tm = _t(env)
            fv, fm = _f(env)
            out = jnp.where(pv, tv.astype(_jd), fv.astype(_jd))
            valid = pm & jnp.where(pv, tm, fm)
            return out, valid

        return run, out_dt

    if isinstance(node, Between):
        x, _ = _compile_node(node.child, schema)
        lo, _ = _compile_node(node.lower, schema)
        hi, _ = _compile_node(node.upper, schema)

        def run(env, _x=x, _lo=lo, _hi=hi):
            xv, xm = _x(env)
            lv, lm = _lo(env)
            hv, hm = _hi(env)
            ge, ge_m = xv >= lv, xm & lm
            le, le_m = xv <= hv, xm & hm
            out = ge & le
            # Kleene AND: valid when both valid, or either side is a valid False
            valid = (ge_m & le_m) | (ge_m & ~ge) | (le_m & ~le)
            return out, valid

        return run, out_dt

    if isinstance(node, BinaryOp):
        shape = _string_cmp_shape(node, schema)
        if shape is not None:
            colname, lit, flipped = shape
            cop = _CMP_FLIP[node.op] if flipped else node.op
            if lit is None:
                # comparison with a null literal: all-null result (SQL)
                def run(env, _c=colname):
                    _v, m = env[_c]
                    z = jnp.zeros_like(m)
                    return z, z

                return run, out_dt
            keq, klt, kle = _strlit_keys(colname, lit)

            def run(env, _c=colname, _op=cop, _keq=keq, _klt=klt, _kle=kle):
                codes, m = env[_c]
                if _op == "==":
                    out = codes == env[_keq]
                elif _op == "!=":
                    out = codes != env[_keq]
                elif _op == "<":
                    out = codes < env[_klt]
                elif _op == ">=":
                    out = codes >= env[_klt]
                elif _op == "<=":
                    out = codes < env[_kle]
                else:  # ">"
                    out = codes >= env[_kle]
                return out, m

            return run, out_dt
        ccshape = _string_colcol_shape(node, schema)
        if ccshape is not None:
            lside, rside = ccshape
            gkey = _joint_gkey(*_cmp_union_group(lside, rside))
            lf2 = _side_code_fn(lside, gkey, schema)
            rf2 = _side_code_fn(rside, gkey, schema)
            op = node.op

            def run(env, _l=lf2, _r=rf2, _op=op):
                lv, lm = _l(env)
                rv, rm = _r(env)
                if _op == "<=>":
                    eq = (lv == rv) & lm & rm
                    return eq | (~lm & ~rm), jnp.ones_like(lm)
                if _op == "==":
                    out = lv == rv
                elif _op == "!=":
                    out = lv != rv
                elif _op == "<":
                    out = lv < rv
                elif _op == "<=":
                    out = lv <= rv
                elif _op == ">":
                    out = lv > rv
                else:
                    out = lv >= rv
                return out, lm & rm

            return run, out_dt
        eshape = None if x64_enabled() else _epoch_cmp_shape(node, schema)
        if eshape is not None:
            lspec, rspec, cop = eshape
            if lspec[0] == "lit":
                # normalize to lane-op-lit / lane-op-lane with the lane side
                # on the left, flipping the comparison when the literal led
                lspec, rspec, cop = rspec, lspec, _CMP_FLIP[cop]
            _tag, lident, _ldt, _lsn = lspec
            hi_k, lo_k = _epoch_lane_keys(lident)
            if rspec[0] == "lit":
                lit = rspec[1]
                if lit.value is None:
                    def run(env, _hk=hi_k):
                        _v, m = env[_hk]
                        z = jnp.zeros_like(m)
                        return z, z

                    return run, out_dt
                lhik, llok = _epoch_lit_keys(lident, lit._key())

                def run(env, _op=cop, _hk=hi_k, _lk=lo_k, _lh=lhik,
                        _ll=llok):
                    hi, m = env[_hk]
                    lo, _m2 = env[_lk]
                    return _two_lane_cmp(_op, hi, lo, env[_lh], env[_ll]), m

                return run, out_dt
            rhi_k, rlo_k = _epoch_lane_keys(rspec[1])

            def run(env, _op=cop, _hk=hi_k, _lk=lo_k, _rhk=rhi_k,
                    _rlk=rlo_k):
                hi, lm = env[_hk]
                lo, _m2 = env[_lk]
                rhi, rm = env[_rhk]
                rlo, _m4 = env[_rlk]
                return _two_lane_cmp(_op, hi, lo, rhi, rlo), lm & rm

            return run, out_dt
        tshape = _transform_cmp_shape(node, schema)
        if tshape is not None:
            ls, rs, cop = tshape
            lk, rk = _transcmp_env_keys(node._key())

            def _lane_reader(s):
                kind, colname, n = s
                if kind == "col":
                    def read(env, _c=colname):
                        return env[_c]
                else:
                    vk, mk = _strtransval_env_keys(n._key())

                    def read(env, _vk=vk, _mk=mk):
                        return env[_vk], env[_mk]
                return read

            lread, rread = _lane_reader(ls), _lane_reader(rs)
            cmp_fn = _CMP_FNS[cop]

            def run(env, _lr=lread, _rr=rread, _lk=lk, _rk=rk, _f=cmp_fn):
                lv, lm = _lr(env)
                rv, rm = _rr(env)
                lj = env[_lk][lv]
                rj = env[_rk][rv]
                return _f(lj, rj), lm & rm

            return run, out_dt
        lf, ldt = _compile_node(node.left, schema)
        rf, rdt = _compile_node(node.right, schema)
        op = node.op
        if op in ("&", "|"):
            def run(env, _l=lf, _r=rf, _op=op):
                lv, lm = _l(env)
                rv, rm = _r(env)
                if _op == "&":
                    out = lv & rv
                    # Kleene: valid if both valid, or either side is a valid False
                    valid = (lm & rm) | (lm & ~lv) | (rm & ~rv)
                else:
                    out = lv | rv
                    valid = (lm & rm) | (lm & lv) | (rm & rv)
                return out, valid

            return run, out_dt
        if op == "^":
            def run(env, _l=lf, _r=rf):
                lv, lm = _l(env)
                rv, rm = _r(env)
                return lv ^ rv, lm & rm

            return run, out_dt

        if op in _CMP_FNS:
            fn = _CMP_FNS[op]

            def run(env, _l=lf, _r=rf, _fn=fn):
                lv, lm = _l(env)
                rv, rm = _r(env)
                return _fn(lv, rv), lm & rm

            return run, out_dt
        if op == "<=>":
            def run(env, _l=lf, _r=rf):
                lv, lm = _l(env)
                rv, rm = _r(env)
                eq = (lv == rv) & lm & rm
                both_null = ~lm & ~rm
                return eq | both_null, jnp.ones_like(lm)

            return run, out_dt

        jd = _jdt(out_dt)

        def arith(lv, rv, _op=op, _jd=jd):
            if _op == "+":
                return (lv.astype(_jd) + rv.astype(_jd))
            if _op == "-":
                return (lv.astype(_jd) - rv.astype(_jd))
            if _op == "*":
                return (lv.astype(_jd) * rv.astype(_jd))
            if _op == "/":
                return lv.astype(_wf()) / rv.astype(_wf())
            if _op == "//":
                if jnp.issubdtype(jnp.result_type(lv, rv), jnp.floating):
                    return jnp.floor(lv / rv).astype(_jd)  # 1.0//0.0 = inf like host
                return jnp.floor_divide(lv, rv).astype(_jd)
            if _op == "%":
                return jnp.mod(lv, rv).astype(_jd)
            if _op == "**":
                return jnp.power(lv.astype(_wf()), rv.astype(_wf()))
            raise AssertionError(_op)

        def run(env, _l=lf, _r=rf, _arith=arith, _op=op):
            lv, lm = _l(env)
            rv, rm = _r(env)
            if _op == "/":
                # float division: inf/nan like the host (arrow) kernel
                return _arith(lv, rv), lm & rm
            if _op in ("//", "%") and not jnp.issubdtype(jnp.result_type(lv, rv), jnp.floating):
                # INT division by zero: null (the host checked kernel raises; on
                # device we cannot raise inside jit, so mask instead). Float
                # operands keep inf/nan semantics to match the host.
                safe = jnp.where(rv == 0, jnp.ones_like(rv), rv)
                out = _arith(lv, safe)
                return out, lm & rm & (rv != 0)
            return _arith(lv, rv), lm & rm

        return run, out_dt

    if isinstance(node, (Function, IsIn)):
        lshape = _string_lut_shape(node, schema)
        if lshape is not None:
            colname, _kind, _payload, node_key = lshape
            lut_k = _strlut_env_key(node_key)

            def run(env, _c=colname, _lk=lut_k):
                codes, m = env[_c]
                return _dict_bool_lookup(env[_lk], codes), m

            return run, out_dt

    if isinstance(node, IsIn):
        items = _numeric_isin_items(node, schema)
        if items is None:
            raise ValueError("is_in not device-compilable here")
        inner, _ = _compile_node(node.child, schema)

        def run(env, _inner=inner, _items=items):
            v, m = _inner(env)
            if not _items:
                return jnp.zeros_like(m), m
            out = jnp.zeros_like(m)
            for it in _items:  # small static lists: unrolled compares fuse
                out = out | (v == it)
            return out, m

        return run, out_dt

    if isinstance(node, Function):
        if node.fname not in _DEVICE_FNS:
            raise ValueError(f"function {node.fname} not device-compilable")
        inner, _ = _compile_node(node.args[0], schema)
        fn = _DEVICE_FNS[node.fname]

        def run(env, _inner=inner, _fn=fn):
            v, m = _inner(env)
            return _fn(v), m

        return run, out_dt

    raise ValueError(f"{type(node).__name__} not device-compilable")


_PROJ_CACHE: Dict = {}


def compile_projection(nodes, schema, input_names: Tuple[str, ...]):
    """Compile a list of NORMALIZED expression nodes to ONE jitted fn:
    env dict -> list[(values, valid)].

    Cached on (node keys, schema, input order, x64 mode); XLA additionally
    caches per bucket.
    """
    key = (tuple(n._key() for n in nodes), tuple((f.name, f.dtype) for f in schema),
           input_names, x64_enabled())
    if key in _PROJ_CACHE:
        return _PROJ_CACHE[key]
    compiled = [_compile_node(n, schema) for n in nodes]
    fns = [c[0] for c in compiled]
    out_dts = [c[1] for c in compiled]

    @jax.jit
    def run(env):
        return [f(env) for f in fns]

    _PROJ_CACHE[key] = (run, out_dts)
    return run, out_dts


def stage_table_columns(table, names, bucket: int, stage_cache: Optional[dict] = None):
    """Stage the named columns of a host Table: returns (env, dcs) where env
    is {name: (values, valid)} for the jitted programs and dcs the backing
    DeviceColumns (string dictionaries live there). HBM-resident columns are
    reused from `stage_cache` (the per-MicroPartition residency cache —
    staging, not compute, is the bottleneck through the host link, so
    repeated queries over the same partition must not re-transfer).
    Returns None if any column is ineligible."""
    env = {}
    dcs = {}
    for name in names:
        ckey = _column_key(name, bucket)
        dc = stage_cache.get(ckey) if stage_cache is not None else None
        if dc is None:
            s = table.get_column(name)
            if not stageable_dtype(s.dtype):
                return None
            dc = stage_series(s, bucket)
            if stage_cache is not None:
                stage_cache[ckey] = dc
        env[name] = (dc.values, dc.valid)
        dcs[name] = dc
    return env, dcs


def _column_key(name: str, bucket: int) -> tuple:
    """A staged column's key in a stage cache (``stage_table_columns``)."""
    return (name, bucket, x64_enabled())


def staged(stage_cache: Optional[dict], names, n: int) -> bool:
    """Whether ``stage_cache`` holds the lanes of every column in ``names``
    over a table of ``n`` rows."""
    if not stage_cache:
        return False
    b = size_bucket(n)
    return all(_column_key(name, b) in stage_cache for name in names)


def carry_staged(stage_cache: Optional[dict], renames: dict) -> dict:
    """The staged column lanes of ``stage_cache``, keyed for a table over
    the same rows in which the column ``src`` is named each of
    ``renames[src]``. The arrays are shared, not copied."""
    out = {}
    for key, dc in (stage_cache or {}).items():
        if (isinstance(dc, DeviceColumn) and isinstance(key, tuple)
                and len(key) == 3 and key[0] in renames
                and key == _column_key(key[0], key[1])):
            for name in renames[key[0]]:
                out[_column_key(name, key[1])] = dc
    return out


def _rewrite_between(node, schema):
    """Between over string/epoch children rewrites to the conjunction of two
    comparisons — exactly the host's implementation (Series.between is
    (x >= lo) & (x <= hi)) — so the dictionary-code and epoch-lane compare
    machinery applies. Numeric Between keeps its fused direct compile."""
    from ..expressions import Between, BinaryOp

    kids = node.children()
    if kids:
        node = node.with_children([_rewrite_between(c, schema) for c in kids])
    if isinstance(node, Between):
        try:
            cdt = node.child.to_field(schema).dtype
        except (ValueError, KeyError):
            return node
        if cdt.is_string() or cdt.kind in _EPOCH_KINDS:
            return BinaryOp("&",
                            BinaryOp(">=", node.child, node.lower),
                            BinaryOp("<=", node.child, node.upper))
    return node


def normalize_and_check(exprs, schema) -> Optional[list]:
    """Normalize each expression's literals against `schema`, apply device
    rewrites, and verify device compilability. Returns the normalized
    nodes, or None if any is ineligible."""
    from ..expressions import normalize_literals

    try:
        nodes = [_rewrite_between(normalize_literals(e._node, schema), schema)
                 for e in exprs]
    except (ValueError, KeyError):
        return None
    for nd in nodes:
        if not expr_is_device_compilable(nd, schema, _normalized=True):
            return None
    return nodes


_INT32_LO, _INT32_HI = -(2 ** 31), 2 ** 31 - 1


def int64_wrap_safe(nodes, schema, env, stage_cache: Optional[dict],
                    bucket: int) -> bool:
    """32-bit mode guard: int64-typed arithmetic computes in int32 lanes and
    can wrap silently (staging only range-checks the LEAF columns). Prove by
    interval arithmetic over the STAGED data's actual min/max that no
    int64-typed arithmetic node can leave the int32 range; anything unproven
    declines to the host path (exact 64-bit semantics there). The per-column
    ranges cost one fused reduction + sync each, cached with the partition.

    Found live: `select((col_i64 * col_i64))` with values ~1e5 returned the
    int32-wrapped product on the device path while the host returned 1e10.
    """
    if x64_enabled():
        return True
    from ..datatypes import DataType
    from ..expressions import Alias, BinaryOp, Column, Function, Literal

    risky_dts = (DataType.int64(), DataType.uint64())

    _lanes_memo: dict = {}

    def rides_lanes(n):
        # an epoch-compare subtree is host-evaluated in exact int64 and
        # reaches the device only as (hi, lo) lane pairs, and a dictionary-
        # predicate subtree is host-evaluated over the dictionary: int32
        # wrap safety is irrelevant below either. Memoized by node identity:
        # has_risky and safe both probe every node, and each probe walks
        # the subtree.
        r = _lanes_memo.get(id(n))
        if r is None:
            r = ((isinstance(n, BinaryOp)
                  and _epoch_cmp_shape(n, schema) is not None)
                 or _string_dict_pred_applies(n, schema) is not None
                 or _string_value_applies(n, schema) is not None
                 or _int_transform_applies(n, schema) is not None)
            _lanes_memo[id(n)] = r
        return r

    def has_risky(n):
        if rides_lanes(n):
            return False
        try:
            if (isinstance(n, (BinaryOp, Function))
                    and n.to_field(schema).dtype in risky_dts):
                return True
        except (ValueError, KeyError):
            return True
        return any(has_risky(c) for c in n.children())

    if not any(has_risky(n) for n in nodes):
        return True

    def col_range(name):
        key = ("__int_range__", name, bucket, x64_enabled())
        r = stage_cache.get(key) if stage_cache is not None else None
        if r is None:
            if name not in env:
                return None
            v, m = env[name]
            if not jnp.issubdtype(v.dtype, jnp.integer):
                return None
            lo_d = jnp.min(jnp.where(m, v, jnp.iinfo(v.dtype).max))
            hi_d = jnp.max(jnp.where(m, v, jnp.iinfo(v.dtype).min))
            lo, hi = (int(x) for x in fetch((lo_d, hi_d)))  # 1 sync
            if hi < lo:  # all-null column
                lo = hi = 0
            r = (lo, hi)
            if stage_cache is not None:
                stage_cache[key] = r
        return r

    def bounds(n):
        """Exact integer interval of a node, or None = unknown."""
        if isinstance(n, Alias):
            return bounds(n.child)
        if isinstance(n, Column):
            return col_range(n.cname)
        if isinstance(n, Literal):
            v = n.value
            return (v, v) if isinstance(v, int) and not isinstance(v, bool) \
                else None
        if isinstance(n, BinaryOp) and n.op in ("+", "-", "*"):
            a = bounds(n.left)
            b = bounds(n.right)
            if a is None or b is None:
                return None
            if n.op == "+":
                return (a[0] + b[0], a[1] + b[1])
            if n.op == "-":
                return (a[0] - b[1], a[1] - b[0])
            prods = [x * y for x in a for y in b]
            return (min(prods), max(prods))
        if isinstance(n, BinaryOp) and n.op == "%":
            b = bounds(n.right)
            if b is None:
                return None
            m = max(abs(b[0]), abs(b[1]))
            if m == 0:
                return None
            return (-(m - 1), m - 1)
        if isinstance(n, BinaryOp) and n.op == "//":
            a = bounds(n.left)
            b = bounds(n.right)
            if a is None or b is None or b[0] <= 0 <= b[1]:
                return None  # divisor range crosses zero
            cands = [a[0] // b[0], a[0] // b[1], a[1] // b[0], a[1] // b[1]]
            return (min(cands), max(cands))
        return None

    def safe(n):
        if rides_lanes(n):
            return True
        if isinstance(n, (BinaryOp, Function)):
            try:
                dt_ = n.to_field(schema).dtype
            except (ValueError, KeyError):
                return False
            if dt_ in risky_dts:
                bd = bounds(n)
                if bd is None or bd[0] < _INT32_LO or bd[1] > _INT32_HI:
                    return False
        return all(safe(c) for c in n.children())

    try:
        return all(safe(n) for n in nodes)
    finally:
        # bounds and safe name themselves: cycles that reach env through
        # col_range and would hold this attempt's device arrays until the
        # cyclic collector next ran
        bounds = safe = None


def _stage_and_run(table, exprs, stage_cache: Optional[dict]):
    """Shared device prologue: normalize + eligibility-check the expressions,
    stage the input columns, compile and launch ONE jitted program. Returns
    (outs, out_dts, nodes, dcs) with `outs` still on device (async), or None
    when ineligible. Used by the projection and sort paths."""

    schema = table.schema
    n = len(table)
    if n == 0:
        return None
    nodes = normalize_and_check(exprs, schema)
    if nodes is None:
        return None
    # epoch-compare subtrees are consumed through host-evaluated lane
    # pairs, never staged normally (their dtypes cannot narrow to int32)
    epoch_cmps = epoch_cmps_for(nodes, schema)
    needed = device_required_columns(nodes, schema)
    if not needed and not epoch_cmps:
        return None
    b = size_bucket(n)
    staged = stage_table_columns(table, needed, b, stage_cache)
    if staged is None:
        return None
    env, dcs = staged
    if not int64_wrap_safe(nodes, schema, env, stage_cache, b):
        return None
    env = string_literal_env(nodes, schema, dcs, env)
    if env is None:
        return None
    env = epoch_cmp_env(epoch_cmps, schema, table, b, stage_cache, env)
    if env is None:
        return None
    env = string_lut_env(nodes, schema, dcs, env)
    if env is None:
        return None
    aux: dict = {}
    env = string_joint_env(nodes, schema, dcs, env, aux)
    if env is None:
        return None
    env = string_transform_env(nodes, schema, table, b, stage_cache, env, aux)
    if env is None:
        return None
    env = transform_cmp_env(nodes, schema, table, b, stage_cache, dcs, env,
                            aux)
    if env is None:
        return None
    with timeline.part("dispatch.lookup", "dispatch_lookup_ns"):
        run, out_dts = compile_projection(nodes, schema,
                                          tuple(sorted(needed)))
    with timeline.part("dispatch.call", "dispatch_call_ns"):
        outs = run(env)
    return outs, out_dts, nodes, dcs, aux


def eval_projection_device_async(table, exprs, stage_cache: Optional[dict] = None):
    """Dispatch a device projection WITHOUT blocking: staging and the jitted
    compute launch happen now (jax dispatch is asynchronous); the returned
    zero-arg resolver materializes the host Table (``fetch``) when called.
    This is what lets the executor double-buffer — stage morsel i+1 while the
    device still computes morsel i (reference role: the pipelined channel
    hand-off of daft-local-execution intermediate_op.rs:71+).
    Returns None if ineligible."""
    from ..schema import Field, Schema
    from ..table import Table

    n = len(table)
    staged = _stage_and_run(table, exprs, stage_cache)
    if staged is None:
        return None
    outs, out_dts, nodes, dcs, aux = staged  # async: device computes from here
    schema = table.schema

    def resolve():
        cols = []
        fields = []
        for e, nd, (v, m), dt in zip(exprs, nodes, outs, out_dts):
            dictionary = None
            if dt.is_string():
                # string outputs are bare column passthroughs OR joint-coded
                # fill_null/if_else results (enforced by the compilability
                # check): decode with the matching dictionary
                dictionary = string_output_dictionary(nd, schema, dcs, aux)
                if dictionary is None:
                    raise RuntimeError(
                        f"string projection {e.name()!r} lost its dictionary")
            dc = DeviceColumn(v, m, n, dt, dictionary=dictionary)
            s = unstage(dc).rename(e.name())
            cols.append(s)
            fields.append(Field(e.name(), s.dtype))
        return Table(Schema(fields), cols)

    return resolve


# ---------------------------------------------------------------------------
# Segment aggregation (grouped agg on device)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_segments", "kind"))
def _segment_agg(values, valid, codes, num_segments: int, kind: str):
    count_dt = jnp.int64 if x64_enabled() else jnp.int32
    v64 = values
    if kind == "sum":
        contrib = jnp.where(valid, v64, jnp.zeros_like(v64))
        return jax.ops.segment_sum(contrib, codes, num_segments)
    if kind == "count":
        return jax.ops.segment_sum(valid.astype(count_dt), codes, num_segments)
    if kind == "min":
        big = _type_max(v64.dtype)
        contrib = jnp.where(valid, v64, jnp.full_like(v64, big))
        return jax.ops.segment_min(contrib, codes, num_segments)
    if kind == "max":
        small = _type_min(v64.dtype)
        contrib = jnp.where(valid, v64, jnp.full_like(v64, small))
        return jax.ops.segment_max(contrib, codes, num_segments)
    raise ValueError(kind)


def _type_max(dt):
    if jnp.issubdtype(dt, jnp.floating):
        return jnp.inf
    return jnp.iinfo(dt).max


def _type_min(dt):
    if jnp.issubdtype(dt, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dt).min


def segment_aggregate(values: jax.Array, valid: jax.Array, codes: jax.Array,
                      num_segments: int, kind: str) -> Tuple[jax.Array, jax.Array]:
    """Masked segment aggregation; returns (per-group values, per-group valid)."""
    out = _segment_agg(values, valid, codes, num_segments, kind)
    if kind == "count":
        return out, jnp.ones(num_segments, dtype=bool)
    counts = _segment_agg(valid, valid, codes, num_segments, "count")
    return out, counts > 0


# Up to this many segments, the one-hot compare-reduce formulation beats the
# scatter-based segment_sum by ~1000x on TPU (measured on v5e: the compare,
# mask and reduction fuse into one HBM-bandwidth pass; XLA's scatter path does
# not). Beyond it: scatter for counts, integer sums, min and max, and the
# sorted-segment form (``_sorted_segment_sum``) for float sums.
_ONEHOT_MAX_SEGMENTS = 4096
_REDUCE_CHUNK = 8192

# Up to this many segments every reduction takes the dense form
# (``_dense_reduce``): the largest bucket at which it beat both the one-hot
# forms and the pallas kernel over 64M rows and tied them over a 128k-row
# morsel, compile time included (tools/segment_sum_sweep.py on a v5e; the
# table is in PERF.md). At 64 it loses to the one-hot form by a factor of two.
DENSE_MAX_SEGMENTS = 32
_LANES = 128
# sublane rows a dense chunk: each lane adds this many floats in sequence
# before the pairwise combine, so a float32 partial stays short
_DENSE_ROWS = 1024


def segment_reduce(values: jax.Array, valid: jax.Array, codes: jax.Array,
                   num_segments: int, kind: str) -> Tuple[jax.Array, jax.Array]:
    """TPU-tuned masked segment reduction -> (per-group values, per-group valid).

    Small buckets (``DENSE_MAX_SEGMENTS``): one masked reduction a group with
    the rows on the lane axis, float sums combined pairwise across chunks.
    Low-cardinality strategy above that: chunked one-hot compare-reduce with a
    Kahan-compensated cross-chunk combine for float sums (accumulation error
    stays at the float32 representation floor, ~5e-8 relative, instead of
    growing with rows — required for TPC-H money-sum parity in 32-bit mode).
    High-cardinality strategy (over ``_ONEHOT_MAX_SEGMENTS``): scatter
    segment ops, and for float sums the sorted-segment form
    (``_sorted_segment_sum``): time and memory grow with rows + segments and
    the error with the logarithm of a group's rows. ``codes`` lie in
    ``[0, num_segments)``."""
    if kind == "count":
        cnt = _segment_count(valid, codes, num_segments)
        return cnt, jnp.ones(num_segments, dtype=bool)
    if values.ndim == 1 and _dense_rows(codes.shape[0], num_segments):
        out = _dense_reduce(values, valid, codes, num_segments, kind)
    elif num_segments <= _ONEHOT_MAX_SEGMENTS and values.ndim == 1:
        out = _onehot_reduce(values, valid, codes, num_segments, kind)
    elif kind == "sum" and jnp.issubdtype(values.dtype, jnp.floating) and values.ndim == 1:
        out = _sorted_segment_sum(jnp.where(valid, values, 0), codes, num_segments)
    else:
        out = _segment_agg(values, valid, codes, num_segments, kind)
    counts = _segment_count(valid, codes, num_segments)
    return out, counts > 0


def _count_dtype():
    return jnp.int64 if x64_enabled() else jnp.int32


def _segment_count(valid, codes, num_segments):
    if _dense_rows(codes.shape[0], num_segments):
        return _dense_reduce(valid, valid, codes, num_segments, "count")
    if num_segments <= _ONEHOT_MAX_SEGMENTS:
        b = valid.shape[0]
        chunk = min(_REDUCE_CHUNK, b)
        nch = b // chunk
        sel = (codes.reshape(nch, chunk, 1)
               == jnp.arange(num_segments, dtype=codes.dtype)) \
            & valid.reshape(nch, chunk, 1)
        return jnp.sum(jnp.sum(sel, axis=1, dtype=_count_dtype()), axis=0)
    return jax.ops.segment_sum(valid.astype(_count_dtype()), codes, num_segments)


def _dense_rows(b: int, num_segments: int) -> int:
    """Sublane rows of one dense chunk over ``b`` rows, or 0 where the dense
    form does not apply: the bucket is over ``DENSE_MAX_SEGMENTS`` or ``b``
    does not split into whole (rows, 128) chunks (every size bucket does)."""
    if num_segments > DENSE_MAX_SEGMENTS or b % _LANES:
        return 0
    rows = min(_DENSE_ROWS, b // _LANES)
    return rows if b % (rows * _LANES) == 0 else 0


def _pairwise_sum(p):
    """Sum over the last axis (a power of two long) as a balanced tree:
    float32 error grows with the tree's depth, not with its width."""
    while p.shape[-1] > 1:
        half = p.shape[-1] // 2
        p = p[..., :half] + p[..., half:]
    return p[..., 0]


def _dense_hits(valid, codes, num_segments):
    """bool (groups, chunks, rows, 128): the row is valid and of the group.
    The columns are viewed in place, the rows on the lane axis. The group
    axis is what keeps the compiler from moving the reshape below the
    ``where``: a lone group is best given a bucket of two (with the axis
    gone the reduction is left unfused: 9.4 against 4.6 ms over 64M rows
    and seven columns, tools/segment_sum_sweep.py on a v5e)."""
    b = codes.shape[0]
    rows = _dense_rows(b, num_segments)
    shape = (1, b // (rows * _LANES), rows, _LANES)
    groups = jnp.arange(num_segments, dtype=codes.dtype)
    return valid.reshape(shape) & (
        codes.reshape(shape) == groups.reshape(-1, 1, 1, 1))


def _dense_reduce(values, valid, codes, num_segments, kind):
    """One masked reduction a group: each group's ``where`` reduces over the
    rows of a chunk, and the (group, chunk, lane) partials combine at the
    end, float sums pairwise. The compare, the mask and whatever elementwise
    work produced ``values`` fuse into the reduction, and sibling
    reductions over the same rows share one pass: nothing is stacked,
    padded or multiplied by a one-hot matrix."""
    hit = _dense_hits(valid, codes, num_segments)
    if kind == "count":
        part = jnp.sum(hit, axis=2, dtype=_count_dtype())
        return jnp.sum(part.reshape(num_segments, -1), axis=1)
    v = values.reshape((1,) + hit.shape[1:])
    if kind == "sum":
        part = jnp.sum(jnp.where(hit, v, jnp.zeros_like(v)), axis=2)
        part = part.reshape(num_segments, -1)
        if jnp.issubdtype(values.dtype, jnp.floating):
            return _pairwise_sum(part)
        return jnp.sum(part, axis=1)
    if kind not in ("min", "max"):
        raise ValueError(kind)
    ident, reduce_ = ((_type_max, jnp.min) if kind == "min"
                      else (_type_min, jnp.max))
    part = reduce_(jnp.where(hit, v, jnp.full_like(v, ident(values.dtype))),
                   axis=2)
    return reduce_(part.reshape(num_segments, -1), axis=1)


def segment_first_index(valid: jax.Array, codes: jax.Array,
                        num_segments: int) -> jax.Array:
    """int32 per segment: the least index of its valid rows (int32's
    maximum where it has none)."""
    b = codes.shape[0]
    if not _dense_rows(b, num_segments):
        idx = jnp.arange(b, dtype=jnp.int32)
        return segment_reduce(idx, valid, codes, num_segments, "min")[0]
    hit = _dense_hits(valid, codes, num_segments)
    # the index is built in the view's shape: an iota reshaped from one
    # dimension is written out and read back
    chunk, row, lane = (jax.lax.broadcasted_iota(jnp.int32, hit.shape, d)
                        for d in (1, 2, 3))
    idx = (chunk * hit.shape[2] + row) * _LANES + lane
    part = jnp.min(jnp.where(hit, idx, _type_max(jnp.int32)), axis=2)
    return jnp.min(part.reshape(num_segments, -1), axis=1)


def _kahan_combine(partials):
    """Compensated sum over the leading (chunk) axis."""
    def step(carry, p):
        s, comp = carry
        y = p - comp
        t = s + y
        return (t, (t - s) - y), None

    zero = jnp.zeros(partials.shape[1:], partials.dtype)
    (s, _), _ = jax.lax.scan(step, (zero, zero), partials)
    return s


def _onehot_reduce(values, valid, codes, num_segments, kind):
    b = values.shape[0]
    chunk = min(_REDUCE_CHUNK, b)
    nch = b // chunk
    vc = values.reshape(nch, chunk, 1)
    sel = (codes.reshape(nch, chunk, 1)
           == jnp.arange(num_segments, dtype=codes.dtype)) \
        & valid.reshape(nch, chunk, 1)
    if kind == "sum":
        partials = jnp.sum(jnp.where(sel, vc, jnp.zeros_like(vc)), axis=1)
        if jnp.issubdtype(values.dtype, jnp.floating):
            return _kahan_combine(partials)
        return jnp.sum(partials, axis=0)
    if kind == "min":
        ident = _type_max(values.dtype)
        part = jnp.min(jnp.where(sel, vc, jnp.full_like(vc, ident)), axis=1)
        return jnp.min(part, axis=0)
    if kind == "max":
        ident = _type_min(values.dtype)
        part = jnp.max(jnp.where(sel, vc, jnp.full_like(vc, ident)), axis=1)
        return jnp.max(part, axis=0)
    raise ValueError(kind)


def _sorted_segment_sum(values, codes, num_segments):
    """Float sums of pre-masked ``values`` by segment for a bucket too wide
    for a one-hot block: sort the (code, value) pairs by code, scan each run
    of equal codes by doubling (lane i adds lane i - d where both hold one
    code, d = 1, 2, 4, ... until no lane adds), and write each run's last
    lane to its code. One sort, a round for each doubling of the longest
    group and one scatter: rows + segments in time and memory, where a row
    of partials a chunk of rows took rows / 8192 x segments (8 GiB for
    TPC-H Q18's 1.5M order keys at SF1). Every lane's sum is a tree over its
    group, so the float32 error grows with the logarithm of a group's rows.
    XLA's own cumulative sums compile erratically here and a prefix sum
    differenced at the run ends would lose the precision."""
    b = values.shape[0]
    sc, sv = jax.lax.sort((codes, values), num_keys=1, is_stable=False)

    def back(x, d, fill):
        # x[i - d], ``fill`` before the first lane; the pad fuses into the
        # slice's consumer and is never written out
        padded = jnp.concatenate([jnp.full((b,), fill, x.dtype), x])
        return jax.lax.dynamic_slice(padded, (b - d,), (b,))

    def double(state):
        d, s, _ = state
        same = back(sc, d, -1) == sc
        return d * 2, s + jnp.where(same, back(s, d, 0), 0), jnp.any(same)

    _, s, _ = jax.lax.while_loop(lambda state: state[2], double,
                                 (jnp.int32(1), sv, jnp.bool_(True)))
    last = jnp.concatenate([sc[1:] != sc[:-1], jnp.ones((1,), bool)])
    return jnp.zeros((num_segments,), values.dtype).at[
        jnp.where(last, sc, num_segments)].set(s, mode="drop")


# ---------------------------------------------------------------------------
# Device sort (jax.lax.sort on bit-transformed keys)
# ---------------------------------------------------------------------------

def _sortable_bits(values: jax.Array, valid: jax.Array, descending: bool,
                   nulls_first: bool) -> List[jax.Array]:
    """Map (values, valid) to one or two uint32 key lanes whose lexicographic
    unsigned order equals the requested total order (nulls at extremes; NaN
    above every number, matching arrow).

    Works in both x64 and 32-bit-only (real TPU) modes: 64-bit inputs (only
    present under x64) are split into hi/lo uint32 lanes.
    """
    v = values
    width64 = v.dtype.itemsize == 8
    if jnp.issubdtype(v.dtype, jnp.bool_):
        bits = v.astype(jnp.uint32)
    elif jnp.issubdtype(v.dtype, jnp.unsignedinteger):
        bits = v if width64 else v.astype(jnp.uint32)
    elif jnp.issubdtype(v.dtype, jnp.signedinteger):
        if width64:
            bits = jax.lax.bitcast_convert_type(v.astype(jnp.int64), jnp.uint64) ^ jnp.uint64(1 << 63)
        else:
            bits = jax.lax.bitcast_convert_type(v.astype(jnp.int32), jnp.uint32) ^ jnp.uint32(1 << 31)
    else:
        # canonicalize every NaN to the POSITIVE quiet NaN: its bit pattern
        # sits strictly above +inf, so NaN sorts after all numbers ascending
        # (and first descending) — exactly arrow's NaN-greatest order. The
        # old inf-substitution made NaN TIE with real +inf.
        if width64:
            f = jnp.where(jnp.isnan(v), jnp.asarray(jnp.nan, v.dtype), v)
            f = jnp.where(f == 0.0, jnp.zeros_like(f), f)  # -0.0 ties +0.0
            b = jax.lax.bitcast_convert_type(f, jnp.int64)
            bits = jnp.where(b < 0, jax.lax.bitcast_convert_type(~b, jnp.uint64),
                             jax.lax.bitcast_convert_type(b, jnp.uint64) ^ jnp.uint64(1 << 63))
        else:
            v32 = v.astype(jnp.float32)
            f = jnp.where(jnp.isnan(v32), jnp.asarray(jnp.nan, jnp.float32), v32)
            f = jnp.where(f == 0.0, jnp.zeros_like(f), f)  # -0.0 ties +0.0
            b = jax.lax.bitcast_convert_type(f, jnp.int32)
            bits = jnp.where(b < 0, jax.lax.bitcast_convert_type(~b, jnp.uint32),
                             jax.lax.bitcast_convert_type(b, jnp.uint32) ^ jnp.uint32(1 << 31))
    if descending:
        bits = ~bits
    if bits.dtype == jnp.uint64:
        hi = (bits >> jnp.uint64(32)).astype(jnp.uint32)
        lo = (bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
        lanes = [hi, lo]
    else:
        lanes = [bits]
    # null handling: prepend a selector lane (0=null-first, 1=value, 2=null-last)
    null_sel = jnp.where(valid, jnp.uint32(1), jnp.uint32(0 if nulls_first else 2))
    return [null_sel] + [jnp.where(valid, l, jnp.uint32(0)) for l in lanes]


def _stage_f64_sort_lanes(table, node, bucket: int,
                          stage_cache: Optional[dict]):
    """EXACT float64 sort key in 32-bit mode: the order-preserving bit
    transform (sign-magnitude -> total order, canonical NaN above +inf)
    applied to the full 64-bit pattern ON HOST, then split into (hi, lo)
    uint32 lanes the device sort consumes as two consecutive keys. No
    precision is lost — this removes the Q1-style money-sort fallback.

    `node` may be ANY f64-typed expression, not just a plain Column (r4
    verdict item 6): the host evaluates the derived key ONCE in exact
    float64 (e.g. Q1's price*(1-discount)), the lanes split from that, and
    the sort itself stays on device. Cached with the partition under the
    expression key."""
    node = _peel_alias(node)
    # UDF-containing keys never cache: a UDF may be non-deterministic and
    # its _key uses id(fn), which CPython can reuse after GC — a stale hit
    # would silently mis-sort (same rule as Expression._memoizable)
    cacheable = stage_cache is not None and node._memoizable()
    key = ("__f64lanes__", node._key(), bucket)
    cached = stage_cache.get(key) if cacheable else None
    if cached is not None:
        return cached
    s = _eval_lane_series(table, node)
    if s is None:
        return None
    n = len(s)
    arr = s.to_arrow()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    vals = np.asarray(pc.fill_null(arr, 0.0), dtype=np.float64)
    # canonical positive quiet NaN: bit pattern above +inf -> NaN-greatest,
    # matching _sortable_bits and arrow; -0.0 canonicalizes to +0.0 (arrow
    # ties signed zeros under the stable sort — distinct bit patterns would
    # order them and break the tiebreak parity)
    vals = np.where(np.isnan(vals), np.float64("nan"), vals)
    vals = np.where(vals == 0.0, np.float64(0.0), vals)
    bits = vals.view(np.uint64)
    flipped = np.where((bits >> np.uint64(63)) == 1, ~bits,
                       bits ^ np.uint64(1 << 63))
    if bucket > n:
        flipped = np.concatenate([flipped,
                                  np.zeros(bucket - n, dtype=np.uint64)])
    hi = (flipped >> np.uint64(32)).astype(np.uint32)
    lo = (flipped & np.uint64(0xFFFFFFFF)).astype(np.uint32)
    out = (jnp.asarray(hi), jnp.asarray(lo),
           jnp.asarray(_staged_validity(arr, n, bucket)))
    if cacheable:
        stage_cache[key] = out
    return out


def device_table_argsort(table, sort_keys, descending=None, nulls_first=None,
                         stage_cache: Optional[dict] = None):
    """Argsort indices for a Table computed ON DEVICE (keys staged/compiled
    like projections, then one `jax.lax.sort` over the bit-transformed
    lanes). Matches Table.argsort's ordering exactly, including the
    nulls-follow-direction default. Returns np.ndarray[int] or None when any
    key is device-ineligible."""
    from ..datatypes import DataType
    from ..table import _norm_flag

    n = len(table)
    if n == 0:
        return None
    keys = list(sort_keys)
    k = len(keys)
    desc = _norm_flag(descending, k, False)
    nf = _norm_flag(nulls_first, k, None)
    f64_lane_keys: Dict[int, Tuple[str, Any]] = {}
    if not x64_enabled():
        # float64 keys must not sort in float32 (spurious ties reorder rows
        # vs the host), and epoch keys cannot narrow to int32 at all. ANY
        # f64/epoch-typed key — plain column OR computed expression (Q1's
        # price*(1-discount) money sorts) — evaluates once on host in exact
        # 64-bit and sorts on device via host-split (hi, lo) lanes.
        from ..expressions import normalize_literals

        try:
            pre = [normalize_literals(e._node, table.schema) for e in keys]
        except (ValueError, KeyError):
            return None
        for i, nd in enumerate(pre):
            try:
                dt_ = nd.to_field(table.schema).dtype
            except (ValueError, KeyError):
                return None
            if dt_ == DataType.float64():
                f64_lane_keys[i] = ("f64", nd)
            elif dt_.kind in _EPOCH_KINDS:
                f64_lane_keys[i] = ("epoch", nd)
            # other keys are vetted by _stage_and_run below — checking
            # compilability here too would walk every tree twice per sort
    entries: List = [None] * k
    b = size_bucket(n)
    # lane keys stage FIRST (cheap host work that can decline) so a decline
    # never wastes the device staging/compile of the other keys
    for i, (kind, nd) in f64_lane_keys.items():
        entry = (_stage_f64_sort_lanes(table, nd, b, stage_cache)
                 if kind == "f64"
                 else _stage_epoch_expr_lanes(table, nd, b, stage_cache))
        if entry is None:
            return None
        entries[i] = entry
    non_lane = [(i, e) for i, e in enumerate(keys) if i not in f64_lane_keys]
    if non_lane:
        staged = _stage_and_run(table, [e for _, e in non_lane], stage_cache)
        if staged is None:
            return None
        outs = staged[0]
        for (i, _), vm in zip(non_lane, outs):
            entries[i] = vm
    nf_resolved = [(f if f is not None else d) for f, d in zip(nf, desc)]
    idx = device_argsort(entries, desc, nf_resolved, n)
    return np.asarray(fetch(idx))[:n]


def device_argsort(key_cols: Sequence[Tuple],
                   descending: Sequence[bool], nulls_first: Sequence[bool],
                   length: int) -> jax.Array:
    """Stable multi-key argsort on device; padding rows sort to the very end.
    Each key is (values, valid) — bit-transformed by _sortable_bits — or an
    exact pre-split (hi_u32, lo_u32, valid) lane triple (64-bit keys staged
    in 32-bit mode)."""
    b = key_cols[0][0].shape[0]
    operands: List[jax.Array] = []
    inbounds = jnp.arange(b) < length
    pad_sel = jnp.where(inbounds, jnp.uint32(0), jnp.uint32(1))
    operands.append(pad_sel)  # padding rows after all real rows
    for entry, d, nf in zip(key_cols, descending, nulls_first):
        if len(entry) == 3:
            hi, lo, m = entry
            # bitwise-not of the 64-bit pattern distributes across the split
            lanes_ = [~hi, ~lo] if d else [hi, lo]
            null_sel = jnp.where(m, jnp.uint32(1),
                                 jnp.uint32(0 if nf else 2))
            ops = [null_sel] + [jnp.where(m, l, jnp.uint32(0))
                                for l in lanes_]
        else:
            v, m = entry
            ops = _sortable_bits(v, m, d, nf)
        for lane in ops:
            operands.append(jnp.where(inbounds, lane, jnp.uint32(0)))
    idx = jnp.arange(b, dtype=jnp.int32)
    out = jax.lax.sort(tuple(operands) + (idx,), num_keys=len(operands), is_stable=True)
    return out[-1]


# ---------------------------------------------------------------------------
# Device hash (for shuffle bucketing; 2x32-bit lanes, TPU-friendly)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("num_buckets",))
def hash_buckets(columns: Tuple[jax.Array, ...], valids: Tuple[jax.Array, ...],
                 num_buckets: int) -> jax.Array:
    """Combine column hashes -> bucket id per row (murmur-style 32-bit mixing)."""
    h = jnp.zeros(columns[0].shape[0], dtype=jnp.uint32)
    for v, m in zip(columns, valids):
        hv = _hash32(v)
        hv = jnp.where(m, hv, jnp.uint32(0x9E3779B9))
        h = _mix32(h ^ hv)
    return (h % jnp.uint32(num_buckets)).astype(jnp.int32)


def _hash32(v: jax.Array) -> jax.Array:
    if jnp.issubdtype(v.dtype, jnp.floating):
        f = v.astype(jnp.float32)
        f = jnp.where(f == 0.0, jnp.zeros_like(f), f)  # -0.0 == 0.0
        x = jax.lax.bitcast_convert_type(f, jnp.uint32)
    elif v.dtype == jnp.bool_:
        x = v.astype(jnp.uint32)
    elif v.dtype.itemsize == 8:
        x64 = v.astype(jnp.int64)
        lo = (x64 & 0xFFFFFFFF).astype(jnp.uint32)
        hi = ((x64 >> 32) & 0xFFFFFFFF).astype(jnp.uint32)
        x = _mix32(lo) ^ hi
    else:
        x = v.astype(jnp.int32).astype(jnp.uint32)
    return _mix32(x)


def _mix32(x: jax.Array) -> jax.Array:
    x = x.astype(jnp.uint32)
    x = (x ^ (x >> jnp.uint32(16))) * jnp.uint32(0x85EBCA6B)
    x = (x ^ (x >> jnp.uint32(13))) * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> jnp.uint32(16))
