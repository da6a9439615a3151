"""Device-side hash-join probe.

Semantic spec: the reference's probe table
(/root/reference/src/daft-table/src/probe_table/mod.rs:14-28 — build one
side, stream the other, null keys never match) and hash_join
(ops/joins/hash_join.rs). The TPU formulation avoids a hash table entirely:
no data-dependent control flow fits XLA, so the build side is SORTED once
(cached with the partition, like column staging) and every probe row makes
ONE lower-bound search over it, with static shapes: its first
PROBE_COMPARE_LEVELS levels are compares against pivots at static positions
of the sorted keys (no gather), the remaining levels one gather each
(_lower_bound). Where the row's run of equal keys ends is read from the
build (one segmented scan of its lanes, _left_in_run), not searched for a
second time (_match_ranges).

Scope: 1-4 keys — integer/date values, and plain STRING columns via
joint-dictionary recoding (_stage_key_pair) — with multi-column keys packed
into one surrogate lane via exact mixed-radix packing. An overflowing
composite key space or other key shapes (computed strings, floats) fall
back to the host acero join. Probe direction adapts:

- build = RIGHT side (right keys unique): inner/left/semi/anti with probe
  over the left rows — output already in host order (left idx, right idx).
- build = LEFT side (left keys unique): inner — output re-sorted stably by
  left idx to match the host join's deterministic order.
- duplicate keys on BOTH sides (N:M): the RANGE probe computes each probe
  row's span of matches over the sorted build keys on device
  (_range_probe_kernel); the data-dependent expansion to (lidx, ridx)
  pairs happens on host (_range_join, side tag "expanded").

The PK probe returns per-probe-row (hit, build_row_idx); the host assembles
output columns with vectorized takes (strings and other host-only payload
never stage)."""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..profile import timeline
from .device import fetch, is_device_dtype, size_bucket, stage_table_columns


# The first levels of a probe's binary search are resolved by COMPARES against
# pivots at static positions of the sorted build keys, the rest by gathers. On
# a v5e a gather of every probe lane costs 7.5 ns a lane whatever the table's
# size, a compare against one more pivot 0.6 ps a lane, so level l (2**l more
# pivots) is cheaper compared up to l = 13: fixed by tools/join_probe_sweep.py
# (PERF.md, PR 35). A build of at most 2**L lanes is searched with no gather.
PROBE_COMPARE_LEVELS = 14
# pivots compared in one pass over the probe lanes: a pass costs about 25 us
# of its own on a v5e and reads and writes the lanes once, so more is faster
# there; where the compare is not fused into its reduction (XLA's CPU
# backend) a pass holds this many int32 a lane
_PIVOT_CHUNK = 64
# lanes a row of _left_in_run's scan
_SCAN_ROW = 1024


def probe_search_levels(b: int) -> Tuple[int, int]:
    """(compare levels, gather levels) of the search over a build of ``b``
    lanes: static, from the build's size bucket alone."""
    n = max(b - 1, 0).bit_length()
    c = min(PROBE_COMPARE_LEVELS, n)
    return c, n - c


def _count_below(pivots, v):
    """Per lane of ``v``, how many of the sorted ``pivots`` are < v:
    ``_PIVOT_CHUNK`` pivots a pass over the lanes, each pass one
    compare-and-reduce with the pivots on the major axis (a row a pivot,
    accumulated lane by lane). The v5e compiler fuses the compare into the
    reduction and holds nothing beyond the lanes' counts; no backend holds
    more than lanes x ``_PIVOT_CHUNK``, never lanes x pivots. (A Python
    loop of scalar compares costs the v5e compiler 32 KB of temporaries a
    pivot.)"""
    def count(chunk, c):
        return c + jnp.sum(chunk[:, None] < v[None, :], axis=0,
                           dtype=jnp.int32)

    zero = jnp.zeros(v.shape, jnp.int32)
    n = pivots.shape[0]
    if n <= _PIVOT_CHUNK:
        return count(pivots, zero)
    pad = -n % _PIVOT_CHUNK
    if pad:  # a pivot of iinfo.max is below no key
        pivots = jnp.concatenate(
            [pivots, jnp.full(pad, jnp.iinfo(pivots.dtype).max, pivots.dtype)])
    return jax.lax.fori_loop(
        0, (n + pad) // _PIVOT_CHUNK,
        lambda i, c: count(jax.lax.dynamic_slice(
            pivots, (i * _PIVOT_CHUNK,), (_PIVOT_CHUNK,)), c),
        zero)


def _lower_bound(sk, v):
    """``searchsorted(sk, v, side="left")`` as int32: the first
    ``probe_search_levels`` levels pick each lane's block of ``stride`` build
    lanes by counting the block-end pivots below it, the remaining levels
    halve the block with one gather each. Lanes past the end of ``sk`` read
    as +infinity, so any build size is searched exactly."""
    b = sk.shape[0]
    _, gather_levels = probe_search_levels(b)
    stride = 1 << gather_levels
    # the last lane of every whole block (none, if one block holds sk)
    pivots = jax.lax.slice(sk, (min(stride - 1, b),), (b,), (stride,))
    pos = _count_below(pivots, v) * stride
    if not gather_levels:
        return pos

    def halve(_, carry):
        pos, step = carry
        step = step // 2
        idx = pos + (step - 1)
        below = (idx < b) & (sk[jnp.minimum(idx, b - 1)] < v)
        return pos + jnp.where(below, step, 0), step

    # a loop, not gather_levels unrolled steps: unrolled, the v5e compiler
    # keeps three arrays of P lanes live where the loop's carry is one
    return jax.lax.fori_loop(0, gather_levels, halve,
                             (pos, jnp.int32(stride)))[0]


def _left_in_run(count, last):
    """Per lane, the sum of ``count`` (>= 0) from the lane to the end of its
    run (``last`` marks each run's final lane): ONE segmented scan from the
    far end, by doubling inside rows of ``_SCAN_ROW`` lanes (each step a
    lane whose run is still open takes in twice as many lanes), then over
    what the rows' first lanes came to. A lane's sum and whether its run has
    closed travel in one int32 (``~sum`` once closed), so a step shifts one
    array. Plain elementwise work that compiles in a second at any size:
    the v5e compiler takes 10-75 s over ONE cumulative window
    (``jnp.cumsum``, ``lax.cummin``) of 256k to 2M lanes, and a program's
    code is held in HBM, a megabyte a scan of 8M lanes."""
    n = count.shape[0]
    width = min(n, _SCAN_ROW)
    # lanes past the end close every run and add nothing (~0)
    acc = jnp.concatenate([jnp.where(last, ~count, count),
                           jnp.full(-n % width, -1, count.dtype)])

    def take_in(acc, nearer):
        # an open lane adds what ``nearer`` came to, and closes if it has
        total = acc + jnp.where(nearer < 0, ~nearer, nearer)
        return jnp.where(acc < 0, acc, jnp.where(nearer < 0, ~total, total))

    acc = acc.reshape(-1, width)
    reach = 1
    while reach < width:
        acc = take_in(acc, jnp.pad(acc[:, reach:], ((0, 0), (0, reach))))
        reach *= 2
    if acc.shape[0] > 1:
        # the rows after this one, for the runs still open at its end
        first = acc[:, 0]
        after = jnp.concatenate([
            _left_in_run(jnp.where(first < 0, ~first, first), first < 0)[1:],
            jnp.zeros(1, count.dtype)])
        acc = take_in(acc, after[:, None])
    acc = acc.reshape(-1)[:n]
    return jnp.where(acc < 0, ~acc, acc)


def _match_ranges(sk, sorted_valid, probe_vals, probe_valid):
    """(lo, counts) of each probe row over the SORTED build keys ``sk`` (valid
    lanes first within a run of equal keys): ``lo`` its lower bound, searched
    for once; ``counts`` the valid lanes of the run of its own key starting
    there. Where a run ends is a property of the build, so the valid lanes
    left in its run from each build lane on are counted over the B build
    lanes (_left_in_run), and a probe row reads them where the key at its
    lower bound equals its own."""
    b = sk.shape[0]
    run_ends = jnp.concatenate([sk[1:] != sk[:-1], jnp.ones(1, bool)])
    run_valid = _left_in_run(sorted_valid.astype(jnp.int32), run_ends)
    lo = _lower_bound(sk, probe_vals)
    # lo == B reads the last build key, which is then below the probe's
    at = jnp.minimum(lo, b - 1)
    counts = jnp.where(probe_valid & (sk[at] == probe_vals), run_valid[at], 0)
    return lo, counts


@functools.partial(jax.jit, static_argnames=())
def _range_probe_kernel(build_vals, build_valid, probe_vals, probe_valid):
    """Per-probe-row match RANGE over the sorted build keys: (lo [P], counts
    [P], perm [B], dup). The ONE sort serves both probe flavors — when dup
    (duplicate valid build keys) is False every count is <= 1, so the PK
    outputs are hit = counts > 0 and build row perm[lo] (_pk_outputs);
    otherwise the match set of probe row i is perm[lo[i] : lo[i]+counts[i]],
    valid lanes only, expanded on host.

    Valid lanes sort before null/padding lanes within an equal-key run
    (lexsort secondary key), so each run's valid matches are a contiguous
    prefix, counted by _match_ranges. The variable-size expansion happens
    on the HOST (data-dependent shapes cannot live under XLA): reference
    semantic is the multi-row probe of
    src/daft-table/src/probe_table/mod.rs."""
    big = jnp.iinfo(build_vals.dtype).max
    k = jnp.where(build_valid, build_vals, big)
    # lexsort((~build_valid, k)) is this sort's last output; its first two
    # are k[perm] and ~build_valid[perm], which cost a gather of B lanes each
    sk, sorted_null, perm = jax.lax.sort(
        (k, ~build_valid, jnp.arange(k.shape[0], dtype=jnp.int32)), num_keys=2)
    sorted_valid = ~sorted_null
    dup = jnp.any((sk[1:] == sk[:-1]) & sorted_valid[1:] & sorted_valid[:-1])
    lo, counts = _match_ranges(sk, sorted_valid, probe_vals, probe_valid)
    return lo, counts, perm, dup


@functools.partial(jax.jit, static_argnames=())
def _pk_outputs(lo, counts, perm):
    """PK-build view of the range probe (dup == False): per-probe-row
    (hit, build_row_idx), computed on device so the host fetches the same
    two probe-sized arrays the dedicated PK kernel used to produce."""
    b = perm.shape[0]
    return counts > 0, perm[jnp.minimum(lo, b - 1)]


def _range_join(lo_d, counts_d, perm_d, ln: int, how: str):
    """N:M join (duplicate build keys): vectorized host expansion of the
    device range probe. Returns the executor contract — ("right_build",
    hit, _) for semi/anti (only the hit mask is consumed), or ("expanded",
    lidx, ridx) index pairs for inner/left (ridx == -1 marks a left-outer
    miss).

    Order contract: rows come out left-row-major with matches in
    sorted-build-key (perm) order — which differs from the acero host
    join's order. That is fine: join output order is UNSPECIFIED
    engine-wide (see Table.hash_join), so a query flipping between device
    and host paths may legitimately reorder rows; only the multiset is
    guaranteed."""
    lo, counts, perm = fetch((lo_d, counts_d, perm_d))
    lo = np.asarray(lo)[:ln].astype(np.int64)
    counts = np.asarray(counts)[:ln].astype(np.int64)
    perm = np.asarray(perm).astype(np.int64)
    # the expansion is the join's own host work, not copying back: its
    # time stays the operator's self time (``join.expand`` when armed)
    with timeline.timed("join.expand"):
        return _expand_ranges(lo, counts, perm, ln, how)


def _expand_ranges(lo, counts, perm, ln: int, how: str):
    hit = counts > 0
    if how in ("semi", "anti"):
        return "right_build", hit, np.zeros(ln, dtype=np.int64)
    # effective row multiplicity: misses keep one output row under left-outer
    ce = counts if how == "inner" else np.where(hit, counts, 1)
    total = int(ce.sum())
    lidx = np.repeat(np.arange(ln, dtype=np.int64), ce)
    starts = np.repeat(lo, ce)
    offs = np.arange(total, dtype=np.int64) - np.repeat(np.cumsum(ce) - ce, ce)
    pos = np.minimum(starts + offs, len(perm) - 1)
    ridx = perm[pos]
    if how != "inner":
        ridx = np.where(np.repeat(hit, ce), ridx, -1)
    return "expanded", lidx, ridx


def _stage_key(table, key_expr, cache) -> Optional[Tuple]:
    """Stage one join-key column (post-normalization) -> (values, valid)."""
    from .device import normalize_and_check

    schema = table.schema
    nodes = normalize_and_check([key_expr], schema)
    if nodes is None:
        return None
    from ..expressions import required_columns

    from ..datatypes import TypeKind

    node = nodes[0]
    dt = node.to_field(schema).dtype
    if not (dt.is_integer() or dt.kind == TypeKind.DATE):
        return None
    cols = required_columns(node)
    if not cols:
        return None
    b = size_bucket(len(table))
    staged = stage_table_columns(table, cols, b, cache)
    if staged is None:
        return None
    env, dcs = staged
    from .device import (compile_projection, int64_wrap_safe,
                         string_literal_env, string_lut_env)

    if not int64_wrap_safe([node], schema, env, cache, b):
        return None  # computed int64 key could wrap in int32 lanes
    # an integer key expression may still embed a string-literal comparison
    # (e.g. (col('s') == 'a').cast(int)): the compiled closure reads the
    # literal's per-partition code bounds from the env
    env = string_literal_env([node], schema, dcs, env)
    if env is None:
        return None
    env = string_lut_env([node], schema, dcs, env)
    if env is None:
        return None
    # int-valued string transforms (length/find) inside the key compile
    # against host dictionary-evaluated lanes; cross-column transform
    # compares (e.g. (upper(a) == b).cast(int) keys) need their pairwise
    # joint remaps too — aux is SHARED so the compare env can see the
    # transform sides' dictionaries
    from .device import string_transform_env, transform_cmp_env

    aux: dict = {}
    env = string_transform_env([node], schema, table, b, cache, env, aux)
    if env is None:
        return None
    env = transform_cmp_env([node], schema, table, b, cache, dcs, env, aux)
    if env is None:
        return None
    with timeline.part("dispatch.lookup", "dispatch_lookup_ns"):
        run, _ = compile_projection([node], schema, tuple(sorted(cols)))
    with timeline.part("dispatch.call", "dispatch_call_ns"):
        (vals, valid), = run(env)
    if not jnp.issubdtype(vals.dtype, jnp.integer):
        return None
    # a null-reviving key expression (fill_null, int transforms through the
    # null slot) marks size-bucket PADDING lanes valid; the probe kernels
    # mask by validity, not row count, so phantom build rows would match —
    # force padding back invalid at THIS staging boundary (covers every
    # compiled key shape)
    n = len(table)
    if int(valid.shape[0]) > n:
        valid = valid & (jnp.arange(int(valid.shape[0]), dtype=jnp.int32) < n)
    return vals, valid


def _is_plain_string_key(table, key_expr) -> bool:
    """Cheap shape check (no staging): the key normalizes to a bare string
    Column OR a row-local transform of one, i.e. the joint-dictionary path
    could apply."""
    node = _normalized_key_node(table, key_expr)
    if node is None:
        return False
    from .device import _plain_string_column

    return (_plain_string_column(node, table.schema) is not None
            or _string_valued_transform_shape(node, table.schema) is not None)


def _string_valued_transform_shape(node, schema):
    """The transform shape ONLY when the node is string-VALUED: a join key
    like length(s) (int) or s=="x" (bool) must not reach the joint
    dictionary, whose merge casts to large_string and would silently join
    ints against their string representations."""
    try:
        if not node.to_field(schema).dtype.is_string():
            return None
    except (ValueError, KeyError):
        return None
    from .device import _string_dict_value_shape

    return _string_dict_value_shape(node, schema)


def _normalized_key_node(table, key_expr):
    """Literal-normalized + Between-rewritten key node (the same
    normalization every dictionary cache key uses), or None."""
    from ..expressions import normalize_literals
    from .device import _rewrite_between

    try:
        return _rewrite_between(
            normalize_literals(key_expr._node, table.schema), table.schema)
    except (ValueError, KeyError):
        return None


class _CodeSide:
    """(values, valid, dictionary) triple for one string join-key side —
    a plain column's dictionary codes, or a TRANSFORMED key's sorted-recode
    lane with its transformed dictionary. Duck-typed like DeviceColumn for
    _joint_remaps (which reads .values/.valid/.dictionary only)."""

    __slots__ = ("values", "valid", "dictionary")

    def __init__(self, values, valid, dictionary):
        self.values = values
        self.valid = valid
        self.dictionary = dictionary


def _string_code_side(table, key_expr, cache) -> Optional[_CodeSide]:
    """Stage one string-key side into code space: plain columns via their
    sorted dictionary, row-local transforms (upper/substr/fill_null chains,
    r5) via the sorted-recode transform lane — both yield (codes, valid,
    dictionary) and merge through the same joint dictionary."""
    from .device import (_plain_string_column, dict_transform_lane,
                         size_bucket, stage_table_columns)

    node = _normalized_key_node(table, key_expr)
    if node is None:
        return None
    cname = _plain_string_column(node, table.schema)
    if cname is not None:
        staged = stage_table_columns(table, [cname],
                                     size_bucket(len(table)), cache)
        if staged is None:
            return None
        dc = staged[1][cname]
        if dc.dictionary is None:
            return None
        return _CodeSide(dc.values, dc.valid, dc.dictionary)
    shape = _string_valued_transform_shape(node, table.schema)
    if shape is None:
        return None
    lane = dict_transform_lane(table, shape, size_bucket(len(table)), cache)
    if lane is None:
        return None
    vals, valid, tuniq = lane
    # a null-reviving transform (fill_null chain) marks the size-bucket
    # PADDING lanes valid (they gather through the null slot); the probe
    # kernels mask by validity, not row count, so phantom build rows would
    # match — force padding back invalid here
    n = len(table)
    b = int(valid.shape[0])
    if b > n:
        valid = valid & (jnp.arange(b, dtype=jnp.int32) < n)
    return _CodeSide(vals, valid, tuniq)


@jax.jit
def _recode(codes, remap):
    """Gather per-side dictionary codes into the JOINT dictionary's code
    space (remap is the small per-dictionary index array)."""
    return remap[codes]


def _joint_remaps(ldc, rdc, lcache, rcache):
    """(lremap, rremap) device arrays mapping each side's dictionary codes
    into their sorted JOINT dictionary's code space. Cached per dictionary
    PAIR in BOTH sides' caches (the entry pins both pa.Arrays, keeping the
    id-keys valid): a broadcast-shaped join of one build side against P
    probe partitions hits the build side's cache, merging the dictionaries
    once, not P times. Remaps pad to a size bucket so _recode compiles per
    bucket, not per dictionary length."""
    key = ("__jointremap__", id(ldc.dictionary), id(rdc.dictionary))
    for cache in (lcache, rcache):
        cached = cache.get(key) if cache is not None else None
        if cached is not None:
            return cached[2], cached[3]
    import pyarrow as pa
    import pyarrow.compute as pc

    from .device import joint_remap

    joint = pc.unique(pa.concat_arrays([
        ldc.dictionary.cast(pa.large_string()),
        rdc.dictionary.cast(pa.large_string())]))
    joint = joint.take(pc.sort_indices(joint))
    lremap = joint_remap(ldc.dictionary, joint)
    rremap = joint_remap(rdc.dictionary, joint)
    entry = (ldc.dictionary, rdc.dictionary, lremap, rremap)
    for cache in (lcache, rcache):
        if cache is not None:
            cache[key] = entry
    return lremap, rremap


def _stage_key_pair(ltable, rtable, lkey, rkey, lcache, rcache,
                    ls=None, rs=None):
    """((lv, lm), (rv, rm)) aligned int lanes for ONE key pair.

    Numeric/date keys stage independently (_stage_key; pass pre-staged
    sides via ls/rs to avoid re-dispatching). Plain STRING columns cannot:
    per-partition dictionary codes are incomparable across tables — so
    both sides' sorted dictionaries merge into one sorted JOINT dictionary
    (host, O(u1+u2), cached per pair) and each side's codes gather through
    a small remap array on device, giving equal strings equal ints across
    tables. The probe then runs unchanged on int lanes. Reference
    semantics: the probe table hashes raw key bytes so cross-table
    equality is inherent (probe_table/mod.rs); the TPU formulation makes
    it inherent by unifying the code space instead."""
    if ls is None:
        ls = _stage_key(ltable, lkey, lcache)
    if rs is None:
        rs = _stage_key(rtable, rkey, rcache)
    if ls is not None and rs is not None:
        return ls, rs
    ldc = _string_code_side(ltable, lkey, lcache)
    rdc = _string_code_side(rtable, rkey, rcache)
    if ldc is None or rdc is None:
        return None
    lremap, rremap = _joint_remaps(ldc, rdc, lcache, rcache)
    lv = _recode(ldc.values, lremap)
    rv = _recode(rdc.values, rremap)
    return (lv, ldc.valid), (rv, rdc.valid)


@jax.jit
def _masked_min_max_multi(vs, ms):
    """Per-column masked min/max for a tuple of key columns, ONE fused call
    (and so one host sync) per side."""
    mins = jnp.stack([jnp.min(jnp.where(m, v, jnp.iinfo(v.dtype).max))
                      for v, m in zip(vs, ms)])
    maxs = jnp.stack([jnp.max(jnp.where(m, v, jnp.iinfo(v.dtype).min))
                      for v, m in zip(vs, ms)])
    return mins, maxs


@functools.partial(jax.jit, static_argnames=("wide",))
def _pack_kernel(vs, ms, mins, strides, wide):
    """Mixed-radix composite-key packing. mins/strides are TRACED arrays —
    they vary per partition pair, so making them static would retrace and
    recompile per call; with them traced, one compilation per (shape, nkeys,
    wide) serves every partition."""
    out_dt = jnp.int64 if wide else jnp.int32
    packed = jnp.zeros(vs[0].shape, out_dt)
    valid = jnp.ones(ms[0].shape, bool)
    for i, (v, m) in enumerate(zip(vs, ms)):
        packed = packed + ((v.astype(out_dt) - mins[i].astype(out_dt))
                           * strides[i].astype(out_dt))
        valid = valid & m
    # clamp invalid lanes so padding garbage stays in-range (matching is
    # still decided by the validity masks in the probe kernel)
    return jnp.where(valid, packed, 0), valid


def _pack_composite_keys(sides):
    """Pack N integer key columns into ONE surrogate key column per side so
    the single-key sorted probe applies unchanged (reference semantic: the
    reference's probe table hashes all key columns together,
    src/daft-table/src/probe_table/mod.rs:14-28; the TPU formulation needs a
    total order, so it uses exact mixed-radix packing instead of hashing —
    collision-free by construction).

    `sides` is a list of [(vals, valid), ...] per side, all of the same key
    count. Offsets/strides come from the min/max over BOTH sides so equal
    keys pack identically. Returns [(packed, valid), ...] per side, or None
    when the combined key space overflows the lane dtype (host join then).
    A row's composite key is valid only if every component is.
    """
    from .device import x64_enabled

    nkeys = len(sides[0])
    per_side = []
    for side in sides:
        vs = tuple(v for v, _ in side)
        ms = tuple(m for _, m in side)
        mns, mxs = _masked_min_max_multi(vs, ms)
        per_side.append((np.asarray(mns), np.asarray(mxs)))  # one sync/side
    mins = []
    spans = []
    for j in range(nkeys):
        lo = min(int(mns[j]) for mns, _ in per_side)
        hi = max(int(mxs[j]) for _, mxs in per_side)
        if hi < lo:  # all-null column on both sides: nothing can match
            lo, hi = 0, 0
        mins.append(lo)
        spans.append(hi - lo + 1)
    wide = x64_enabled()
    limit = (2 ** 63 - 1) if wide else (2 ** 31 - 1)
    total = 1
    for s in spans:
        total *= s
        if total > limit:
            return None
    strides = []
    acc = 1
    for s in reversed(spans):
        strides.append(acc)
        acc *= s
    strides = tuple(reversed(strides))

    lane_np = np.int64 if wide else np.int32
    mins_arr = np.asarray(mins, dtype=lane_np)
    strides_arr = np.asarray(strides, dtype=lane_np)
    out = []
    for side in sides:
        vs = tuple(v for v, _ in side)
        ms = tuple(m for _, m in side)
        out.append(_pack_kernel(vs, ms, mins_arr, strides_arr, wide))
    return out


def _replica_cache_key(key_expr):
    from .device import x64_enabled

    return ("__join_key_replica__", key_expr._node._key(), x64_enabled())


def replicate_join_key(part, key_expr, mesh) -> bool:
    """Stage `key_expr` over `part` once and replicate it into every device of
    `mesh` (one fully-replicated `jax.device_put` — an ICI broadcast, the TPU
    form of the reference's broadcast-join small-side replication,
    daft/execution/physical_plan.py:374). The per-device copies are cached on
    the partition; `device_join_indices` then probes against the copy local
    to the probe shard's device. Returns True when replicated."""
    from jax.sharding import NamedSharding, PartitionSpec

    tbl = part.table()
    staged = _stage_key(tbl, key_expr, part.device_stage_cache())
    if staged is None:
        return False
    vals, valid = staged
    rep = NamedSharding(mesh, PartitionSpec(*([None] * vals.ndim)))
    rep1 = NamedSharding(mesh, PartitionSpec(None))
    gv = jax.device_put(vals, rep)
    gm = jax.device_put(valid, rep1)
    vmap = {s.device: s.data for s in gv.addressable_shards}
    mmap = {s.device: s.data for s in gm.addressable_shards}
    part.device_stage_cache()[_replica_cache_key(key_expr)] = {
        d: (vmap[d], mmap[d]) for d in vmap}
    return True


def join_key_replicas(part, key_expr):
    """The {device: (vals, valid)} replica map cached by replicate_join_key,
    or None."""
    if part is None:
        return None
    try:
        return part.device_stage_cache().get(_replica_cache_key(key_expr))
    except Exception:
        return None


def _device_of(arr):
    try:
        devs = arr.devices()
        if len(devs) == 1:
            return next(iter(devs))
    except Exception:
        pass
    return None


def device_join_indices(left_table, right_table, left_keys, right_keys,
                        left_cache=None, right_cache=None, how: str = "inner",
                        left_replicas=None, right_replicas=None):
    """Blocking device probe: launch + resolve in one call (see
    device_join_launch for the pipelined split). Returns (side, hit, bidx)
    or None when ineligible."""
    launch = device_join_launch(left_table, right_table, left_keys,
                                right_keys, left_cache, right_cache, how,
                                left_replicas, right_replicas)
    return None if launch is None else launch()


def device_join_launch(left_table, right_table, left_keys, right_keys,
                       left_cache=None, right_cache=None, how: str = "inner",
                       left_replicas=None, right_replicas=None):
    """Stage the keys and LAUNCH the right-build range probe WITHOUT
    blocking (jax dispatch is asynchronous); the returned zero-arg resolver
    makes the dup decision, runs any second-orientation probe, and returns
    (side, hit, bidx) — the executor stages pair i+1 while pair i probes,
    the join flavor of the double-buffered projection dispatch (PARITY
    known-gap 36). Resolver contract, or None when ineligible:

    - side == "right_build": hit/bidx are per LEFT row (bidx indexes right)
    - side == "left_build": hit/bidx are per RIGHT row (bidx indexes left)
    - side == "expanded": hit/bidx are pre-expanded (lidx, ridx) row-index
      pairs from the N:M range join (ridx == -1 marks a left-outer miss)
    or None when ineligible (non-integer keys, overflowing key space, ...).

    Accepts a single key or a list of keys per side: multi-column keys pack
    into one surrogate lane via exact mixed-radix packing
    (_pack_composite_keys) and then take the same sorted probe.

    When a side carries mesh replicas (replicate_join_key), the copy living on
    the OTHER side's device is swapped in, keeping the probe device-local.
    """
    if not isinstance(left_keys, (list, tuple)):
        left_keys = [left_keys]
    if not isinstance(right_keys, (list, tuple)):
        right_keys = [right_keys]
    if len(left_keys) != len(right_keys) or not left_keys:
        return None
    ln, rn = len(left_table), len(right_table)
    if ln == 0 or rn == 0:
        return None
    if len(left_keys) > 1:
        pairs = [_stage_key_pair(left_table, right_table, lk_, rk_,
                                 left_cache, right_cache)
                 for lk_, rk_ in zip(left_keys, right_keys)]
        if any(p is None for p in pairs):
            return None
        lks = [p[0] for p in pairs]
        rks = [p[1] for p in pairs]
        packed = _pack_composite_keys([lks, rks])
        if packed is None:
            return None
        (lv, lm), (rv, rm) = packed
        return _launch_probe(lv, lm, rv, rm, ln, rn, how)
    left_key, right_key = left_keys[0], right_keys[0]
    lk = _stage_key(left_table, left_key, left_cache)
    rk = None
    if lk is not None and right_replicas:
        # replica hit: skip staging the build side entirely — its existence
        # already proves the key passed the device-eligibility checks
        d = _device_of(lk[0])
        if d is not None and d in right_replicas:
            rk = right_replicas[d]
    if rk is not None:
        lv, lm = lk
    else:
        if lk is None and not _is_plain_string_key(left_table, left_key):
            return None  # ineligible left key: don't stage the right side
        rk0 = _stage_key(right_table, right_key, right_cache)
        if lk is None or rk0 is None:
            # string keys (or one string side): recode through the joint
            # dictionary so equal strings get equal ints across tables
            # (pre-staged non-None sides pass through)
            pair = _stage_key_pair(left_table, right_table,
                                   left_key, right_key,
                                   left_cache, right_cache,
                                   ls=lk, rs=rk0)
            if pair is None:
                return None
            (lv, lm), rk = pair
        else:
            lv, lm = lk
            rk = rk0
            if left_replicas:
                d = _device_of(rk[0])
                if d is not None and d in left_replicas:
                    lv, lm = left_replicas[d]
    rv, rm = rk
    if lv.dtype != rv.dtype:
        return None
    return _launch_probe(lv, lm, rv, rm, ln, rn, how)


def _probe(build_vals, build_valid, probe_vals, probe_valid):
    """Launch one range probe and count the levels its search resolves by
    compares and by gathers (static, from the build's size)."""
    compares, gathers = probe_search_levels(int(build_vals.shape[0]))
    timeline.add("join_probe_compare_levels", compares)
    timeline.add("join_probe_gather_levels", gathers)
    return _range_probe_kernel(build_vals, build_valid, probe_vals,
                               probe_valid)


def _launch_probe(lv, lm, rv, rm, ln: int, rn: int, how: str):
    """Dispatch the right-build range probe now (async); return the
    resolver that makes the dup decision and finishes the probe."""
    with timeline.part("dispatch.call", "dispatch_call_ns"):
        lo, counts, perm, dup = _probe(rv, rm, lv, lm)

    def resolve():
        # build=right first (probe order == host output order); ONE sort
        # serves whichever path the dup flag selects
        if not bool(fetch(dup)):
            hit, bidx = fetch(_pk_outputs(lo, counts, perm))
            return ("right_build", np.asarray(hit)[:ln],
                    np.asarray(bidx)[:ln].astype(np.int64))
        if how == "inner":
            lo2, counts2, perm2, dup2 = _probe(lv, lm, rv, rm)
            if not bool(fetch(dup2)):
                hit, bidx = fetch(_pk_outputs(lo2, counts2, perm2))
                return ("left_build", np.asarray(hit)[:rn],
                        np.asarray(bidx)[:rn].astype(np.int64))
        # duplicate build keys on every usable orientation: N:M range join,
        # reusing the right-build probe already on device
        return _range_join(lo, counts, perm, ln, how)

    return resolve
