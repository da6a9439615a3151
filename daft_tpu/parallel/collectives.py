"""ICI collective kernels: the all_to_all exchange behind every shuffle.

Role-equivalent to the reference's shuffle data plane (Ray object-store
transfer of fanout outputs, daft/execution/physical_plan.py:1365-1413;
FanoutHash/FanoutRange + ReduceMerge, daft/execution/execution_step.py:834-985)
— redesigned for TPU: each device scatters its rows into per-destination send
buffers and ONE `jax.lax.all_to_all` moves every (src, dst) slab over ICI
simultaneously. No host round-trip for the payload.

XLA's all_to_all needs equal static split sizes, so the exchange is
capacity-padded: rows are scattered to `[n_dev, capacity]` send slabs with a
validity mask; capacity is negotiated host-side from exact bucket counts
(`exchange_capacity`), rounded to a power of two so each distinct capacity
compiles once.

Bucket assignment (the control plane) is computed on host — hashing via
kernels/host_hash (works for every dtype incl. strings) or range boundaries —
while the data plane ships only device-representable columns. This mirrors the
reference's split of planner-side fanout logic vs object-store movement.
"""

from __future__ import annotations

import functools
from typing import Dict, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MIN_CAPACITY = 128


def _shard_map(body, mesh: Mesh, in_specs, out_specs):
    # check_vma off: the bodies index their per-shard [1, R, ...] views and
    # return per-shard results; nothing here is replicated across the axis
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def _scatter_to_slabs(bucket, valid, cols, n: int, capacity: int):
    """Per-shard send-side scatter: route each row to its destination slab.

    Rows are stably sorted by destination; a row's slab position is its rank
    within its bucket. Invalid/padding rows go to a virtual overflow bucket n
    and out-of-capacity rows scatter out of bounds — both dropped (mode="drop").
    Returns (send_valid [n, capacity], [slab [n, capacity, *trailing] per col]).
    """
    r = bucket.shape[0]
    b = jnp.where(valid, bucket, jnp.int32(n))
    order = jnp.argsort(b, stable=True)
    sb = b[order]
    counts = jax.ops.segment_sum(jnp.ones(r, jnp.int32), sb, num_segments=n + 1)
    starts = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(counts)[:-1].astype(jnp.int32)])
    pos = jnp.arange(r, dtype=jnp.int32) - starts[sb]
    keep = (sb < n) & (pos < capacity)
    pos = jnp.where(keep, pos, capacity)
    send_valid = jnp.zeros((n, capacity), dtype=bool).at[sb, pos].set(keep, mode="drop")
    slabs = []
    for c in cols:
        slab = jnp.zeros((n, capacity) + c.shape[1:], c.dtype)
        slabs.append(slab.at[sb, pos].set(c[order], mode="drop"))
    return send_valid, slabs


def exchange_capacity(buckets: Sequence[np.ndarray], valids: Sequence[np.ndarray],
                      n_dev: int) -> int:
    """Max rows any (src shard, dst shard) pair exchanges, rounded up to a power
    of two (>= MIN_CAPACITY) so capacities bucket into few compilations."""
    worst = 0
    for b, v in zip(buckets, valids):
        bb = b[v] if v is not None else b
        if bb.size:
            worst = max(worst, int(np.bincount(bb, minlength=n_dev).max()))
    cap = MIN_CAPACITY
    while cap < worst:
        cap <<= 1
    return cap


_EXCHANGE_CACHE: Dict = {}


def build_exchange(mesh: Mesh, capacity: int, col_dtypes: Tuple,
                   col_trailing: Tuple[Tuple[int, ...], ...]):
    """Build (cached) the jitted shard_map exchange for this mesh/capacity/column
    signature.

    Returned fn: (bucket [n,R] i32, valid [n,R] bool, *cols [n,R,*trailing])
      -> (recv_valid [n, n, capacity] bool, *recv_cols [n, n, capacity, *trailing])
    where recv[d, s] holds the rows device s sent to device d (mask-compacted
    later on host or consumed masked on device).
    """
    axis = mesh.axis_names[0]
    n = mesh.shape[axis]
    key = (mesh, capacity, tuple(str(d) for d in col_dtypes), col_trailing)
    if key in _EXCHANGE_CACHE:
        return _EXCHANGE_CACHE[key]

    def body(bucket, valid, *cols):
        # per-shard views: [1, R, ...] -> [R, ...]
        bucket = bucket[0]
        valid = valid[0]
        cols = tuple(c[0] for c in cols)
        send_valid, outs = _scatter_to_slabs(bucket, valid, cols, n, capacity)
        recv_valid = lax.all_to_all(send_valid, axis, split_axis=0, concat_axis=0)
        recv = [lax.all_to_all(s, axis, split_axis=0, concat_axis=0) for s in outs]
        return (recv_valid[None], *[x[None] for x in recv])

    spec2 = P(axis, None)
    spec3 = P(axis, None, None)
    in_specs = (spec2, spec2) + tuple(
        P(axis, *([None] * (1 + len(t)))) for t in col_trailing)
    out_specs = (spec3,) + tuple(
        P(axis, *([None] * (2 + len(t)))) for t in col_trailing)
    fn = jax.jit(_shard_map(body, mesh, in_specs, out_specs))
    _EXCHANGE_CACHE[key] = fn
    return fn


def shard_to_mesh(arr: np.ndarray, mesh: Mesh) -> jax.Array:
    """Place a [n_dev, ...] host array so row i lives on mesh device i."""
    axis = mesh.axis_names[0]
    spec = P(axis, *([None] * (arr.ndim - 1)))
    return jax.device_put(arr, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Fused exchange + segment-aggregate (the stage1 -> shuffle -> stage2 pipeline
# of a distributed groupby as ONE compiled program; reference semantics:
# populate_aggregation_stages, src/daft-plan/src/physical_planner/translate.rs:761)
# ---------------------------------------------------------------------------

_GROUPED_CACHE: Dict = {}


def build_exchange_groupby_sum(mesh: Mesh, capacity: int, num_segments: int):
    """Jitted: hash-exchange (codes, values) then per-device masked segment-sum.

    fn(bucket [n,R] i32, valid [n,R] bool, codes [n,R] i32, values [n,R] f)
      -> (sums [n, num_segments] f, counts [n, num_segments] i32)
    `codes` are global group codes; `bucket` must equal `codes % n_dev` (so a
    group's rows all land on one device). Device d owns segments with
    code % n == d; its `sums[d]` row is authoritative for those.
    """
    axis = mesh.axis_names[0]
    n = mesh.shape[axis]
    key = (mesh, capacity, num_segments)
    if key in _GROUPED_CACHE:
        return _GROUPED_CACHE[key]

    def body(bucket, valid, codes, values):
        bucket, valid = bucket[0], valid[0]
        codes, values = codes[0], values[0]
        sv, (sc, sx) = _scatter_to_slabs(bucket, valid, (codes, values), n, capacity)
        rv = lax.all_to_all(sv, axis, split_axis=0, concat_axis=0).reshape(-1)
        rc = lax.all_to_all(sc, axis, split_axis=0, concat_axis=0).reshape(-1)
        rx = lax.all_to_all(sx, axis, split_axis=0, concat_axis=0).reshape(-1)
        contrib = jnp.where(rv, rx, jnp.zeros_like(rx))
        sums = jax.ops.segment_sum(contrib, jnp.where(rv, rc, num_segments),
                                   num_segments=num_segments + 1)[:num_segments]
        cnts = jax.ops.segment_sum(rv.astype(jnp.int32),
                                   jnp.where(rv, rc, num_segments),
                                   num_segments=num_segments + 1)[:num_segments]
        return sums[None], cnts[None]

    spec2 = P(axis, None)
    fn = jax.jit(_shard_map(body, mesh, (spec2, spec2, spec2, spec2),
                            (spec2, spec2)))
    _GROUPED_CACHE[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Sketch register merge (the global stage-2 of an approximate aggregation as
# ONE collective: per-device HLL register rows all_gather over ICI and merge
# with an elementwise max — reference semantics: the hyperloglog merge stage
# of translate.rs:761's sketch decomposition, mapped onto the mesh the way
# DrJAX maps MapReduce merge primitives onto jax meshes)
# ---------------------------------------------------------------------------

_REGISTER_MERGE_CACHE: Dict = {}


def build_register_allmerge(mesh: Mesh, m: int):
    """Build (cached) the jitted shard_map register merge for this mesh and
    register width.

    Returned fn: (regs [n_dev, m] uint8, one sketch row per device)
      -> merged [n_dev, m] uint8 where EVERY row holds the elementwise max
    (fully replicated result, like the host-side gather it replaces).
    """
    axis = mesh.axis_names[0]
    key = (mesh, m)
    if key in _REGISTER_MERGE_CACHE:
        return _REGISTER_MERGE_CACHE[key]

    def body(regs):
        r = regs[0].astype(jnp.int32)
        g = lax.all_gather(r, axis)  # [n_dev, m]
        return jnp.max(g, axis=0).astype(jnp.uint8)[None]

    spec = P(axis, None)
    fn = jax.jit(_shard_map(body, mesh, spec, spec))
    _REGISTER_MERGE_CACHE[key] = fn
    return fn
