"""Device register-scatter for HLL sketch builds.

The control plane stays on host (murmur hashes + group codes — the same
split every shuffle uses); the data plane, scattering register ranks into
[num_groups, HLL_M] with a segment max, runs as ONE jit'd XLA program on
the device. Callers route through ExecutionContext._device_attempt, so the
scatter sits behind the existing DeviceHealth breaker and the
`device.kernel` fault site like every other device kernel.
"""
# daftlint: migrated

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

from ..kernels.sketches import HLL_M

#: register-matrix ceiling for the device path: past this the [G, HLL_M]
#: scatter output (int32 on device) stops being a sensible HBM tenant
MAX_DEVICE_REGISTERS = 1 << 24


@functools.lru_cache(maxsize=32)
def _scatter_fn(num_segments: int):
    import jax
    import jax.numpy as jnp

    def body(seg, rank):
        regs = jax.ops.segment_max(rank, seg, num_segments=num_segments)
        # empty segments come back at int32 min; registers floor at 0
        return jnp.maximum(regs, 0).astype(jnp.uint8)

    return jax.jit(body)


def _segment_bucket(n: int) -> int:
    """Round the segment count up to a power of two so distinct group
    cardinalities bucket into few compilations (same discipline as
    collectives.exchange_capacity)."""
    cap = HLL_M  # at least one group
    while cap < n:
        cap <<= 1
    return cap


def aggs_all_sketch_hll(aggregations) -> bool:
    """Cheap host-side gate: every aggregation is a stage-1 `sketch_hll`.
    Callers MUST check this before touching the breaker or the device
    fault site — a declined probe for a non-sketch agg would double-count
    breaker state and shift deterministic fault plans."""
    from ..expressions import AggExpr, Alias

    if not aggregations:
        return False
    for e in aggregations:
        node = e._node
        while isinstance(node, Alias):
            node = node.child
        if not (isinstance(node, AggExpr) and node.kind == "sketch_hll"):
            return False
    return True


def hll_scatter_device_launch(codes: np.ndarray, idx: np.ndarray,
                              rank: np.ndarray, num_groups: int):
    """Dispatch the register segment-max on device WITHOUT blocking (jax
    arrays are async until fetched); returns a zero-arg resolver yielding
    [num_groups, HLL_M] uint8 rows, or None when the shape is
    device-ineligible. Raises on device failure — the caller's
    _device_attempt / finish() records it against the breaker."""
    total = num_groups * HLL_M
    if total > MAX_DEVICE_REGISTERS or total >= (1 << 31):
        return None
    import jax

    nseg = _segment_bucket(total)
    seg = (codes.astype(np.int64) * HLL_M + idx).astype(np.int32)
    fn = _scatter_fn(nseg)
    out_dev = fn(jax.numpy.asarray(seg), jax.numpy.asarray(rank.astype(np.int32)))

    def resolve() -> np.ndarray:
        out = np.asarray(jax.device_get(out_dev))
        return out[:total].reshape(num_groups, HLL_M)

    return resolve


def hll_scatter_device(codes: np.ndarray, idx: np.ndarray, rank: np.ndarray,
                       num_groups: int) -> Optional[np.ndarray]:
    """Blocking variant of hll_scatter_device_launch (tests, direct use)."""
    resolve = hll_scatter_device_launch(codes, idx, rank, num_groups)
    return None if resolve is None else resolve()


def hll_build_table_device_launch(table, aggregations, groupby):
    """Stage-1 `sketch_hll` aggregation of one partition with the register
    scatter on device, split launch/resolve so the executor stages the next
    partition while this one's scatter runs: staging (hashing, group codes,
    device dispatch) happens NOW; the returned resolver fetches the
    registers and assembles the (keys + Binary sketch columns) Table.
    Returns None when ineligible (non-HLL agg kinds, oversized group
    count). Shares _group_codes with the host path so group order is
    identical."""
    from ..datatypes import DataType
    from ..schema import Field, Schema
    from ..series import Series
    from ..table import Table, _group_codes
    from .hll import registers_to_binary, scatter_operands

    if not aggs_all_sketch_hll(aggregations):
        return None
    from ..expressions import Alias

    nodes = []
    for e in aggregations:
        node = e._node
        while isinstance(node, Alias):
            node = node.child
        nodes.append((e.name(), node))
    n = len(table)
    if groupby:
        key_tbl = table.eval_expression_list(list(groupby))
        codes, uniq = _group_codes(key_tbl)
        num_groups = len(uniq)
        out_cols = list(uniq._columns)
        out_fields = list(uniq.schema)
    else:
        codes = np.zeros(n, dtype=np.int64)
        num_groups = 1
        out_cols = []
        out_fields = []
    if num_groups * HLL_M > MAX_DEVICE_REGISTERS:
        return None
    pending = []
    for alias, node in nodes:
        child = node.child.evaluate(table)
        if child.is_python():
            child = child.cast(DataType.string())
        gcodes, idx, rank = scatter_operands(child.to_arrow(), codes)
        resolve = hll_scatter_device_launch(gcodes, idx, rank, num_groups)
        if resolve is None:
            return None
        pending.append((alias, resolve))

    def finish() -> Table:
        cols = list(out_cols)
        fields = list(out_fields)
        for alias, resolve in pending:
            s = Series.from_arrow(registers_to_binary(resolve()), alias,
                                  DataType.binary())
            cols.append(s.rename(alias))
            fields.append(Field(alias, DataType.binary()))
        return Table(Schema(fields), cols)

    return finish
