"""Pallas TPU kernels for the aggregation hot path.

K weighted segment sums over a middling group cardinality are one MXU program
here: each grid step loads a block of rows into VMEM, forms the one-hot group
matrix, and accumulates its contraction with the values into a (K, groups)
VMEM accumulator — so ALL K aggregate columns ride a single data pass through
the systolic array, instead of K separate `segment_sum` lowerings touching HBM
K times.

Which buckets it serves: a fused aggregate program (device_agg._compile_agg)
batches its float sums here in 32-bit mode when its segment bucket is over
``device.DENSE_MAX_SEGMENTS`` (32) and at most ``_ONEHOT_MAX_SEGMENTS``
(4096); over that a one-hot block of even one lane tile outgrows VMEM and
the sums take ``device._sorted_segment_sum`` (sort, segmented scan, one
scatter). At or under the dense bound the compiler's own code is faster: the
kernel's cost is flat in the group count (one matmul against a 128-lane
one-hot tile at ``Precision.HIGHEST`` a block of rows, whatever the groups),
42-59 ms over 64M rows for one to seven columns, where per-group masked
reductions take 2-9 ms up to 16 groups and 6-21 ms at 32. Against
segment_reduce's one-hot form it wins from 128 groups with several columns
(96 against 284 ms at seven) and loses at 64 and with a single column at
1024 (tools/segment_sum_sweep.py on a v5e; the table is in PERF.md).

Layout: rows run along the LANE axis everywhere — codes are (1, n), values
are (K, n), the one-hot is (groups, block). A (n, 1) or (n, K) operand is
laid out by XLA in (8, 128) tiles, i.e. padded 128x (or 128/K x) in HBM:
at SF1's 8M-row bucket that is 4 GB per column, which the v5e refused to
allocate. With rows on lanes nothing pads by more than the 8-sublane tile,
and the contraction is the MXU-native `A @ B^T` (no in-kernel transpose).

The multiply pins Precision.HIGHEST: f32 operands at the TPU's default
precision take one bf16 pass, which put Q1's money sums 1e-6..1e-4 off the
oracle on the chip (interpret mode on the CPU never shows it). The Kahan step
guards the accumulation ACROSS grid steps, not the multiply.

Counts come from an exact host bincount (float32 one-hot accumulation would
silently stall at 2^24 rows per group); the kernel carries the K weighted
sums, which is where the FLOPs are.

Grid iteration on TPU is sequential per core, which makes the accumulate-into-
out_ref pattern sound (out block index is constant across steps; step 0 zeroes
it). Tests run `interpret=True` on CPU; on TPU the same call compiles to a
Mosaic kernel.

Reference role-equivalent: the grouped-aggregation kernels of
src/daft-core/src/array/ops/groups.rs + agg.rs, redesigned as a dense MXU
contraction rather than hash-bucket scatter (SURVEY.md §7 "Hard parts":
groupby on device without pointer-chasing).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .device import fetch

# smallest row block (one lane tile); every size bucket is a multiple of it
MIN_BLOCK_ROWS = 128


def block_rows(num_groups: int, n: int) -> int:
    """Rows per grid step: as many as keep the (groups, block) f32 one-hot
    at 2 MiB of VMEM, between one lane tile and 8192, never past n. Powers
    of two throughout, so the block divides every size bucket >= it."""
    return min(n, max(MIN_BLOCK_ROWS, min(8192, (1 << 19) // num_groups)))


def _kernel(codes_ref, vals_ref, out_ref, comp_ref, *, num_groups: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _zero():
        out_ref[:] = jnp.zeros_like(out_ref)
        comp_ref[:] = jnp.zeros_like(comp_ref)

    codes = codes_ref[:]  # (1, B) int32
    group_ids = jax.lax.broadcasted_iota(jnp.int32, (num_groups, 1), 0)
    one_hot_t = (group_ids == codes).astype(jnp.float32)  # (G, B)
    # (K, B) x (G, B)^T -> (K, G): both operands contract their lane axis
    block = jax.lax.dot_general(
        vals_ref[:], one_hot_t, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)
    # Kahan-compensated accumulation ACROSS grid steps: naive float32 adds
    # drift past 1e-6 relative on TPC-H-scale money sums (the one-hot
    # route this kernel replaces compensates too, device.py _kahan_combine)
    y = block - comp_ref[:]
    t = out_ref[:] + y
    comp_ref[:] = (t - out_ref[:]) - y
    out_ref[:] = t


@functools.partial(jax.jit, static_argnames=("num_groups", "interpret"))
def segment_sums_lanes(codes, vals, num_groups: int, interpret: bool):
    """codes (1, n) int32, vals (K, n) float32 with every row that must not
    count already zeroed -> sums (K, num_groups) float32. n is a power of
    two >= MIN_BLOCK_ROWS (a size bucket).

    The matmul runs on whole tiles: K rounds up to the 8-sublane tile with
    zero rows and the groups to the 128-lane tile with codes nobody has, so
    Mosaic sees an aligned (K8, B) x (G128, B)^T and unmasked stores."""
    k, n = vals.shape
    k8 = -(-k // 8) * 8
    g = -(-num_groups // 128) * 128
    if k8 != k:
        vals = jnp.pad(vals, ((0, k8 - k), (0, 0)))
    b = block_rows(g, n)
    sums, _comp = pl.pallas_call(
        functools.partial(_kernel, num_groups=g),
        out_shape=(jax.ShapeDtypeStruct((k8, g), jnp.float32),
                   jax.ShapeDtypeStruct((k8, g), jnp.float32)),
        grid=(n // b,),
        in_specs=[
            pl.BlockSpec((1, b), lambda i: (0, i)),
            pl.BlockSpec((k8, b), lambda i: (0, i)),
        ],
        out_specs=(pl.BlockSpec((k8, g), lambda i: (0, 0)),
                   pl.BlockSpec((k8, g), lambda i: (0, 0))),
        interpret=interpret,
    )(codes, vals)
    return sums[:k, :num_groups]


def masked_segment_sums(codes: np.ndarray, mask: Optional[np.ndarray],
                        values: np.ndarray, num_groups: int,
                        interpret: Optional[bool] = None) -> Tuple[np.ndarray, np.ndarray]:
    """Fused sums + counts for K value columns grouped by `codes`.

    codes: (n,) int group ids in [0, num_groups); mask: (n,) bool or None;
    values: (n, K) float64/float32 (NaNs allowed where masked out).
    Returns (sums (num_groups, K) float64, counts (num_groups,) int64).

    float32 accumulation on the MXU — callers needing exact float64 sums
    (the host parity path) should use the arrow/bincount route; this kernel
    is the device-throughput path.
    """
    n = len(codes)
    k = values.shape[1]
    if n == 0:
        # grid=(0,) would skip the kernel entirely, leaving out_ref unwritten
        return np.zeros((num_groups, k)), np.zeros(num_groups, np.int64)
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    # counts must be exact (float32 accumulation stalls at 2^24), so they come
    # from a host bincount; the kernel carries only the K weighted sums
    if mask is None:
        counts = np.bincount(codes, minlength=num_groups).astype(np.int64)
        vk = values.astype(np.float32)
    else:
        counts = np.bincount(codes[mask], minlength=num_groups).astype(np.int64)
        # masked-out rows contribute nothing: zero their values (which also
        # keeps a masked NaN out of the matmul)
        vk = np.where(mask[:, None], values, 0.0).astype(np.float32)
    # pad to a power-of-two row count (zero values: padding adds nothing)
    padded = MIN_BLOCK_ROWS
    while padded < n:
        padded <<= 1
    codes_p = np.zeros((1, padded), np.int32)
    codes_p[0, :n] = codes
    vals_p = np.zeros((k, padded), np.float32)
    vals_p[:, :n] = vk.T
    out = segment_sums_lanes(jnp.asarray(codes_p), jnp.asarray(vals_p),
                             num_groups, interpret)
    return np.asarray(fetch(out)).astype(np.float64).T, counts
