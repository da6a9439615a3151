"""segment-sum-sweep: the measurement behind ``DENSE_MAX_SEGMENTS`` and
behind the sorted-segment form over ``_ONEHOT_MAX_SEGMENTS``.

    python tools/segment_sum_sweep.py [--rows 131072,67108864]
        [--groups 1,2,...] [--columns 1,7] [--out chiprun_out/...jsonl]
    python tools/segment_sum_sweep.py --forms scatter,sorted --columns 1
        --rows 32768,8388608 --groups 8192,32768,262144,2097152

For each row count, segment bucket and number of value columns it times one
jitted consumer, the masked float32 sums and int32 counts a fused aggregate
program takes over ``--columns`` value columns, with the sums in each of the
forms the program can give them, and in the one it gave them before:

- ``dense``: ``daft_tpu/kernels/device._dense_reduce`` (per-group masked
  reductions, the rows on the lane axis, pairwise combine);
- ``onehot``: ``_onehot_reduce`` (a ``(chunks, 8192, G)`` compare-reduce and
  the Kahan scan over the chunks);
- ``kernel``: ``pallas_ops.segment_sums_lanes`` over the stacked, pre-masked
  columns, the counts in the one-hot form, as ``device_agg._compile_agg``
  batches them above the bound;
- ``sorted``: ``_sorted_segment_sum`` (one sort of the (code, value) pairs,
  a scan of each run of equal codes by doubling, one scatter of the runs'
  last lanes): what ``segment_reduce`` gives a float sum over a bucket of
  more than 4096 segments since PR 39;
- ``scatter``: what it gave there before, kept here to be measured against:
  a ``segment_sum`` a chunk of 8192 rows into a ``(rows / 8192, G)`` array
  of partials and a Kahan scan over its rows (8 GiB at 8M rows and 2M
  segments: ask for it only where it fits).

``dense`` and ``onehot`` go through the program's own ``segment_reduce``; the
form is forced by moving the bound round the bucket while the consumer is
traced. ``--skew S`` gives group 0 the share ``S`` of the rows (the sorted
form's rounds grow with the logarithm of the longest group). One line of
JSON a point: ``<form>_ms`` (median of ``--reps`` calls after one warm call,
each ending in ``block_until_ready``),
``<form>_compile_s`` (the first call: compile + one run), ``<form>_rel_err``
(the widest gap of any sum from a float64 host sum, as a share of it) and the
device. Counts must be exact and every gap at or under 1e-6, else exit 1.

One process that holds the chip (``chiprun -- python tools/segment_sum_sweep.py``).
On the CPU it rehearses the code at ``--rows 131072``; a time read there is
no device number.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

DEFAULT_ROWS = (1 << 17, 1 << 26)
DEFAULT_GROUPS = (1, 2, 4, 8, 16, 32, 64, 128, 1024)
DEFAULT_COLUMNS = (1, 7)
FORMS = ("dense", "onehot", "kernel")  # the default; also: scatter, sorted
REL_ERR_LIMIT = 1e-6


def scatter_sum_kahan(values, codes, num_segments):
    """``device._scatter_sum_kahan`` as it stood to PR 38."""
    import jax

    from daft_tpu.kernels import device as dev

    chunk = min(dev._REDUCE_CHUNK, values.shape[0])
    partials = jax.vmap(
        lambda vv, cd: jax.ops.segment_sum(vv, cd, num_segments))(
        values.reshape(-1, chunk), codes.reshape(-1, chunk))
    return dev._kahan_combine(partials)


def build_consumer(form: str, groups: int):
    """A jitted ``(codes, valid, *columns) -> (sums (K, G), counts (K, G))``
    whose float sums take ``form``."""
    import jax
    import jax.numpy as jnp

    from daft_tpu.kernels import device as dev
    from daft_tpu.kernels import pallas_ops

    bound = groups if form == "dense" else 0

    def consumer(codes, valid, *columns):
        counts = dev.segment_reduce(valid, valid, codes, groups, "count")[0]
        if form == "kernel":
            vk = jnp.stack([jnp.where(valid, v, 0.0) for v in columns])
            sums = pallas_ops.segment_sums_lanes(
                codes[None, :], vk, groups, jax.default_backend() == "cpu")
        elif form in ("scatter", "sorted"):
            one = (scatter_sum_kahan if form == "scatter"
                   else dev._sorted_segment_sum)
            sums = jnp.stack([one(jnp.where(valid, v, 0), codes, groups)
                              for v in columns])
        else:
            sums = jnp.stack([
                dev.segment_reduce(v, valid, codes, groups, "sum")[0]
                for v in columns])
        return sums, jnp.broadcast_to(counts, (len(columns), groups))

    jitted = jax.jit(consumer)

    def traced_under_bound(*args):
        kept = dev.DENSE_MAX_SEGMENTS
        dev.DENSE_MAX_SEGMENTS = bound
        try:
            return jitted(*args)
        finally:
            dev.DENSE_MAX_SEGMENTS = kept

    return traced_under_bound


def timed_ms(fn, args, reps: int):
    """(median ms over ``reps`` warm calls, seconds of the first call, value)."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    first_s = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        walls.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(walls), first_s, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", default=",".join(map(str, DEFAULT_ROWS)))
    ap.add_argument("--groups", default=",".join(map(str, DEFAULT_GROUPS)))
    ap.add_argument("--columns", default=",".join(map(str, DEFAULT_COLUMNS)))
    ap.add_argument("--forms", default=",".join(FORMS))
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=32)
    ap.add_argument("--skew", type=float, default=0.0,
                    help="share of the rows given to group 0")
    ap.add_argument("--out", default=None,
                    help="also append every line to this file")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_enable_x64", False)  # float32 sums, as on the chip
    d = jax.devices()[0]
    device = {"platform": d.platform, "device_kind": d.device_kind,
              "count": jax.device_count()}
    forms = args.forms.split(",")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    columns_asked = [int(k) for k in args.columns.split(",")]
    rng = np.random.default_rng(args.seed)
    bad = 0
    for rows in (int(r) for r in args.rows.split(",")):
        # money-sized values (l_extendedprice's range), 3% of the rows masked
        host_cols = [rng.uniform(900.0, 105000.0, rows).astype(np.float32)
                     for _ in range(max(columns_asked))]
        host_valid = rng.random(rows) < 0.97
        dev_cols = [jnp.asarray(c) for c in host_cols]
        dev_valid = jnp.asarray(host_valid)
        for groups in (int(g) for g in args.groups.split(",")):
            host_codes = rng.integers(0, groups, rows, dtype=np.int32)
            if args.skew:
                host_codes[rng.random(rows) < args.skew] = 0
            dev_codes = jnp.asarray(host_codes)
            want_counts = np.bincount(host_codes[host_valid],
                                      minlength=groups)
            want_sums = [np.bincount(
                host_codes[host_valid],
                weights=c[host_valid].astype(np.float64), minlength=groups)
                for c in host_cols]
            for k in columns_asked:
                line = {"rows": rows, "groups": groups, "columns": k,
                        "skew": args.skew, "device": device}
                call = (dev_codes, dev_valid, *dev_cols[:k])
                for form in forms:
                    ms, first_s, (sums, counts) = timed_ms(
                        build_consumer(form, groups), call, args.reps)
                    gap = max(float(np.max(
                        np.abs(np.asarray(sums[j], np.float64) - want_sums[j])
                        / np.maximum(np.abs(want_sums[j]), 1e-300)))
                        for j in range(k))
                    exact = bool((np.asarray(counts) == want_counts).all())
                    line[f"{form}_ms"] = ms
                    line[f"{form}_compile_s"] = first_s
                    line[f"{form}_rel_err"] = gap
                    if not exact or gap > REL_ERR_LIMIT:
                        line.setdefault("wrong", []).append(form)
                bad += "wrong" in line
                text = json.dumps(line)
                print(text, flush=True)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(text + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
