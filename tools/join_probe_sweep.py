"""join-probe-sweep: the measurement behind ``PROBE_COMPARE_LEVELS``.

    python tools/join_probe_sweep.py [--shapes 8388608x65536,...]
        [--levels 0,4,7,10,12,14] [--out chiprun_out/join_probe_sweep.jsonl]

For each shape ``PxB`` (probe lanes x sorted build lanes) it times how a
probe row finds its match range over the sorted build keys:

- ``two_searches``: what the probe was before PR 35, kept here as the
  yardstick and nowhere in the program: ``jnp.searchsorted`` left and right,
  ``counts = vp[hi] - vp[lo]``;
- ``L<n>``: the program's own ``device_join._match_ranges`` (one lower bound
  a row, its first ``n`` levels by compares against pivots, the rest by
  gathers, the run's end read from the build), the depth forced by moving
  ``PROBE_COMPARE_LEVELS`` while the search is traced;
- ``kernel`` / ``kernel_two_searches``: the whole ``_range_probe_kernel`` at
  the program's own depth, build sort included, beside the same kernel with
  the two searches: what a query's probe costs, and what a new seed's first
  probe costs inside set-up.

One line of JSON a point: ``ms`` (median of ``--reps`` calls after the first,
each ending in ``block_until_ready``), ``first_s`` (the first call: compile
+ one run), ``compare_levels`` / ``gather_levels`` and the device. Every
form's ``lo`` and ``counts`` must equal the two searches' element for
element, else exit 1.

One process that holds the chip (``chiprun -- python tools/join_probe_sweep.py``).
On the CPU it rehearses the code (``--shapes 4096x1024,1024x4096``); a time
read there is no device number.
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

# tpch1-join's Q5 and Q3 probes in both orientations, a square morsel-sized
# join and the largest square one
DEFAULT_SHAPES = ("8388608x65536", "4194304x262144", "65536x8388608",
                  "262144x4194304", "131072x131072", "8388608x8388608")
DEFAULT_LEVELS = (0, 4, 7, 10, 12, 14)


def two_searches(sk, sorted_valid, probe_vals, probe_valid):
    """(lo, counts) by a left and a right ``searchsorted``: the parent's."""
    import jax.numpy as jnp

    vp = jnp.concatenate([jnp.zeros(1, jnp.int32),
                          jnp.cumsum(sorted_valid.astype(jnp.int32))])
    lo = jnp.searchsorted(sk, probe_vals, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(sk, probe_vals, side="right").astype(jnp.int32)
    return lo, jnp.where(probe_valid, vp[hi] - vp[lo], 0)


def kernel_two_searches(build_vals, build_valid, probe_vals, probe_valid):
    """The parent's ``_range_probe_kernel``: the same sort, two searches."""
    import jax.numpy as jnp

    k = jnp.where(build_valid, build_vals, jnp.iinfo(build_vals.dtype).max)
    perm = jnp.lexsort((~build_valid, k))
    sk = k[perm]
    sv = build_valid[perm]
    dup = jnp.any((sk[1:] == sk[:-1]) & sv[1:] & sv[:-1])
    lo, counts = two_searches(sk, sv, probe_vals, probe_valid)
    return lo, counts, perm.astype(jnp.int32), dup


def timed(fn, args, reps: int):
    """(median ms of ``reps`` calls after the first, seconds of the first
    call, its outputs)."""
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
    ap.add_argument("--shapes", default=",".join(DEFAULT_SHAPES))
    ap.add_argument("--levels", default=",".join(map(str, DEFAULT_LEVELS)))
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=35)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from daft_tpu.kernels import device_join as dj

    d = jax.devices()[0]
    device = {"platform": d.platform, "device_kind": d.device_kind,
              "count": jax.device_count()}
    out = open(args.out, "w") if args.out else None
    bad = 0

    def emit(line, got, want):
        nonlocal bad
        same = all(np.array_equal(np.asarray(g), np.asarray(w))
                   for g, w in zip(got, want))
        bad += not same
        line = dict(line, same_answer=same, device=device)
        print(json.dumps(line), flush=True)
        if out:
            out.write(json.dumps(line) + "\n")
            out.flush()

    rng = np.random.default_rng(args.seed)
    program_depth = dj.PROBE_COMPARE_LEVELS
    for shape in args.shapes.split(","):
        p, b = (int(x) for x in shape.split("x"))
        # a key column with a few repeats and 3% nulls on either side; the
        # probe hits about half the time
        bv = rng.integers(0, 2 * b, b, dtype=np.int32)
        bm = rng.random(b) < 0.97
        pv = rng.integers(0, 2 * b, p, dtype=np.int32)
        pm = rng.random(p) < 0.97
        k = np.where(bm, bv, np.iinfo(np.int32).max)
        order = np.lexsort((~bm, k))
        sorted_args = tuple(jnp.asarray(a) for a in
                            (k[order], bm[order], pv, pm))
        raw_args = tuple(jnp.asarray(a) for a in (bv, bm, pv, pm))
        point = {"probe_lanes": p, "build_lanes": b}

        ms, first_s, want = timed(jax.jit(two_searches), sorted_args, args.reps)
        emit(dict(point, form="two_searches", ms=ms, first_s=first_s),
             want, want)
        seen = set()
        for depth in (int(x) for x in args.levels.split(",")):
            dj.PROBE_COMPARE_LEVELS = depth
            try:
                compares, gathers = dj.probe_search_levels(b)
                if compares in seen:  # the build is shallower than ``depth``
                    continue
                seen.add(compares)
                ms, first_s, got = timed(
                    jax.jit(lambda *a: dj._match_ranges(*a)), sorted_args,
                    args.reps)
            finally:
                dj.PROBE_COMPARE_LEVELS = program_depth
            emit(dict(point, form=f"L{depth}", compare_levels=compares,
                      gather_levels=gathers, ms=ms, first_s=first_s),
                 got, want)
        ms, first_s, want = timed(jax.jit(kernel_two_searches), raw_args,
                                  args.reps)
        emit(dict(point, form="kernel_two_searches", ms=ms, first_s=first_s),
             want, want)
        ms, first_s, got = timed(dj._range_probe_kernel, raw_args, args.reps)
        emit(dict(point, form="kernel", compare_levels=program_depth, ms=ms,
                  first_s=first_s), got, want)
    if out:
        out.close()
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
