"""join-filter-sweep: the measurement behind the runtime join filter's
device keep-mask (``daft_tpu/exchange/joinfilter.py``).

    python tools/join_filter_sweep.py [--shapes 6000000x200,...]
        [--reps 5] [--out FILE.jsonl]

A shape is ``ROWSxKEYS[@SPAN]``: ROWS probe keys drawn uniformly from
``[1, SPAN]`` (SPAN defaults to the shape's own: 200,000 part keys for Q17's
200 build keys, 6,000,000 order keys for the others), KEYS distinct build
keys drawn from the same range, int32 lanes as on the chip. For each shape
it times the keep-mask two ways:

- ``bloom``: the device path the keep-mask had before it read staged
  lanes, deleted from the program and kept here to be measured against: the
  min-max test and two uint64 hashes of every key on the host, an
  ``int32[4, ROWS]`` array of Bloom positions built there, uploaded, and
  gathered from the ``uint8`` bit table by one jitted program
  (``bloom_host_ms``, ``bloom_upload_ms``, ``bloom_device_ms``; ``bloom_ms``
  the whole, the mask back on the host);
- ``direct``: ``joinfilter._keep_program`` over the staged lanes against
  bits addressed by ``key - lo``: exact. A build whose span is over
  ``DIRECT_MAX_RANGE`` keeps the host path in the program, so the line
  has no ``direct`` keys there.

``<form>_ms`` is the median of ``--reps`` calls after one warm call, the
mask fetched to the host (``<form>_program_ms`` the program alone, ending
in ``block_until_ready``); ``<form>_compile_s`` the first call;
``<form>_temp_bytes`` the compiled program's temporaries
(``memory_analysis()``); ``<form>_kept`` the rows the mask keeps. A
``bloom`` mask that drops a row whose key the build holds, or a ``direct``
mask that is not exactly ``is_in``, marks the line ``wrong`` and the exit
code 1.

Run it as one process that holds the TPU chip (``python
tools/join_filter_sweep.py``). On the CPU it rehearses the code at small
shapes (``--shapes 65536x200@200000,65536x5000@100000000``); a time read
there is no device number.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Q17 (6.0M lines, ~200 part keys of 200k), Q3-like and Q5-like probes,
# a large build, and a span over the direct-address bound
DEFAULT_SHAPES = ("6000000x200@200000", "740000x30000", "180000x5000",
                  "6000000x150000", "6000000x200000@1000000000")
DEFAULT_SPAN = 6_000_000


def parse_shape(text: str):
    dims, _, span = text.partition("@")
    rows, keys = (int(x) for x in dims.split("x"))
    return rows, keys, int(span) if span else DEFAULT_SPAN


@functools.lru_cache(maxsize=1)
def bloom_program():
    """The deleted ``joinfilter._probe_jitted``: k Bloom gathers and their
    AND in one program."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def _probe(bits, ix):
        g = jnp.take(bits, ix, axis=0)  # [k, n] uint8
        return jnp.min(g, axis=0).astype(jnp.bool_)

    return _probe


def bloom_host(jf, arr):
    """The host half of the deleted device path of
    ``RuntimeJoinFilter.keep_mask``: (in range, the ``int32[BLOOM_PROBES,
    n]`` positions)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc

    from daft_tpu.exchange import joinfilter as jfm

    lo, hi = jf.minmax[0]
    inr = pc.and_kleene(pc.greater_equal(arr, pa.scalar(lo, type=arr.type)),
                        pc.less_equal(arr, pa.scalar(hi, type=arr.type)))
    rng_ok = np.asarray(pc.fill_null(inr, False), dtype=bool)
    h1, h2 = jfm._hash_pair([arr])
    mask = np.uint64(jf.nbits - 1)
    idx = np.empty((jfm.BLOOM_PROBES, len(h1)), dtype=np.int32)
    h = h1.copy()
    for i in range(jfm.BLOOM_PROBES):
        idx[i] = (h & mask).astype(np.int32)
        h += h2
    return rng_ok, idx


def median_ms(fn, reps: int):
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        walls.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(walls)


def first_s(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


def temp_bytes(jitted, *args):
    try:
        ma = jitted.lower(*args).compile().memory_analysis()
        return int(ma.temp_size_in_bytes)
    except Exception:
        return None


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(DEFAULT_SHAPES))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default=None,
                    help="also append every line to this file")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    import pyarrow as pa

    jax.config.update("jax_enable_x64", False)  # int32 lanes, as on the chip
    from daft_tpu import col
    from daft_tpu.datatypes import DataType
    from daft_tpu.exchange import joinfilter as jfm
    from daft_tpu.kernels.device import size_bucket
    from daft_tpu.table import Table

    d = jax.devices()[0]
    device = {"platform": d.platform, "device_kind": d.device_kind}
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    rng = np.random.default_rng(args.seed)
    bad = 0
    for shape in args.shapes.split(","):
        rows, nkeys, span = parse_shape(shape)
        probe = rng.integers(1, span + 1, rows, dtype=np.int64)
        build = rng.choice(span, size=min(nkeys, span), replace=False) + 1
        b = jfm.JoinFilterBuilder([col("k")], [DataType.int64()])
        b.add(Table.from_arrow(pa.table({"k": pa.array(build)})))
        jf = b.seal()
        member = np.isin(probe, build)
        line = {"rows": rows, "keys": nkeys, "span": span, "device": device,
                "members": int(member.sum())}
        arr = pa.array(probe)

        # the staged lanes, as _stage_key hands them over (padding invalid)
        bucket = size_bucket(rows)
        lanes = np.zeros(bucket, np.int32)
        lanes[:rows] = probe
        valid = np.zeros(bucket, bool)
        valid[:rows] = True
        vals_d, valid_d = jnp.asarray(lanes), jnp.asarray(valid)

        # ---- bloom: the deleted path
        bits_d = jnp.asarray(jf.table.astype(np.uint8))
        prog = bloom_program()
        host_ms = median_ms(lambda: bloom_host(jf, arr), args.reps)
        rng_ok, idx = bloom_host(jf, arr)
        up_ms = median_ms(lambda: jnp.asarray(idx).block_until_ready(),
                          args.reps)
        idx_d = jnp.asarray(idx)
        comp, _ = first_s(lambda: prog(bits_d, idx_d).block_until_ready())
        dev_ms = median_ms(lambda: prog(bits_d, idx_d).block_until_ready(),
                           args.reps)

        def bloom_whole():
            ok, ix = bloom_host(jf, arr)
            hit = np.asarray(jax.device_get(prog(bits_d, jnp.asarray(ix))))
            return ok & hit

        mask = bloom_whole()
        line.update(bloom_host_ms=host_ms, bloom_upload_ms=up_ms,
                    bloom_device_ms=dev_ms,
                    bloom_ms=median_ms(bloom_whole, args.reps),
                    bloom_compile_s=comp,
                    bloom_temp_bytes=temp_bytes(prog, bits_d, idx_d),
                    bloom_kept=int(mask.sum()))
        if (member & ~mask).any():
            line.setdefault("wrong", []).append("bloom")
        del idx_d, idx

        # ---- direct: the keep program over the staged lanes
        if jf.keys is not None:
            keep = jfm._keep_program()
            words, lo, hi = jfm._lane_table(jf.keys, np.int32)
            call = (vals_d, valid_d, jnp.asarray(words),
                    jnp.asarray(np.int32(lo)), jnp.asarray(np.int32(hi)))
            comp, _ = first_s(lambda: keep(*call).block_until_ready())
            prog_ms = median_ms(lambda: keep(*call).block_until_ready(),
                                args.reps)

            def whole():
                return np.asarray(jax.device_get(keep(*call)))[:rows]

            mask = whole()
            line.update(direct_ms=median_ms(whole, args.reps),
                        direct_program_ms=prog_ms, direct_compile_s=comp,
                        direct_temp_bytes=temp_bytes(keep, *call),
                        direct_table_words=int(len(words)),
                        direct_kept=int(mask.sum()))
            if (mask != member).any():
                line.setdefault("wrong", []).append("direct")
        bad += "wrong" in line
        text = json.dumps(line)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
