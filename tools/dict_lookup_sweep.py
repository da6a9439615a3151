"""dict-lookup-sweep: the measurement behind ``DICT_PACKED_MAX_ENTRIES``.

    python tools/dict_lookup_sweep.py [--rows 67108864] [--entries 7,32,...]

For each dictionary size it times one jitted consumer over ``--rows`` codes,
a masked sum as Q12's filter feeds its aggregate, with the boolean table in
each of the two forms ``daft_tpu/kernels/device._dict_bool_tables`` can build:
packed ``uint32`` words (compares, selects and one shift, fused into the
consumer) and ``bool[bucket]`` gathered by code. Both go through the program's
own ``_dict_bool_lookup``; the form is forced by moving the bound round the
size for the one call that builds the table. One line of JSON a size:
``entries``, ``words``, ``packed_ms`` / ``gather_ms`` (median of ``--reps``
calls after one warm call, each ending in ``block_until_ready``),
``packed_compile_s`` / ``gather_compile_s`` (the first call) and the device.
The answers of the two forms are compared; a difference exits 1.

One process that holds the chip (``chiprun -- python tools/dict_lookup_sweep.py``).
On the CPU it rehearses the code at ``--rows 100000``; a time read there is
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

DEFAULT_ENTRIES = (7, 32, 64, 128, 256, 512, 1024, 2048, 4096, 8192, 16384)


def build_table(dev, hits, packed: bool):
    """The table in the form asked for, by the program's own builder."""
    bound = dev.DICT_PACKED_MAX_ENTRIES
    dev.DICT_PACKED_MAX_ENTRIES = len(hits) if packed else -1
    try:
        table, = dev._dict_bool_tables(hits)
    finally:
        dev.DICT_PACKED_MAX_ENTRIES = bound
    return table


def timed_ms(fn, *args, reps: int):
    """(median ms over ``reps`` warm calls, seconds of the first call, value)."""
    t0 = time.perf_counter()
    out = fn(*args).block_until_ready()
    first_s = time.perf_counter() - t0
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        walls.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(walls), first_s, int(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=1 << 26)
    ap.add_argument("--entries", default=",".join(map(str, DEFAULT_ENTRIES)))
    ap.add_argument("--reps", type=int, default=7)
    ap.add_argument("--seed", type=int, default=28)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from daft_tpu.kernels import device as dev

    d = jax.devices()[0]
    device = {"platform": d.platform, "device_kind": d.device_kind,
              "count": jax.device_count()}

    @jax.jit
    def consumer(table, codes, valid, x):
        keep = dev._dict_bool_lookup(table, codes) & valid
        return jnp.sum(jnp.where(keep, x, 0))

    rng = np.random.default_rng(args.seed)
    # int32 sums wrap the same way in any order: the two forms must agree
    x = jnp.asarray(rng.integers(0, 100, args.rows, dtype=np.int32))
    valid = jnp.asarray(rng.random(args.rows) < 0.97)
    bad = 0
    for entries in (int(e) for e in args.entries.split(",")):
        hits = rng.random(entries) < 0.3
        codes = jnp.asarray(rng.integers(0, entries, args.rows, dtype=np.int32))
        line = {"entries": entries, "rows": args.rows, "device": device}
        sums = {}
        for form in ("packed", "gather"):
            table = build_table(dev, hits, form == "packed")
            if form == "packed":
                line["words"] = int(table.shape[0])
            ms, first_s, sums[form] = timed_ms(consumer, table, codes, valid,
                                               x, reps=args.reps)
            line[f"{form}_ms"] = ms
            line[f"{form}_compile_s"] = first_s
        line["same_answer"] = sums["packed"] == sums["gather"]
        bad += not line["same_answer"]
        print(json.dumps(line), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
