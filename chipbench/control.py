"""The control of the comparison that decides ``correct``.

    python chipbench/control.py --workload <cell> --seed <n>

The plain reference is put in the program's place, computed in the nearest
precision below the float32 the configuration states: every float column
rounded to bfloat16 (``compare.lowered``), the step that would tempt a later
PR (stage the columns as bf16, or let the MXU round them, as PR 21's kernel
did). Its answers go through ``compare.judge`` with the configuration's
limits and have to come out as not correct. A benchmark run never runs
this; ``prove.py`` does, at the cell's own size, and ``tests/chipbench``
keeps it at a test size. It needs no chip and touches no JAX.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import compare, run as harness  # noqa: E402


def control(workload: str, seed: int, scale=None) -> dict:
    """``{"correct", "compared", "by_query"}`` of the lowered reference
    held against the reference, at ``scale`` (the configuration's own when
    None)."""
    _, _, config, _, queries, dataset = harness.load_cell(workload)
    tables = dataset.generate(config["scale"] if scale is None else scale,
                              seed, harness.union_columns(queries))
    low = compare.lowered(tables)
    references = {n: q.reference(tables) for n, q in queries.items()}
    answers = [(n, q.reference(low)) for n, q in queries.items()]
    correct, compared, by_query = compare.judge(answers, references,
                                                config["compare"])
    return {"correct": correct, "compared": compared, "by_query": by_query}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    print(json.dumps(control(args.workload, args.seed)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
