"""Drive a whole rehearsed run of ``chipbench/run.py`` with the timed path
broken underneath it (started by ``test_correct.py``, one process a fault).

    python tests/chipbench/broken_run.py <fault> --workload ... --seed ...

- ``answer_altered``: one float of every answer is moved by 1e-4 of itself
  where the engine hands it over (``DataFrame.to_pydict``);
- ``half_rows``: the resident frames hold only the first half of each
  table's rows, the reference all of them;
- ``device_error``: every device attempt raises, so the host path answers
  (right answers, and every query has to count as failed).
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ["CHIPBENCH_REHEARSE"] = "1"
os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> int:
    fault, argv = sys.argv[1], sys.argv[2:]
    import daft_tpu as dt
    from daft_tpu import faults
    from daft_tpu.dataframe import DataFrame

    from chipbench import run

    if fault == "answer_altered":
        whole = DataFrame.to_pydict

        def altered(self):
            out = whole(self)
            for values in out.values():
                if values and isinstance(values[0], float):
                    values[0] *= 1.0 + 1e-4
                    break
            return out

        DataFrame.to_pydict = altered
    elif fault == "half_rows":
        whole_from_arrow = dt.from_arrow
        dt.from_arrow = lambda table: whole_from_arrow(
            table.slice(0, max(table.num_rows // 2, 1)))
    elif fault == "device_error":
        faults.arm("device.kernel", "always")
    else:
        raise SystemExit(f"unknown fault {fault!r}")
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
