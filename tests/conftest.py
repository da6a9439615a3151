"""Test bootstrap: force jax onto a virtual 8-device CPU mesh BEFORE jax imports.

Mirrors the reference's runner-matrix CI trick (SURVEY.md §4): the same suite runs on a
single-device and a multi-device mesh; TPU hardware is not required for tests.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# JAX_PLATFORMS=cpu (set above, before jax imports) is all the platform pinning
# this installation needs; x64 has no environment switch worth relying on.
import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import pytest  # noqa: E402


@pytest.fixture(params=[1, 4])
def num_partitions(request):
    return request.param


@pytest.fixture(params=["arrow", "parquet"])
def data_source(request):
    """Like the reference's make_df fixture: in-memory arrow vs parquet tmp files."""
    return request.param


@pytest.fixture
def make_df(data_source, tmp_path):
    import itertools

    import daft_tpu

    counter = itertools.count()

    def _make(data: dict, repartition: int = 1):
        if data_source == "arrow":
            df = daft_tpu.from_pydict(data)
        else:
            import pyarrow as pa
            import pyarrow.parquet as papq

            p = str(tmp_path / f"make_df_{next(counter)}.parquet")
            papq.write_table(pa.table(data), p)
            df = daft_tpu.read_parquet(p)
        if repartition != 1:
            df = df.repartition(repartition)
        return df

    return _make


# Files added after the seed, kept behind its files in the schedule. The seed
# has tests that fail when the host is busy at the wrong moment (PERF.md, PR 26:
# test_dist_runner's speculation threshold, test_bpe's 2 s wall limit), and
# under `--dist loadfile` a file added in the middle of the alphabet moves every
# later file to another worker and moment. Every xdist worker applies the same
# reorder, so the workers still collect alike (tests/chipbench/conftest.py does
# the same for its rehearsed runs).
_AFTER_THE_SEEDS_FILES = ("tests/test_device_timeline.py",
                          "tests/test_trace_gaps.py")


def pytest_collection_modifyitems(items):
    late = [i for i in items if i.nodeid.startswith(_AFTER_THE_SEEDS_FILES)]
    if late and len(late) < len(items):
        items[:] = [i for i in items
                    if not i.nodeid.startswith(_AFTER_THE_SEEDS_FILES)] + late
