"""A device map over a resident partition larger than a morsel is one launch
a partition over a stage view of it (``execution._unsplit``,
``MicroPartition.stage_view``): it reads the lanes the partition holds and
leaves it no new ones. What the morsel stream used to take, with the
answers of the host path and of the partition-granular path."""

import dataclasses

import numpy as np
import pyarrow as pa
import pytest

import daft_tpu as dt
from daft_tpu import col
from daft_tpu.context import get_context
from daft_tpu.execution import RuntimeStats, _stage_views
from daft_tpu.micropartition import MicroPartition

ROWS = 10_000
MORSEL = 1024


@pytest.fixture
def cfg():
    ctx = get_context()
    old = ctx.execution_config
    ctx.execution_config = dataclasses.replace(
        old, enable_result_cache=False, use_device_kernels=True,
        device_min_rows=8, device_residency=True, streaming_execution=True,
        morsel_size_rows=MORSEL)
    yield ctx.execution_config
    ctx.execution_config = old


def _frame(n_parts):
    rng = np.random.RandomState(11)
    df = dt.from_arrow(pa.table({
        "a": rng.randint(0, 100, ROWS).astype(np.int64),
        "b": rng.rand(ROWS),
        "c": np.arange(ROWS, dtype=np.int64)}))
    if n_parts > 1:
        df = df.repartition(n_parts)
    df = df.collect()
    parts = df._result.partitions
    assert len(parts) == n_parts and all(len(p) > MORSEL for p in parts)
    return df, parts


def _keys(parts):
    return [sorted(p.device_stage_cache()) for p in parts]


# the shape, the counter that counts its launches, and the columns a launch
# stages: a fused map's selected columns come from the host, not the device
SHAPES = {
    "filter": (lambda f: f.where((col("a") > 50) & (col("b") < 0.7)),
               "device_filter_dispatches", 2),
    "filter_select": (lambda f: f.where(col("a") > 50).select(
        col("c"), col("b")), "device_fused_map_dispatches", 1),
}


@pytest.mark.parametrize("n_parts", [1, 3])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_a_map_over_a_large_partition_is_one_launch(cfg, shape, n_parts):
    build, dispatches, staged = SHAPES[shape]
    f, _ = _frame(n_parts)
    q = build(f)
    got = q.to_arrow()
    c = q.stats.snapshot()["counters"]
    assert c.get("stream_morsels", 0) == 0
    assert c[dispatches] == n_parts
    assert c["device_filters"] == n_parts
    assert c["device_maps_unsplit"] == n_parts
    assert c["stage_columns"] == staged * n_parts
    ctx = get_context()
    for knobs in ({"use_device_kernels": False},
                  {"streaming_execution": False}):
        ctx.execution_config = dataclasses.replace(cfg, **knobs)
        assert build(f).to_arrow().equals(got), knobs


@pytest.mark.parametrize("n_parts", [1, 3])
def test_the_source_keeps_its_stage_cache(cfg, n_parts):
    f, parts = _frame(n_parts)
    # lanes of "a" resident beforehand: an aggregate keeps what it stages
    f.groupby("a").agg(col("c").count().alias("n")).to_pydict()
    before = _keys(parts)
    assert all(before)
    for _ in range(2):
        q = SHAPES["filter"][0](f)
        q.to_pydict()
        assert q.stats.snapshot()["counters"]["device_maps_unsplit"] == n_parts
    assert _keys(parts) == before


@pytest.mark.parametrize("n_parts", [1, 3])
def test_a_resident_lane_is_read_not_staged(cfg, n_parts):
    f, parts = _frame(n_parts)
    f.groupby("a").agg(col("c").count().alias("n")).to_pydict()
    assert all(any(k[0] == "a" for k in p.device_stage_cache())
               for p in parts)
    q = SHAPES["filter"][0](f)
    q.to_pydict()
    c = q.stats.snapshot()["counters"]
    assert c["device_maps_unsplit"] == n_parts
    assert c["stage_columns"] == n_parts  # "b" alone, once a partition
    assert not any(k[0] == "b" for p in parts
                   for k in p.device_stage_cache())


def test_a_mixed_chain_keeps_its_path_and_lanes(cfg):
    # unfused, the filter sits over the host's selecting projection, which
    # hands the source's lanes on: the partition path, as before
    cfg.expr_fusion = False
    f, parts = _frame(3)
    f.groupby("a").agg(col("c").count().alias("n")).to_pydict()
    before = _keys(parts)

    def build():
        return f.where(col("a") > 50).select(col("a"), col("b"))
    q = build()
    got = q.to_arrow()
    c = q.stats.snapshot()["counters"]
    assert c.get("device_maps_unsplit", 0) == 0
    assert c.get("stream_morsels", 0) == 0
    assert c["device_filter_dispatches"] == 3
    assert c.get("stage_columns", 0) == 0  # "a" read through the projection
    assert _keys(parts) == before
    cfg.use_device_kernels = False
    assert build().to_arrow().equals(got)


@pytest.mark.parametrize("kind", ["map_at_a_morsel", "aggregate"])
def test_what_keeps_its_lanes(cfg, kind):
    # a partition at or under a morsel, and an aggregate over one larger,
    # keep the partition path and what it stages
    f, parts = _frame(1)
    if kind == "map_at_a_morsel":
        cfg.morsel_size_rows = ROWS
        q = SHAPES["filter"][0](f)
    else:
        q = f.where(col("b") < 0.5).groupby("a").agg(
            col("c").sum().alias("s"))
    q.to_pydict()
    assert q.stats.snapshot()["counters"].get("device_maps_unsplit", 0) == 0
    assert {k[0] for k in parts[0].device_stage_cache()} >= {"a", "b"}


def test_a_fused_maps_selected_columns_are_the_inputs_own(cfg):
    # in 32-bit mode a float64 column through the device comes back float32;
    # selected by the host it stays the input's, as on the host path
    import jax

    x64_was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    try:
        f, _ = _frame(1)
        q = SHAPES["filter_select"][0](f)
        got = q.to_arrow()
        c = q.stats.snapshot()["counters"]
        assert c["device_fused_map_dispatches"] == 1
        # the mask alone comes back: values and validity, 16384 lanes each
        assert c["gather_bytes"] == 2 * 16384
        cfg.use_device_kernels = False
        assert SHAPES["filter_select"][0](f).to_arrow().equals(got)
    finally:
        jax.config.update("jax_enable_x64", x64_was)


def test_a_view_shares_rows_not_lanes():
    part = MicroPartition.from_pydict({"x": [1, 2, 3]})
    part.device_stage_cache()["held"] = 1
    view = part.stage_view()
    assert view.table() is part.table()
    assert view.device_stage_cache() == {"held": 1}
    view.device_stage_cache()["staged"] = 2
    assert part.device_stage_cache() == {"held": 1}
    view.drop_staged()
    assert view.device_stage_cache() == {}
    assert part.device_stage_cache() == {"held": 1}


def test_a_view_drops_its_lanes_once_the_next_is_asked_for():
    stats = RuntimeStats()
    ctx = type("Ctx", (), {"stats": stats})()
    parts = [MicroPartition.from_pydict({"x": [i]}) for i in range(3)]
    views = _stage_views(iter(parts), ctx)
    first = next(views)
    first.device_stage_cache()["staged"] = 1
    second = next(views)
    assert first.device_stage_cache() == {}
    second.device_stage_cache()["staged"] = 1
    third = next(views)
    third.device_stage_cache()["staged"] = 1
    assert next(views, None) is None
    assert second.device_stage_cache() == third.device_stage_cache() == {}
    assert stats.counters["device_maps_unsplit"] == 3
    assert all(p.device_stage_cache() == {} for p in parts)
