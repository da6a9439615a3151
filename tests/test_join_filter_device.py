"""The runtime join filter's device keep-mask: the probe side's staged key
lanes against a bit table of the build keys addressed by ``key - lo``
(``exchange/joinfilter.py``). It is exact; a build whose keys span more
than ``DIRECT_MAX_RANGE`` values keeps the host Bloom path. A resident key
column is read from the partition's stage cache, neither hashed nor staged
again, and a partition gains no residency from its filter."""

import contextlib
import dataclasses
import datetime

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

import daft_tpu as dt
from daft_tpu import col
from daft_tpu.context import get_context
from daft_tpu.datatypes import DataType
from daft_tpu.exchange import joinfilter
from daft_tpu.exchange.joinfilter import (DIRECT_MAX_RANGE, JoinFilterBuilder,
                                          prune_partition)
from daft_tpu.execution import ExecutionContext, RuntimeStats
from daft_tpu.micropartition import MicroPartition
from daft_tpu.table import Table

from device_mode import real_tpu_mode_cfg

N = 5000


@contextlib.contextmanager
def _device(min_rows=8, x64=True):
    ctx = get_context()
    old = ctx.execution_config
    ctx.execution_config = dataclasses.replace(
        old, enable_result_cache=False, use_device_kernels=True,
        device_min_rows=min_rows)
    try:
        if x64:
            yield ctx.execution_config
        else:
            with real_tpu_mode_cfg(device_min_rows=min_rows) as cfg:
                yield cfg
    finally:
        ctx.execution_config = old


def _filter(build: pa.Array, dtype: DataType):
    b = JoinFilterBuilder([col("k")], [dtype])
    b.add(Table.from_arrow(pa.table({"k": build})))
    return b.seal()


def _ints(rng, lo, hi, n, dtype):
    return rng.randint(lo, hi, n).astype(dtype)


def _case(name, rng):
    """(build, probe, dtype) arrow arrays for one case."""
    if name == "int32":
        b = pa.array(_ints(rng, 0, 50_000, 300, np.int32))
        p = pa.array(_ints(rng, 0, 50_000, N, np.int32))
        return b, p, DataType.int32()
    if name == "int64_over_2_31":
        base = 3 << 31
        b = pa.array(_ints(rng, 0, 40_000, 200, np.int64) + base)
        p = pa.array(_ints(rng, 0, 40_000, N, np.int64) + base)
        return b, p, DataType.int64()
    if name == "date":
        days = _ints(rng, 8000, 11000, 150, np.int32)
        pdays = _ints(rng, 8000, 11000, N, np.int32)
        b = pa.array(days).cast(pa.date32())
        p = pa.array(pdays).cast(pa.date32())
        return b, p, DataType.date()
    if name == "nulls":
        bv = _ints(rng, 0, 2000, 100, np.int64)
        pv = _ints(rng, 0, 2000, N, np.int64)
        b = pa.array(bv, mask=rng.rand(100) < 0.2)
        p = pa.array(pv, mask=rng.rand(N) < 0.2)
        return b, p, DataType.int64()
    if name == "outside_lo_hi":
        b = pa.array(_ints(rng, 1000, 2000, 100, np.int64))
        p = pa.array(_ints(rng, -5000, 9000, N, np.int64))
        return b, p, DataType.int64()
    if name == "one_key":
        b = pa.array([42], pa.int64())
        p = pa.array(_ints(rng, 0, 100, N, np.int64))
        return b, p, DataType.int64()
    if name == "over_direct_bound":
        spread = np.array([-(1 << 25), 0, 17, 1 << 24, 3 * DIRECT_MAX_RANGE],
                          dtype=np.int64)
        b = pa.array(np.concatenate([spread,
                                     _ints(rng, 0, 1 << 26, 400, np.int64)]))
        p = pa.array(np.concatenate([spread, _ints(rng, -(1 << 25), 1 << 26,
                                                   N, np.int64)]))
        return b, p, DataType.int64()
    raise AssertionError(name)


CASES = ("int32", "int64_over_2_31", "date", "nulls", "outside_lo_hi",
         "one_key", "over_direct_bound")


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "32bit"])
@pytest.mark.parametrize("case", CASES)
def test_device_mask_never_drops_a_build_key(case, x64):
    rng = np.random.RandomState(CASES.index(case))
    build, probe, dtype = _case(case, rng)
    # every build key is probed at least once
    probe = pa.concat_arrays([probe, build.cast(probe.type)])
    with _device(x64=x64) as cfg:
        jf = _filter(build, dtype)
        ctx = ExecutionContext(cfg, RuntimeStats())
        tbl = Table.from_arrow(pa.table({"k": probe}))
        mask = jf.keep_mask(tbl, [col("k")], ctx)
    counters = ctx.stats.snapshot()["counters"]
    member = np.asarray(pc.fill_null(
        pc.is_in(probe, value_set=build.drop_null()), False), dtype=bool)
    assert mask.shape == (len(probe),) and mask.dtype == np.bool_
    assert not (member & ~mask).any(), "a row the join would match was dropped"
    assert not mask[np.asarray(pc.is_null(probe), dtype=bool)].any()
    # 32-bit lanes cannot hold keys over 2**31: staging declines, the host
    # answers (the device join refuses the same column the same way); a
    # build spanning more than DIRECT_MAX_RANGE values keeps the host path
    on_host = (case == "int64_over_2_31" and not x64
               or case == "over_direct_bound")
    assert counters.get("join_filter_device_probes", 0) == (0 if on_host
                                                            else 1)
    assert (jf.keys is None) == (case == "over_direct_bound")
    if not on_host:
        np.testing.assert_array_equal(mask, member)  # bits by key - lo


@pytest.mark.parametrize("lo,hi", [(0, DIRECT_MAX_RANGE - 1),
                                   (0, DIRECT_MAX_RANGE),
                                   (-(1 << 63), (1 << 63) - 1)],
                         ids=["at_bound", "over_bound", "int64_extremes"])
def test_the_device_form_takes_builds_within_the_direct_bound(lo, hi):
    jf = _filter(pa.array([lo, hi], pa.int64()), DataType.int64())
    if hi - lo < DIRECT_MAX_RANGE:
        assert jf.keys.tolist() == [lo, hi]
    else:
        assert jf.keys is None  # the span does not wrap round int64


def _resident_part(keys: np.ndarray):
    from daft_tpu.kernels.device_join import _stage_key

    part = MicroPartition.from_pydict({"k": keys.tolist(),
                                       "v": list(range(len(keys)))})
    assert _stage_key(part.table(), col("k"), part.device_stage_cache())
    return part


def test_a_resident_key_column_is_neither_staged_nor_hashed(monkeypatch):
    rng = np.random.RandomState(4)
    keys = rng.randint(0, 100_000, N).astype(np.int64)
    build = pa.array(keys[:50])
    with _device() as cfg:
        jf = _filter(build, DataType.int64())
        part = _resident_part(keys)
        cached = dict(part.device_stage_cache())
        ctx = ExecutionContext(cfg, RuntimeStats())

        def no_hashing(cols):
            raise AssertionError("the probe side was hashed on the host")

        monkeypatch.setattr(joinfilter, "_hash_pair", no_hashing)
        out = prune_partition(part, jf, [col("k")], ctx)
    c = ctx.stats.snapshot()["counters"]
    assert c.get("stage_columns", 0) == 0 and c.get("stage_bytes", 0) == 0
    assert part.device_stage_cache().keys() == cached.keys()
    assert c["join_filter_device_probes"] == 1
    assert c["join_filter_resident_keys"] == 1
    assert c.get("join_filter_errors", 0) == 0
    want = np.isin(keys, keys[:50])
    assert out.to_pydict()["k"] == keys[want].tolist()
    assert c["join_filter_rows_pruned"] == int((~want).sum())


def test_a_partition_staged_anew_is_not_counted_resident():
    rng = np.random.RandomState(6)
    keys = rng.randint(0, 100_000, N).astype(np.int64)
    with _device() as cfg:
        jf = _filter(pa.array(keys[:50]), DataType.int64())
        part = MicroPartition.from_pydict({"k": keys.tolist()})
        ctx = ExecutionContext(cfg, RuntimeStats())
        out = prune_partition(part, jf, [col("k")], ctx)
    c = ctx.stats.snapshot()["counters"]
    assert c["join_filter_device_probes"] == 1
    assert c.get("join_filter_resident_keys", 0) == 0
    assert c.get("stage_columns", 0) == 1
    assert part.device_stage_cache() == {}  # staged without keeping
    assert sorted(out.to_pydict()["k"]) == sorted(
        keys[np.isin(keys, keys[:50])].tolist())


def test_below_device_min_rows_the_host_answers(monkeypatch):
    rng = np.random.RandomState(5)
    keys = rng.randint(0, 1000, 300).astype(np.int64)
    calls = []
    real = joinfilter._hash_pair

    def counted(cols):
        calls.append(len(cols[0]))
        return real(cols)

    monkeypatch.setattr(joinfilter, "_hash_pair", counted)
    with _device(min_rows=len(keys) + 1) as cfg:
        jf = _filter(pa.array(keys[:20]), DataType.int64())
        ctx = ExecutionContext(cfg, RuntimeStats())
        out = prune_partition(_resident_part(keys), jf, [col("k")], ctx)
    c = ctx.stats.snapshot()["counters"]
    assert c.get("join_filter_device_probes", 0) == 0
    assert c.get("join_filter_resident_keys", 0) == 0
    assert calls == [20, len(keys)]  # the build, then the probe on the host
    assert set(out.to_pydict()["k"]) == set(keys[:20].tolist())
    assert c["join_filter_rows_pruned"] == int((~np.isin(keys, keys[:20])).sum())


def test_string_and_multi_key_filters_keep_the_host_path():
    with _device() as cfg:
        b = JoinFilterBuilder([col("s")], [DataType.string()])
        b.add(Table.from_pydict({"s": ["a", "b"]}))
        jf = b.seal()
        assert jf.keys is None
        ctx = ExecutionContext(cfg, RuntimeStats())
        mask = jf.keep_mask(Table.from_pydict({"s": ["a", "c", "b"] * 10}),
                            [col("s")], ctx)
        assert mask.tolist() == [True, False, True] * 10
        b = JoinFilterBuilder([col("a"), col("b")],
                              [DataType.int64(), DataType.int64()])
        b.add(Table.from_pydict({"a": [1, 2], "b": [3, 4]}))
        assert b.seal().keys is None
    assert ctx.stats.snapshot()["counters"].get(
        "join_filter_device_probes", 0) == 0


def test_staged_reads_the_lanes_stage_table_columns_keeps():
    from daft_tpu.kernels.device import (size_bucket, stage_table_columns,
                                         staged)

    part = MicroPartition.from_pydict({"k": list(range(N)), "v": [1] * N})
    cache = part.device_stage_cache()
    assert not staged(cache, ["k"], N)
    assert stage_table_columns(part.table(), ["k"], size_bucket(N), cache)
    assert staged(cache, ["k"], N)
    assert not staged(cache, ["k", "v"], N)
    assert not staged(cache, ["k"], 2 * N + 1)  # another bucket
    assert not staged(None, ["k"], N)


@pytest.mark.parametrize("device", [True, False], ids=["device", "host"])
def test_a_projection_passes_its_inputs_lanes_on(device):
    from daft_tpu.kernels.device import size_bucket, x64_enabled
    from daft_tpu.physical import InMemoryOp, ProjectOp

    rng = np.random.RandomState(8)
    part = MicroPartition.from_pydict({
        "k": rng.randint(0, 1000, N).tolist(),
        "v": rng.randint(0, 1000, N).tolist(),
        "u": rng.randint(0, 1000, N).tolist()})
    exprs = [col("k"), col("v").alias("w"), (col("u") + 1).alias("x")]
    with _device(min_rows=8 if device else N + 1) as cfg:
        # k and u resident before the projection; v is staged by it, if at
        # all, and so dies with its input
        from daft_tpu.kernels.device_join import _stage_key

        for c in ("k", "u"):
            assert _stage_key(part.table(), col(c), part.device_stage_cache())
        op = ProjectOp(InMemoryOp([part], part.schema), exprs,
                       part.eval_expression_list(exprs).schema)
        ctx = ExecutionContext(cfg, RuntimeStats())
        out = ctx.run(op, part)
        b, x64 = size_bucket(N), x64_enabled()
    c = ctx.stats.snapshot()["counters"]
    assert c.get("device_projections", 0) == (1 if device else 0)
    cache, src = out.device_stage_cache(), part.device_stage_cache()
    assert cache[("k", b, x64)] is src[("k", b, x64)]
    assert set(cache) == {("k", b, x64)}  # not v (staged here), not x
    assert out.to_pydict()["w"] == part.to_pydict()["v"]


def test_a_selecting_projection_runs_no_program():
    from daft_tpu.kernels.device import size_bucket, x64_enabled
    from daft_tpu.physical import InMemoryOp, ProjectOp

    part = MicroPartition.from_pydict({"k": list(range(N)),
                                       "v": list(range(N))})
    exprs = [col("k"), col("k").alias("j"), col("v").alias("w")]
    with _device() as cfg:
        from daft_tpu.kernels.device_join import _stage_key

        assert _stage_key(part.table(), col("k"), part.device_stage_cache())
        op = ProjectOp(InMemoryOp([part], part.schema), exprs,
                       part.eval_expression_list(exprs).schema)
        ctx = ExecutionContext(cfg, RuntimeStats())
        assert not op.has_program and not op.device_pipelinable(ctx)
        assert ProjectOp(op.children[0], [col("k") + 1],
                         part.schema).has_program
        out = ctx.run(op, part)
        b, x64 = size_bucket(N), x64_enabled()
    c = ctx.stats.snapshot()["counters"]
    assert c.get("device_projections", 0) == 0
    assert c["host_projections"] == 1 and c.get("stage_columns", 0) == 0
    src = part.device_stage_cache()[("k", b, x64)]
    assert out.device_stage_cache() == {("k", b, x64): src,
                                        ("j", b, x64): src}
    assert out.to_pydict()["w"] == part.to_pydict()["v"]


# ---------------------------------------------------------------- Q17 e2e

@pytest.fixture(scope="module")
def tpch():
    from benchmarks import tpch_full

    return tpch_full.generate(scale=0.002, seed=7)


def _q17(tpch, device: bool, filters: bool, runs: int = 1, **cfg):
    from benchmarks import tpch_queries

    ctx = get_context()
    old = ctx.execution_config
    ctx.execution_config = dataclasses.replace(
        old, enable_result_cache=False, use_device_kernels=device,
        device_min_rows=8, runtime_join_filters=filters, **cfg)
    try:
        frames = {n: dt.from_arrow(tpch[n]).collect()
                  for n in ("lineitem", "part")}
        for _ in range(runs):
            df = dt.sql(tpch_queries.SQL[17], **frames)
            got = df.to_pydict()
        return got, df.stats.snapshot()["counters"]
    finally:
        ctx.execution_config = old


def test_q17_answers_alike_with_filters_and_device_on_and_off(tpch):
    runs = {(d, f): _q17(tpch, d, f) for d in (True, False)
            for f in (True, False)}
    (want,) = {str(v) for v in [runs[(False, False)][0]]}
    for (d, f), (got, counters) in runs.items():
        assert str(got) == want, (d, f)
        if f:
            assert counters.get("join_filter_rows_pruned", 0) > 0
        else:
            assert counters.get("join_filter_built", 0) == 0
    on = runs[(True, True)][1]
    assert on.get("join_filter_device_probes", 0) >= 1
    # the outer join's LINEITEM was staged by the average under it
    assert on.get("join_filter_resident_keys", 0) >= 1
    assert on.get("join_filter_errors", 0) == 0
    assert runs[(False, True)][1].get("join_filter_device_probes", 0) == 0
    assert (on["join_filter_rows_pruned"]
            >= runs[(False, True)][1]["join_filter_rows_pruned"])


def test_q17_reads_resident_keys_over_partitions_larger_than_a_morsel(tpch):
    # LINEITEM larger than a morsel: the projection that selects Q17's three
    # columns from it neither streams as morsels nor runs a program, so the
    # outer join's filter reads the lanes the average left resident
    assert tpch["lineitem"].num_rows > 4 * 1024
    want, _ = _q17(tpch, device=False, filters=False)
    got, c = _q17(tpch, device=True, filters=True, runs=2,
                  morsel_size_rows=1024)
    assert str(got) == str(want)
    assert c["join_filter_resident_keys"] >= 1
    assert c.get("stream_morsels", 0) == 0
    assert c.get("join_filter_errors", 0) == 0


def test_date_keys_probe_with_python_dates():
    # a DATE build key collected as days: the device lanes are days too
    d = [datetime.date(1995, 1, 1) + datetime.timedelta(days=i)
         for i in range(0, 400, 7)]
    with _device() as cfg:
        b = JoinFilterBuilder([col("k")], [DataType.date()])
        b.add(Table.from_pydict({"k": d[:5]}))
        jf = b.seal()
        ctx = ExecutionContext(cfg, RuntimeStats())
        mask = jf.keep_mask(Table.from_pydict({"k": d}), [col("k")], ctx)
    assert mask.tolist() == [i < 5 for i in range(len(d))]
    assert ctx.stats.snapshot()["counters"]["join_filter_device_probes"] == 1
