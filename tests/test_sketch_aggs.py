"""Mergeable-sketch aggregation subsystem (daft_tpu/sketch/, ISSUE 3).

Pins the two-phase contract: multi-partition approx_count_distinct /
approx_percentiles plan as sketch->merge stages whose exchange ships
serialized sketch BYTES (never raw rows), estimates carry property-tested
error bounds (HLL relative error <= 2 x 1.04/sqrt(m); quantile rank error
<= 1/cap), results are partition-count invariant, and the breaker/fault
paths of the new `sketch.merge` / `collective.sketch` sites behave
deterministically.
"""

import numpy as np
import pytest

import daft_tpu as dt
from daft_tpu import col, faults
from daft_tpu.context import get_context
from daft_tpu.optimizer import optimize
from daft_tpu.physical import (
    AggregateOp,
    GatherOp,
    ProjectOp,
    ShuffleOp,
    aggs_decomposable,
    translate,
)
from daft_tpu.sketch import (
    HLL_M,
    HLL_STANDARD_ERROR,
    QUANTILE_CAP,
    SKETCH_STAGE_KINDS,
)


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faults.disarm()


def _rand_frame(n=20000, card=4000, groups=8, parts=8, seed=0):
    rng = np.random.RandomState(seed)
    data = {"k": (np.arange(n) % groups).tolist(),
            "v": rng.randint(0, card, n).tolist(),
            "x": rng.rand(n).tolist()}
    return dt.from_pydict(data).into_partitions(parts), data


def _physical(df):
    return translate(optimize(df._plan), get_context().execution_config)


def _find_ops(op, klass):
    out = [op] if isinstance(op, klass) else []
    for c in op.children:
        out.extend(_find_ops(c, klass))
    return out


def _agg_kinds(agg_op):
    from daft_tpu.expressions import AggExpr, Alias

    kinds = set()
    for e in agg_op.aggregations:
        n = e._node
        while isinstance(n, Alias):
            n = n.child
        if isinstance(n, AggExpr):
            kinds.add(n.kind)
    return kinds


# ---------------------------------------------------------------------------
# plan shape: sketch -> exchange(bytes) -> merge -> estimate
# ---------------------------------------------------------------------------

class TestPlanShape:
    def test_grouped_approx_plans_sketch_merge_stages(self):
        df, _ = _rand_frame()
        plan = _physical(df.groupby("k").agg(
            col("v").approx_count_distinct().alias("acd")))
        shuffles = _find_ops(plan, ShuffleOp)
        assert len(shuffles) == 1
        # the exchange's child is the stage-1 SKETCH aggregate: rows crossing
        # the shuffle are one Binary sketch per (partition, group), NOT the
        # raw input rows
        child = shuffles[0].children[0]
        assert isinstance(child, AggregateOp)
        assert _agg_kinds(child) == {"sketch_hll"}
        # above the exchange: the register-merge stage, then the estimate
        merge_stage = [op for op in _find_ops(plan, AggregateOp)
                       if "merge_sketch_hll" in _agg_kinds(op)]
        assert len(merge_stage) == 1
        assert any("hll_estimate" in e._node.display()
                   for p in _find_ops(plan, ProjectOp) for e in p.exprs)

    def test_global_approx_gathers_sketches_not_rows(self):
        df, _ = _rand_frame()
        plan = _physical(df.agg(col("x").approx_percentiles(0.5).alias("p")))
        gathers = _find_ops(plan, GatherOp)
        assert len(gathers) == 1
        child = gathers[0].children[0]
        assert isinstance(child, AggregateOp)
        assert _agg_kinds(child) == {"sketch_quantile"}
        assert not _find_ops(plan, ShuffleOp)

    def test_mixed_agg_list_decomposes_in_one_pipeline(self):
        df, data = _rand_frame()
        q = df.groupby("k").agg(col("v").sum().alias("s"),
                                col("v").approx_count_distinct().alias("acd"))
        plan = _physical(q)
        # one exchange total: plain partials and sketches ride together
        assert len(_find_ops(plan, ShuffleOp)) == 1
        out = q.collect().to_pydict()
        import collections

        sums = collections.defaultdict(int)
        for k, v in zip(data["k"], data["v"]):
            sums[k] += v
        got = dict(zip(out["k"], out["s"]))
        assert got == dict(sums)

    def test_explain_shows_sketch_stages(self):
        df, _ = _rand_frame()
        text = df.groupby("k").agg(
            col("v").approx_count_distinct()).explain(show_all=True)
        assert "sketch_hll" in text
        assert "merge_sketch_hll" in text
        assert "hll_estimate" in text

    def test_disabled_knob_restores_raw_row_plan(self):
        cfg = get_context().execution_config
        df, _ = _rand_frame()
        q = df.groupby("k").agg(col("v").approx_count_distinct())
        prev = cfg.sketch_aggregations
        try:
            cfg.sketch_aggregations = False
            plan = _physical(q)
        finally:
            cfg.sketch_aggregations = prev
        shuffles = _find_ops(plan, ShuffleOp)
        assert len(shuffles) == 1
        # raw-row plan: the shuffle's input is NOT a sketch stage
        assert not isinstance(shuffles[0].children[0], AggregateOp)

    def test_aggs_decomposable_gate(self):
        e = [col("v").approx_count_distinct()]
        assert not aggs_decomposable(e)
        assert aggs_decomposable(e, include_sketch=True)
        assert not aggs_decomposable([col("v").count_distinct()],
                                     include_sketch=True)


# ---------------------------------------------------------------------------
# exchange payload: O(sketch_size x partitions), never raw rows
# ---------------------------------------------------------------------------

class TestExchangePayload:
    def test_rows_exchanged_bounded_by_partitions_x_groups(self):
        n, parts, groups = 20000, 8, 8
        df, _ = _rand_frame(n=n, parts=parts, groups=groups)
        q = df.groupby("k").agg(col("v").approx_count_distinct())
        q.collect()
        exchanged = q.stats.snapshot()["counters"]["exchange_rows"]
        assert exchanged <= parts * groups  # sketch rows
        assert exchanged < n / 100  # and nothing like the raw input

    def test_before_after_counter_comparison(self):
        import bench

        out = bench.measure_sketch_exchange(n_rows=30000, n_parts=8)
        assert out["raw_rows_exchanged"] == 30000
        assert out["sketch_rows_exchanged"] <= 8 * 16
        assert out["exchange_reduction_x"] > 100
        # bytes tracked too: rows alone can't see payload inflation
        assert out["sketch_bytes_exchanged"] < out["raw_bytes_exchanged"]
        assert out["bytes_reduction_x"] > 1

    def test_high_group_cardinality_stays_sparse(self):
        # the SF100 motivation: one group per row must NOT cost 16 KiB per
        # group on the exchange (adaptive sparse encoding, hll.SPARSE_LIMIT)
        n = 20000
        df = dt.from_pydict({"k": list(range(n)),
                             "v": list(range(n))}).into_partitions(4)
        q = df.groupby("k").agg(col("v").approx_count_distinct().alias("a"))
        out = q.collect().to_pydict()
        assert all(a == 1 for a in out["a"])
        c = q.stats.snapshot()["counters"]
        # sparse sketches: ~tens of bytes per group, nowhere near 16 KiB
        assert c["exchange_bytes"] < n * 256
        assert c["exchange_bytes"] > 0

    def test_sparse_dense_encodings_merge_identically(self):
        from daft_tpu.sketch import hll

        rng = np.random.RandomState(3)
        arr = __import__("pyarrow").array(rng.randint(0, 100000, 30000))
        dense_regs = hll.build_grouped_registers(arr, None, 1)  # well occupied
        via_binary = hll.binary_to_registers(hll.registers_to_binary(dense_regs))
        assert np.array_equal(dense_regs, via_binary)
        # a sparse sketch round-trips through the same decoder
        small = __import__("pyarrow").array([1, 2, 3])
        sregs = hll.build_grouped_registers(small, None, 1)
        sbin = hll.registers_to_binary(sregs)
        assert len(sbin[0].as_py()) < 100  # sparse: a few entries, not 16 KiB
        assert np.array_equal(sregs, hll.binary_to_registers(sbin))


# ---------------------------------------------------------------------------
# property-tested error bounds (enforced, not eyeballed)
# ---------------------------------------------------------------------------

class TestErrorBounds:
    @pytest.mark.parametrize("card,seed", [(100, 1), (1000, 2), (5000, 3),
                                           (20000, 4), (60000, 5)])
    def test_hll_relative_error_bound(self, card, seed):
        rng = np.random.RandomState(seed)
        vals = rng.randint(0, card * 10, card * 3)
        exact = len(np.unique(vals))
        df = dt.from_pydict({"v": vals.tolist()}).into_partitions(7)
        got = df.agg(col("v").approx_count_distinct().alias("a")) \
            .collect().to_pydict()["a"][0]
        assert abs(got - exact) / exact <= 2 * HLL_STANDARD_ERROR

    @pytest.mark.parametrize("n,seed", [(1000, 1), (50000, 2), (200000, 3)])
    def test_quantile_rank_error_bound(self, n, seed):
        rng = np.random.RandomState(seed)
        vals = np.sort(rng.randn(n) * 100)
        df = dt.from_pydict({"x": vals.tolist()}).into_partitions(6)
        qs = [0.01, 0.25, 0.5, 0.75, 0.99]
        got = df.agg(col("x").approx_percentiles(qs).alias("p")) \
            .collect().to_pydict()["p"][0]
        eps = 1.0 / QUANTILE_CAP
        for q, est in zip(qs, got):
            # rank of the estimate must be within eps of the target rank
            # (plus one-partition slack: each of the 6 partial sketches
            # contributes its own <= eps summary error before the merge)
            rank = np.searchsorted(vals, est) / n
            assert abs(rank - q) <= 8 * eps, (q, est, rank)

    def test_grouped_bounds_hold_per_group(self):
        df, data = _rand_frame(n=60000, card=8000, groups=4, parts=8)
        out = df.groupby("k").agg(
            col("v").approx_count_distinct().alias("a")).collect().to_pydict()
        import collections

        exact = collections.defaultdict(set)
        for k, v in zip(data["k"], data["v"]):
            exact[k].add(v)
        for k, got in zip(out["k"], out["a"]):
            e = len(exact[k])
            assert abs(got - e) / e <= 2 * HLL_STANDARD_ERROR


# ---------------------------------------------------------------------------
# determinism / invariance
# ---------------------------------------------------------------------------

class TestInvariance:
    def test_partition_count_invariant(self):
        # n below QUANTILE_CAP: partial sketches never compress, so both
        # estimators must be BIT-identical whatever the partitioning (HLL
        # register merge is exactly associative at any size)
        _, data = _rand_frame(n=3000, card=900)
        results = []
        for parts in (1, 2, 8):
            df = dt.from_pydict(data).into_partitions(parts)
            out = df.agg(col("v").approx_count_distinct().alias("a"),
                         col("x").approx_percentiles(0.5).alias("p")) \
                .collect().to_pydict()
            results.append((out["a"][0], out["p"][0]))
        assert results[0] == results[1] == results[2]

    def test_partition_variance_within_rank_bound_when_compressed(self):
        # above the cap the quantile sketches compress per partition; the
        # estimates may drift across partitionings but only within the
        # documented rank error
        _, data = _rand_frame(n=40000)
        xs = np.sort(np.asarray(data["x"]))
        for parts in (1, 8):
            df = dt.from_pydict(data).into_partitions(parts)
            p = df.agg(col("x").approx_percentiles(0.5).alias("p")) \
                .collect().to_pydict()["p"][0]
            rank = np.searchsorted(xs, p) / len(xs)
            assert abs(rank - 0.5) <= 8.0 / QUANTILE_CAP
        acd = [dt.from_pydict(data).into_partitions(parts)
               .agg(col("v").approx_count_distinct().alias("a"))
               .collect().to_pydict()["a"][0] for parts in (1, 8)]
        assert acd[0] == acd[1]  # HLL stays exactly partition-invariant

    def test_single_partition_grouped_matches_two_phase(self):
        _, data = _rand_frame(n=5000, card=800)
        one = dt.from_pydict(data).groupby("k").agg(
            col("v").approx_count_distinct().alias("a")).collect().to_pydict()
        many = dt.from_pydict(data).into_partitions(8).groupby("k").agg(
            col("v").approx_count_distinct().alias("a")).collect().to_pydict()
        assert dict(zip(one["k"], one["a"])) == dict(zip(many["k"], many["a"]))

    def test_rerun_deterministic(self):
        df, _ = _rand_frame(n=30000)
        q = lambda: df.groupby("k").agg(  # noqa: E731
            col("x").approx_percentiles([0.1, 0.9]).alias("p")) \
            .collect().to_pydict()
        a, b = q(), q()
        assert a == b


# ---------------------------------------------------------------------------
# kernels: serialization + edge cases
# ---------------------------------------------------------------------------

class TestKernels:
    def test_hll_roundtrip_and_merge_associativity(self):
        from daft_tpu.kernels.sketches import HllSketch

        rng = np.random.RandomState(0)
        h1 = rng.randint(0, 2**63, 1000).astype(np.uint64)
        h2 = rng.randint(0, 2**63, 1000).astype(np.uint64)
        a = HllSketch().add_hashes(h1)
        b = HllSketch().add_hashes(h2)
        whole = HllSketch().add_hashes(np.concatenate([h1, h2]))
        merged = HllSketch.from_bytes(a.to_bytes()).merge(
            HllSketch.from_bytes(b.to_bytes()))
        assert np.array_equal(merged.registers, whole.registers)

    def test_quantile_bytes_roundtrip(self):
        from daft_tpu.kernels.sketches import QuantileSketch

        s = QuantileSketch().add(np.arange(100.0))
        r = QuantileSketch.from_bytes(s.to_bytes())
        assert np.array_equal(r.values, s.values)
        assert np.array_equal(r.weights, s.weights)
        assert r.quantiles([0.5])[0] == s.quantiles([0.5])[0]

    def test_quantile_compress_deterministic(self):
        from daft_tpu.kernels.sketches import quantile_compress

        v = np.random.RandomState(3).rand(20000)
        w = np.ones(20000)
        a = quantile_compress(v.copy(), w.copy(), 512)
        b = quantile_compress(v.copy(), w.copy(), 512)
        assert np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])
        assert len(a[0]) == 512

    def test_empty_and_all_null_inputs(self):
        df = dt.from_pydict({"k": [0, 0, 1], "v": [None, None, None],
                             "x": [None, None, None]}).into_partitions(2)
        out = df.groupby("k").agg(
            col("v").approx_count_distinct().alias("a"),
            col("x").cast(dt.DataType.float64())
            .approx_percentiles(0.5).alias("p")).collect().to_pydict()
        assert out["a"] == [0, 0]
        assert out["p"] == [None, None]

    def test_binary_sketch_dtype_on_stage_schema(self):
        from daft_tpu.expressions import AggExpr, Expression

        e = Expression(AggExpr("sketch_hll", col("v")._node))
        f = e._node.to_field(dt.from_pydict({"v": [1]}).schema)
        assert f.dtype == dt.DataType.binary()

    def test_corrupt_sketch_raises_typed_error(self):
        from daft_tpu.kernels.sketches import estimate_from_registers

        bad = np.full((1, HLL_M), 200, dtype=np.uint8)  # rank > q+1
        with pytest.raises(dt.errors.DaftValueError):
            estimate_from_registers(bad)
        from daft_tpu.sketch.hll import binary_to_registers

        with pytest.raises(dt.errors.DaftValueError):
            binary_to_registers(
                dt.Series.from_pylist([b"xx"], "s", dt.DataType.binary()))

    def test_saturated_sketch_finite_ceiling(self):
        from daft_tpu.kernels.sketches import estimate_from_registers

        sat = np.full((1, HLL_M), 51, dtype=np.uint8)  # every register maxed
        out = estimate_from_registers(sat)
        assert out[0] == 1 << 63  # finite "past the estimable range"

    def test_quantile_merge_preserves_custom_cap(self):
        from daft_tpu.kernels.sketches import (quantile_state_from_bytes,
                                               quantile_state_to_bytes)
        from daft_tpu.sketch import quantile as q

        big_cap = 16384
        v = np.random.RandomState(0).rand(20000)
        sk = quantile_state_to_bytes(v, np.ones(len(v)), big_cap)
        s = dt.Series.from_pylist([sk, sk], "s", dt.DataType.binary())
        merged = q.merge_grouped(s, np.zeros(2, np.int64), 1)
        mv, mw, cap = quantile_state_from_bytes(merged.to_pylist()[0])
        assert cap == big_cap  # merging never lowers a sketch's precision
        assert len(mv) <= big_cap

    def test_stage_kind_registry(self):
        assert SKETCH_STAGE_KINDS == {"sketch_hll", "sketch_quantile",
                                      "merge_sketch_hll",
                                      "merge_sketch_quantile"}
        assert HLL_M == 1 << 14


# ---------------------------------------------------------------------------
# fault sites + breaker paths (deterministically testable, DTL004-covered)
# ---------------------------------------------------------------------------

class TestFaultSites:
    def test_sites_registered(self):
        assert "sketch.merge" in faults.SITES
        assert "collective.sketch" in faults.SITES

    def test_sketch_merge_fault_fires_and_propagates(self):
        df, _ = _rand_frame(n=2000, parts=4)
        q = df.groupby("k").agg(col("v").approx_count_distinct())
        with faults.inject("sketch.merge", "always"):
            with pytest.raises(dt.errors.DaftTransientError):
                q.collect()
        snap = faults.snapshot()
        assert snap["armed"] == {}  # scoped injection disarmed on exit
        assert snap["injected"]["sketch.merge"] >= 1

    def test_sketch_merge_heals_after_first_n(self):
        _, data = _rand_frame(n=2000, parts=4)
        with faults.inject("sketch.merge", "first_n", n=1):
            df = dt.from_pydict(data).into_partitions(4)
            q = df.groupby("k").agg(col("v").approx_count_distinct().alias("a"))
            with pytest.raises(dt.errors.DaftTransientError):
                q.collect()
            # site healed: a fresh run of the same query succeeds
            q2 = dt.from_pydict(data).into_partitions(4).groupby("k").agg(
                col("v").approx_count_distinct().alias("a"))
            out = q2.collect().to_pydict()
            assert len(out["a"]) == 8
            assert faults.snapshot()["injected"]["sketch.merge"] == 1

    def test_collective_sketch_fault_falls_back_to_host(self):
        jax = pytest.importorskip("jax")
        if len(jax.devices()) < 2:
            pytest.skip("needs a multi-device CPU mesh")
        from daft_tpu.execution import execute_plan
        from daft_tpu.parallel import MeshExecutionContext, default_mesh

        _, data = _rand_frame(n=4000, card=500)
        df = dt.from_pydict(data).into_partitions(4)
        q = df.agg(col("v").approx_count_distinct().alias("a"))
        cfg = get_context().execution_config
        prev = cfg.use_device_kernels
        try:
            cfg.use_device_kernels = True
            ctx = MeshExecutionContext(cfg, mesh=default_mesh(8))
            with faults.inject("collective.sketch", "always"):
                plan = translate(optimize(q._plan), cfg)
                parts = list(execute_plan(plan, ctx, trace=False))
            got = parts[0].to_pydict()["a"][0]
        finally:
            cfg.use_device_kernels = prev
        # host merge took over with an identical estimate
        want = dt.from_pydict(data).agg(
            col("v").approx_count_distinct().alias("a")) \
            .collect().to_pydict()["a"][0]
        assert got == want
        assert ctx.stats.counters.get("collective_breaker_trips", 0) >= 0
        assert faults.snapshot()["injected"]["collective.sketch"] >= 1


# ---------------------------------------------------------------------------
# device paths: mesh collective merge + breaker-guarded register scatter
# ---------------------------------------------------------------------------

class TestDevicePaths:
    def test_mesh_collective_register_merge(self):
        jax = pytest.importorskip("jax")
        if len(jax.devices()) < 2:
            pytest.skip("needs a multi-device CPU mesh")
        from daft_tpu.execution import execute_plan
        from daft_tpu.parallel import MeshExecutionContext, default_mesh

        _, data = _rand_frame(n=4000, card=700)
        df = dt.from_pydict(data).into_partitions(4)
        q = df.agg(col("v").approx_count_distinct().alias("a"))
        cfg = get_context().execution_config
        prev = cfg.use_device_kernels
        try:
            cfg.use_device_kernels = True
            ctx = MeshExecutionContext(cfg, mesh=default_mesh(8))
            plan = translate(optimize(q._plan), cfg)
            parts = list(execute_plan(plan, ctx, trace=False))
        finally:
            cfg.use_device_kernels = prev
        got = parts[0].to_pydict()["a"][0]
        want = dt.from_pydict(data).agg(
            col("v").approx_count_distinct().alias("a")) \
            .collect().to_pydict()["a"][0]
        assert got == want  # register max over ICI == host register max
        assert ctx.stats.counters.get("collective_sketch_merges", 0) >= 1

    def test_register_allmerge_collective_matches_numpy(self):
        jax = pytest.importorskip("jax")
        if len(jax.devices()) < 2:
            pytest.skip("needs a multi-device CPU mesh")
        from daft_tpu.parallel import MeshExecutionContext, default_mesh

        cfg = get_context().execution_config
        ctx = MeshExecutionContext(cfg, mesh=default_mesh(8))
        rng = np.random.RandomState(0)
        regs = rng.randint(0, 30, (5, HLL_M)).astype(np.uint8)
        out = ctx.try_sketch_register_merge(regs)
        assert out is not None
        assert np.array_equal(out, regs.max(axis=0))

    def test_device_register_scatter_matches_host(self):
        pytest.importorskip("jax")
        from daft_tpu.sketch.device import hll_scatter_device
        from daft_tpu.sketch.hll import build_grouped_registers, scatter_operands
        import pyarrow as pa

        rng = np.random.RandomState(1)
        arr = pa.array(rng.randint(0, 1000, 5000))
        codes = rng.randint(0, 4, 5000).astype(np.int64)
        host = build_grouped_registers(arr, codes, 4)
        gcodes, idx, rank = scatter_operands(arr, codes)
        dev = hll_scatter_device(gcodes, idx, rank, 4)
        assert dev is not None
        assert np.array_equal(host, dev)

    def test_sketch_build_device_route_with_breaker_fallback(self):
        pytest.importorskip("jax")
        from daft_tpu.execution import ExecutionContext
        from daft_tpu.micropartition import MicroPartition

        cfg = get_context().execution_config
        prev_dev, prev_min = cfg.use_device_kernels, cfg.device_min_rows
        try:
            cfg.use_device_kernels = True
            cfg.device_min_rows = 1
            ctx = ExecutionContext(cfg)
            part = MicroPartition.from_pydict(
                {"v": list(range(2000)) * 2})
            from daft_tpu.expressions import AggExpr, Expression
            from daft_tpu.physical import AggregateOp, InMemoryOp

            aggs = [Expression(AggExpr("sketch_hll", col("v")._node))
                    .alias("s")]
            op = AggregateOp(InMemoryOp([part], part.schema), aggs, [],
                             part.schema)
            out = ctx.run(op, part)
            assert ctx.stats.counters.get("device_sketch_builds") == 1
            # breaker path: an injected device fault falls back to host
            # with an identical sketch
            ctx2 = ExecutionContext(cfg)
            with faults.inject("device.kernel", "always"):
                out2 = ctx2.run(op, part)
            assert not ctx2.stats.counters.get("device_sketch_builds")
            assert out.to_pydict() == out2.to_pydict()
        finally:
            cfg.use_device_kernels = prev_dev
            cfg.device_min_rows = prev_min


# ---------------------------------------------------------------------------
# observability: throughput instrumentation rides the new stages
# ---------------------------------------------------------------------------

class TestThroughputStats:
    def test_op_throughput_populated(self):
        df, _ = _rand_frame(n=10000)
        q = df.groupby("k").agg(col("v").approx_count_distinct())
        q.collect()
        tput = q.stats.op_throughput()
        assert tput, "per-op throughput should be recorded"
        agg = next((v for k, v in tput.items() if "Aggregate" in k), None)
        assert agg is not None
        assert agg["rows_per_sec"] > 0
        snap = q.stats.snapshot()
        assert "op_bytes" in snap

    def test_explain_analyze_renders_throughput_columns(self):
        df, _ = _rand_frame(n=5000)
        text = df.groupby("k").agg(
            col("v").approx_count_distinct()).explain_analyze()
        assert "rows/s" in text
        assert "MB/s" in text
