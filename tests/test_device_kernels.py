"""Device (jax) kernel layer: parity vs host kernels on the virtual CPU mesh.

The executor routes eligible projections/aggregations through these kernels; every
kernel must match the host (pyarrow) path bit-for-bit on device-representable dtypes.
"""

import datetime

import numpy as np
import pytest

import jax.numpy as jnp

from daft_tpu.datatypes import DataType
from daft_tpu.expressions import col, lit
from daft_tpu.kernels import device as dev
from daft_tpu.table import Table


@pytest.fixture
def table():
    return Table.from_pydict({
        "a": [1, 2, None, 4, 5] * 40,
        "b": [1.5, 2.5, 3.5, None, 0.0] * 40,
        "d": [datetime.date(2020, 1, i + 1) for i in range(5)] * 40,
        "flag": [True, False, None, True, False] * 40,
    })


def eval_projection_device(table, exprs):
    """Launch and resolve at once: a host Table, or None when ineligible."""
    resolve = dev.eval_projection_device_async(table, exprs)
    return None if resolve is None else resolve()


PROJ_EXPRS = [
    (col("a") * 2 + 1).alias("x"),
    (col("b") / col("a")).alias("div"),
    (col("a") > 2).alias("gt"),
    col("a").fill_null(0).alias("fz"),
    ((col("d") <= datetime.date(2020, 1, 3)) & col("a").not_null()).alias("pred"),
    (col("a") % 3).alias("mod"),
    (col("a") // 2).alias("fdiv"),
    col("b").float.is_nan().alias("nan"),
    (col("a") > 1).if_else(col("b"), lit(0.0)).alias("ie"),
    col("a").between(2, 4).alias("btw"),
    (~col("flag")).alias("nf"),
    (col("flag") | (col("a") > 3)).alias("or_k"),
    col("a").is_null().alias("isn"),
    col("b").abs().alias("ab"),
    col("a").cast(DataType.float32()).alias("cf"),
]


class TestDeviceProjection:
    def test_parity_with_host(self, table):
        host = table.eval_expression_list(PROJ_EXPRS)
        devout = eval_projection_device(table, PROJ_EXPRS)
        assert devout is not None
        hd, dd = host.to_pydict(), devout.to_pydict()
        for k in hd:
            assert hd[k] == dd[k], k

    def test_single_column_string_transform_now_eligible(self, table):
        # upper(s) rides the transformed-dictionary lane (sorted-order ids
        # gathered by code, decoded at unstage) — exact host parity
        t = Table.from_pydict({"s": ["a", "B", None, "c"]})
        out = eval_projection_device(t, [col("s").str.upper()])
        assert out is not None
        assert out.to_pydict() == {"s": ["A", "B", None, "C"]}

    def test_two_column_string_compute_ineligible(self, table):
        # a string producer over TWO columns has no single source
        # dictionary to transform: stays host
        t = Table.from_pydict({"s": ["a", "b"], "t": ["x", "y"]})
        assert eval_projection_device(t, [col("s") + col("t")]) is None

    def test_float_division_by_zero_matches_host(self):
        t = Table.from_pydict({"a": [1.0, 2.0], "z": [0, 2]})
        exprs = [(col("a") / col("z")).alias("q")]
        host = t.eval_expression_list(exprs).to_pydict()
        devout = eval_projection_device(t, exprs).to_pydict()
        assert devout["q"] == host["q"] == [float("inf"), 1.0]

    def test_kleene_and_or(self):
        t = Table.from_pydict({"p": [True, False, None] * 3,
                               "q": [True, True, True, False, False, False, None, None, None]})
        exprs = [(col("p") & col("q")).alias("and_"), (col("p") | col("q")).alias("or_")]
        host = t.eval_expression_list(exprs).to_pydict()
        devout = eval_projection_device(t, exprs).to_pydict()
        assert devout == host

    def test_compile_cache_reused(self, table):
        dev._PROJ_CACHE.clear()
        eval_projection_device(table, [(col("a") + 1).alias("y")])
        assert len(dev._PROJ_CACHE) == 1
        eval_projection_device(table.head(50), [(col("a") + 1).alias("y")])
        assert len(dev._PROJ_CACHE) == 1  # same expr+schema: one entry, bucket via jit


class TestStaging:
    def test_roundtrip_with_nulls(self):
        from daft_tpu.series import Series

        s = Series.from_pylist([1, None, 3], "x", DataType.int32())
        back = dev.unstage(dev.stage_series(s))
        assert back.to_pylist() == [1, None, 3]
        assert back.dtype == DataType.int32()

    def test_temporal_roundtrip(self):
        from daft_tpu.series import Series

        vals = [datetime.datetime(2021, 5, 1, 12), None]
        s = Series.from_pylist(vals, "ts")
        back = dev.unstage(dev.stage_series(s))
        assert back.to_pylist() == vals

    def test_embedding_staging(self):
        from daft_tpu.series import Series

        s = Series.from_numpy(np.arange(12, dtype=np.float32).reshape(3, 4), "e",
                              DataType.embedding(DataType.float32(), 4))
        dc = dev.stage_series(s)
        assert dc.values.shape[1] == 4
        back = dev.unstage(dc)
        assert back.to_numpy().tolist() == s.to_numpy().tolist()

    def test_python_dtype_rejected(self):
        from daft_tpu.series import Series

        s = Series.from_pylist([object()], "o")
        with pytest.raises(ValueError):
            dev.stage_series(s)


class TestSegmentAgg:
    def test_parity_all_kinds(self, table):
        n = len(table)
        codes_np = (np.arange(n) % 3).astype(np.int32)
        b = dev.size_bucket(n)
        dc = dev.stage_series(table.get_column("b"), b)
        codes = jnp.asarray(np.concatenate([codes_np, np.zeros(b - n, np.int32)]))
        bvals = table.get_column("b").to_pylist()
        for kind in ("sum", "count", "min", "max"):
            out, valid = dev.segment_aggregate(dc.values, dc.valid, codes, 3, kind)
            out = np.asarray(out)[:3]
            for g in range(3):
                seg = [v for v, c in zip(bvals, codes_np) if c == g and v is not None]
                exp = {"sum": sum(seg), "count": len(seg),
                       "min": min(seg), "max": max(seg)}[kind]
                assert np.isclose(out[g], exp), (kind, g, out[g], exp)

    def test_all_null_group_invalid(self):
        vals = jnp.asarray(np.zeros(dev._MIN_BUCKET, np.float64))
        valid = jnp.zeros(dev._MIN_BUCKET, bool)
        codes = jnp.zeros(dev._MIN_BUCKET, jnp.int32)
        out, v = dev.segment_aggregate(vals, valid, codes, 2, "sum")
        assert not bool(v[0]) and not bool(v[1])


class TestDeviceSort:
    def test_multikey_parity(self):
        t = Table.from_pydict({"k": [3, None, 1, 2, 1, 3], "v": [1.0, 2.0, None, 4.0, 5.0, 0.5]})
        b = dev.size_bucket(len(t))
        kc = dev.stage_series(t.get_column("k"), b)
        vc = dev.stage_series(t.get_column("v"), b)
        for desc in ([False, True], [True, False], [False, False]):
            idx = dev.device_argsort([(kc.values, kc.valid), (vc.values, vc.valid)],
                                     desc, [d for d in desc], len(t))
            host = np.asarray(t.argsort([col("k"), col("v")], descending=desc).to_arrow())
            assert list(np.asarray(idx)[:len(t)]) == list(host), desc

    def test_float_nan_sorts_last(self):
        t = Table.from_pydict({"f": [2.0, float("nan"), 1.0]})
        b = dev.size_bucket(3)
        fc = dev.stage_series(t.get_column("f"), b)
        idx = np.asarray(dev.device_argsort([(fc.values, fc.valid)], [False], [False], 3))[:3]
        assert list(idx) == [2, 0, 1]


class TestDeviceHash:
    def test_deterministic_and_null_aware(self):
        t = Table.from_pydict({"k": [1, 2, None, 1]})
        b = dev.size_bucket(4)
        kc = dev.stage_series(t.get_column("k"), b)
        h1 = np.asarray(dev.hash_buckets((kc.values,), (kc.valid,), 8))[:4]
        h2 = np.asarray(dev.hash_buckets((kc.values,), (kc.valid,), 8))[:4]
        assert list(h1) == list(h2)
        assert h1[0] == h1[3]  # equal keys, equal bucket
        assert (h1 >= 0).all() and (h1 < 8).all()


class TestPipelinedDeviceProjection:
    """Double-buffered device projections: map_partition_dispatch launches
    partition i+1 before partition i's result is fetched (reference role:
    pipelined intermediate ops, daft-local-execution intermediate_op.rs:71)."""

    def _cfg(self):
        import daft_tpu

        return daft_tpu.context.get_context().execution_config

    def test_order_preserved_and_devices_used(self):
        import numpy as np

        import daft_tpu
        from daft_tpu import col
        from daft_tpu.execution import execute_plan, ExecutionContext, RuntimeStats
        from daft_tpu.optimizer import optimize
        from daft_tpu.physical import translate

        cfg = self._cfg()
        old = cfg.use_device_kernels, cfg.device_min_rows
        cfg.use_device_kernels = True
        cfg.device_min_rows = 1
        try:
            df = daft_tpu.from_pydict({
                "x": np.arange(40_000, dtype=np.int64) % 997,
            }).into_partitions(6).select((col("x") * 2 + 1).alias("y"))
            ctx = ExecutionContext(cfg, RuntimeStats())
            parts = list(execute_plan(translate(optimize(df._plan), cfg), ctx))
            got = [v for p in parts for v in p.to_pydict()["y"]]
            assert got == [int(x) % 997 * 2 + 1 for x in range(40_000)]
            assert ctx.stats.counters.get("device_projections", 0) >= 6, \
                ctx.stats.counters
            # the PIPELINED dispatch path must be what ran, not the sync path
            assert ctx.stats.counters.get("device_projection_dispatches", 0) >= 6
        finally:
            cfg.use_device_kernels, cfg.device_min_rows = old

    def test_mixed_host_device_partitions_stay_ordered(self):
        import numpy as np
        import pyarrow as pa

        import daft_tpu
        from daft_tpu import col
        from daft_tpu.execution import execute_plan, ExecutionContext, RuntimeStats
        from daft_tpu.micropartition import MicroPartition
        from daft_tpu.optimizer import optimize
        from daft_tpu.physical import translate

        cfg = self._cfg()
        old = cfg.use_device_kernels, cfg.device_min_rows
        cfg.use_device_kernels = True
        cfg.device_min_rows = 100  # small partitions take the host path
        try:
            # alternate large (device) and small (host) partitions
            parts = []
            base = 0
            sizes = [500, 3, 500, 3, 500]
            for sz in sizes:
                parts.append(MicroPartition.from_arrow(pa.table({
                    "x": pa.array(np.arange(base, base + sz, dtype=np.int64))})))
                base += sz
            df = daft_tpu.from_partitions(parts, parts[0].schema).select(
                (col("x") + 10).alias("y"))
            ctx = ExecutionContext(cfg, RuntimeStats())
            out = list(execute_plan(translate(optimize(df._plan), cfg), ctx))
            got = [v for p in out for v in p.to_pydict()["y"]]
            assert got == [x + 10 for x in range(sum(sizes))]
            assert ctx.stats.counters.get("device_projections", 0) == 3
            assert ctx.stats.counters.get("device_projection_dispatches", 0) == 3
            assert ctx.stats.counters.get("host_projections", 0) == 2
        finally:
            cfg.use_device_kernels, cfg.device_min_rows = old

    def test_adaptive_fallback_to_worker_pool_when_first_declines(self):
        import numpy as np

        import daft_tpu
        from daft_tpu import col
        from daft_tpu.execution import execute_plan, ExecutionContext, RuntimeStats
        from daft_tpu.optimizer import optimize
        from daft_tpu.physical import translate

        cfg = self._cfg()
        old = (cfg.use_device_kernels, cfg.device_min_rows, cfg.executor_threads)
        cfg.use_device_kernels = True
        cfg.device_min_rows = 10_000  # every partition below -> all decline
        cfg.executor_threads = 4
        try:
            df = daft_tpu.from_pydict({
                "x": np.arange(2_000, dtype=np.int64),
            }).into_partitions(8).select((col("x") * 5).alias("y"))
            ctx = ExecutionContext(cfg, RuntimeStats())
            parts = list(execute_plan(translate(optimize(df._plan), cfg), ctx))
            got = sorted(v for p in parts for v in p.to_pydict()["y"])
            assert got == [x * 5 for x in range(2_000)]
            c = ctx.stats.counters
            assert c.get("device_projection_dispatches", 0) == 0, c
            assert c.get("device_projections", 0) == 0, c
            assert c.get("host_projections", 0) == 8, c
        finally:
            (cfg.use_device_kernels, cfg.device_min_rows,
             cfg.executor_threads) = old


class TestPipelinedDeviceAgg:
    """Per-partition aggregations double-buffer like projections: dispatch
    launches the fused kernel for partition i+1 before partition i's single
    result fetch."""

    def _cfg(self):
        import daft_tpu

        return daft_tpu.context.get_context().execution_config

    def test_grouped_agg_dispatches_and_matches(self):
        import numpy as np

        import daft_tpu
        from daft_tpu import col
        from daft_tpu.execution import ExecutionContext, RuntimeStats, execute_plan
        from daft_tpu.optimizer import optimize
        from daft_tpu.physical import translate

        cfg = self._cfg()
        old = cfg.use_device_kernels, cfg.device_min_rows
        cfg.use_device_kernels = True
        cfg.device_min_rows = 1
        try:
            rng = np.random.RandomState(3)
            df = daft_tpu.from_pydict({
                "k": rng.randint(0, 50, 60_000).astype(np.int64),
                "v": rng.rand(60_000)}).into_partitions(6) \
                .where(col("v") < 0.5) \
                .groupby("k").agg(col("v").sum().alias("s"),
                                  col("v").count().alias("c"))
            ctx = ExecutionContext(cfg, RuntimeStats())
            parts = list(execute_plan(translate(optimize(df._plan), cfg), ctx))
            c = ctx.stats.counters
            assert c.get("device_agg_dispatches", 0) >= 6, c
            got = {}
            for p in parts:
                d = p.to_pydict()
                for k, s, cnt in zip(d["k"], d["s"], d["c"]):
                    a, b = got.get(k, (0.0, 0))
                    got[k] = (a + s, b + cnt)
        finally:
            cfg.use_device_kernels, cfg.device_min_rows = old
        # host oracle with numpy
        rng = np.random.RandomState(3)
        k = rng.randint(0, 50, 60_000).astype(np.int64)
        v = rng.rand(60_000)
        m = v < 0.5
        for kk in range(50):
            sel = m & (k == kk)
            s, cnt = got[kk]
            assert cnt == int(sel.sum())
            assert abs(s - v[sel].sum()) < 1e-9 * max(1.0, abs(v[sel].sum()))

    def test_overflow_guard_falls_back_at_resolve(self):
        import numpy as np

        import daft_tpu
        from daft_tpu import col
        from daft_tpu.execution import ExecutionContext, RuntimeStats, execute_plan
        from daft_tpu.optimizer import optimize
        from daft_tpu.physical import translate
        import jax

        cfg = self._cfg()
        old = (cfg.use_device_kernels, cfg.device_min_rows)
        x64_was = jax.config.jax_enable_x64
        jax.config.update("jax_enable_x64", False)
        cfg.use_device_kernels = True
        cfg.device_min_rows = 1
        try:
            # values fit int32 but the per-group SUM cannot: the deferred
            # resolver must detect it and recompute on host, counters truthful
            df = daft_tpu.from_pydict({
                "g": np.zeros(10_000, dtype=np.int64),
                "v": np.full(10_000, 2**30, dtype=np.int64),
            }).into_partitions(2).groupby("g").agg(col("v").sum().alias("s"))
            ctx = ExecutionContext(cfg, RuntimeStats())
            parts = list(execute_plan(translate(optimize(df._plan), cfg), ctx))
            total = sum(s for p in parts for s in p.to_pydict()["s"])
            assert total == 10_000 * 2**30
            c = ctx.stats.counters
            assert c.get("device_agg_fallbacks", 0) >= 1, c
            assert c.get("device_aggregations", 0) == \
                c.get("device_agg_dispatches", 0) - c.get("device_agg_fallbacks", 0), c
        finally:
            jax.config.update("jax_enable_x64", x64_was)
            (cfg.use_device_kernels, cfg.device_min_rows) = old


class TestPipelinedDeviceFilter:
    def test_filter_dispatches_and_matches(self):
        import numpy as np

        import daft_tpu
        from daft_tpu import col
        from daft_tpu.execution import ExecutionContext, RuntimeStats, execute_plan
        from daft_tpu.optimizer import optimize
        from daft_tpu.physical import translate

        cfg = daft_tpu.context.get_context().execution_config
        old = cfg.use_device_kernels, cfg.device_min_rows
        cfg.use_device_kernels = True
        cfg.device_min_rows = 1
        try:
            import pyarrow as pa

            from daft_tpu.micropartition import MicroPartition

            rng = np.random.RandomState(8)
            x = rng.randint(0, 1000, 50_000).astype(np.int64)
            # REAL pre-existing partitions (into_partitions would be planned
            # after the filter); filter feeds a non-fusable op (sort) so
            # FilterOp stays its own op
            mps = [MicroPartition.from_arrow(pa.table({"x": pa.array(c)}))
                   for c in np.array_split(x, 5)]
            df = daft_tpu.from_partitions(mps, mps[0].schema) \
                .where(col("x") % 7 == 0).sort("x")
            ctx = ExecutionContext(cfg, RuntimeStats())
            parts = list(execute_plan(translate(optimize(df._plan), cfg), ctx))
            got = [v for p in parts for v in p.to_pydict()["x"]]
            want = sorted(int(v) for v in x if v % 7 == 0)
            assert got == want
            c = ctx.stats.counters
            assert c.get("device_filter_dispatches", 0) >= 5, c
        finally:
            cfg.use_device_kernels, cfg.device_min_rows = old


# --------------------------------------------------------------------------
# Boolean dictionary tables looked up by code (PR 28): packed uint32 words and
# bit tests up to DICT_PACKED_MAX_ENTRIES, bool[bucket] and a gather above.
OVER_BOUND = dev.DICT_PACKED_MAX_ENTRIES + 1
DICT_SIZES = [1, 7, 31, 32, 33, 1000, 1024, OVER_BOUND]
HIT_PATTERNS = {
    "none": lambda u: np.zeros(u, dtype=bool),
    "all": lambda u: np.ones(u, dtype=bool),
    "one": lambda u: np.arange(u) == u // 2,
    "every_other": lambda u: np.arange(u) % 2 == 0,
}


def _is_in_case(hits):
    vals = [f"v{i:05d}" for i in range(len(hits))]
    wanted = [v for v, h in zip(vals, hits) if h] or ["absent"]
    return vals, col("s").is_in(wanted)


# kind -> hits -> (dictionary values, predicate matching exactly the hits)
DICT_PRED_CASES = {
    "is_in": _is_in_case,
    "contains": lambda hits: (
        [f"{i:05d}{'_X_' if h else '___'}" for i, h in enumerate(hits)],
        col("s").str.contains("X")),
    "startswith": lambda hits: (
        [f"{'X' if h else '_'}{i:05d}" for i, h in enumerate(hits)],
        col("s").str.startswith("X")),
    "like": lambda hits: (
        [f"{i:05d}{'ab' if h else 'ba'}" for i, h in enumerate(hits)],
        col("s").str.like("%ab")),
    # the general dictionary predicate: values and validity, null slot
    "upper_endswith": lambda hits: (
        [f"{i:05d}{'x' if h else 'y'}" for i, h in enumerate(hits)],
        col("s").str.upper().str.endswith("X")),
}


def _dict_table(values, nulls: bool, seed: int = 0) -> Table:
    """Every dictionary value at least once, in a shuffled column; with
    ``nulls`` every fifth row is null on top."""
    rng = np.random.RandomState(seed)
    n = max(64, 2 * len(values))
    rows = [values[i] for i in rng.permutation(np.arange(n) % len(values))]
    if nulls:
        rows = [v for i, v in enumerate(rows) for v in
                ((v, None) if i % 4 == 0 else (v,))]
    return Table.from_pydict({"s": rows})


def _device_vs_host(t: Table, exprs):
    host = t.eval_expression_list(exprs).to_pydict()
    devout = eval_projection_device(t, exprs)
    assert devout is not None, "left the device path"
    return devout.to_pydict(), host


def _lookup_counters(df):
    c = df.stats.snapshot()["counters"]
    return (c.get("dict_lookup_packed", 0), c.get("dict_lookup_gather", 0),
            c.get("xla_compiles", 0))


@pytest.fixture
def device_kernels_on():
    import daft_tpu

    cfg = daft_tpu.context.get_context().execution_config
    old = cfg.use_device_kernels, cfg.device_min_rows
    cfg.use_device_kernels, cfg.device_min_rows = True, 1
    yield
    cfg.use_device_kernels, cfg.device_min_rows = old


@pytest.fixture
def jaxprs(monkeypatch):
    """The jaxpr of every projection program launched, as text."""
    import jax

    seen = []
    real = dev.compile_projection

    def spy(nodes, schema, names):
        run, dts = real(nodes, schema, names)

        def traced(env):
            seen.append(str(jax.make_jaxpr(run)(env)))
            return run(env)

        return traced, dts

    monkeypatch.setattr(dev, "compile_projection", spy)
    return seen


class TestDictLookup:
    @pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
    @pytest.mark.parametrize("pattern", list(HIT_PATTERNS))
    @pytest.mark.parametrize("size", DICT_SIZES)
    @pytest.mark.parametrize("kind", list(DICT_PRED_CASES))
    def test_parity_with_host(self, kind, size, pattern, nulls):
        hits = HIT_PATTERNS[pattern](size)
        values, pred = DICT_PRED_CASES[kind](hits)
        t = _dict_table(values, nulls)
        got, want = _device_vs_host(t, [pred.alias("p")])
        assert got == want
        # the case is what it says: exactly the hit entries answer True
        by_value = dict(zip(values, hits))
        assert all(p == by_value[s] for s, p in
                   zip(t.to_pydict()["s"], got["p"]) if s is not None)

    @pytest.mark.parametrize("nulls", [False, True], ids=["dense", "nulls"])
    @pytest.mark.parametrize("size", DICT_SIZES)
    @pytest.mark.parametrize("pred", [
        col("s").str.upper() == "V00000",
        # the null slot answers True: one more dictionary entry, so the
        # packed form ends at DICT_PACKED_MAX_ENTRIES - 1 strings
        col("s").fill_null("v00000").is_in(["v00000", "absent"]),
    ], ids=["upper_eq", "fill_null_is_in"])
    def test_general_predicate_null_slot(self, pred, size, nulls):
        t = _dict_table([f"v{i:05d}" for i in range(size)], nulls)
        got, want = _device_vs_host(t, [pred.alias("p")])
        assert got == want
        assert sum(bool(p) for p in got["p"]) >= 1

    @pytest.mark.parametrize("size", [31, 32, 1023, 1024])
    def test_null_slot_counts_as_an_entry(self, size, monkeypatch):
        seen = []
        real = dev._dict_bool_tables
        monkeypatch.setattr(
            dev, "_dict_bool_tables",
            lambda *tables: seen.extend(tables) or real(*tables))
        t = _dict_table([f"v{i:05d}" for i in range(size)], True)
        got, want = _device_vs_host(
            t, [col("s").fill_null("v00000").is_in(["v00000"]).alias("p")])
        assert got == want
        assert [len(tb) for tb in seen] == [size + 1, size + 1]

    @pytest.mark.parametrize("entries,words", [
        (0, 1), (1, 1), (32, 1), (33, 2), (64, 2), (65, 4), (129, 8),
        (1000, 32), (1024, 32)])
    def test_packed_shape_follows_a_bucket(self, entries, words):
        table, = dev._dict_bool_tables(np.ones(entries, dtype=bool))
        assert (table.dtype, table.shape) == (jnp.uint32, (words,))
        assert sum(bin(int(w)).count("1") for w in table) == entries

    @pytest.mark.parametrize("entries", [7, 33, 1024, OVER_BOUND])
    def test_code_outside_the_dictionary_reads_false(self, entries):
        # staging never makes such a code; the packed form owes False for
        # one all the same (no shift past 31, no word past the last)
        table, = dev._dict_bool_tables(np.ones(entries, dtype=bool))
        inside = np.arange(entries, dtype=np.int32)
        assert np.asarray(dev._dict_bool_lookup(table, inside)).all()
        if entries <= dev.DICT_PACKED_MAX_ENTRIES:
            outside = np.array([entries, entries + 31, entries + 32, 1 << 20,
                                2**31 - 1, -1, -32, -2**31], dtype=np.int32)
            assert not np.asarray(dev._dict_bool_lookup(table, outside)).any()

    @pytest.mark.parametrize("size,gathers", [
        (7, 0), (dev.DICT_PACKED_MAX_ENTRIES, 0), (OVER_BOUND, 1)])
    def test_q12_predicate_gathers_only_over_the_bound(self, size, gathers,
                                                       jaxprs):
        modes = (["MAIL", "SHIP"] + [f"m{i:05d}" for i in range(size)])[:size]
        t = _dict_table(modes, False)
        t = Table.from_pydict({
            "l_shipmode": t.to_pydict()["s"],
            "l_shipdate": [datetime.date(1994, 1 + i % 12, 1)
                           for i in range(len(t))]})
        q12 = (col("l_shipmode").is_in(["MAIL", "SHIP"])
               & (col("l_shipdate") >= datetime.date(1994, 1, 1))
               & (col("l_shipdate") < datetime.date(1995, 1, 1)))
        got, want = _device_vs_host(t, [q12.alias("keep")])
        assert got == want
        assert len(jaxprs) == 1
        assert jaxprs[0].count("gather") == gathers, jaxprs[0]

    def test_counters_on_the_two_sides_of_the_bound(self, device_kernels_on):
        import daft_tpu

        for size, want in [(dev.DICT_PACKED_MAX_ENTRIES, (1, 0)),
                           (OVER_BOUND, (0, 1))]:
            s = _dict_table([f"v{i:05d}" for i in range(size)], True)
            df = daft_tpu.from_pydict(
                {"s": s.to_pydict()["s"], "x": list(range(len(s)))}
            ).where(col("s").is_in(["v00000", "v00001"])).groupby("s").agg(
                col("x").sum().alias("t"))
            df.collect()
            assert _lookup_counters(df)[:2] == want
            c = df.stats.snapshot()["counters"]
            assert c.get("device_aggregations", 0) == 1, c

    def test_new_dictionary_of_one_bucket_compiles_nothing(
            self, device_kernels_on):
        import daft_tpu

        def q12ish(values, wanted):
            s = _dict_table(values, True, seed=len(values))
            df = daft_tpu.from_pydict(
                {"s": s.to_pydict()["s"], "x": [1.0] * len(s)}
            ).where(col("s").is_in(wanted) & (col("x") > 0.5)).groupby(
                "s").agg(col("x").sum().alias("t")).sort("s")
            out = df.collect().to_pydict()
            return out, _lookup_counters(df)

        modes = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
        out, (packed, gathered, _) = q12ish(modes, ["MAIL", "SHIP"])
        assert out["s"] == ["MAIL", "SHIP"] and (packed, gathered) == (1, 0)
        # another dictionary of the same bucket (one word; the same bucket
        # of 8 groups too), other contents and other hit positions: the
        # words are a traced input
        others = [f"N{i:02d}" for i in range(4)] + ["MAIL", "SHIP"]
        out, (packed, gathered, compiles) = q12ish(others, ["MAIL", "SHIP"])
        assert out["s"] == ["MAIL", "SHIP"]
        assert (packed, gathered, compiles) == (1, 0, 0)

    def test_literal_list_is_no_constant_of_the_program(self, jaxprs):
        # program caches are keyed by node key, literals included, so a new
        # literal list is a new closure; what this mechanism owes is that
        # the list's table is an input and not a constant: the two closures
        # trace to the same jaxpr (a persistent cache then serves the second)
        t = _dict_table(["AIR", "FOB", "MAIL", "RAIL", "SHIP"], True)
        for wanted in (["MAIL", "SHIP"], ["AIR", "FOB", "RAIL"]):
            got, want = _device_vs_host(t, [col("s").is_in(wanted).alias("p")])
            assert got == want
        assert len(jaxprs) == 2 and jaxprs[0] == jaxprs[1]


# A device attempt's staged arrays and outputs have to die with the query,
# by reference count: a recursive local closure that captured `env` kept them
# until the cyclic collector next ran, and the peak of device memory followed
# the collector's timing (tpch1-join read +4.3% for an edit elsewhere, PR 28).
def _orders_and_lines():
    import daft_tpu

    n = 4000
    rng = np.random.RandomState(3)
    orders = daft_tpu.from_pydict({
        "o_key": np.arange(n // 4, dtype=np.int64),
        "o_seg": [["BUILDING", "AUTOMOBILE", "MACHINERY"][i % 3]
                  for i in range(n // 4)]}).collect()
    lines = daft_tpu.from_pydict({
        "l_key": rng.randint(0, n // 4, n).astype(np.int64),
        "l_price": rng.rand(n), "l_disc": rng.rand(n) / 10,
        "l_mode": [["MAIL", "SHIP", "AIR", "RAIL"][i % 4] for i in range(n)],
        "l_qty": rng.randint(1, 50, n).astype(np.int64)}).collect()
    return orders, lines


QUERY_SHAPES = {
    "projection": lambda o, li: li.select(
        (col("l_price") * (1 - col("l_disc"))).alias("rev")),
    "filter_agg": lambda o, li: li.where(
        col("l_mode").is_in(["MAIL", "SHIP"]) & (col("l_price") > 0.1)
    ).groupby("l_mode").agg(col("l_price").sum().alias("s")),
    "string_transform": lambda o, li: li.where(
        col("l_mode").str.lower() == "mail").select(col("l_qty")),
    "filter": lambda o, li: li.where(col("l_price") > 0.5),
    "fused_map": lambda o, li: li.where(col("l_qty") > 10).select(
        (col("l_price") * col("l_qty")).alias("gross"), col("l_key")),
    "join_agg": lambda o, li: o.where(col("o_seg") == "BUILDING").join(
        li, left_on="o_key", right_on="l_key").select(
        col("o_key"), (col("l_price") * (1 - col("l_disc"))).alias("rev")
    ).groupby("o_key").agg(col("rev").sum().alias("rev")).sort("rev").limit(5),
    # over a partition larger than a morsel: one launch over a stage view
    "filter_over_a_view": lambda o, li: li.where(col("l_qty") > 10).select(
        col("l_key"), col("l_price")),
}
# the shapes run with morsels smaller than LINEITEM's one partition
OVER_A_MORSEL = {"filter_over_a_view"}


@pytest.mark.parametrize("x64", [True, False], ids=["x64", "x32"])
@pytest.mark.parametrize("shape", list(QUERY_SHAPES))
def test_device_arrays_die_with_their_query(shape, x64, device_kernels_on,
                                            monkeypatch):
    import gc

    import jax

    import daft_tpu

    if shape in OVER_A_MORSEL:
        monkeypatch.setattr(daft_tpu.context.get_context().execution_config,
                            "morsel_size_rows", 1024)
    x64_was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", x64)
    try:
        orders, lines = _orders_and_lines()
        QUERY_SHAPES[shape](orders, lines).collect()  # warm: stage caches
        gc.collect()
        alive = len(jax.live_arrays())
        gc.disable()
        try:
            df = QUERY_SHAPES[shape](orders, lines)
            out = df.collect().to_pydict()
            c = df.stats.snapshot()["counters"]
            assert any(k.startswith("device_") and not k.endswith("_ns")
                       and v for k, v in c.items()), c
            assert (c.get("device_maps_unsplit", 0) > 0) == (
                shape in OVER_A_MORSEL), c
            del df, out
            assert len(jax.live_arrays()) == alive
        finally:
            gc.enable()
    finally:
        jax.config.update("jax_enable_x64", x64_was)


def test_int64_wrap_safe_lets_go_of_env():
    # its interval walk is recursive closures over `env`: they are dropped
    # on return, so the attempt's arrays do not wait for the collector
    import gc
    import weakref

    import jax

    class Env(dict):
        pass

    x64_was = bool(jax.config.jax_enable_x64)
    jax.config.update("jax_enable_x64", False)
    gc.collect()
    gc.disable()
    try:
        t = Table.from_pydict({"a": np.arange(100, dtype=np.int64)})
        nodes = dev.normalize_and_check([(col("a") * col("a") + 1).alias("x")],
                                        t.schema)
        env = Env(dev.stage_table_columns(t, ["a"], 1024, None)[0])
        alive = weakref.ref(env)
        assert dev.int64_wrap_safe(nodes, t.schema, env, None, 1024)
        del env
        assert alive() is None
    finally:
        gc.enable()
        jax.config.update("jax_enable_x64", x64_was)
