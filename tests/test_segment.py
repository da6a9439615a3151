"""Plan-segment compiler (ISSUE 19): byte-identity with residency off
across the dtype/null/breaker/streaming matrix, warm plan-cache reuse with
zero segment compiles, stage-cache reuse, fuse.segment fault semantics
(compile-time and runtime firing both degrade to the staged path, never a
query failure), and the residency observability surfaces."""

import dataclasses

import pyarrow as pa
import pytest

import daft_tpu as dt
from daft_tpu import col, faults
from daft_tpu.context import get_context
from daft_tpu.execution import ExecutionContext, RuntimeStats, execute_plan
from daft_tpu.fuse import DeviceSegmentOp
from daft_tpu.optimizer import optimize
from daft_tpu.physical import translate


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    yield
    faults.disarm()


@pytest.fixture
def cfg():
    """Fresh ExecutionConfig copy, restored afterwards."""
    ctx = get_context()
    old = ctx.execution_config
    ctx.execution_config = dataclasses.replace(
        old, enable_result_cache=False, use_device_kernels=True,
        device_min_rows=1, device_residency=True)
    yield ctx.execution_config
    ctx.execution_config = old


def _data(nulls="some", n=200):
    """str key, never-null int (drives the predicate so even the all-null
    leg reaches the resident kernel), int64/float64 agg columns under the
    requested null pattern, and a nullable-free bool filter column."""
    if nulls == "none":
        v = list(range(n))
        f = [i * 0.25 for i in range(n)]
    elif nulls == "some":
        v = [i if i % 7 else None for i in range(n)]
        f = [i * 0.25 if i % 5 else None for i in range(n)]
    else:  # all: the aggregated columns carry no values at all
        v = [None] * n
        f = [None] * n
    return pa.table({
        "k": pa.array(["a", "b", "c", "d"] * (n // 4)),
        "u": pa.array(list(range(n)), type=pa.int64()),
        "v": pa.array(v, type=pa.int64()),
        "f": pa.array(f, type=pa.float64()),
        "b": pa.array([True, True, False, True] * (n // 4)),
    })


def _query(nulls="some", n=200):
    """project -> filter -> grouped agg: the maximal device-eligible
    segment shape (derived int/float columns, a mask from a conjunction,
    sum/mean/max/count over nullable inputs, string group key)."""
    df = dt.from_arrow(_data(nulls, n)).into_partitions(2)
    return (df.select((col("v") * 2 + 1).alias("x"),
                      (col("f") * 0.5).alias("g"),
                      (col("u") * 3).alias("w"), col("k"), col("b"))
            .where((col("w") > 30) & col("b"))
            .groupby("k")
            .agg(col("x").sum().alias("sx"), col("g").mean().alias("mg"),
                 col("g").max().alias("xg"), col("x").count().alias("c"),
                 col("w").sum().alias("sw"))
            .sort("k"))


def _find_segments(phys):
    found = []

    def walk(op):
        if isinstance(op, DeviceSegmentOp):
            found.append(op)
        for c in op.children:
            walk(c)

    walk(phys)
    return found


def _run_phys(phys, cfg):
    stats = RuntimeStats()
    ctx = ExecutionContext(cfg, stats)
    out = {}
    for p in execute_plan(phys, ctx):
        for k, vals in p.to_pydict().items():
            out.setdefault(k, []).extend(vals)
    return out, stats


# ---------------------------------------------------------------------------
# acceptance: byte-identity matrix — residency on/off x null patterns x
# {device, host, breaker-tripped} x streaming on/off
# ---------------------------------------------------------------------------

class TestByteIdentityMatrix:
    @pytest.mark.parametrize("streaming", [False, True],
                             ids=["nostream", "stream"])
    @pytest.mark.parametrize("nulls", ["none", "some", "all"])
    @pytest.mark.parametrize("leg", ["device", "host", "breaker_tripped"])
    def test_matrix(self, cfg, leg, nulls, streaming):
        cfg.streaming_execution = streaming
        cfg.morsel_size_rows = 64  # 100-row partitions subdivide
        if leg == "host":
            cfg.use_device_kernels = False
        elif leg == "breaker_tripped":
            # every device attempt fails: the breaker trips on the first
            # and the whole query lands on the host path both ways
            cfg.device_breaker_threshold = 1
            cfg.device_breaker_cooldown_s = 600.0
            faults.arm("device.kernel", "always")
        cfg.device_residency = True
        q_on = _query(nulls)
        on = q_on.collect().to_pydict()
        cfg.device_residency = False
        q_off = _query(nulls)
        off = q_off.collect().to_pydict()
        assert on == off  # the hard invariant: byte-identical results
        c_on = q_on.stats.snapshot()["counters"]
        c_off = q_off.stats.snapshot()["counters"]
        assert c_off.get("device_resident_segments", 0) == 0, c_off
        if leg == "device":
            assert c_on.get("device_resident_segments", 0) == 1, c_on
            assert c_on.get("device_handoffs_elided", 0) >= 1, c_on
        else:
            # host leg never plans a segment; a tripped breaker declines
            # every handoff — neither may claim residency
            assert c_on.get("device_resident_segments", 0) == 0, c_on
            assert c_on.get("device_handoffs_elided", 0) == 0, c_on

    def test_empty_input_declines_without_degrading(self, cfg):
        # a filter upstream of the segment can starve it to zero rows:
        # that is an eligibility decline (device_min_rows), not a failure,
        # so the fallback counter must stay untouched
        df = dt.from_arrow(_data("some")).into_partitions(2)
        q = (df.where(col("v") > 10_000)  # nothing survives
             .select((col("v") * 2).alias("x"), col("k"))
             .groupby("k").agg(col("x").sum().alias("sx")).sort("k"))
        out = q.collect().to_pydict()
        assert out["sx"] == []
        c = q.stats.snapshot()["counters"]
        assert c.get("segment_fallbacks", 0) == 0, c


# ---------------------------------------------------------------------------
# acceptance: warm plan-cache runs perform zero segment compiles
# ---------------------------------------------------------------------------

class TestPlanCacheReuse:
    def test_warm_run_zero_segment_compiles(self, cfg):
        from daft_tpu.adapt.plancache import PLAN_CACHE, plan_query

        PLAN_CACHE.clear()
        plan = _query("some")._plan
        s1 = RuntimeStats()
        _, phys1, _ = plan_query(plan, cfg, stats=s1)
        assert s1.counters.get("segment_compiles", 0) == 1, s1.counters
        assert len(_find_segments(phys1)) == 1
        out1, r1 = _run_phys(phys1, cfg)
        assert r1.counters.get("device_resident_segments", 0) == 1

        s2 = RuntimeStats()
        _, phys2, _ = plan_query(plan, cfg, stats=s2)
        assert s2.counters.get("plan_cache_hits", 0) == 1, s2.counters
        # the pinned acceptance: a warm plan performs NO segment compiles
        assert s2.counters.get("segment_compiles", 0) == 0, s2.counters
        out2, r2 = _run_phys(phys2, cfg)
        assert out2 == out1
        # the clone resets the once-per-query latch: the warm run claims
        # its own residency, it does not inherit the cold run's
        assert r2.counters.get("device_resident_segments", 0) == 1

    def test_residency_knob_is_part_of_the_cache_key(self, cfg):
        from daft_tpu.adapt.plancache import PLAN_CACHE, plan_query

        PLAN_CACHE.clear()
        plan = _query("some")._plan
        _, phys_on, _ = plan_query(plan, cfg, stats=RuntimeStats())
        cfg.device_residency = False
        s = RuntimeStats()
        _, phys_off, _ = plan_query(plan, cfg, stats=s)
        # a config flip must never be served the resident plan
        assert s.counters.get("plan_cache_hits", 0) == 0, s.counters
        assert _find_segments(phys_on) and not _find_segments(phys_off)


# ---------------------------------------------------------------------------
# residency cache across runs
# ---------------------------------------------------------------------------

class TestStageCacheReuse:
    def test_stage_cache_survives_repeated_resident_runs(self, cfg):
        # running the same resident partitions twice must reuse the staged
        # buffers: nothing a resident run does may consume them
        df = dt.from_arrow(_data("some")).into_partitions(2).collect()

        def run():
            q = (df.select((col("v") * 2 + 1).alias("x"),
                           (col("u") * 3).alias("w"), col("k"))
                 .where(col("w") > 30)
                 .groupby("k").agg(col("x").sum().alias("sx")).sort("k"))
            out = q.collect().to_pydict()
            return out, q.stats.snapshot()["counters"]

        first, c1 = run()
        second, c2 = run()
        assert first == second
        assert c1.get("device_resident_segments", 0) == 1, c1
        assert c2.get("device_resident_segments", 0) == 1, c2


# ---------------------------------------------------------------------------
# fuse.segment fault site: compile-time AND runtime firing
# ---------------------------------------------------------------------------

class TestSegmentFaultSite:
    def test_site_registered(self):
        assert "fuse.segment" in faults.SITES

    def test_compile_time_fault_degrades_to_staged_plan(self, cfg):
        # armed at translate: the segment never compiles, the staged plan
        # runs, the answer is identical — a planner fault is invisible
        faults.arm("fuse.segment", "first_n", n=1)
        q = _query("some")
        phys = translate(optimize(q._plan), cfg)
        faults.disarm()
        assert _find_segments(phys) == []
        got, stats = _run_phys(phys, cfg)
        assert stats.counters.get("device_resident_segments", 0) == 0
        cfg.device_residency = False
        want = _query("some").collect().to_pydict()
        got_sorted = {k: got[k] for k in want}
        assert got_sorted == want

    def test_runtime_fault_degrades_and_is_counted(self, cfg):
        # armed after translate: the first resident handoff raises inside
        # run_segment_async, the breaker records it, the partition lands
        # on the staged path — counted, never a query failure
        q = _query("some")
        phys = translate(optimize(q._plan), cfg)
        assert _find_segments(phys)
        faults.arm("fuse.segment", "first_n", n=1)
        got, stats = _run_phys(phys, cfg)
        faults.disarm()
        assert stats.counters.get("faults_injected", 0) >= 1, stats.counters
        assert stats.counters.get("segment_fallbacks", 0) >= 1, stats.counters
        cfg.device_residency = False
        want = _query("some").collect().to_pydict()
        assert {k: got[k] for k in want} == want

    def test_always_armed_fault_never_fails_the_query(self, cfg):
        faults.arm("fuse.segment", "always")
        q = _query("some")
        got = q.collect().to_pydict()  # must not raise
        faults.disarm()
        cfg.device_residency = False
        assert got == _query("some").collect().to_pydict()


# ---------------------------------------------------------------------------
# observability: explain_analyze line, QueryRecord fold, health section
# ---------------------------------------------------------------------------

class TestResidencyObservability:
    def test_explain_analyze_and_query_record(self, cfg):
        from daft_tpu.obs.querylog import validate_record

        q = _query("some")
        q.collect()
        txt = q.explain_analyze()
        assert "residency:" in txt
        assert "resident segment(s)" in txt
        rec = q.last_query_record()
        assert validate_record(rec) == []
        assert rec["residency"]["resident_segments"] == 1
        assert rec["residency"]["handoffs_elided"] >= 1
        assert rec["residency"]["segment_compiles"] >= 1

    def test_record_omits_residency_when_nothing_ran_resident(self, cfg):
        cfg.device_residency = False
        q = _query("some")
        q.collect()
        assert "residency" not in q.last_query_record()

    def test_health_device_section_validates(self, cfg):
        from daft_tpu.obs.health import engine_health, validate_health

        _query("some").collect()
        h = engine_health()
        assert validate_health(h) == []
        dev = h["device"]
        assert dev["resident_segments"] >= 1
        assert dev["handoffs_elided"] >= 1
        assert dev["segment_compiles"] >= 1

    def test_segment_describe_names_the_fused_chain(self, cfg):
        phys = translate(optimize(_query("some")._plan), cfg)
        (seg,) = _find_segments(phys)
        d = seg.describe()
        assert d.startswith("DeviceSegment[")
        assert "=>" in d
