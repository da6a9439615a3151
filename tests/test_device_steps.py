"""The one launch/resolve contract of the device path (ISSUE 33): every
``physical.DeviceStep`` (projection, filter, fused map, aggregate with and
without a predicate, sketch build, resident segment, join probe) goes
through ``ExecutionContext.launch`` / ``run``, and the policy written there
holds for each of them alike: the counters' arithmetic, the breaker, the
probe slot, the fallback to the step's host kernel."""

import dataclasses

import numpy as np
import pytest

import daft_tpu as dt
from daft_tpu import col, faults
from daft_tpu.context import get_context
from daft_tpu.execution import DeviceHealth, ExecutionContext, RuntimeStats
from daft_tpu.expressions import AggExpr, Expression
from daft_tpu.fuse import DeviceSegmentOp
from daft_tpu.fuse.compile import FusedMapOp
from daft_tpu.micropartition import MicroPartition
from daft_tpu.optimizer import optimize
from daft_tpu.physical import (AggregateOp, FilterOp, FusedFilterAggregateOp,
                               InMemoryOp, JoinProbe, ProjectOp, translate)

N = 400


@pytest.fixture(autouse=True)
def _clean():
    faults.disarm()
    yield
    faults.disarm()


@pytest.fixture
def cfg():
    ctx = get_context()
    old = ctx.execution_config
    ctx.execution_config = dataclasses.replace(
        old, enable_result_cache=False, use_device_kernels=True,
        device_min_rows=1, device_residency=True)
    yield ctx.execution_config
    ctx.execution_config = old


def _part():
    rng = np.random.RandomState(5)
    return MicroPartition.from_pydict({
        "k": (np.arange(N, dtype=np.int64) % 7).tolist(),
        "v": rng.randint(0, 1000, N).astype(np.int64).tolist(),
        "w": rng.randint(0, 50, N).astype(np.int64).tolist()})


def _find(op, kind):
    if isinstance(op, kind):
        return op
    for c in op.children:
        hit = _find(c, kind)
        if hit is not None:
            return hit
    return None


def _planned(df, cfg, kind):
    """The operator of class `kind` in the plan of `df`, as translate
    builds it (fused maps and segments exist only as its output)."""
    op = _find(translate(optimize(df._plan), cfg), kind)
    assert op is not None, kind
    return op


def _projection(cfg, part, src):
    exprs = [(col("v") * 2 + col("w")).alias("x"), col("k")]
    schema = part.eval_expression_list(exprs).schema
    return ProjectOp(src, exprs, schema), (part,)


def _filter(cfg, part, src):
    return FilterOp(src, (col("v") > 300) & (col("w") < 40)), (part,)


def _fused_map(cfg, part, src):
    df = dt.from_partitions([part], part.schema).where(
        col("v") > 300).select((col("v") + col("w")).alias("x"), col("k"))
    return _planned(df, cfg, FusedMapOp), (part,)


_AGGS = [col("v").sum().alias("s"), col("w").max().alias("m"),
         col("v").count().alias("c")]


def _agg(cfg, part, src):
    schema = part.agg(_AGGS, [col("k")]).schema
    return AggregateOp(src, _AGGS, [col("k")], schema), (part,)


def _agg_predicate(cfg, part, src):
    schema = part.agg(_AGGS, [col("k")]).schema
    return FusedFilterAggregateOp(src, col("w") > 10, _AGGS, [col("k")],
                                  schema), (part,)


def _sketch(cfg, part, src):
    aggs = [Expression(AggExpr("sketch_hll", col("v")._node)).alias("s")]
    return AggregateOp(src, aggs, [], part.agg(aggs, None).schema), (part,)


def _segment(cfg, part, src):
    df = (dt.from_partitions([part], part.schema)
          .select((col("v") * 2 + 1).alias("x"), col("w"), col("k"))
          .where(col("w") > 10).groupby("k")
          .agg(col("x").sum().alias("sx"), col("w").max().alias("mw")))
    return _planned(df, cfg, DeviceSegmentOp), (part,)


def _join(cfg, part, src):
    right = MicroPartition.from_pydict({
        "k2": list(range(7)), "name": [f"n{i}" for i in range(7)]})
    return JoinProbe([col("k")], [col("k2")], "inner", "right."), (part, right)


STEPS = {"projection": _projection, "filter": _filter,
         "fused_map": _fused_map, "agg": _agg,
         "agg_predicate": _agg_predicate, "sketch": _sketch,
         "segment": _segment, "join": _join}


@pytest.fixture(params=list(STEPS))
def step_parts(request, cfg):
    part = _part()
    step, parts = STEPS[request.param](
        cfg, part, InMemoryOp([part], part.schema))
    return step, parts


class CountingHealth(DeviceHealth):
    """A breaker that remembers how often it was told of a failure (the
    segment's staged ops succeed on the device afterwards and clear the
    consecutive count)."""

    failures = 0

    def record_failure(self, stats=None):
        self.failures += 1
        super().record_failure(stats)


def _ctx(cfg, health=None):
    return ExecutionContext(cfg, RuntimeStats(), device_health=health)


def _host_answer(cfg, step, parts):
    return step.host(_ctx(cfg), *parts).to_pydict()


def _counts(ctx):
    """The counters, timing and bytes apart (they follow the clock)."""
    return {k: v for k, v in ctx.stats.counters.items()
            if v and not k.endswith(("_ns", "_bytes"))}


def _device_side(step, counters):
    """What a step leaves bumped while the device has its partition."""
    names = [n for n in (step.counter, step.dispatches) if n]
    if isinstance(step, FusedMapOp):
        names.append("device_fused_maps")
    return {n: counters.get(n, 0) for n in names}


def test_launched_counts_and_tells_the_breaker(step_parts, cfg):
    step, parts = step_parts
    health = DeviceHealth(threshold=3, cooldown_s=30.0)
    health.record_failure()  # one earlier failure, for success to clear
    ctx = _ctx(cfg, health)
    fin = ctx.launch(step, *parts)
    assert fin is not None
    # launched, not resolved: the device-side counters are already up
    up = _device_side(step, ctx.stats.counters)
    assert up and all(v == 1 for v in up.values()), up
    assert health._consecutive == 1  # the launch alone proves nothing
    got = fin().to_pydict()
    assert got == _host_answer(cfg, step, parts)
    c = _counts(ctx)
    assert all(c.get(k, 0) == 1 for k in up), c
    if isinstance(step, JoinProbe):
        assert c.get("device_join_probes") == 1, c
    if step.fallbacks:
        assert step.fallbacks not in c, c
    assert "device_attempt_errors" not in c, c
    assert not any(k.startswith("host_") for k in c), c
    assert health._consecutive == 0 and health.state == DeviceHealth.CLOSED


def test_declined_launch_is_the_hosts_and_frees_the_probe(
        step_parts, cfg, monkeypatch):
    step, parts = step_parts
    monkeypatch.setattr(step, "launch", lambda ctx, *p: None, raising=False)
    # an open breaker past its cooldown: the attempt is the one probe
    health = DeviceHealth(threshold=1, cooldown_s=0.0)
    health.record_failure()
    assert health.state == DeviceHealth.OPEN
    ctx = _ctx(cfg, health)
    fin = ctx.launch(step, *parts)
    assert health._probe_inflight is False  # released: not wedged
    assert health.state == DeviceHealth.HALF_OPEN
    assert _counts(ctx) == {"device_breaker_probes": 1}
    if step.counts_failed_launch:
        # the segment: a fallback, answered by its staged ops (which take
        # the freed probe slot themselves and close the breaker)
        got = fin().to_pydict()
        assert ctx.stats.counters.get(step.fallbacks) == 1
        assert not ctx.stats.counters.get(step.dispatches)
    else:
        assert fin is None
        got = step.host(ctx, *parts).to_pydict()
        assert not any(_device_side(step, _counts(ctx)).values())
    assert got == _host_answer(cfg, step, parts)
    assert "device_attempt_errors" not in _counts(ctx)


def test_raising_resolver_falls_back_once(step_parts, cfg, monkeypatch):
    step, parts = step_parts
    want_answer = _host_answer(cfg, step, parts)  # and warms stage caches
    host = _ctx(cfg)
    step.host(host, *parts)

    def boom():
        raise RuntimeError("resolver blew up")

    monkeypatch.setattr(step, "launch", lambda ctx, *p: boom, raising=False)
    health = CountingHealth(threshold=3, cooldown_s=30.0)
    ctx = _ctx(cfg, health)
    fin = ctx.launch(step, *parts)
    assert fin is not None
    assert all(v == 1 for v in _device_side(step, ctx.stats.counters).values())
    assert fin().to_pydict() == want_answer
    assert ctx.stats.device_error.startswith(step.site + ": RuntimeError"), \
        ctx.stats.device_error
    assert health.failures == 1 and health.state == DeviceHealth.CLOSED
    # the device did not answer after all: its counter is taken back, and
    # what is left is one launch, one fallback, one reported error, and
    # exactly what the host kernel counts when it is asked directly
    want = _counts(host)
    for name in (step.dispatches, step.fallbacks, "device_attempt_errors"):
        if name:
            want[name] = want.get(name, 0) + 1
    assert _counts(ctx) == want


def test_run_is_launch_then_call(step_parts, cfg):
    step, parts = step_parts
    _ctx(cfg).run(step, *parts)  # warm: the partition's stage cache
    a, b = _ctx(cfg), _ctx(cfg)
    ran = a.run(step, *parts).to_pydict()
    fin = b.launch(step, *parts)
    assert ran == fin().to_pydict()
    assert _counts(a) == _counts(b)
    # and below the device threshold `run` is the host kernel, no attempt
    cfg.device_min_rows = 10 * N
    low, host = _ctx(cfg), _ctx(cfg)
    assert low.launch(step, *parts) is None
    assert low.run(step, *parts).to_pydict() == ran
    step.host(host, *parts)
    assert _counts(low) == _counts(host)
