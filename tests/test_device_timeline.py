"""The program's spans on the device's clock (daft_tpu/profile/timeline.py):
``device_trace_live``, spans as ``TraceAnnotation``s of the profiler's own
xplane, arming before planning under every cause, the disarmed guard, and
the always-on stage / dispatch / wait / gather / host-self / compile
counters. CPU backend: the xplane is the same file a TPU run writes, less
the device planes."""

import glob
import os
import time

import jax
import numpy as np
import pytest

import daft_tpu as dt
from daft_tpu import col, tracing
from daft_tpu.profile import arm_for_query, device_trace_live
from daft_tpu.profile.spans import DISARMED

PREFIX = "daft_tpu:"
LAYER_NS = ("stage_ns", "device_dispatch_ns", "device_wait_ns", "gather_ns",
            "op_self_host_ns")


@pytest.fixture
def device_cfg():
    """Every partition takes the device path, on one thread, uncached."""
    cfg = dt.get_context().execution_config
    names = ("use_device_kernels", "device_min_rows", "enable_result_cache",
             "executor_threads", "enable_profiling")
    saved = {k: getattr(cfg, k) for k in names}
    cfg.use_device_kernels = True
    cfg.device_min_rows = 1
    cfg.enable_result_cache = False
    cfg.executor_threads = 1
    yield cfg
    for k, v in saved.items():
        setattr(cfg, k, v)


@pytest.fixture
def session(tmp_path):
    """``start()`` / ``stop()`` of a jax.profiler session at the levels the
    benchmark uses; ``stop()`` returns the xplane's events per line."""
    state = {"live": False}

    def start():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1  # TraceAnnotation's level
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        state["live"] = True

    def stop():
        jax.profiler.stop_trace()
        state["live"] = False
        found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                       "*", "*.xplane.pb"))
        assert len(found) == 1
        lines = {}
        for plane in jax.profiler.ProfileData.from_file(found[0]).planes:
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events if e.name.startswith(PREFIX)]
                if evs:
                    lines[f"{plane.name}/{line.name}"] = evs
        return lines

    yield start, stop
    if state["live"]:
        jax.profiler.stop_trace()


def _frames(n=4096, seed=3):
    rng = np.random.default_rng(seed)
    fact = dt.from_pydict({"k": rng.integers(0, 64, n).tolist(),
                           "v": rng.random(n).tolist()}).collect()
    dim = dt.from_pydict({"k": list(range(64)) * 2,
                          "w": list(range(128))}).collect()
    return fact, dim


def _join_query(fact, dim):
    # N:M join (dim's keys repeat) -> filter -> grouped aggregate -> sort
    return (fact.join(dim, on="k").where(col("v") > 0.1)
            .groupby("k").agg(col("w").sum().alias("s")).sort("k"))


def _counters(df):
    return df.stats.snapshot()["counters"]


def test_device_trace_live_follows_the_session(session):
    start, stop = session
    assert device_trace_live() is False
    start()
    assert device_trace_live() is True
    stop()
    assert device_trace_live() is False


def test_spans_are_events_of_the_xplane_nested_like_the_tree(device_cfg,
                                                             session):
    fact, dim = _frames()
    _join_query(fact, dim).collect()  # compile outside the session
    start, stop = session
    start()
    df = _join_query(fact, dim).collect()
    lines = stop()

    prof = df.stats.profiler
    assert prof.armed and prof.device_timeline
    assert df.profile() is None  # armed by the session, no artifact asked
    events = [ev for evs in lines.values() for ev in evs]
    names = {name for name, _, _ in events}
    for want in ("phase:plan", "phase:stage", "phase:dispatch",
                 "phase:device.wait", "phase:gather", "phase:join.expand",
                 "phase:join.assemble"):
        assert PREFIX + want in names, (want, sorted(names))
    assert any(n.startswith(PREFIX + "op:") for n in names)

    # every recorded span has its event: same name, and the two clocks
    # differ by one constant (taken from the query's only `plan` span)
    spans = prof.spans_snapshot()
    by_name = {}
    for ev in events:
        by_name.setdefault(ev[0], []).append(ev)
    (plan,) = [s for s in spans if s.name == "plan"]
    (plan_ev,) = by_name[PREFIX + "phase:plan"]
    offset = plan_ev[1] - plan.t0_ns
    event_of = {}
    for s in spans:
        cands = by_name[f"{PREFIX}{s.kind}:{s.name}"]
        ev = min(cands, key=lambda e: abs(e[1] - (s.t0_ns + offset)))
        assert abs(ev[1] - (s.t0_ns + offset)) < 2_000_000, (s, ev)
        event_of[s.sid] = ev
    assert len(set(event_of.values())) == len(spans)

    # each child inside its parent's interval, on the device's clock
    checked = set()
    for s in spans:
        if s.parent is None or s.parent not in event_of:
            continue
        _, c0, cd = event_of[s.sid]
        pname, p0, pd = event_of[s.parent]
        assert p0 <= c0 and c0 + cd <= p0 + pd, (s, pname)
        checked.add((pname.split(":", 1)[1], f"{s.kind}:{s.name}"))
    kinds = {(p.split(":")[0], c) for p, c in checked}
    assert ("op", "phase:dispatch") in kinds
    assert ("op", "phase:gather") in kinds
    assert ("phase:dispatch", "phase:stage") in checked
    assert ("phase:gather", "phase:device.wait") in checked
    assert ("phase:gather", "phase:join.expand") in checked
    # planning is no operator's child: it ran inside collect()'s set-up,
    # before any operator opened
    first_op = min(e[1] for e in events if e[0].startswith(PREFIX + "op:"))
    (setup,) = [s for s in spans if s.name == "entry.setup"]
    assert plan.parent == setup.sid and setup.parent is None
    assert plan_ev[1] + plan_ev[2] <= first_op


def test_no_session_no_profiler_no_annotation(device_cfg, monkeypatch):
    made = []
    real = jax.profiler.TraceAnnotation

    def counting(*args, **kwargs):
        made.append(args)
        return real(*args, **kwargs)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counting)
    fact, dim = _frames()
    df = _join_query(fact, dim).collect()
    assert df.stats.profiler is DISARMED
    assert _counters(df)["device_join_probes"] == 1
    # an armed profiler with no session live builds none either
    df = _join_query(fact, dim).collect(profile=True)
    assert df.stats.profiler.armed and not df.stats.profiler.device_timeline
    assert df.profile() is not None
    assert made == []


def test_layer_counters_fit_inside_the_querys_wall(device_cfg):
    fact, dim = _frames(seed=5)
    walls = []
    for _ in range(2):
        q = _join_query(fact, dim)
        t0 = time.perf_counter_ns()
        q.collect()
        walls.append((time.perf_counter_ns() - t0, _counters(q)))
    for wall, c in walls:
        assert all(c[k] >= 0 for k in LAYER_NS), c
        assert c["device_dispatch_ns"] > 0 and c["gather_ns"] > 0
        assert c["gather_bytes"] > 0
        assert sum(c[k] for k in LAYER_NS) <= wall, (wall, c)
        assert sum(c[k] for k in LAYER_NS) + c["planning_wall_ns"] <= wall


def test_a_resident_frame_stages_once(device_cfg):
    fact, _ = _frames(n=2048, seed=7)

    def q():
        return (fact.where(col("v") > 0.5)
                .agg(col("v").sum().alias("s"))).collect()

    first, second = _counters(q()), _counters(q())
    assert first["stage_columns"] >= 1
    assert first["stage_bytes"] >= 2048 * 8  # padded values + validity
    assert first["stage_ns"] > 0
    # the second query finds every column in the frame's stage cache
    assert second.get("stage_bytes", 0) == 0
    assert second.get("stage_columns", 0) == 0
    assert second["stage_ns"] == 0
    assert second["device_dispatch_ns"] > 0


def test_a_new_shape_compiles_once_and_its_repeat_not_at_all(device_cfg):
    # a row count in a size bucket no other test of this file uses
    rng = np.random.default_rng(11)
    frame = dt.from_pydict({"v": rng.random(70_000).tolist()}).collect()

    def q():
        return (frame.where(col("v") * 3.25 > 1.0)
                .agg(col("v").max().alias("m"))).collect()

    first, second = _counters(q()), _counters(q())
    assert first["xla_compiles"] >= 1
    assert first["xla_compile_ns"] > 0
    assert second["xla_compiles"] == 0
    assert "xla_compile_ns" not in second
    # a host-only query reads the layer counters too, all zero
    host = dt.from_pydict({"a": [1, 2, 3]})
    cfg = dt.get_context().execution_config
    cfg.use_device_kernels = False
    c = _counters(host.where(col("a") > 1).collect())
    assert [c[k] for k in LAYER_NS[:4]] == [0, 0, 0, 0]
    assert c["xla_compiles"] == 0 and c["op_self_host_ns"] > 0


@pytest.mark.parametrize("cause", ["profile_arg", "enable_profiling",
                                   "chrome_trace", "device_trace",
                                   "serving_device_trace"])
def test_planning_is_inside_a_span_under_every_arming_cause(
        cause, device_cfg, session, tmp_path):
    fact, dim = _frames(n=1024)
    q = _join_query(fact, dim)
    start, stop = session
    if cause == "profile_arg":
        stats = q.collect(profile=True).stats
    elif cause == "enable_profiling":
        device_cfg.enable_profiling = True
        stats = q.collect().stats
    elif cause == "chrome_trace":
        with tracing.chrome_trace(str(tmp_path / "chrome.json")):
            stats = q.collect().stats
    elif cause == "device_trace":
        start()
        stats = q.collect().stats
        stop()
    else:
        from daft_tpu.serve import ServingRuntime

        rt = ServingRuntime(max_concurrent_queries=1, queue_depth=2)
        start()
        try:
            handle = rt.submit(q)
            handle.result(timeout=120)
        finally:
            stop()
            rt.shutdown()
        stats = handle.stats
        assert stats.profiler.query_id == handle.query_id
    prof = stats.profiler
    assert prof.armed
    assert prof.device_timeline == cause.endswith("device_trace")
    spans = prof.spans_snapshot()
    (plan,) = [s for s in spans if s.name == "plan"]
    # inside collect()'s set-up, or at the top where the serving runtime
    # runs the plan: in no operator either way
    setup = [s for s in spans if s.name == "entry.setup"]
    assert len(setup) == (cause != "serving_device_trace")
    assert plan.kind == "phase"
    assert plan.parent == (setup[0].sid if setup else None)
    ops = [s for s in spans if s.kind == "op"]
    assert ops and plan.t0_ns + plan.dur_ns <= min(s.t0_ns for s in ops)
    # the span is what planning_wall_ns times
    assert plan.dur_ns >= stats.snapshot()["counters"]["planning_wall_ns"]


def test_the_fallback_arms_plans_that_reach_execution_unarmed(device_cfg,
                                                              session):
    fact, dim = _frames(n=1024)
    q = _join_query(fact, dim)
    start, stop = session
    start()
    list(q.iter_partitions())  # no collect(): execute_plan's fallback arms
    lines = stop()
    assert q.stats.profiler.armed and q.stats.profiler.device_timeline
    names = {n for evs in lines.values() for n, _, _ in evs}
    assert PREFIX + "phase:dispatch" in names
    assert PREFIX + "phase:plan" not in names  # planned before it was armed


def test_arm_for_query_leaves_an_unasked_query_alone():
    from daft_tpu.execution import RuntimeStats

    stats = RuntimeStats()
    assert arm_for_query(stats, "q-x") is False
    assert stats.profiler is DISARMED
    assert arm_for_query(stats, "q-x", profile=True) is True
    assert stats.profiler.armed and stats.profiler.query_id == "q-x"


def test_frames_on_many_threads_lose_no_update_and_count_no_ns_twice():
    import sys
    import threading

    from daft_tpu.execution import RuntimeStats
    from daft_tpu.profile import timeline

    stats = RuntimeStats()
    n_threads, n_iter = 16, 300
    walls = [0] * n_threads
    outside = []

    def work(i):
        for _ in range(n_iter):
            t0 = time.perf_counter_ns()
            with timeline.DeviceFrame(stats, "dispatch",
                                      "device_dispatch_ns"):
                with timeline.timed("stage", "stage_ns"):
                    timeline.add("stage_columns", 1)
                with timeline.DeviceFrame(stats, "gather", "gather_ns"):
                    with timeline.timed("device.wait", "device_wait_ns"):
                        pass
                    with timeline.timed("join.expand"):  # owned, no counter
                        pass
            walls[i] += time.perf_counter_ns() - t0
        outside.append(timeline.current_frame())

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    c = stats.snapshot()["counters"]
    assert c["stage_columns"] == n_threads * n_iter
    keys = ("device_dispatch_ns", "stage_ns", "gather_ns", "device_wait_ns")
    assert all(c[k] >= 0 for k in keys)
    # every thread's frames partition its wall: nothing counted twice
    assert sum(c[k] for k in keys) <= sum(walls)
    assert outside == [None] * n_threads  # every frame was popped
    assert timeline.current_frame() is None


# ------------------------------------------- the entry layer, dispatch parts,
# the collector and the plan cache's misses

ENTRY_NS = ("entry_setup_ns", "entry_finish_ns", "entry_convert_ns")
FINISH_HOOKS_NS = ("entry_finish_teardown_ns", "entry_finish_metrics_ns",
                   "entry_finish_record_ns", "entry_finish_history_ns")


def test_one_query_counts_every_entry_region_and_both_dispatch_parts(
        device_cfg):
    fact, dim = _frames(n=2048, seed=13)
    for _ in range(2):  # the second plans from the plan cache
        q = _join_query(fact, dim)
        t0 = time.perf_counter_ns()
        q.collect().to_pydict()
        wall = time.perf_counter_ns() - t0
        c = _counters(q)
        assert all(c[k] > 0 for k in ENTRY_NS + FINISH_HOOKS_NS), c
        # no cache_dir: nothing to persist, and no counter for it
        assert "entry_finish_persist_ns" not in c
        assert sum(c[k] for k in FINISH_HOOKS_NS) <= c["entry_finish_ns"]
        assert c["dispatch_lookup_ns"] > 0 and c["dispatch_call_ns"] > 0
        # parts of the dispatch frames, not taken off them
        assert (c["dispatch_lookup_ns"] + c["dispatch_call_ns"]
                <= c["device_dispatch_ns"])
        # the regions nest as frames: together they stay inside the wall
        owned = sum(c[k] for k in LAYER_NS + ENTRY_NS) + c["planning_wall_ns"]
        assert owned <= wall, (wall, c)
        assert c["gc_collections"] >= 0 and c["gc_pause_ns"] >= 0


def test_a_part_is_named_inside_its_frame_and_taken_off_nothing():
    from daft_tpu.execution import RuntimeStats
    from daft_tpu.profile import timeline

    stats = RuntimeStats()
    with timeline.DeviceFrame(stats, "dispatch", "device_dispatch_ns"):
        with timeline.part("dispatch.call", "dispatch_call_ns"):
            time.sleep(0.02)
            # owned inside the part: off the part and the frame alike
            with timeline.timed("stage", "stage_ns"):
                time.sleep(0.01)
            # a part inside a part records nothing of its own
            with timeline.part("dispatch.lookup", "dispatch_lookup_ns"):
                pass
    c = stats.snapshot()["counters"]
    assert c["dispatch_call_ns"] >= 20_000_000
    assert c["stage_ns"] >= 10_000_000
    assert c["device_dispatch_ns"] >= c["dispatch_call_ns"]
    assert "dispatch_lookup_ns" not in c
    # outside a frame a part is a no-op
    with timeline.part("dispatch.call", "dispatch_call_ns"):
        pass
    assert stats.snapshot()["counters"]["dispatch_call_ns"] == \
        c["dispatch_call_ns"]


def test_a_forced_collection_lands_in_its_own_querys_counters(device_cfg):
    import gc

    from daft_tpu import DataType

    frame = dt.from_pydict({"a": list(range(64))}).collect()

    def collecting(x):
        if x == 0:
            gc.collect()  # a generation-2 collection, on the query's thread
        return x

    def q(fn):
        return frame.select(col("a").apply(fn, return_dtype=DataType.int64()))

    enabled = gc.isenabled()
    gc.disable()  # no collection but the forced one
    try:
        forced = q(collecting)
        forced.collect().to_pydict()
        other = q(lambda x: x)
        other.collect().to_pydict()
    finally:
        if enabled:
            gc.enable()
    c, o = _counters(forced), _counters(other)
    assert c["gc_collections_gen2"] == 1 and c["gc_collections"] >= 1
    assert c["gc_pause_ns"] > 0
    assert o["gc_collections_gen2"] == 0 and o["gc_pause_ns"] == 0


def test_a_profiled_query_records_its_collections_as_events(device_cfg):
    import gc

    from daft_tpu import DataType

    frame = dt.from_pydict({"a": list(range(8))}).collect()
    df = frame.select(col("a").apply(lambda x: gc.collect() and x,
                                     return_dtype=DataType.int64()))
    df.collect(profile=True)
    evs = [e for e in df.stats.profiler.events_snapshot()
           if e["kind"] == "gc"]
    assert evs and all(e["attrs"]["generation"] == 2
                       and e["attrs"]["dur_ns"] > 0 for e in evs)
    assert sum(e["attrs"]["dur_ns"] for e in evs) == \
        _counters(df)["gc_pause_ns"]


def test_every_plan_cache_miss_has_its_reason(device_cfg):
    from daft_tpu import faults
    from daft_tpu.adapt.plancache import PLAN_CACHE

    frame = dt.from_pydict({"k": [1, 2, 3] * 50, "v": list(range(150))})

    def q(lit):
        return frame.where(col("v") > lit).groupby("k").agg(
            col("v").sum().alias("s"))

    def reasons(df):
        c = _counters(df)
        return {k[len("plan_cache_miss_"):]: v for k, v in c.items()
                if k.startswith("plan_cache_miss_")}, c.get(
                    "plan_cache_misses", 0)

    PLAN_CACHE.clear()
    assert reasons(q(10).collect()) == ({"shape": 1}, 1)
    assert reasons(q(10).collect()) == ({}, 0)  # a hit
    assert reasons(q(20).collect()) == ({"binding": 1}, 1)
    device_cfg.device_min_rows = 2  # another config key
    assert reasons(q(10).collect()) == ({"config": 1}, 1)
    with faults.inject("fuse.compile", "always"):  # the cache stands down
        assert reasons(q(10).collect()) == ({"uncached": 1}, 1)


def test_a_miss_says_whether_only_the_generation_changed():
    from daft_tpu.adapt.plancache import CompiledPlan, PlanCache

    cache = PlanCache()
    key = "cfg=1|v1|g{}|rnative"
    assert cache.miss_reason("fp", key.format(0)) == ("shape", False)
    cache.store("fp", key.format(0), "b", CompiledPlan(None, None, 100),
                1 << 20)
    assert cache.miss_reason("fp", key.format(0)) == ("binding", False)
    assert cache.miss_reason("fp", key.format(3)) == ("config", True)
    assert cache.miss_reason("fp", "cfg=2|v1|g0|rnative") == ("config", False)
    assert cache.miss_reason("other", key.format(0)) == ("shape", False)


def test_the_entry_regions_and_dispatch_parts_are_events_of_the_xplane(
        device_cfg, session):
    fact, dim = _frames(n=1024, seed=17)
    _join_query(fact, dim).collect()  # compile outside the session
    start, stop = session
    start()
    _join_query(fact, dim).collect().to_pydict()
    lines = stop()
    names = {n for evs in lines.values() for n, _, _ in evs}
    for want in ("phase:entry.setup", "phase:entry.finish",
                 "phase:entry.convert", "phase:finish.teardown",
                 "phase:finish.metrics", "phase:finish.record",
                 "phase:finish.history", "phase:dispatch.lookup",
                 "phase:dispatch.call"):
        assert PREFIX + want in names, (want, sorted(names))
