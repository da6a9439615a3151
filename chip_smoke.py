"""chip_smoke.py — does the engine's main path run on the chip, and is it right?

One process that holds the accelerator and drives the engine through the
entry points a user calls (``set_execution_config``, ``from_arrow``,
``read_parquet``, the DataFrame API, ``dt.sql``, ``ServingRuntime.submit``,
the mesh runner) over TPC-H at ``--scale`` (SF1 by default: 6.0M lineitem,
1.5M orders, 150k customer rows, generated from ``--seed``), in 32-bit mode
with the device path switched on. Every answer is compared with the
``pyarrow.compute`` oracles of ``benchmarks/tpch.py`` (Q17 and Q18 with
``chipbench/queries``' references) at rtol 1e-6, and every
leg fails if any fallback, breaker, degraded or device-error counter moved:
on the chip a kernel the compiler refuses still gives the right answer from
the host path, and only the counters show it.

    python chip_smoke.py                      # on a machine with a TPU
    python chip_smoke.py --cpu --scale 0.01   # tier-1: 8 virtual CPU devices

Without ``--cpu`` a platform other than ``tpu`` is refused before any work.
Wall times are printed as smoke timings; they are not metrics and go in no
record as speed. The last line of stdout is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``; the exit code is 0
only when every leg that applies passed.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))

# counters that say "this ran on the device"; a warm run must repeat them
DEVICE_COUNTERS = (
    "device_aggregations", "device_resident_segments", "segment_dispatches",
    "device_agg_dispatches", "device_join_probes", "device_sorts",
    "device_projections", "device_filters", "device_fused_maps",
    "device_shuffles",
)


# where a query's host time went, by layer (daft_tpu/profile/timeline.py):
# always on, so a smoke run shows stage, wait and gather time without a trace
LAYER_COUNTERS = (
    ("plan", "planning_wall_ns"), ("stage", "stage_ns"),
    ("dispatch", "device_dispatch_ns"), ("wait", "device_wait_ns"),
    ("gather", "gather_ns"), ("host", "op_self_host_ns"),
)


def layer_times(counters: dict) -> str:
    """``plan 1ms stage 12ms ... staged 3.1MB in 2 columns morsels 0 views
    1 ... compiles 0
    (0ms) cache loads 0 dict lookups 1 packed 0 gathered agg reduce 1 dense
    0 kernel 0 sorted probe levels 56 compared 17 gathered sql plan 1.2ms 2
    subqueries 1 scalar subquery joins 1 (1 on the device) join filter 1
    built 2 device 1 resident 5990000 pruned entry setup 1.1ms finish
    0.9ms (teardown 0.0 metrics 0.7 record 0.1 history 0.0 persist 0.0)
    convert 0.2ms dispatch lookup 0.2ms call 1.0ms gc 3 (0 gen2) 0.0ms
    plan misses 0 (shape 0 config 0 binding 0 uncached 0)``: the layer
    counters of one query, for its printed line. A
    warm query that stages columns lost its stage cache; one that compiles
    (and for how long) met a shape the warm-up did not; the device maps
    that ran over a stage view of a partition larger than a morsel (and
    any morsels the host path streamed); one that gathers a
    dictionary predicate met a dictionary over ``DICT_PACKED_MAX_ENTRIES``;
    one whose aggregate took the kernel grouped into a bucket over
    ``DENSE_MAX_SEGMENTS``, one whose float sums took the sorted form into
    one over 4096; the levels of its join probes' searches that
    gathered are those beyond ``PROBE_COMPARE_LEVELS`` of each build; a query
    that came as SQL text says what its front end took and what its
    subqueries became (0 throughout for one built through the API); then
    the runtime join filter, the entry layer's regions and the hooks of
    its finish, the parts of the dispatch frames, the collections while it
    ran, and why the plan cache missed."""
    parts = [f"{label} {counters.get(key, 0) / 1e6:.0f}ms"
             for label, key in LAYER_COUNTERS]
    parts.append(f"staged {counters.get('stage_bytes', 0) / 1e6:.1f}MB "
                 f"in {counters.get('stage_columns', 0)} columns "
                 f"morsels {counters.get('stream_morsels', 0)} "
                 f"views {counters.get('device_maps_unsplit', 0)} "
                 f"gathered {counters.get('gather_bytes', 0) / 1e6:.1f}MB "
                 f"compiles {counters.get('xla_compiles', 0)} "
                 f"({counters.get('xla_compile_ns', 0) / 1e6:.0f}ms) "
                 f"cache loads {counters.get('xla_cache_loads', 0)} "
                 f"dict lookups {counters.get('dict_lookup_packed', 0)} packed "
                 f"{counters.get('dict_lookup_gather', 0)} gathered "
                 f"agg reduce {counters.get('agg_reduce_dense', 0)} dense "
                 f"{counters.get('agg_reduce_kernel', 0)} kernel "
                 f"{counters.get('agg_reduce_sorted', 0)} sorted "
                 f"probe levels {counters.get('join_probe_compare_levels', 0)} "
                 f"compared {counters.get('join_probe_gather_levels', 0)} "
                 f"gathered "
                 f"sql plan {counters.get('sql_plan_ns', 0) / 1e6:.1f}ms "
                 f"{counters.get('sql_subqueries', 0)} subqueries "
                 f"{counters.get('sql_scalar_subqueries', 0)} scalar "
                 f"subquery joins {counters.get('sql_subquery_joins', 0)} "
                 f"({counters.get('sql_subquery_joins_device', 0)} "
                 f"on the device) "
                 f"join filter {counters.get('join_filter_built', 0)} built "
                 f"{counters.get('join_filter_device_probes', 0)} device "
                 f"{counters.get('join_filter_resident_keys', 0)} resident "
                 f"{counters.get('join_filter_rows_pruned', 0)} pruned")

    def ms(key):
        return f"{counters.get(key, 0) / 1e6:.1f}"

    hooks = " ".join(f"{h} {ms(f'entry_finish_{h}_ns')}" for h in (
        "teardown", "metrics", "record", "history", "persist"))
    reasons = " ".join(
        f"{r} {counters.get(f'plan_cache_miss_{r}', 0)}"
        for r in ("shape", "config", "binding", "uncached"))
    parts.append(f"entry setup {ms('entry_setup_ns')}ms "
                 f"finish {ms('entry_finish_ns')}ms ({hooks}) "
                 f"convert {ms('entry_convert_ns')}ms "
                 f"dispatch lookup {ms('dispatch_lookup_ns')}ms "
                 f"call {ms('dispatch_call_ns')}ms "
                 f"gc {counters.get('gc_collections', 0)} "
                 f"({counters.get('gc_collections_gen2', 0)} gen2) "
                 f"{ms('gc_pause_ns')}ms "
                 f"plan misses {counters.get('plan_cache_misses', 0)} "
                 f"({reasons})")
    return " ".join(parts)


def failure_counters(counters: dict) -> dict:
    """Every counter that means a device path was refused, broke or was
    bypassed — all must stay zero."""
    bad = {}
    for k, v in counters.items():
        if not v:
            continue
        if (k in ("degraded_completions", "segment_fallbacks",
                  "degraded_shuffles", "degraded_sketch_merges",
                  "device_attempt_errors")
                or k.endswith("_breaker_trips")
                or (k.startswith("device_") and k.endswith("_fallbacks"))):
            bad[k] = v
    return bad


class Leg:
    """One leg's checks and its printed verdict. Notes print as they are
    made, so a run that is killed still shows how far it got."""

    def __init__(self, name: str):
        self.name = name
        self.problems: list = []
        self.t0 = time.perf_counter()

    def check(self, ok: bool, what: str) -> bool:
        if not ok:
            self.problems.append(what)
        return ok

    def note(self, text: str) -> None:
        print(f"    {self.name}: {text}", flush=True)

    def counters_clean(self, label: str, counters: dict, **at_least) -> None:
        bad = failure_counters(counters)
        self.check(not bad, f"{label}: failure counters {bad}")
        for key, floor in at_least.items():
            self.check(counters.get(key, 0) >= floor,
                       f"{label}: {key}={counters.get(key, 0)} < {floor}")
        shown = {k: counters[k] for k in DEVICE_COUNTERS if counters.get(k)}
        self.note(f"{label}: {shown}")

    def finish(self) -> bool:
        ok = not self.problems
        wall = time.perf_counter() - self.t0
        for p in self.problems:
            print(f"    {self.name}: FAIL: {p}")
        print(f"[{self.name}] {'ok' if ok else 'FAIL'}  ({wall:.1f}s)",
              flush=True)
        return ok


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def run_query(build):
    """Build, execute and fetch one query: (answer dict, counters)."""
    q = build()
    got = q.collect().to_pydict()
    return got, q.stats.snapshot()["counters"]


Q1_SQL = """
    SELECT l_returnflag, l_linestatus,
           SUM(l_quantity) AS sum_qty,
           SUM(l_extendedprice) AS sum_base_price,
           SUM(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
           SUM(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
           AVG(l_quantity) AS avg_qty,
           AVG(l_extendedprice) AS avg_price,
           AVG(l_discount) AS avg_disc,
           COUNT(l_quantity) AS count_order
    FROM lineitem
    WHERE l_shipdate <= DATE '1998-09-02'
    GROUP BY l_returnflag, l_linestatus
    ORDER BY l_returnflag, l_linestatus
"""

Q6_SQL = """
    SELECT SUM(l_extendedprice * l_discount) AS revenue
    FROM lineitem
    WHERE l_shipdate >= DATE '1994-01-01' AND l_shipdate < DATE '1995-01-01'
      AND l_discount BETWEEN 0.05 AND 0.07 AND l_quantity < 24
"""

# a subquery: dt.sql decorrelates EXISTS into a semi join of its own making
EXISTS_SQL = """
    SELECT COUNT(*) AS n FROM orders
    WHERE EXISTS (SELECT * FROM lineitem
                  WHERE l_orderkey = o_orderkey AND l_quantity >= 50)
"""


class Smoke:
    def __init__(self, args, jax):
        from benchmarks import tpch

        import daft_tpu as dt

        self.args, self.jax, self.dt, self.tpch = args, jax, dt, tpch
        self.rtol = 1e-6
        t0 = time.perf_counter()
        self.tables = tpch.generate_tables(scale=args.scale, seed=args.seed)
        t_gen = time.perf_counter() - t0
        li, orders = self.tables["lineitem"], self.tables["orders"]
        cust, nation = self.tables["customer"], self.tables["nation"]
        # collected once: the partitions carry the per-partition stage
        # cache, so the columns stay HBM-resident across every query below
        self.li = dt.from_arrow(li).collect()
        self.orders = dt.from_arrow(orders).collect()
        self.cust = dt.from_arrow(cust).collect()
        self.nation = dt.from_arrow(nation).collect()
        t0 = time.perf_counter()
        self.want = {
            "q1": tpch.oracle_q1(li),
            "q6": {"revenue": [tpch.oracle_q6(li)]},
            "q12": tpch.oracle_q12(li),
            "q3": tpch.oracle_q3(cust, orders, li),
            "q5": tpch.oracle_q5(cust, orders, li, nation),
        }
        print(f"data: lineitem={li.num_rows} orders={orders.num_rows} "
              f"customer={cust.num_rows} rows, seed={args.seed} "
              f"(generate {t_gen:.1f}s, oracles "
              f"{time.perf_counter() - t0:.1f}s)")
        self.build = {
            "q1": lambda: tpch.q1(self.li),
            "q6": lambda: tpch.q6(self.li),
            "q12": lambda: tpch.q12(self.li),
            "q3": lambda: tpch.q3(self.cust, self.orders, self.li),
            "q5": lambda: tpch.q5(self.cust, self.orders, self.li,
                                  self.nation),
        }
        # first-run device counters per query, for the sql leg to compare
        self.api_counters: dict = {}

    def matches(self, name: str, got: dict) -> bool:
        return self.tpch.parity(got, self.want[name], self.rtol)

    # ------------------------------------------------------------ resident
    def leg_resident(self) -> bool:
        leg = Leg("resident")
        # q1/q6/q12 plan as ONE fused filter+aggregate program over the
        # resident columns; q3/q5 project `revenue` below their aggregate,
        # which is the shape the planner compiles into a resident segment.
        # q5's final sort orders five nation rows (host, under any
        # device_min_rows), so only q3's sort over its groups is asked for.
        floors = {
            "q1": dict(device_aggregations=1),
            "q6": dict(device_aggregations=1),
            "q12": dict(device_aggregations=1),
            "q3": dict(device_aggregations=1, device_resident_segments=1,
                       device_join_probes=1, device_sorts=1),
            "q5": dict(device_aggregations=1, device_resident_segments=1,
                       device_join_probes=1),
        }
        for name in ("q1", "q6", "q12", "q3", "q5"):
            (got1, c1), cold = timed(lambda: run_query(self.build[name]))
            (got2, c2), warm = timed(lambda: run_query(self.build[name]))
            leg.check(self.matches(name, got1), f"{name} run 1 != oracle")
            # the second run reads what the first left in the stage cache:
            # anything that consumed or freed a resident buffer fails HERE
            leg.check(self.matches(name, got2), f"{name} run 2 != oracle")
            leg.counters_clean(f"{name} run 1", c1, **floors[name])
            bad2 = failure_counters(c2)
            leg.check(not bad2, f"{name} run 2: failure counters {bad2}")
            d1 = {k: c1.get(k, 0) for k in DEVICE_COUNTERS}
            d2 = {k: c2.get(k, 0) for k in DEVICE_COUNTERS}
            leg.check(d1 == d2, f"{name}: device counters moved between "
                                f"runs: {d1} -> {d2}")
            leg.note(f"{name}: smoke timing cold {cold:.2f}s warm {warm:.2f}s")
            leg.note(f"{name} warm: {layer_times(c2)}")
            self.api_counters[name] = d1
        return leg.finish()

    # ----------------------------------------------------------------- sql
    def leg_sql(self) -> bool:
        leg = Leg("sql")
        for name, text in (("q1", Q1_SQL), ("q6", Q6_SQL)):
            (got, c), wall = timed(lambda: run_query(
                lambda: self.dt.sql(text, lineitem=self.li)))
            leg.check(self.matches(name, got), f"{name} sql != oracle")
            leg.counters_clean(f"{name} sql", c, device_aggregations=1)
            d = {k: c.get(k, 0) for k in DEVICE_COUNTERS}
            leg.check(d == self.api_counters.get(name),
                      f"{name}: sql device counters {d} != DataFrame API's "
                      f"{self.api_counters.get(name)}")
            leg.note(f"{name}: smoke timing {wall:.2f}s")
            leg.note(f"{name} sql: {layer_times(c)}")
        import pyarrow.compute as pc

        li = self.tables["lineitem"]
        want = len(pc.unique(li.filter(pc.greater_equal(
            li["l_quantity"], 50))["l_orderkey"]))
        (got, c), wall = timed(lambda: run_query(lambda: self.dt.sql(
            EXISTS_SQL, orders=self.orders, lineitem=self.li)))
        leg.check(got == {"n": [want]}, f"exists sql: {got} != {want}")
        leg.counters_clean("exists sql", c, device_join_probes=1,
                           sql_subqueries=1, sql_subquery_joins_device=1)
        leg.note(f"exists: smoke timing {wall:.2f}s")
        leg.note(f"exists sql: {layer_times(c)}")
        return leg.finish()

    # ------------------------------------------------------------ subquery
    def leg_subquery(self) -> bool:
        """TPC-H Q17 and Q18 from their SQL text, over chipbench's tables for
        them (PART and ``c_name`` are theirs) and against its references:
        float sums over one group a part key and one an order key, the
        sorted-segment form wherever that is over 4096 groups."""
        from importlib import import_module

        leg = Leg("subquery")
        queries = {n: import_module(f"chipbench.queries.{n}")
                   for n in ("sql17", "sql18")}
        columns: dict = {}
        for q in queries.values():
            for table, cols in q.COLUMNS.items():
                columns[table] = sorted({*columns.get(table, ()), *cols})
        tables = import_module("chipbench.datasets.tpch_sql").generate(
            self.args.scale, self.args.seed, columns)
        frames = {t: self.dt.from_arrow(a).collect() for t, a in tables.items()}
        groups = {"sql17": tables["part"].num_rows,
                  "sql18": tables["orders"].num_rows}
        for name, q in queries.items():
            want = q.reference(tables)
            (got, _), cold = timed(lambda: run_query(lambda: q.build(frames)))
            (got2, c), warm = timed(lambda: run_query(lambda: q.build(frames)))
            leg.check(self.tpch.parity(got, want, self.rtol)
                      and self.tpch.parity(got2, want, self.rtol),
                      f"{name} != reference")
            leg.counters_clean(
                f"{name} run 2", c, device_aggregations=1,
                agg_reduce_sorted=int(groups[name] > 4096))
            leg.note(f"{name}: smoke timing cold {cold:.2f}s warm {warm:.2f}s")
            leg.note(f"{name} warm: {layer_times(c)}")
        return leg.finish()

    # ---------------------------------------------------------------- scan
    def leg_scan(self) -> bool:
        import pyarrow.parquet as papq

        leg = Leg("scan")
        li = self.tables["lineitem"]
        nfiles = 8
        per = -(-li.num_rows // nfiles)
        pq_dir = os.path.join(self.args.out, "lineitem_parquet")
        shutil.rmtree(pq_dir, ignore_errors=True)
        os.makedirs(pq_dir)
        cfg = self.dt.get_context().execution_config
        saved_min = cfg.scan_tasks_min_size_bytes
        try:
            for i in range(nfiles):
                papq.write_table(li.slice(i * per, per), os.path.join(
                    pq_dir, f"part-{i:02d}.parquet"))
            # one scan task per file at every scale (small files would
            # otherwise merge into one task and one partition)
            self.dt.set_execution_config(scan_tasks_min_size_bytes=1)
            (got, c), wall = timed(lambda: run_query(
                lambda: self.tpch.q1(self.dt.read_parquet(
                    os.path.join(pq_dir, "*.parquet")))))
        finally:
            self.dt.set_execution_config(scan_tasks_min_size_bytes=saved_min)
            shutil.rmtree(pq_dir, ignore_errors=True)
        leg.check(self.matches("q1", got), "q1 over parquet != oracle")
        dispatches = (c.get("segment_dispatches", 0)
                      + c.get("device_agg_dispatches", 0))
        leg.check(dispatches >= nfiles,
                  f"{dispatches} double-buffered dispatch(es) for {nfiles} "
                  "partitions")
        leg.counters_clean("q1 parquet", c, device_aggregations=nfiles)
        leg.note(f"q1: smoke timing {wall:.2f}s over {nfiles} files")
        return leg.finish()

    # ------------------------------------------------------------- serving
    def leg_serving(self) -> bool:
        leg = Leg("serving")
        rt = self.dt.ServingRuntime()
        mix = ["q1", "q6", "q3", "q1", "q6", "q3", "q1", "q6"]
        try:
            t0 = time.perf_counter()
            handles = [(name, rt.submit(self.build[name]())) for name in mix]
            for name, h in handles:
                out = h.result(timeout=900)
                leg.check(self.matches(name, out.to_pydict()),
                          f"{h.query_id} ({name}) != oracle")
                bad = failure_counters(h.stats.snapshot()["counters"])
                leg.check(not bad, f"{h.query_id} ({name}): failure "
                                   f"counters {bad}")
            wall = time.perf_counter() - t0
            leg.check(rt.admission.shed_total == 0,
                      f"{rt.admission.shed_total} submission(s) shed")
            leg.note(f"{len(handles)} submissions in flight together, "
                     f"{rt.admission.admitted_total} admitted, "
                     f"{rt.admission.shed_total} shed; smoke timing "
                     f"{wall:.2f}s")
        finally:
            rt.shutdown(timeout_s=30)
        return leg.finish()

    # -------------------------------------------------------------- resize
    def leg_resize(self) -> bool:
        import numpy as np
        from benchmarks import laion

        from daft_tpu import DataType, col, multimodal

        leg = Leg("resize")
        jax = self.jax
        # 2560 = one full 2048-image device chunk plus a padded tail
        n = 2560 if self.args.scale >= 1 else 96
        rng = np.random.RandomState(self.args.seed)
        blocks = rng.randint(0, 256, (n, 6, 6, 3), dtype=np.uint8)
        imgs = np.repeat(np.repeat(blocks, 16, axis=1), 16, axis=2)
        series = multimodal.image_series_from_arrays(list(imgs), "img").cast(
            DataType.image("RGB", 96, 96))
        df = self.dt.from_pydict({"img": series})
        q = (df.select(col("img").image.resize(224, 224).alias("r"))
             .select(col("r").cast(DataType.tensor(
                 DataType.uint8(), (224, 224, 3))).alias("t")))
        out, wall = timed(q.collect)
        got = laion.frame_tensors(out, 224)
        want = np.clip(np.rint(np.asarray(jax.device_get(jax.image.resize(
            jax.numpy.asarray(imgs.astype(np.float32)),
            (n, 224, 224, 3), method="bilinear")))), 0, 255).astype(np.uint8)
        diff = np.abs(got.astype(np.int16) - want.astype(np.int16))
        # the +-1-count gate of benchmarks/laion.run_rung
        leg.check(float(diff.mean()) <= 0.5 and int(diff.max()) <= 2,
                  f"resize off the jax.image.resize reference: mean "
                  f"{diff.mean():.4f} max {diff.max()}")
        jitted = multimodal._RS_JIT is not None
        if jax.default_backend() != "cpu":
            leg.check(jitted, "the jitted resize branch did not run")
        leg.note(f"{n} images 96x96 -> 224x224, jitted branch ran: {jitted}, "
                 f"mean |diff| {diff.mean():.4f}, max {diff.max()}; smoke "
                 f"timing {wall:.2f}s")
        return leg.finish()

    # ---------------------------------------------------------------- mesh
    def leg_mesh(self) -> bool:
        from __graft_entry__ import run_multichip_steps

        from daft_tpu import col
        from daft_tpu.parallel import default_mesh

        leg = Leg("mesh")
        jax, dt, tpch = self.jax, self.dt, self.tpch
        n = len(jax.devices())
        _, wall = timed(lambda: run_multichip_steps(default_mesh(n)))
        leg.note(f"run_multichip_steps on {n} device(s): smoke timing "
                 f"{wall:.2f}s")
        # an n-way hash repartition of the fact table over the mesh, then
        # the query (q3's sort over its groups is a global range shuffle)
        builds = {
            "q1": lambda: tpch.q1(self.li.repartition(
                n, col("l_returnflag"), col("l_linestatus"))),
            "q3": lambda: tpch.q3(self.cust, self.orders,
                                  self.li.repartition(n, col("l_orderkey"))),
        }
        for name, build in builds.items():
            dt.set_runner_native()
            native, _ = run_query(build)
            dt.set_runner_mesh()
            try:
                (got, c), wall = timed(lambda: run_query(build))
            finally:
                dt.set_runner_native()
            leg.check(self.matches(name, got), f"{name} on mesh != oracle")
            leg.check(tpch.parity(got, native, self.rtol),
                      f"{name} on mesh != NativeRunner")
            # (a tripped collective breaker is one of the failure counters)
            leg.counters_clean(f"{name} mesh", c, device_shuffles=1)
            leg.note(f"{name}: smoke timing {wall:.2f}s")
        for d in jax.devices():
            ms = d.memory_stats() or {}
            leg.note(f"device {d.id}: bytes_in_use={ms.get('bytes_in_use')} "
                     f"peak_bytes_in_use={ms.get('peak_bytes_in_use')}")
        return leg.finish()


def peak_hbm(jax) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def cache_files(path) -> int:
    if not path or not os.path.isdir(path):
        return 0
    return sum(len(files) for _, _, files in os.walk(path))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scale", type=float, default=1.0,
                    help="TPC-H scale factor (1.0 = 6.0M lineitem rows)")
    ap.add_argument("--seed", type=int, default=42)
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out",
                                                  "chip_smoke"),
                    help="directory for the scan leg's Parquet files")
    ap.add_argument("--cpu", action="store_true",
                    help="run on 8 virtual CPU devices (tier-1 tests); "
                         "without it anything but a TPU is refused")
    args = ap.parse_args(argv)

    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()
    import jax

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    if dev.platform != "tpu" and not args.cpu:
        print(f"chip_smoke: refused — jax found platform {dev.platform!r}, "
              "not a TPU (tier-1 runs pass --cpu)", file=sys.stderr)
        return 2

    sys.path.insert(0, HERE)
    try:
        import daft_tpu as dt
        from daft_tpu import native
        from daft_tpu.kernels.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: refused — the engine is not beside this script "
              f"({e})", file=sys.stderr)
        return 2

    cache_dir = configure_compile_cache()
    files_start = cache_files(cache_dir)
    print(f"chip_smoke: jax={jax.__version__} platform={dev.platform} "
          f"device_kind={dev.device_kind!r} devices={device['count']} "
          f"x64={bool(jax.config.jax_enable_x64)} compile_cache={cache_dir} "
          f"native={native.available()}")
    os.makedirs(args.out, exist_ok=True)
    dt.set_execution_config(
        use_device_kernels=True,
        # the device threshold shrinks with the data, so the operators that
        # engage at SF1 engage at a test scale too (4096 = the default)
        device_min_rows=max(8, int(4096 * min(1.0, args.scale))),
        # every run must execute: the second run of a query checks the
        # resident buffers, not a replayed result
        enable_result_cache=False)

    smoke = Smoke(args, jax)
    legs = [smoke.leg_resident, smoke.leg_sql, smoke.leg_subquery,
            smoke.leg_scan, smoke.leg_serving, smoke.leg_resize]
    if device["count"] >= 2:
        legs.append(smoke.leg_mesh)
    failed = []
    for leg in legs:
        name = leg.__name__[len("leg_"):]
        try:
            ok = leg()
        except Exception:  # report it, go on to the next leg, exit non-zero
            traceback.print_exc(file=sys.stdout)
            print(f"[{name}] FAIL  (raised)")
            ok = False
        if not ok:
            failed.append(name)
        print(f"    {name}: peak_bytes_in_use so far {peak_hbm(jax)}",
              flush=True)
    if device["count"] < 2:
        print("mesh: skipped (1 device)")

    print(f"footer: peak_bytes_in_use per device {peak_hbm(jax)}; compile cache "
          f"files {files_start} at start, {cache_files(cache_dir)} at end; "
          f"failed legs: {failed or 'none'}")
    dt.shutdown()
    print(json.dumps({"ok": not failed, "device": device}))
    return 0 if not failed else 1


if __name__ == "__main__":
    sys.exit(main())
