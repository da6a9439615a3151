"""Subqueries through ``dt.sql``: the grammar, what decorrelation turns each
shape into, the refusals, and TPC-H Q4/Q17/Q18/Q22 from their SQL text
against the SQLite oracle (ISSUE 38)."""

import contextlib

import pytest

import daft_tpu as dt
from benchmarks import tpch_full, tpch_queries
from daft_tpu.logical import Aggregate, Filter, Join
from daft_tpu.optimizer import optimize

from test_tpch_suite import _assert_match, _rows, _sqlite_rows

SCALE = 0.002
TEXTS = (4, 17, 18, 22)   # the cell's four (ISSUE 38)
ALSO = (2, 20)            # what the same three shapes let through besides


@pytest.fixture(scope="module")
def data():
    return tpch_full.generate(scale=SCALE, seed=7)


@pytest.fixture(scope="module")
def oracle(data):
    conn = tpch_full.load_sqlite(data)
    yield conn
    conn.close()


@contextlib.contextmanager
def device_path(on=True, min_rows=8):
    cfg = dt.context.get_context().execution_config
    saved = (cfg.use_device_kernels, cfg.device_min_rows)
    cfg.use_device_kernels, cfg.device_min_rows = on, min_rows
    try:
        yield
    finally:
        cfg.use_device_kernels, cfg.device_min_rows = saved


def _catalog(data, num_parts=1):
    T = {}
    for name, tbl in data.items():
        df = dt.from_arrow(tbl)
        if num_parts > 1 and name in ("lineitem", "orders", "customer",
                                      "partsupp"):
            df = df.into_partitions(num_parts)
        T[name] = df
    return T


def _nodes(plan, kind):
    out = [plan] if isinstance(plan, kind) else []
    for c in plan.children():
        out += _nodes(c, kind)
    return out


def _joins(df, optimized=False):
    plan = optimize(df._plan) if optimized else df._plan
    return [(j.how, j.origin) for j in _nodes(plan, Join)]


# ------------------------------------------------- the oracle: four texts

@pytest.mark.parametrize("device", [False, True], ids=["host", "device"])
@pytest.mark.parametrize("num_parts", [1, 3])
@pytest.mark.parametrize("qn", TEXTS + ALSO)
def test_tpch_text_equals_sqlite(qn, num_parts, device, data, oracle):
    with device_path(device):
        q = dt.sql(tpch_queries.SQL[qn], **_catalog(data, num_parts))
        got = q.collect().to_pydict()
    _assert_match(_rows(got)[0], _sqlite_rows(oracle, tpch_queries.SQL[qn]),
                  qn)
    c = q.stats.snapshot()["counters"]
    assert c["sql_plan_ns"] > 0 and c["sql_subqueries"] >= 1
    assert c["sql_scalar_subqueries"] == (0 if qn in (4, 18) else 1)
    assert c["sql_subquery_joins"] >= 1
    if device and qn in (4, 18, 22):  # the others' filters leave no row here
        assert c["sql_subquery_joins_device"] == c["sql_subquery_joins"]
    if not device:
        assert c.get("sql_subquery_joins_device", 0) == 0


def test_q2_and_q20_answer_with_rows_where_the_data_has_some():
    """At the oracle's scale both answers are empty. Q2 as it is at 0.02;
    Q20 with a name prefix, a nation and a share that this data answers to:
    nested ``IN``, and a scalar correlated by two equalities."""
    data = tpch_full.generate(scale=0.02, seed=7)
    conn = tpch_full.load_sqlite(data)
    T = _catalog(data)
    first = data["part"]["p_name"][0].as_py()[:1]
    q20 = (tpch_queries.SQL[20].replace("forest%", first + "%")
           .replace("CANADA", "FRANCE").replace("0.5 *", "0.05 *"))
    try:
        for qn, text in ((2, tpch_queries.SQL[2]), (20, q20)):
            want = _sqlite_rows(conn, text)
            assert want, qn
            _assert_match(_rows(dt.sql(text, **T).to_pydict())[0], want, qn)
    finally:
        conn.close()


def test_q22_text_keeps_the_customers_without_orders():
    """The oracle's data gives every customer an order; here a third have
    none, so ``NOT EXISTS`` and the scalar average both decide rows."""
    n = 600
    customer = dt.from_pydict({
        "c_custkey": list(range(1, n + 1)),
        "c_phone": [f"{10 + k % 25}-555-000-{k:04d}" for k in range(n)],
        "c_acctbal": [float((k * 37) % 1000 - 100) for k in range(n)]})
    orders = dt.from_pydict({
        "o_custkey": [k for k in range(1, n + 1) if k % 3]})
    got = dt.sql(tpch_queries.SQL[22], customer=customer,
                 orders=orders).to_pydict()
    codes = {"13", "31", "23", "29", "30", "18", "17"}
    rows = [(f"{10 + k % 25}", float((k * 37) % 1000 - 100), k + 1)
            for k in range(n)]
    pool = [b for c, b, _ in rows if c in codes and b > 0.0]
    avg = sum(pool) / len(pool)
    want = {}
    for code, bal, key in rows:
        if code in codes and bal > avg and key % 3 == 0:
            cnt, tot = want.get(code, (0, 0.0))
            want[code] = (cnt + 1, tot + bal)
    assert got["cntrycode"] == sorted(want) and got["cntrycode"]
    assert got["numcust"] == [want[c][0] for c in sorted(want)]
    assert got["totacctbal"] == pytest.approx(
        [want[c][1] for c in sorted(want)])


# ---------------------------------------------------------- plan shapes

@pytest.fixture
def frames():
    return {
        "t": dt.from_pydict({"k": [1, 2, 3, 4, None], "g": [1, 1, 2, 2, 3],
                             "x": [1.0, 5.0, 2.0, 8.0, 3.0]}),
        "u": dt.from_pydict({"uk": [1, 1, 3, None], "y": [10, 20, 30, 40]}),
    }


def test_exists_is_one_semi_join_with_the_inner_conjunct_on_its_side(frames):
    df = dt.sql("SELECT k FROM t WHERE x > 0 AND EXISTS "
                "(SELECT * FROM u WHERE uk = k AND y >= 20)", **frames)
    assert _joins(df) == [("semi", "sql_subquery")]
    join = _nodes(df._plan, Join)[0]
    assert [f.predicate._node.display() for f in _nodes(join.right, Filter)] \
        == ["(col(y) >= lit(20))"]
    assert sorted(df.to_pydict()["k"]) == [1, 3]


def test_not_exists_is_an_anti_join_and_keeps_null_keys(frames):
    df = dt.sql("SELECT k, g FROM t WHERE NOT EXISTS "
                "(SELECT * FROM u WHERE uk = k)", **frames)
    assert _joins(df) == [("anti", "sql_subquery")]
    assert sorted(df.to_pydict()["g"]) == [1, 2, 3]  # k = 2, 4 and NULL


def test_in_select_is_a_semi_join_on_the_selected_column(frames):
    df = dt.sql("SELECT k FROM t WHERE k IN (SELECT uk FROM u GROUP BY uk "
                "HAVING SUM(y) >= 30)", **frames)
    assert _joins(df) == [("semi", "sql_subquery")]
    assert sorted(df.to_pydict()["k"]) == [1, 3]
    df = dt.sql("SELECT k FROM t WHERE k + 1 IN (SELECT uk FROM u)", **frames)
    assert _joins(df) == [("semi", "sql_subquery")]
    assert df.column_names == ["k"] and df.to_pydict()["k"] == [2]


def test_correlated_scalar_is_aggregate_inner_join_filter(frames):
    df = dt.sql("SELECT k FROM t WHERE x < (SELECT 0.1 * SUM(y) FROM u "
                "WHERE uk = k)", **frames)
    assert _joins(df) == [("inner", "sql_subquery")]
    join = _nodes(df._plan, Join)[0]
    agg = _nodes(join.right, Aggregate)
    assert len(agg) == 1 and [e.name() for e in agg[0].groupby] == ["uk"]
    above = _nodes(df._plan, Filter)[0]
    assert above.input is join and "__sq0" in above.predicate._node.display()
    assert df.column_names == ["k"]
    assert df.to_pydict()["k"] == [1, 3]  # 1.0 < 3.0, 2.0 < 3.0; 2, 4: no u


def test_uncorrelated_scalar_is_a_one_row_side_inside_the_plan(frames):
    df = dt.sql("SELECT k FROM t WHERE x > (SELECT AVG(x) FROM t "
                "WHERE x > 1.5)", **frames)
    assert _joins(df) == [("cross", None)]
    side = _nodes(df._plan, Join)[0].right
    agg = _nodes(side, Aggregate)
    assert len(agg) == 1 and agg[0].groupby == []
    assert side.schema.field_names() == ["__sq0"]
    assert sorted(df.to_pydict()["k"]) == [2, 4]  # the average is 4.5


def test_tpch_texts_take_the_shapes_the_issue_names(data):
    T = _catalog(data)
    shapes = {qn: _joins(dt.sql(tpch_queries.SQL[qn], **T), optimized=True)
              for qn in TEXTS}
    assert shapes[4] == [("semi", "sql_subquery")]
    # FROM lineitem, part WHERE p_partkey = l_partkey is a keyed join too
    assert shapes[17] == [("inner", "sql_subquery"), ("inner", None)]
    assert sorted(shapes[18], key=str) == [
        ("inner", None), ("inner", None), ("semi", "sql_subquery")]
    assert shapes[22] == [("anti", "sql_subquery"), ("cross", None)]


def test_a_semi_join_goes_onto_the_comma_factor_that_has_its_columns(data):
    """Q18: ``o_orderkey IN (...)`` restricts ORDERS before the three-way
    join, not its 6M-row result."""
    df = dt.sql(tpch_queries.SQL[18], **_catalog(data))
    semi = next(j for j in _nodes(optimize(df._plan), Join)
                if j.how == "semi")
    assert "o_totalprice" in semi.schema.field_names()
    assert "c_name" not in semi.schema.field_names()
    assert "l_quantity" not in semi.schema.field_names()


def test_sql_plans_and_executes_nothing(frames):
    before = {n: dict(f.stats.snapshot()["counters"])
              for n, f in frames.items()}
    df = dt.sql("SELECT k FROM t WHERE x > (SELECT AVG(x) FROM t) AND EXISTS "
                "(SELECT * FROM u WHERE uk = k)", **frames)
    snap = df.stats.snapshot()
    assert set(snap["counters"]) == {"sql_plan_ns", "sql_subqueries",
                                     "sql_scalar_subqueries"}
    assert snap["counters"]["sql_subqueries"] == 2
    assert snap["counters"]["sql_scalar_subqueries"] == 1
    assert snap["op_rows"] == {} and snap["op_wall_ns"] == {}
    assert df._result is None
    for n, f in frames.items():
        assert f.stats.snapshot()["counters"] == before[n]
    planned = snap["counters"]["sql_plan_ns"]
    df.collect()
    after = df.stats.snapshot()["counters"]
    assert after["sql_plan_ns"] == planned  # planned once, carried over
    assert after["sql_subquery_joins"] == 1 and after["host_joins"] >= 1


def test_the_front_end_has_spans_when_the_query_is_profiled(frames):
    cfg = dt.context.get_context().execution_config
    saved = cfg.enable_profiling
    cfg.enable_profiling = True
    try:
        df = dt.sql("SELECT k FROM t WHERE EXISTS "
                    "(SELECT * FROM u WHERE uk = k)", **frames).collect()
    finally:
        cfg.enable_profiling = saved
    spans = {s["name"]: s for s in df.profile().to_dict()["spans"]}
    assert {"sql.parse", "sql.plan", "sql.decorrelate", "plan"} <= set(spans)
    assert all(spans[n]["kind"] == "phase" for n in
               ("sql.parse", "sql.plan", "sql.decorrelate"))
    assert spans["sql.decorrelate"]["parent"] == spans["sql.plan"]["id"]
    inside = spans["sql.parse"]["dur_ns"] + spans["sql.plan"]["dur_ns"]
    assert inside <= df.stats.snapshot()["counters"]["sql_plan_ns"]
    # unprofiled: the counters all the same, no profiler left armed
    plain = dt.sql("SELECT k FROM t", **frames)
    assert not plain.stats.profiler.armed
    assert plain.stats.snapshot()["counters"]["sql_subqueries"] == 0


# -------------------------------------------------------------- refusals

@pytest.mark.parametrize("text,message", [
    ("SELECT k FROM t WHERE k NOT IN (SELECT uk FROM u)", "NOT IN"),
    ("SELECT k FROM t WHERE NOT (k IN (SELECT uk FROM u))", "NOT IN"),
    ("SELECT k FROM t WHERE g = 1 OR EXISTS (SELECT * FROM u WHERE uk = k)",
     "under OR"),
    ("SELECT k FROM t WHERE NOT (g = 1 AND EXISTS "
     "(SELECT * FROM u WHERE uk = k))", "under OR"),
    ("SELECT k FROM t WHERE g = 1 OR x > (SELECT AVG(x) FROM t)", "under OR"),
    ("SELECT k FROM t WHERE EXISTS (SELECT * FROM u WHERE uk < k)",
     "equality between one inner and one outer"),
    ("SELECT k FROM t WHERE x > (SELECT MAX(y) FROM u WHERE uk + 1 = k)",
     "equality between one inner and one outer"),
    ("SELECT k FROM t WHERE x > (SELECT y FROM u)", "more than one row"),
    ("SELECT k FROM t WHERE x > (SELECT SUM(y) FROM u GROUP BY uk)",
     "more than one row"),
    ("SELECT k, (SELECT MAX(y) FROM u) FROM t", "the SELECT list"),
    ("SELECT g FROM t GROUP BY g HAVING SUM(x) > (SELECT AVG(x) FROM t)",
     "HAVING"),
    ("SELECT k FROM t ORDER BY (SELECT MAX(y) FROM u)", "ORDER BY"),
    ("SELECT k FROM t JOIN u ON k = uk AND EXISTS (SELECT * FROM u)",
     "FROM / JOIN"),
    ("SELECT k FROM t WHERE EXISTS (SELECT * FROM u)", "uncorrelated EXISTS"),
    ("SELECT k FROM t WHERE EXISTS (SELECT uk FROM u WHERE uk = k "
     "GROUP BY uk)", "EXISTS over GROUP BY"),
    ("SELECT k FROM t WHERE k IN (SELECT uk, y FROM u)", "exactly one column"),
    ("SELECT k FROM t WHERE k IN (SELECT uk FROM u WHERE y = g)",
     "correlated IN"),
    ("SELECT k FROM t WHERE x > (SELECT COUNT(*) FROM u WHERE uk = k)",
     "COUNT"),
    ("SELECT k FROM t WHERE x > (SELECT MAX(y) FROM u ORDER BY 1 LIMIT 1)",
     "ORDER BY / LIMIT"),
    ("SELECT k FROM t AS a WHERE EXISTS (SELECT * FROM t AS b "
     "WHERE b.g = a.g AND b.k = a.k + 1)", "two instances of one column"),
])
def test_what_cannot_be_decorrelated_is_refused_by_name(text, message, frames):
    with pytest.raises(ValueError, match=message):
        dt.sql(text, **frames)


def test_sql_expr_has_no_catalog_for_a_subquery():
    with pytest.raises(ValueError, match="use sql"):
        dt.sql_expr("x > (SELECT MAX(y) FROM u)")


# ------------------------------------------------------------ NULL and empty

@pytest.mark.parametrize("device", [False, True], ids=["host", "device"])
def test_a_scalar_subquery_over_no_rows_is_null_and_compares_false(device,
                                                                    frames):
    with device_path(device):
        for cmp in ("<", ">", "=", "<>"):
            df = dt.sql(f"SELECT k FROM t WHERE x {cmp} (SELECT AVG(y) FROM u "
                        "WHERE y > 1000)", **frames)
            assert df.to_pydict() == {"k": []}
        # correlated: a row whose group is empty meets NULL too
        df = dt.sql("SELECT k FROM t WHERE x <> (SELECT MAX(y) FROM u "
                    "WHERE uk = k AND y > 1000)", **frames)
        assert df.to_pydict() == {"k": []}


def test_a_subquery_sees_outer_names_and_inner_names_shadow_them():
    t = dt.from_pydict({"k": [1, 2, 3], "v": [10.0, 20.0, 30.0]})
    # inside, ``v`` and ``k`` are the subquery's own t2 columns; ``ok`` is outer
    got = dt.sql(
        "SELECT ok FROM (SELECT k AS ok, v AS ov FROM t) o WHERE ov >= "
        "(SELECT MAX(v) FROM t WHERE k = ok)", t=t).to_pydict()
    assert sorted(got["ok"]) == [1, 2, 3]
    # a table alias of the subquery ends with it
    u = dt.from_pydict({"uk": [1, 3]})
    text = "SELECT k FROM t WHERE EXISTS (SELECT * FROM u AS z WHERE z.uk = t.k)"
    assert sorted(dt.sql(text, t=t, u=u).to_pydict()["k"]) == [1, 3]
    with pytest.raises(ValueError, match="unknown table alias 'z'"):
        dt.sql(text + " AND z.uk = 1", t=t, u=u)


# ----------------------------------------------- Q18's order, on the device

def test_q18_orders_prices_one_cent_apart_as_float64_has_them():
    """Near 5e5 float32 steps by 0.03: the two prices are one value there,
    and the second key (the date) would then order them the other way."""
    import datetime

    n = 64
    price = [1000.0 + i for i in range(n)]
    price[10], price[20] = 500000.01, 500000.02
    date = [datetime.date(1995, 1, 1) + datetime.timedelta(days=i)
            for i in range(n)]
    date[10], date[20] = datetime.date(1992, 1, 1), datetime.date(1998, 1, 1)
    T = {
        "customer": dt.from_pydict({
            "c_custkey": list(range(1, 9)),
            "c_name": [f"Customer#{k:09d}" for k in range(1, 9)]}),
        "orders": dt.from_pydict({
            "o_orderkey": list(range(1, n + 1)),
            "o_custkey": [1 + i % 8 for i in range(n)],
            "o_orderdate": date, "o_totalprice": price}),
        "lineitem": dt.from_pydict({
            "l_orderkey": [1 + i // 8 for i in range(8 * n)],
            "l_quantity": [50.0] * (8 * n)}),
    }
    with device_path(True):
        q = dt.sql(tpch_queries.SQL[18], **T).collect()
    got = q.to_pydict()
    c = q.stats.snapshot()["counters"]
    assert c["device_join_probes"] >= 1 and c["device_sorts"] >= 1, c
    assert got["o_orderkey"][:2] == [21, 11]
    assert got["o_totalprice"][:2] == [500000.02, 500000.01]
    assert len(got["o_orderkey"]) == n and got["l_quantity"] == [400.0] * n


# --------------------------------------- FROM a, b WHERE a.x = b.y (optimizer)

def test_a_filter_over_a_cross_join_becomes_a_keyed_join_of_the_same_schema():
    a = dt.from_pydict({"id": [1, 2, 3, None], "x": [1.0, 2.0, 3.0, 4.0]})
    b = dt.from_pydict({"id": [1, 1, 3, None], "bk": [7, 8, 9, 10],
                        "w": ["p", "q", "r", "s"]})
    crossed = a.join(b, how="cross")
    df = crossed.where((dt.col("id") == dt.col("right.id"))
                       & (dt.col("x") < 3.0) & (dt.col("w") != "q")
                       & (dt.col("bk") > dt.col("x")))
    plan = optimize(df._plan)
    assert [(j.how, [e.name() for e in j.left_on],
             [e.name() for e in j.right_on]) for j in _nodes(plan, Join)] \
        == [("inner", ["id"], ["id"])]
    assert plan.schema == df.schema == crossed.schema
    got = df.sort("bk").to_pydict()
    assert got == {"id": [1], "x": [1.0], "right.id": [1], "bk": [7],
                   "w": ["p"]}
    # keys of two types stay a filter over the cross join: same answer
    c = dt.from_pydict({"cid": [1.0, 3.0]})
    mixed = a.join(c, how="cross").where(dt.col("id") == dt.col("cid"))
    assert [j.how for j in _nodes(optimize(mixed._plan), Join)] == ["cross"]
    assert sorted(mixed.to_pydict()["id"]) == [1, 3]


def test_plans_without_a_cross_join_or_a_subquery_are_left_as_they_were(data):
    """The fence: what Q3 and Q5 plan to holds no node or attribute of this
    change."""
    T = _catalog(data)
    for qn in (3, 5):
        plan = optimize(tpch_queries.QUERIES[qn](T)._plan)
        for j in _nodes(plan, Join):
            assert j.how == "inner" and "origin" not in vars(j)


def test_the_front_ends_spans_are_events_of_a_live_device_trace(tmp_path,
                                                                frames):
    """A ``jax.profiler`` session live while ``dt.sql`` plans: the three
    spans are ``daft_tpu:phase:sql.*`` annotations of the xplane, before the
    query's ``phase:plan``, and the query's own Profiler holds them too."""
    import glob
    import os

    import jax

    text = ("SELECT k FROM t WHERE EXISTS (SELECT * FROM u WHERE uk = k)")
    dt.sql(text, **frames).collect()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1  # TraceAnnotation's level
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        df = dt.sql(text, **frames)
        assert df.stats.profiler.armed and df.stats.profiler.device_timeline
        df.collect()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert len(found) == 1
    starts = {}
    for plane in jax.profiler.ProfileData.from_file(found[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("daft_tpu:phase:"):
                    starts.setdefault(e.name[len("daft_tpu:phase:"):],
                                      int(e.start_ns))
    assert {"sql.parse", "sql.plan", "sql.decorrelate", "plan"} <= set(starts)
    assert starts["sql.parse"] < starts["sql.plan"] \
        < starts["sql.decorrelate"] < starts["plan"]
    names = [s.name for s in df.stats.profiler.spans_snapshot()]
    assert {"sql.parse", "sql.plan", "sql.decorrelate", "plan"} <= set(names)
