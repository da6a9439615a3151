"""Parse-only SQL smoke test over all 22 official TPC-H query texts
(benchmarks/tpch_queries.SQL, SQLite dialect) — frontend breadth is
MEASURED, not guessed (ISSUE 3 satellite / VERDICT item 3).

dt.sql() plans (schema inference included) without executing, so this pins
exactly which query shapes the SQL frontend accepts today. Unsupported
queries are STRICT xfails with the missing feature named: when the frontend
grows (WITH, strftime, outer-join non-equi conditions, subqueries in HAVING,
NOT IN, correlation by a non-equality), the xpass flips loudly and the marker
must be removed. PR 38 took 2, 4, 17, 18, 20 and 22 off the list (EXISTS, IN
and scalar subqueries as conjuncts of WHERE: tests/test_sql_subquery.py).
"""

import pytest

import daft_tpu as dt
from benchmarks import tpch_full, tpch_queries

# why each unsupported query fails to plan today
UNSUPPORTED = {
    7: "strftime() over date columns",
    8: "strftime() over date columns",
    9: "strftime() over date columns",
    11: "scalar subquery in HAVING",
    13: "non-equi condition in OUTER JOIN ON clause",
    15: "WITH (common table expression)",
    16: "NOT IN (SELECT ...): not an anti join when a NULL is about",
    21: "EXISTS correlated by a non-equality (l2.l_suppkey <> l1.l_suppkey)",
}


@pytest.fixture(scope="module")
def catalog():
    data = tpch_full.generate(scale=0.001, seed=7)
    return {name: dt.from_arrow(tbl) for name, tbl in data.items()}


@pytest.mark.parametrize("qn", sorted(tpch_queries.SQL))
def test_tpch_sql_parses(qn, catalog, request):
    if qn in UNSUPPORTED:
        request.applymarker(pytest.mark.xfail(
            strict=True, reason=f"q{qn}: {UNSUPPORTED[qn]}"))
    df = dt.sql(tpch_queries.SQL[qn], **catalog)
    assert df.schema is not None
    assert len(df.column_names) > 0


def test_supported_breadth_floor():
    """At least 14 of the 22 official texts must keep planning — a frontend
    regression below this floor fails loudly even if individual xfail
    markers drift."""
    data = tpch_full.generate(scale=0.001, seed=7)
    catalog = {name: dt.from_arrow(tbl) for name, tbl in data.items()}
    ok = []
    for qn in sorted(tpch_queries.SQL):
        try:
            dt.sql(tpch_queries.SQL[qn], **catalog)
            ok.append(qn)
        except Exception:  # noqa: BLE001
            pass
    assert len(ok) >= 14, f"SQL frontend breadth regressed: only {ok} parse"


def test_repeated_sql_calls_stay_callable():
    """Regression: the first real import of the daft_tpu.sql SUBMODULE used
    to rebind the package's `sql` attribute from the entry-point function to
    the module, so the second dt.sql() call raised TypeError. Fixed by an
    eager importlib import in __init__ (the `from . import sql` spelling was
    a no-op — the attribute already existed)."""
    df = dt.from_pydict({"a": [1, 2, 3]})
    for _ in range(3):
        out = dt.sql("SELECT a FROM t WHERE a > 1", t=df)
        assert callable(dt.sql)
    assert out.collect().to_pydict() == {"a": [2, 3]}
