"""Tracing / explain-analyze / progress tests (reference: common/tracing
chrome layer, runtime_stats.rs, progress_bar.py)."""

import json

import daft_tpu as dt
from daft_tpu import col, tracing


def _query():
    df = dt.from_pydict({"k": ["a", "b", "a", "c"] * 25, "v": list(range(100))})
    return df.where(col("v") > 10).groupby("k").agg(col("v").sum().alias("s")).sort("k")


class TestChromeTrace:
    def test_trace_file_written(self, tmp_path):
        path = str(tmp_path / "trace.json")
        with tracing.chrome_trace(path):
            _query().collect()
        data = json.load(open(path))
        evs = data["traceEvents"]
        assert evs, "no events captured"
        names = {e["name"] for e in evs}
        assert any("Aggregate" in n for n in names), names
        # the query is armed before it is planned, so the planner's one
        # typed event is in the trace: an instant, and the only one
        instants = [e for e in evs if e["ph"] != "X"]
        assert [(e["name"], e["ph"]) for e in instants] == [("plancache", "i")]
        for e in evs:
            if e not in instants:
                assert e["ph"] == "X" and "ts" in e and "dur" in e
        assert any(e["ph"] == "X" and e["name"] == "plan" for e in evs)

    def test_disabled_by_default(self, tmp_path):
        assert not tracing.active()
        _query().collect()  # must not raise or buffer


class TestExplainAnalyze:
    def test_reports_ops_and_rows(self, capsys):
        q = _query()
        text = q.explain_analyze()
        assert "Runtime Stats" in text
        assert "Aggregate" in text
        assert "rows out" in text

    def test_counters_section(self):
        df = dt.from_pydict({"v": list(range(50))})
        q = df.select((col("v") + 1).alias("w")).collect()
        text = q.explain_analyze()
        assert "counters:" in text and "projections" in text


class TestProgress:
    def test_progress_callback(self):
        seen = []
        tracing.set_progress_callback(lambda name, rows: seen.append((name, rows)))
        try:
            _query().collect()
        finally:
            tracing.set_progress_callback(None)
        assert seen and any(rows > 0 for _, rows in seen)


class TestVizHooks:
    """HTML previews + register_viz_hook (reference:
    daft/viz/html_viz_hooks.py:17-27, dataframe/display.py)."""

    def test_repr_html_basic(self):
        import daft_tpu as dt

        df = dt.from_pydict({"a": [1, 2, 3], "s": ["x", "<b>y</b>", None]})
        h = df.collect()._repr_html_()
        assert "<table" in h and "a" in h
        assert "int64" in h.lower()
        assert "&lt;b&gt;y&lt;/b&gt;" in h  # escaped, not injected
        assert "<i>None</i>" in h
        assert "3 rows" in h

    def test_register_viz_hook_custom_type(self):
        import daft_tpu as dt
        from daft_tpu import DataType

        class Blob:
            def __init__(self, tag):
                self.tag = tag

        dt.register_viz_hook(Blob, lambda b: f'<span class="blob">{b.tag}</span>')
        df = dt.from_pydict({"o": dt.Series.from_pylist(
            [Blob("t1"), Blob("t2")], "o", DataType.python())})
        h = df.collect()._repr_html_()
        assert '<span class="blob">t1</span>' in h
        assert '<span class="blob">t2</span>' in h

    def test_pil_image_hook_renders_img(self):
        import pytest

        PIL = pytest.importorskip("PIL")
        import numpy as np
        from PIL import Image

        import daft_tpu as dt
        from daft_tpu import DataType

        img = Image.fromarray(np.zeros((4, 4, 3), dtype=np.uint8))
        df = dt.from_pydict({"im": dt.Series.from_pylist(
            [img], "im", DataType.python())})
        h = df.collect()._repr_html_()
        assert "data:image/png;base64," in h

    def test_repr_html_uncollected_shows_schema_only(self):
        import daft_tpu as dt

        df = dt.from_pydict({"a": [1, 2]}).where(dt.col("a") > 0)
        h = df._repr_html_()  # NOT collected: must not execute the plan
        assert h.startswith("<pre>DataFrame(") and "a" in h
