"""The comparison that decides ``correct``.

Every answer the timed window produced is held against the plain reference
(``queries/<q>.py``'s ``reference``, pyarrow.compute in float64 over the same
seeded tables). Two numbers are compared, each with a limit the
configuration's file states under ``compare``:

- ``rel_gap``: the widest gap of a float cell from the reference's, as a
  share of the reference's value;
- ``mismatched``: how many other cells (keys, counts, dates, strings), and
  how many missing or surplus columns and rows, differ. Its limit is 0.
"""

from __future__ import annotations

import math

_TINY = 1e-12
_WORST = 1e300  # a gap that is no number at all (and still valid JSON)


def gaps(got: dict, want: dict) -> tuple:
    """``(rel_gap, mismatched)`` of one answer against its reference."""
    rel_gap, mismatched = 0.0, 0
    mismatched += len(set(got) ^ set(want))
    for name, want_col in want.items():
        got_col = got.get(name)
        if got_col is None:
            continue
        mismatched += abs(len(got_col) - len(want_col))
        for a, b in zip(got_col, want_col):
            if isinstance(b, float):
                if not isinstance(a, (int, float)) or math.isnan(a):
                    rel_gap = _WORST
                elif a != b:
                    rel_gap = max(rel_gap,
                                  abs(a - b) / max(abs(b), _TINY))
            elif a != b:
                mismatched += 1
    return rel_gap, mismatched


def judge(answers: list, references: dict, limits: dict) -> tuple:
    """``answers`` is ``[(query name, answer dict), ...]``. Returns
    ``(correct, compared, by_query)``: ``compared`` maps each number to its
    value and its limit, ``by_query`` gives each query's own widest gap and
    mismatches (printed, not judged apart)."""
    by_query: dict = {}
    for name, got in answers:
        rel, mis = gaps(got, references[name])
        prev = by_query.setdefault(name, {"rel_gap": 0.0, "mismatched": 0})
        prev["rel_gap"] = max(prev["rel_gap"], rel)
        prev["mismatched"] += mis
    compared = {
        "rel_gap": {
            "value": max((v["rel_gap"] for v in by_query.values()),
                         default=_WORST),
            "limit": limits["rel_gap"]},
        "mismatched": {
            "value": sum(v["mismatched"] for v in by_query.values()),
            "limit": limits["mismatched"]},
    }
    correct = bool(answers) and all(
        c["value"] <= c["limit"] for c in compared.values())
    return correct, compared, by_query


def lowered(tables: dict) -> dict:
    """The control's tables: every float column rounded to bfloat16, the
    nearest precision below the float32 the configurations state. The
    reference run over these stands in the program's place and has to come
    out as not correct (``control.py``)."""
    import ml_dtypes
    import numpy as np
    import pyarrow as pa

    out = {}
    for name, table in tables.items():
        cols = {}
        for field in table.schema:
            col = table[field.name]
            if pa.types.is_floating(field.type):
                x = col.to_numpy()
                col = pa.array(x.astype(ml_dtypes.bfloat16)
                               .astype(np.float64))
            cols[field.name] = col
        out[name] = pa.table(cols)
    return out
