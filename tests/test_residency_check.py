"""tools/residency_check.py: what a window leaves in the resident frames'
stage caches, and each query kind's routing counters. Hand-made inputs,
no run."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from tools import residency_check  # noqa: E402


def test_keys_that_stayed_read_the_same():
    keys = {"lineitem": [["('a', 8, False)"], []], "orders": [[]]}
    assert residency_check.compare_keys(keys, keys) == {
        "lineitem": {"same": True}, "orders": {"same": True}}


def test_keys_gained_and_lost_are_named():
    before = {"lineitem": [["a", "b"], ["a"]]}
    after = {"lineitem": [["a", "c"], ["a"]]}
    assert residency_check.compare_keys(before, after) == {
        "lineitem": {"same": False, "gained": ["c"], "lost": ["b"]}}


def test_counters_are_the_median_of_each_query_kind():
    records = [{"name": "sql4", "counters": {"stream_morsels": 0,
                                             "device_maps_unsplit": 2}},
               {"name": "sql4", "counters": {"device_maps_unsplit": 4}},
               {"name": "sql4", "counters": {"device_maps_unsplit": 2}},
               {"name": "sql18", "counters": {}}]
    got = residency_check.per_query(records)
    assert got["sql4"]["device_maps_unsplit"] == 2
    assert got["sql4"]["stream_morsels"] == 0
    assert set(got["sql18"]) == set(residency_check.COUNTERS)
    assert all(v == 0 for v in got["sql18"].values())
