"""The rehearsed runs go last.

They are whole processes that compile on the CPU; at the head of the suite
(where ``tests/chipbench`` sorts) they sat beside the seed's timing-sensitive
tests on the other workers and shifted every file's place in the schedule.
At the tail they meet only the last few files. Every xdist worker applies
the same reorder, so the workers still collect alike.
"""


def pytest_collection_modifyitems(items):
    mine = [i for i in items if i.nodeid.startswith("tests/chipbench/")]
    if mine and len(mine) < len(items):
        theirs = [i for i in items
                  if not i.nodeid.startswith("tests/chipbench/")]
        items[:] = theirs + mine
