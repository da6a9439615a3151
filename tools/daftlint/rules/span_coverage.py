"""DTL006 span coverage: physical-operator execute() entry points must be
visible to the profiler.

The structured profiler (daft_tpu/profile/) gets per-op attribution two
ways: map-class ops route through ``self._map_execute`` (the driver's
pull/worker wrappers open their spans), and custom ``execute`` bodies open
phase spans around their internal blocking sections
(``ctx.stats.profiler.span(...)``). An op that does neither executes as a
blind spot — its fanout/build/merge work lands in whichever parent span
happened to be open, which is exactly the attribution gap the profiler
exists to close.

This rule mirrors DTL004's registry cross-check pattern: every class named
``*Op`` defining ``execute(self, inputs, ctx)`` (the physical-operator
signature) must, somewhere in that method body, either

- delegate to ``self._map_execute(...)`` (driver-instrumented), or
- open a profiler span (a ``.span(...)`` / ``.begin(...)`` call on a
  profiler object).

Since the morsel-driven streaming executor (daft_tpu/stream/) the rule
also pins the *morsel contract* and the stream driver's coverage:

- a class declaring ``morsel_streamable = True`` must define
  ``map_partition`` in the same class body, or declare a ``DeviceStep``
  among its bases (``PhysicalOp.map_partition`` then runs the step through
  ``ExecutionContext.run``) — claiming streamability without the
  per-morsel entry point means the driver would silently fall back to
  whole-partition materialization inside a streaming stage;
- the stream driver's producer entry point (a function named
  ``_produce_partition``) must itself open a profiler span, so morsel
  work is never an attribution blind spot on the pool workers.

Pre-existing uncovered ops are grandfathered via baseline.json (the
DTL004 discipline: the backlog is visible, new blind spots fail the run).
"""

from __future__ import annotations

import ast
from typing import List

from ..engine import Finding, Project, Rule, dotted_name

# sanctioned span-opening attribute names on a call, e.g.
# ctx.stats.profiler.span(...), prof.begin(...)
_SPAN_ATTRS = {"span", "begin"}


def _execute_is_covered(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if name is None:
            continue
        parts = name.split(".")
        if parts[-1] == "_map_execute":
            return True
        if parts[-1] in _SPAN_ATTRS and len(parts) >= 2:
            # require a profiler-ish receiver so str.span()-style helpers
            # never count as coverage: ...profiler.span(...) or a local
            # bound to one (prof.span / profiler.begin)
            recv = parts[-2]
            if recv in ("profiler", "prof") or "profiler" in parts:
                return True
    return False


def _is_physical_execute(fn: ast.FunctionDef) -> bool:
    args = [a.arg for a in fn.args.args]
    if not (len(args) >= 3 and args[0] == "self" and args[1] == "inputs"):
        return False
    # skip abstract stubs (docstring + raise/pass only) — the base class
    # contract, not an entry point
    body = [n for n in fn.body
            if not (isinstance(n, ast.Expr)
                    and isinstance(n.value, ast.Constant))]
    return not all(isinstance(n, (ast.Raise, ast.Pass)) for n in body)


# stream-driver producer entry points (daft_tpu/stream/pipeline.py): each
# runs morsel work on a pool worker and must open its own profiler span —
# or delegate to another function in this set that does (the retry wrapper
# chain _produce_partition -> _produce_with_retry -> _produce_once)
_STREAM_DRIVER_FNS = {"_produce_partition", "_produce_with_retry",
                      "_produce_once"}

# distributed-worker task entry point (daft_tpu/dist/worker.py): every
# remote task execution must open a task-scope span — it is the root the
# driver splices the worker's telemetry subtree under (obs/cluster.py),
# and without it the whole worker becomes a cluster-wide attribution
# blind spot exactly when queries get hardest to debug
_WORKER_TASK_FNS = {"_execute_task"}

# dynamic-batching apply entry point (daft_tpu/batch/executor.py): every
# coalesced batch runs through here, and its "batch.coalesce"/"actor.apply"
# spans are what parent batched-UDF work to the causing op — without them
# batched inference is a per-batch attribution blind spot
_BATCH_EXEC_FNS = {"_run_flush"}

# the device-step driver's second half (daft_tpu/execution.py): every
# launched step resolves, finishes or falls back through here, under the
# step's own phase span when it names one. DeviceSegmentOp does
# ("fuse.segment"): parented to the driving op, zero orphans, it is what
# attributes whole-segment resident execution (gather + the staged fallback
# as ONE phase) in the merged trace
_SEGMENT_EXEC_FNS = {"_finish_step"}


def _delegates_to_stream_driver(fn: ast.FunctionDef) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            name = dotted_name(node.func)
            if name is not None \
                    and name.split(".")[-1] in _STREAM_DRIVER_FNS:
                return True
    return False


def _claims_morsel_streamable(cls: ast.ClassDef) -> bool:
    # both `morsel_streamable = True` and the annotated
    # `morsel_streamable: bool = True` — the runtime getattr sees either
    for item in cls.body:
        if isinstance(item, ast.Assign):
            targets = item.targets
        elif isinstance(item, ast.AnnAssign) and item.value is not None:
            targets = [item.target]
        else:
            continue
        for tgt in targets:
            if isinstance(tgt, ast.Name) \
                    and tgt.id == "morsel_streamable" \
                    and isinstance(item.value, ast.Constant) \
                    and item.value.value is True:
                return True
    return False


class SpanCoverageRule(Rule):
    code = "DTL006"
    name = "span-coverage"
    description = ("every *Op.execute(self, inputs, ctx) entry point "
                   "delegates to _map_execute or opens a profiler span; "
                   "morsel_streamable ops implement map_partition or "
                   "declare a DeviceStep; the stream driver's producer, "
                   "the distributed worker's task entry point, and the "
                   "device-step finisher open spans")

    def run(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        for rel in project.lint_files:
            tree = project.tree(rel)
            if tree is None:
                continue
            for node in ast.walk(tree):
                if isinstance(node, ast.FunctionDef) \
                        and node.name in _STREAM_DRIVER_FNS:
                    if not (_execute_is_covered(node)
                            or _delegates_to_stream_driver(node)):
                        out.append(self.finding(
                            rel, node.lineno,
                            f"stream-driver `{node.name}` opens no "
                            "profiler span — morsel work on pool workers "
                            "must not be an attribution blind spot"))
                    continue
                if isinstance(node, ast.FunctionDef) \
                        and node.name in _WORKER_TASK_FNS:
                    if not _execute_is_covered(node):
                        out.append(self.finding(
                            rel, node.lineno,
                            f"worker task entry `{node.name}` opens no "
                            "task-scope profiler span — remote work "
                            "would vanish from the merged cluster trace"))
                    continue
                if isinstance(node, ast.FunctionDef) \
                        and node.name in _BATCH_EXEC_FNS:
                    if not _execute_is_covered(node):
                        out.append(self.finding(
                            rel, node.lineno,
                            f"batch-executor entry `{node.name}` opens no "
                            "profiler span — coalesced batch applies must "
                            "carry batch.coalesce/actor.apply attribution"))
                    continue
                if isinstance(node, ast.FunctionDef) \
                        and node.name in _SEGMENT_EXEC_FNS:
                    if not _execute_is_covered(node):
                        out.append(self.finding(
                            rel, node.lineno,
                            f"device-step finisher `{node.name}` opens "
                            "no profiler span — HBM-resident segment "
                            "execution must carry fuse.segment attribution"))
                    continue
                if not isinstance(node, ast.ClassDef) or \
                        not node.name.endswith("Op"):
                    continue
                methods = {item.name for item in node.body
                           if isinstance(item, ast.FunctionDef)}
                bases = {(dotted_name(b) or "").split(".")[-1]
                         for b in node.bases}
                if _claims_morsel_streamable(node) \
                        and "map_partition" not in methods \
                        and "DeviceStep" not in bases:
                    out.append(self.finding(
                        rel, node.lineno,
                        f"`{node.name}` claims `morsel_streamable = True` "
                        "but defines no `map_partition` — the streaming "
                        "driver would silently materialize whole "
                        "partitions inside a streaming stage"))
                for item in node.body:
                    if not isinstance(item, ast.FunctionDef) or \
                            item.name != "execute":
                        continue
                    if not _is_physical_execute(item):
                        continue
                    if _execute_is_covered(item):
                        continue
                    out.append(self.finding(
                        rel, item.lineno,
                        f"`{node.name}.execute` opens no profiler span — "
                        "route through `self._map_execute` or wrap its "
                        "blocking phases in "
                        "`ctx.stats.profiler.span(...)`"))
        return out
