# daftlint: migrated
"""The morsel-driven pipeline driver (README "Streaming execution").

``try_stream`` inspects a physical op during the executor's tree build and,
when it roots a *streamable segment* — ``[Limit?] -> {Project | Filter |
FusedMap}* -> source`` on the host path — replaces the whole segment with
one pipelined stream:

- **producer stages** (one shared-pool task per source partition, a
  bounded window of them in flight — one per worker by default, the same
  fan-out ``_parallel_map`` gives the partition-granular path) morselize
  the partition
  (``iter_morsels``: chunk-wise decode, zero-copy slices) and run every
  map op of the segment per morsel, pushing results into that partition's
  :class:`BoundedChannel`;
- the **consumer** (the pulling thread — the downstream op) drains
  channels in source-partition order and re-chunks morsels back into
  partitions at the segment boundary, so pipeline breakers above keep
  their partition-granular contract and results are byte-identical with
  ``cfg.streaming_execution`` off;
- a **Limit sink** consumes morsels directly: the first output partition
  leaves as soon as enough morsels exist (time-to-first-row no longer
  waits for a whole partition decode), and hitting the limit closes every
  channel — producers stop scanning/decoding work nobody will read
  (``morsels_short_circuited`` counts what was abandoned).

Eligibility (the *morsel contract*): an op streams iff it declares
``morsel_streamable = True`` AND implements ``map_partition`` (daftlint
DTL006 pins the pair), is row-local (UDFs decline: a batch-dependent UDF
applied per morsel could change results), and requests no resources. The
device-kernel path and mesh/multi-host contexts decline entirely — their
execution units are whole resident partitions by design. A device map over
an in-memory partition larger than a morsel is one launch over a stage view
of the partition (``execution._unsplit``), which keeps no new lanes in the
partition's stage cache.

Error contract: a producer failure (including injected ``scan.read`` /
``fuse.compile``-site faults) parks on the channel and re-raises on the
CONSUMER thread at the next pull — never a hung channel; consumer-side
teardown (limit, cancellation, deadline, GeneratorExit) closes every
channel, waking blocked producers into an immediate stop.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Iterator, List, Optional

from ..micropartition import MicroPartition
from .channel import WAIT, BoundedChannel, ChannelClosed
from .morsel import iter_morsels

__all__ = ["try_stream", "extract_segment", "pinned_partitions",
           "StreamSegment"]

# how long the consumer sleeps on an empty channel before re-checking
# deadline/cancellation and producer liveness (a cancelled future must
# surface as query cancellation, never a hang)
_POLL_S = 0.05


class _StopSignal(threading.Event):
    """Cooperative stop for producer stages. ``short_circuit`` tells an
    unwinding producer whether the stop was deliberate early termination
    (limit hit / upstream close — avoided work counts as
    ``morsels_short_circuited``) or error/cancel/deadline teardown (NOT
    counted: a failed query's record must not read as if a limit fired)."""

    short_circuit = False


def _map_streamable(op, ctx) -> bool:
    """The morsel contract: declared streamable (``morsel_streamable``),
    map-class, row-local (no UDFs — they see whole partitions on the
    partition-granular path and may be batch-dependent), and no resource
    requests (accountant admission is per partition task, not per morsel)."""
    from ..execution import op_resource_request
    from ..expressions import expr_has_udf

    if not getattr(op, "morsel_streamable", False) \
            or op.map_partition is None:
        return False
    if len(op.children) != 1:
        return False
    if any(expr_has_udf(e) for e in op._map_exprs()) \
            and not getattr(op, "batch_declared", False):
        # batch-declared UDFs (physical.BatchedUdfOp) lift the decline:
        # the batching declaration IS a row-locality + concurrency
        # contract, and the producer loop gives each one a per-producer
        # BatchingExecutor (see _produce_once)
        return False
    if op_resource_request(op):
        return False
    return True


class StreamSegment:
    """One streamable chain: ``maps`` bottom-up over ``source``, with an
    optional row ``limit`` sink on top. ``count_source`` marks a bypassed
    Scan/InMemory source whose read time the producer must attribute
    (a generic source is pulled through its own traced stream instead)."""

    __slots__ = ("maps", "limit", "source", "count_source")

    def __init__(self, maps: List, limit: Optional[int], source,
                 count_source: bool):
        self.maps = maps
        self.limit = limit
        self.source = source
        self.count_source = count_source


def extract_segment(op, ctx) -> Optional[StreamSegment]:
    """The maximal streamable segment rooted at ``op``, or None when
    streaming would not change anything (no maps and no limit over a
    direct source — the plain lazy pull is already optimal there)."""
    from ..physical import InMemoryOp, LimitOp, ScanOp

    limit = None
    cur = op
    if isinstance(cur, LimitOp) and type(cur) is LimitOp:
        limit = cur.limit
        cur = cur.children[0]
    maps: List = []
    while _map_streamable(cur, ctx):
        maps.append(cur)
        cur = cur.children[0]
    maps.reverse()  # bottom-up application order
    source = cur
    direct = isinstance(source, (ScanOp, InMemoryOp))
    if not maps and not (limit is not None and direct):
        return None
    return StreamSegment(maps, limit, source, count_source=direct)


def pinned_partitions(ctx) -> bool:
    """True where partitions are pinned: to devices or processes on a mesh
    or multi-host context (morselizing would force foreign reads), to
    worker PROCESSES under the distributed runner (map-class work ships
    there at partition granularity; in-process morsel channels would keep
    it on the driver)."""
    return (getattr(ctx, "try_device_shuffle", None) is not None
            or getattr(ctx, "scan_owner", None) is not None
            or getattr(ctx, "dist_backend", None) is not None)


def try_stream(op, ctx, build, trace: bool = True):
    """Return a pipelined partition stream replacing the segment rooted at
    ``op``, or None when the op/context does not stream. ``build`` is the
    executor's recursive stream builder, used for generic (non-source)
    segment bases."""
    cfg = ctx.cfg
    if not getattr(cfg, "streaming_execution", True):
        return None
    if getattr(cfg, "use_device_kernels", False):
        # the device path's unit is the whole partition: one launch over
        # one buffer. A device map over a partition larger than a morsel
        # runs over a stage view of it instead (execution._unsplit)
        return None
    if pinned_partitions(ctx):
        return None
    seg = extract_segment(op, ctx)
    if seg is None:
        return None
    from ..physical import InMemoryOp, ScanOp

    src = seg.source
    if isinstance(src, ScanOp):
        def parts_fn():
            prof = ctx.stats.profiler
            with prof.span("scan.plan", kind="phase"):
                parts = src.plan_parts(ctx)
            return iter(parts), True
    elif isinstance(src, InMemoryOp):
        def parts_fn():
            return iter(src.parts), True
    else:
        def parts_fn():
            # generic base: partitions pulled through the normally-built
            # (traced) upstream stream on the consumer thread
            return build(src), False
    top = seg.maps[-1] if seg.maps else op
    return _run_segment(seg, parts_fn, ctx, top, trace)


def _run_segment(seg: StreamSegment, parts_fn, ctx, top_op,
                 trace: bool) -> Iterator[MicroPartition]:
    """The consumer generator: windowed producer dispatch, in-order channel
    drain, morsel->partition re-chunk (or the limit sink), teardown."""
    from .. import tracing
    from ..execution import QueryCancelledError, _tl

    cfg = ctx.cfg
    stats = ctx.stats
    prof = stats.profiler
    morsel_rows = max(1, int(getattr(cfg, "morsel_size_rows", 128 * 1024)))
    capacity = max(1, int(getattr(cfg, "stream_channel_capacity", 4)))
    window = int(getattr(cfg, "stream_producer_window", 0))
    if window <= 0:
        # one producer stage per worker: the streaming path replaces
        # _parallel_map's full worker fan-out and must not cap the map
        # parallelism below it (memory stays bounded — the per-channel
        # byte cap below divides the budget share by the window)
        window = max(1, ctx.num_workers)
    budget = ctx.memory_budget
    # byte cap per channel: a slice of the query budget split across the
    # producer window, so total streaming working set stays a bounded
    # fraction of memory_budget_bytes (one morsel always admitted)
    max_bytes = None if budget is None else max(1, budget // (4 * window))
    out_schema = seg.maps[-1].schema if seg.maps else seg.source.schema
    top_name = top_op.name()
    stop = _StopSignal()
    pool = ctx.pool()
    pending: deque = deque()  # (channel, future)
    src_iter, skippable = parts_fn()
    state = {"exhausted": False, "closed": False}

    from ..obs.log import current_query_id

    qid = current_query_id()

    def submit_next() -> bool:
        if state["exhausted"]:
            return False
        part = next(src_iter, None)
        if part is None:
            state["exhausted"] = True
            return False
        chan = BoundedChannel(capacity, max_bytes=max_bytes,
                              ledger=ctx.ledger, stats=stats)
        token = prof.capture() if prof.armed else None
        fut = pool.submit(_produce_partition, seg, part, chan, ctx, stop,
                          morsel_rows, token, qid)
        pending.append((chan, fut))
        return True

    def shutdown(short_circuit: bool) -> None:
        # first close wins (and fixes the short-circuit attribution):
        # execute_plan's teardown may shut an orphaned segment down via
        # close_streams() before GC closes the suspended generator, whose
        # GeneratorExit path would then re-enter with short_circuit=True
        if state["closed"]:
            return
        state["closed"] = True
        if short_circuit:
            stop.short_circuit = True
        stop.set()
        while pending:
            chan, fut = pending.popleft()
            if fut.cancel() and short_circuit:
                # the producer never ran: its whole partition was skipped
                stats.bump("morsels_short_circuited")
            chan.close()
        if short_circuit and skippable and not state["exhausted"]:
            # count the source partitions the early stop never read
            # (metadata-only iteration over the remaining scan/in-memory
            # parts list — never materializes)
            n = sum(1 for _ in src_iter)
            if n:
                stats.bump("morsels_short_circuited", n)
            state["exhausted"] = True
        elif not skippable:
            close = getattr(src_iter, "close", None)
            if close is not None:
                close()

    def drain_head(remaining):
        """Drain the head channel into a morsel list; returns (morsels,
        rows, new_remaining, hit_limit). Blocked-on-channel time is
        attributed like dispatch waits (queue_wait phase), so the
        io_wait-vs-compute split still tells a starved pipeline from a
        compute-bound one. Every ``get`` is timed — including slices that
        END with a morsel: a producer-bound pipeline blocks tens of ms
        per get without ever hitting the WAIT timeout and must still
        show as starved (a ready channel costs ~µs, which is noise)."""
        chan, fut = pending[0]
        morsels: List[MicroPartition] = []
        rows = 0
        hit = False
        waited_ns = 0
        while True:
            t0g = time.perf_counter_ns()
            got = chan.get(timeout=_POLL_S)
            waited_ns += time.perf_counter_ns() - t0g
            if got is WAIT:
                if stats.is_cancelled():
                    raise QueryCancelledError(
                        f"query cancelled (at {top_name})")
                ctx.check_deadline()
                if fut.cancelled():
                    raise QueryCancelledError(
                        "query cancelled (stream producer cancelled)")
                if fut.done():
                    # a producer that died without fail()-ing (engine bug)
                    # must surface, never hang the channel
                    exc = fut.exception()
                    if exc is not None:
                        raise exc
                continue
            if got is None:
                break
            m = got
            n = len(m)
            if remaining is not None and rows + n >= remaining:
                if rows + n > remaining:
                    m = m.head(remaining - rows)
                    n = len(m)
                hit = True
            morsels.append(m)
            rows += n
            if hit:
                break
        pending.popleft()
        if hit:
            # the head producer may still be running (or blocked in put()):
            # close ITS channel too — shutdown() only sees channels still
            # in `pending`, and a producer parked on an unclosed channel
            # would hold a pool worker until process exit. Flag the stop
            # as limit-driven FIRST so the unwinding producer counts its
            # abandoned work as short-circuited.
            stop.short_circuit = True
            chan.close()
        stats.bump_max("stream_channel_high_water", chan.high_water)
        if waited_ns:
            stats.dispatch_wait(waited_ns)
        if remaining is not None:
            remaining -= rows
        return morsels, rows, remaining, hit

    remaining = seg.limit
    seq = 0
    short_circuit = False
    # teardown reachability: while this generator is suspended at a yield,
    # only the registry can shut it down if the chain above dies (plain
    # `for` loops never close their inputs, and an exception traceback
    # keeps the suspended frame alive past the pool's lifetime)
    token = ctx.register_stream(shutdown)
    try:
        if remaining is not None and remaining <= 0:
            return
        while True:
            if stats.is_cancelled():
                raise QueryCancelledError(f"query cancelled (at {top_name})")
            ctx.check_deadline()
            # consumer-side op span: covers the windowed submits and the
            # head-channel drain, so producer "morsel" spans captured at
            # submit time parent to THIS op (cross-thread propagation).
            # trace=False mirrors execute_plan skipping the _traced
            # wrapper: no span, no self-time stack, no progress report
            # (producer-side record_op stays, matching _parallel_map's
            # in-worker instrumentation on the partition-granular path)
            sp = (prof.begin(top_name, op=top_name, part=seq)
                  if trace and prof.armed else None)
            t0 = time.perf_counter_ns()
            stack = None
            if trace:
                # mirror _traced's self-time stack so the parent op's
                # explain_analyze self time excludes this pull
                stack = getattr(_tl, "stack", None)
                if stack is None:
                    stack = _tl.stack = []
                stack.append(0)
            pulled = False
            try:
                while len(pending) < window and submit_next():
                    pass
                if not pending:
                    return
                morsels, rows, remaining, hit = drain_head(remaining)
                pulled = True
            finally:
                if stack is not None:
                    dt = time.perf_counter_ns() - t0
                    stack.pop()
                    if stack:
                        stack[-1] += dt
                if sp is not None:
                    if pulled:
                        sp.set_attr("rows", rows)
                        prof.end(sp)
                    else:
                        prof.cancel(sp)
            out = _rechunk(morsels, out_schema)
            seq += 1
            if trace:
                tracing.report_progress(top_name, rows)
            yield out
            if hit:
                # limit satisfied: stop every producer before they decode
                # partitions nobody will read
                short_circuit = True
                shutdown(short_circuit=True)
                return
    except GeneratorExit:
        # deliberate early close from above (LimitOp's partition-granular
        # early-termination, or an abandoned iterator): the avoided scan/
        # decode work IS a short-circuit. Errors/cancel/deadline fall to
        # the bare finally and are never counted — a failed query's
        # record must not read as if a limit fired.
        short_circuit = True
        raise
    finally:
        shutdown(short_circuit=short_circuit)
        ctx.unregister_stream(token)


def _part_bytes(part: MicroPartition) -> int:
    b = part.size_bytes()
    return b if b is not None else 0


def _rechunk(morsels: List[MicroPartition], out_schema) -> MicroPartition:
    """Morsel -> partition re-chunk boundary: ONE concrete Table, exactly
    what the partition-granular map would have produced. A multi-table
    partition here would silently change downstream kernel routing (e.g.
    the chunked-acero grouped agg reassociates float sums differently than
    the collapsed path) and break the byte-identity invariant."""
    from ..table import Table

    tables = [t for m in morsels for t in m._tables if len(t)]
    if not tables:
        return MicroPartition.empty(out_schema)
    if len(tables) == 1:
        return MicroPartition.from_table(tables[0])
    return MicroPartition.from_table(Table.concat(tables))


def _produce_partition(seg: StreamSegment, part: MicroPartition, chan,
                       ctx, stop: threading.Event, morsel_rows: int,
                       token, qid) -> None:
    """Producer stage body (one source partition, runs on the shared
    executor pool): morselize, run the segment's maps per morsel, push
    into the bounded channel. Each morsel's work is a ``morsel`` span
    parented — via the captured ``token`` — to the consumer-side op span,
    and per-op rows/wall feed RuntimeStats so explain_analyze keeps real
    per-op attribution. Any failure parks on the channel for the consumer;
    a close (limit early-stop) unwinds quietly as a short-circuit."""
    from .. import scheduler
    from ..obs.log import query_context

    stats = ctx.stats
    prof = stats.profiler
    scheduler._WORKER_TL.active = True
    act = prof.activate(token) if prof.armed else None
    if act is not None:
        act.__enter__()
    try:
        with query_context(qid):
            try:
                _produce_with_retry(seg, part, chan, ctx, stop, morsel_rows)
                chan.finish()
            except ChannelClosed:
                if stop.short_circuit:
                    stats.bump("morsels_short_circuited")
            except BaseException as e:
                chan.fail(e)
    finally:
        if act is not None:
            act.__exit__(None, None, None)
        scheduler._WORKER_TL.active = False


def _produce_with_retry(seg: StreamSegment, part: MicroPartition, chan,
                        ctx, stop: threading.Event,
                        morsel_rows: int) -> None:
    """The producer's morselize+map loop, with the scheduler's per-task
    transient-retry contract (cfg ``task_retry_attempts``): a
    DaftTransientError — e.g. an injected ``scan.read`` fault that
    exhausted the IO layer's own retries, which leaves the partition
    unloaded and re-readable — re-runs the partition up to the same retry
    budget, but ONLY while nothing has been pushed yet (a mid-stream
    retry would duplicate rows the consumer already drained; that rare
    case fails the query exactly like a non-retryable error)."""
    from ..errors import DaftTransientError
    from ..execution import QueryCancelledError
    from ..obs.log import get_logger

    stats = ctx.stats
    retries_left = max(0, getattr(ctx.cfg, "task_retry_attempts", 0))
    while True:
        try:
            _produce_once(seg, part, chan, ctx, stop, morsel_rows)
            return
        except DaftTransientError:
            if chan.pushed or retries_left <= 0:
                raise
            if stats.is_cancelled():
                raise QueryCancelledError(
                    f"query cancelled (retrying {seg.source.name()})")
            ctx.check_deadline()
            retries_left -= 1
            stats.bump("task_retries")
            get_logger("stream").warning(
                "stream_task_retry", op=seg.source.name(),
                attempts_left=retries_left)
            time.sleep(max(0.0, getattr(ctx.cfg, "task_retry_backoff_s",
                                        0.05)))


def _batch_executors(seg: StreamSegment, ctx) -> dict:
    """One BatchingExecutor per batch-declared map stage, owned by THIS
    producer call (one partition): morsels coalesce across morsel
    boundaries within the partition, outputs re-split to the exact morsel
    boundaries the unbatched path would have produced."""
    execs: dict = {}
    if not getattr(ctx.cfg, "dynamic_batching", True):
        return execs
    for i, mop in enumerate(seg.maps):
        if getattr(mop, "batch_declared", False):
            from ..batch.executor import BatchingExecutor

            execs[i] = BatchingExecutor(mop.name(), mop.exprs, ctx,
                                        settings=mop._settings(ctx))
    return execs


def _produce_once(seg: StreamSegment, part: MicroPartition, chan, ctx,
                  stop: threading.Event, morsel_rows: int) -> None:
    stats = ctx.stats
    prof = stats.profiler
    src_name = seg.source.name()
    execs = _batch_executors(seg, ctx)

    def apply_maps(ms, i0):
        """Run output morsels through maps[i0:]. A batch stage may hold
        morsels back (still coalescing) or release several at once; every
        released morsel keeps its source-boundary identity."""
        for i in range(i0, len(seg.maps)):
            mop = seg.maps[i]
            bx = execs.get(i)
            nxt = []
            for m in ms:
                t0 = time.perf_counter_ns()
                outs = bx.feed(m) if bx is not None \
                    else [mop.map_partition(m, ctx)]
                stats.record_op(mop.name(), sum(len(o) for o in outs),
                                time.perf_counter_ns() - t0,
                                sum(_part_bytes(o) for o in outs))
                nxt.extend(outs)
            ms = nxt
        return ms

    try:
        t_read = time.perf_counter_ns()
        for m in iter_morsels(part, morsel_rows):
            read_ns = time.perf_counter_ns() - t_read
            if stop.is_set():
                if getattr(stop, "short_circuit", False):
                    stats.bump("morsels_short_circuited")
                return
            sp = (prof.begin("morsel", kind="bg")
                  if prof.armed else None)
            outs = []
            try:
                if seg.count_source:
                    # chunk decode happened inside iter_morsels'
                    # pull: attribute it to the (bypassed) source
                    stats.record_op(src_name, len(m), read_ns,
                                    _part_bytes(m))
                outs = apply_maps([m], 0)
            finally:
                if sp is not None:
                    sp.set_attr("rows", sum(len(o) for o in outs))
                    prof.end(sp)
            stats.bump("stream_morsels")
            for o in outs:
                chan.put(o, _part_bytes(o))
            t_read = time.perf_counter_ns()
        # partition end: drain each batch stage bottom-up — a lower
        # stage's tail still flows through every stage above it
        for i in sorted(execs):
            if stop.is_set():
                return
            t0 = time.perf_counter_ns()
            tail = execs[i].finish()
            stats.record_op(seg.maps[i].name(),
                            sum(len(o) for o in tail),
                            time.perf_counter_ns() - t0,
                            sum(_part_bytes(o) for o in tail))
            for o in apply_maps(tail, i + 1):
                chan.put(o, _part_bytes(o))
    finally:
        # stop/error teardown with morsels still buffered: settle their
        # ledger charge (a leaked batch_inflight account fails the leak
        # tests) without running the apply
        for bx in execs.values():
            bx.abort()
