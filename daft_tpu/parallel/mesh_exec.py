"""Mesh execution context: partition shuffles ride ICI collectives.

Role-equivalent to the reference's RayRunner data plane
(daft/runners/ray_runner.py:504-685 — dispatch loop + object-store transfer).
Redesign for TPU: the fanout+reduce pair of a shuffle becomes ONE all_to_all
collective (collectives.build_exchange) over a `jax.sharding.Mesh`. Host keeps
the control plane: bucket assignment (host hash kernels work for every dtype
incl. strings; range boundaries sampled host-side like the reference's
ReduceToQuantiles, execution_step.py:878), capacity negotiation, and
re-chunking partitions onto the mesh axis.

Generality (round-3):
- hash, random AND range schemes ship their payload over ICI (range buckets
  come from the same aligned-boundary ranking the host path uses, so a
  device range-shuffle + per-device sort is a global sort);
- any fanout `num` works: num < n_devices leaves trailing devices idle,
  num > n_devices packs bucket b onto device b % n and ships the bucket id
  as an extra lane so receivers split their slab;
- staging is per-device: each source shard is device_put straight onto its
  mesh device and assembled with make_array_from_single_device_arrays — the
  host never materializes the old dense [n_devices, R] global matrix.

STRING columns ride the exchange as int32 codes against a GLOBAL sorted
dictionary (r5): every process contributes its local distinct values, the
dictionaries allgather as one packed byte buffer over the jax multihost
runtime (the DCN control channel), and every process merges them into the
same sorted global dictionary — codes are then exchange-able ints and
receivers decode (or keep the codes resident for downstream device string
ops, which expect exactly this sorted-dictionary shape). High-cardinality
columns (dictionary above _STRING_DICT_CAP values / _STRING_DICT_BYTES_CAP
bytes globally) decline to the host shuffle — past that point shipping raw
bytes beats syncing dictionaries. Columns that are neither device dtypes
nor strings (lists, python objects) still force the host path — the same
Native-vs-Python storage split the reference keeps (SURVEY.md §7 step 1).
"""
# daftlint: migrated

from __future__ import annotations

from typing import List, Optional

import numpy as np

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..execution import DeviceHealth, ExecutionContext, RuntimeStats
from ..kernels.device import DeviceColumn, is_device_dtype, size_bucket, stage_np, unstage
from ..micropartition import MicroPartition
from .collectives import build_exchange, exchange_capacity


import functools

# Global-dictionary caps for string exchange columns: above these the
# dictionary sync would rival shipping the raw bytes, so the host shuffle
# takes over (both sides of every process agree — the caps evaluate on
# allgathered totals).
_STRING_DICT_CAP = 1 << 18
_STRING_DICT_BYTES_CAP = 16 << 20


def _gather_global_dictionaries(local_dicts, multiproc: bool):
    """One sorted GLOBAL dictionary (pa.Array, large_string) per string
    column, or None when a cap trips. Single-process: sort the local
    distincts. Multi-process: pack every column's distinct values into one
    byte buffer + length/count arrays, allgather (2 size-agreement rounds +
    3 data rounds over the jax multihost runtime), and merge identically on
    every process — UTF-8 byte order equals code-point order, so python
    sorted() and pyarrow's binary sort agree."""
    import pyarrow as pa

    if not multiproc:
        import pyarrow.compute as pc

        out = []
        total_vals = 0
        total_bytes = 0
        for d in local_dicts:
            srt = d.take(pc.sort_indices(d)) if len(d) else d
            total_vals += len(srt)
            # value bytes only — the same UNIT the multi-process branch
            # sums (encoded payload). Note the multiproc branch sums
            # pre-merge per-process distincts, so a value present on all P
            # processes counts P times there: near the caps a cluster can
            # decline where one host proceeds (conservative, never unsound)
            total_bytes += int(pc.binary_length(srt.cast(pa.large_binary()))
                               .cast(pa.int64()).sum().as_py() or 0) \
                if len(srt) else 0
            out.append(srt)
        if total_vals > _STRING_DICT_CAP or total_bytes > _STRING_DICT_BYTES_CAP:
            return None
        return out

    from jax.experimental import multihost_utils

    enc: List[bytes] = []
    counts = []
    for d in local_dicts:
        vals = d.to_pylist()
        counts.append(len(vals))
        enc.extend(v.encode("utf-8") for v in vals)
    lens = np.array([len(b) for b in enc], dtype=np.int64)
    buf = (np.frombuffer(b"".join(enc), dtype=np.uint8)
           if enc else np.zeros(0, np.uint8))
    header = np.array([len(buf), len(lens)], dtype=np.int64)
    sizes = np.asarray(multihost_utils.process_allgather(header))  # [P, 2]
    if (int(sizes[:, 1].sum()) > _STRING_DICT_CAP
            or int(sizes[:, 0].sum()) > _STRING_DICT_BYTES_CAP):
        return None  # agreed on every process: sizes are global
    maxb = max(int(sizes[:, 0].max()), 1)
    maxn = max(int(sizes[:, 1].max()), 1)
    pb = np.zeros(maxb, np.uint8)
    pb[:len(buf)] = buf
    pl = np.full(maxn, -1, np.int64)
    pl[:len(lens)] = lens
    gb = np.asarray(multihost_utils.process_allgather(pb))
    gl = np.asarray(multihost_utils.process_allgather(pl))
    gc = np.asarray(multihost_utils.process_allgather(
        np.array(counts, dtype=np.int64)))
    ncols = len(local_dicts)
    per_col = [set() for _ in range(ncols)]
    for p in range(gb.shape[0]):
        pos = 0
        item = 0
        pbuf = gb[p].tobytes()
        for cidx in range(ncols):
            for _ in range(int(gc[p, cidx])):
                ln = int(gl[p, item])
                item += 1
                per_col[cidx].add(pbuf[pos:pos + ln].decode("utf-8"))
                pos += ln
    return [pa.array(sorted(s), type=pa.large_string()) for s in per_col]


def exchangeable_dtype(dt) -> bool:
    """Dtypes the device exchange can ship: native device dtypes, plus
    strings (as codes against a global sorted dictionary) — the same rule
    as per-partition staging, defined once."""
    from ..kernels.device import stageable_dtype

    return stageable_dtype(dt)


def _stage_global_codes(series, global_dict, r: int):
    """(vals int32 [r], valid bool [r]) for a string column as codes into
    the GLOBAL sorted dictionary (every value is present by construction —
    the dictionary is the union of all contributions)."""
    import pyarrow as pa
    import pyarrow.compute as pc

    arr = series.to_arrow()
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    codes = pc.index_in(arr.cast(pa.large_string()), value_set=global_dict)
    vals = np.zeros(r, dtype=np.int32)
    valid = np.zeros(r, dtype=bool)
    n = len(arr)
    vals[:n] = np.asarray(pc.fill_null(codes, 0), dtype=np.int32)
    valid[:n] = np.asarray(pc.is_valid(codes), dtype=bool)
    return vals, valid


@functools.partial(jax.jit, static_argnums=(3,))
def _pack_slab(vals, nulls, sel, out_rows: int):
    """Pack a received slab's selected rows to the front (static shapes):
    returns (values [out_rows, *trailing], null_validity [out_rows]) in the
    DeviceColumn packed-prefix layout. Runs on whatever device holds `vals`."""
    import jax.numpy as jnp

    order = jnp.argsort(~sel, stable=True)
    pv = jnp.take(vals, order, axis=0)
    pn = nulls[order] & sel[order]
    r = pv.shape[0]
    if out_rows <= r:
        return pv[:out_rows], pn[:out_rows]
    pad = [(0, out_rows - r)] + [(0, 0)] * (pv.ndim - 1)
    return jnp.pad(pv, pad), jnp.pad(pn, (0, out_rows - r))


def default_mesh(n: Optional[int] = None):
    """A 1-D mesh over the first n (default: all) local devices, axis 'parts'."""
    devs = jax.devices()
    if n is not None:
        devs = devs[:n]
    return jax.sharding.Mesh(np.array(devs), ("parts",))


class MeshExecutionContext(ExecutionContext):
    """ExecutionContext whose shuffles use the device exchange when eligible."""

    def __init__(self, cfg, stats: Optional[RuntimeStats] = None, mesh=None,
                 deadline: Optional[float] = None, device_health=None,
                 collective_health=None, qctx=None):
        super().__init__(cfg, stats, deadline=deadline,
                         device_health=device_health, qctx=qctx)
        from ..kernels.compile_cache import configure_compile_cache

        configure_compile_cache()  # the exchange compiles outside _device_attempt
        self.mesh = mesh if mesh is not None else default_mesh()
        # mesh collectives get the same circuit-breaker treatment as device
        # kernels: K consecutive exchange failures trip it and every later
        # shuffle goes straight to the host path until the cooldown probe
        # proves the link healthy again. The QueryContext carries one
        # instance per QUERY so AQE stages share trip/cooldown state (same
        # contract as device_health).
        self.collective_health = (collective_health
                                  or self.qctx.collective_health
                                  or DeviceHealth(
                                      cfg.device_breaker_threshold,
                                      cfg.device_breaker_cooldown_s,
                                      kind="collective"))

    @property
    def n_devices(self) -> int:
        return int(np.prod(list(self.mesh.shape.values())))

    @property
    def _multiproc(self) -> bool:
        me = jax.process_index()
        return any(d.process_index != me for d in self.mesh.devices.flat)

    def scan_owner(self, idx: int) -> Optional[int]:
        """Owner process for scan task `idx` in multi-process mode — each
        host materializes (and reads) only its share (reference: per-node
        scan dispatch, ray_runner.py:504-685). None single-process."""
        if not self._multiproc:
            return None
        return idx % jax.process_count()

    def foreign_owned(self, part: MicroPartition) -> bool:
        return (part.owner_process is not None
                and self._multiproc
                and part.owner_process != jax.process_index())

    def prepare_broadcast(self, part: MicroPartition, on_exprs,
                          how: str = "inner") -> MicroPartition:
        """Replicate a broadcast-join build side's join keys into every mesh
        device's HBM with ONE fully-replicated device_put (an ICI broadcast),
        so each device probes its local replica instead of pulling the build
        keys over the link per partition (reference role: broadcast_join's
        small-side replication, daft/execution/physical_plan.py:374)."""
        if (self.cfg.use_device_kernels and self.n_devices > 1
                and how in ("inner", "left", "semi", "anti")  # _join_eligible's gate
                and on_exprs and len(on_exprs) == 1
                and (part.num_rows_or_none() or 0) > 0):
            try:
                from ..kernels.device_join import replicate_join_key

                if replicate_join_key(part, on_exprs[0], self.mesh):
                    self.stats.bump("broadcast_replications")
            except Exception as e:
                # replication is a fast path: the probe re-ships the build
                # keys per partition without it
                self.stats.note_device_error("mesh.broadcast", e)
        return part

    def _shard_onto_devices(self, shards: List[jax.Array], trailing, r: int):
        """Assemble n single-device [1, r, *trailing] buffers into one global
        [n, r, *trailing] array laid out one-row-per-device — per-device
        staging with no host-side global matrix."""
        n = self.n_devices
        axis = self.mesh.axis_names[0]
        shape = (n, r) + tuple(trailing)
        sharding = NamedSharding(self.mesh, P(axis, *([None] * (len(shape) - 1))))
        return jax.make_array_from_single_device_arrays(shape, sharding, shards)

    def try_device_shuffle(self, parts: List[MicroPartition], by, num: int,
                           scheme: str, descending=None, nulls_first=None,
                           boundaries=None,
                           combine=None) -> Optional[List[MicroPartition]]:
        """All-to-all shuffle over the mesh; None if ineligible (unsupported
        scheme, non-device payload dtype, empty input, missing boundaries),
        if the collective breaker is open, or if the exchange itself fails
        (the failure is recorded against the breaker and the caller's host
        shuffle path takes over).

        Multi-process caveat: a REAL mid-collective failure on one process
        can leave peers blocked in the exchange — same exposure as before
        this catch existed (the process previously crashed outright);
        injected faults fire identically on every process (the registry is
        armed SPMD) so test fallbacks stay collectively consistent."""
        from .. import faults

        if not self.collective_health.allow(self.stats):
            self.stats.bump("degraded_shuffles")
            return self._try_transport_shuffle(parts, by, num, scheme,
                                               descending, nulls_first,
                                               boundaries)
        try:
            faults.check("collective.exchange", self.stats)
            # the whole mesh exchange (staging + all_to_all + gather-back)
            # is one phase on the profile timeline
            with self.stats.profiler.span("collective.exchange",
                                          kind="phase"):
                out = self._device_shuffle_impl(parts, by, num, scheme,
                                                descending, nulls_first,
                                                boundaries, combine)
        except Exception as e:
            self.stats.note_device_error("collective.exchange", e)
            self.collective_health.record_failure(self.stats)
            # multi-process clusters whose collective backend cannot move
            # bytes between processes (the jaxlib CPU gap) still have the
            # dist/ peer transport as a data plane; single-process meshes
            # fall to the plain host shuffle as before
            return self._try_transport_shuffle(parts, by, num, scheme,
                                               descending, nulls_first,
                                               boundaries)
        if out is None:
            self.collective_health.release_probe()
        else:
            self.collective_health.record_success(self.stats)
        return out

    def _try_transport_shuffle(self, parts, by, num, scheme, descending,
                               nulls_first, boundaries):
        """Never raises: None (host path takes over) when the transport
        cannot serve or itself fails."""
        if not self._multiproc:
            return None
        try:
            return self._transport_shuffle(parts, by, num, scheme,
                                           descending, nulls_first,
                                           boundaries)
        except Exception as e:
            from ..obs.log import get_logger

            get_logger("mesh").warning("transport_shuffle_failed",
                                       error=repr(e))
            return None

    def _transport_shuffle(self, parts, by, num, scheme, descending,
                           nulls_first, boundaries):
        """Cross-process exchange over the dist/ peer allgather plane: each
        process materializes only the partitions it OWNS (per-host scan
        locality holds), allgathers the pickled contributions, and every
        process reconstitutes the full input and buckets it identically —
        the same SPMD reconvergence contract as the collective exchange's
        post-all_to_all allgather. Returns None when no peer plane exists
        or the scheme cannot be served."""
        import pickle

        from ..dist.peer import get_peer_group

        if scheme not in ("hash", "random", "range"):
            return None
        if scheme == "range" and boundaries is None:
            return None
        peer = get_peer_group()
        if peer is None:
            return None
        nproc = jax.process_count()
        my_proc = jax.process_index()
        # contribution ownership by part index — identical rule to
        # _device_shuffle_impl, so in-memory SPMD-duplicated inputs are
        # contributed exactly once and foreign scan partitions stay unread
        local = []
        sent_rows = sent_bytes = 0
        for i, p in enumerate(parts):
            owner = (p.owner_process if p.owner_process is not None
                     else i % nproc)
            if owner == my_proc:
                t = p.table()
                local.append((i, t))
                sent_rows += len(t)
                sent_bytes += t.size_bytes()
        datas = peer.allgather(
            pickle.dumps(local, protocol=pickle.HIGHEST_PROTOCOL))
        full = {}
        for d in datas:
            for i, t in pickle.loads(d):
                full[i] = t
        schema = parts[0].schema
        ordered = []
        for i in range(len(parts)):
            t = full.get(i)
            mp = (MicroPartition.from_table(t) if t is not None
                  else MicroPartition.empty(schema))
            ordered.append(mp)
        # identical bucketing to ShuffleOp's host fanout (piece i of every
        # part, concatenated in part order) so results are byte-identical
        # with the exchange the collective/host paths produce
        buckets = [[] for _ in range(num)]
        for pi, mp in enumerate(ordered):
            if scheme == "hash":
                pieces = mp.partition_by_hash(by, num)
            elif scheme == "random":
                pieces = mp.partition_by_random(num, seed=pi)
            else:
                pieces = mp.partition_by_range(by, boundaries, descending,
                                               nulls_first)
            for i, piece in enumerate(pieces):
                if len(piece):
                    buckets[min(i, num - 1)].append(piece)
        self.stats.bump("transport_shuffles")
        if sent_rows:
            self.stats.bump("exchange_rows", sent_rows)
        if sent_bytes:
            self.stats.bump("exchange_bytes", sent_bytes)
        out = []
        for b in range(num):
            out.append(MicroPartition.concat(buckets[b]) if buckets[b]
                       else MicroPartition.empty(schema))
        return out

    def _device_shuffle_impl(self, parts: List[MicroPartition], by, num: int,
                             scheme: str, descending=None, nulls_first=None,
                             boundaries=None,
                             combine=None) -> Optional[List[MicroPartition]]:
        n = self.n_devices
        if scheme not in ("hash", "random", "range"):
            return None
        if scheme == "range" and boundaries is None:
            return None
        schema = parts[0].schema
        if any(not exchangeable_dtype(f.dtype) for f in schema):
            return None
        str_idx = [j for j, f in enumerate(schema) if f.dtype.is_string()]
        from ..schema import Schema
        from ..table import Table, _composite_rank

        devs = list(self.mesh.devices.flat)
        my_proc = jax.process_index()
        multiproc = any(d.process_index != my_proc for d in devs)
        if multiproc:
            # Per-host scan locality (reference: per-node scan dispatch,
            # ray_runner.py:504-685): the part list is globally consistent
            # (SPMD control plane), so contribution ownership is assigned by
            # part INDEX — process p materializes and stages only parts with
            # i % nproc == p. An unloaded scan partition owned elsewhere is
            # never table()'d, so each host READS only its share of the
            # input files; every row is contributed exactly once whether the
            # inputs are process-duplicated (in-memory SPMD) or disjoint
            # (scan tasks). The post-exchange allgather below reconstitutes
            # full outputs on every process, reconverging the control plane.
            nproc = jax.process_count()
            tables = [p.table() for i, p in enumerate(parts)
                      if (p.owner_process if p.owner_process is not None
                          else i % nproc) == my_proc]
        else:
            tables = [p.table() for p in parts]
        total = sum(len(t) for t in tables)
        if not multiproc and total == 0:
            return None

        # Re-chunk onto the devices THIS process stages: all n in single
        # process; the process-local devices in multi-process mode.
        chunk_dev_idx = [i for i, d in enumerate(devs)
                        if not multiproc or d.process_index == my_proc]
        nchunks = len(chunk_dev_idx)
        if tables:
            merged = Table.concat(tables) if len(tables) != 1 else tables[0]
        else:
            merged = Table.empty(schema)
        precombined = 0
        if combine is not None and len(merged):
            # hierarchical exchange, mesh mirror: fold THIS process's local
            # contribution through the stage-2 combine ahead of the ICI
            # all_to_all — the local rows ride the collective pre-reduced
            # (intra-host combine -> inter-host all_to_all). Schema-closure
            # was gated at translate time; re-check and decline on drift.
            try:
                folded = merged.agg(list(combine[0]), list(combine[1]))
            except Exception:
                folded = None
            if folded is not None and folded.schema == merged.schema:
                # counted only on exchange SUCCESS (see the bumps before
                # return) — a late collective failure falls back to the
                # host path, which re-counts everything
                precombined = len(merged) - len(folded)
                merged = folded
                total = len(merged)
        step = -(-total // nchunks) if total else 0
        chunks = [merged.slice(min(i * step, total), min((i + 1) * step, total))
                  for i in range(nchunks)]
        # String columns exchange as codes against GLOBAL sorted
        # dictionaries agreed across every process (see module docstring);
        # the agreement must run on every process in the same order even
        # when this process's contribution is empty.
        global_dicts = {}
        if str_idx:
            import pyarrow as pa
            import pyarrow.compute as pc

            fields = list(schema)
            local_dicts = []
            for j in str_idx:
                arr = merged.get_column(fields[j].name).to_arrow()
                if isinstance(arr, pa.ChunkedArray):
                    arr = arr.combine_chunks()
                local_dicts.append(
                    pc.unique(arr.drop_null()).cast(pa.large_string()))
            gds = _gather_global_dictionaries(local_dicts, multiproc)
            if gds is None:
                return None  # cap tripped (agreed globally)
            global_dicts = dict(zip(str_idx, gds))
        # Control plane: per-row destination PARTITION, computed with the host
        # kernels (identical assignment to the host shuffle path).
        k = len(by or [])
        desc = list(descending) if descending is not None else [False] * k
        nf = list(nulls_first) if nulls_first is not None else [None] * k
        part_buckets, dev_buckets, inbounds = [], [], []
        for ci, c in enumerate(chunks):
            if scheme == "hash":
                h = c.hash_rows(by)
                b = (h % np.uint64(num)).astype(np.int32)
            elif scheme == "random":
                # seed by GLOBAL device index: local chunk indices repeat
                # across processes and would correlate the bucket sequences
                rng = np.random.RandomState(chunk_dev_idx[ci])
                b = rng.randint(0, num, size=len(c)).astype(np.int32)
            else:
                bnds = boundaries._columns
                if not bnds or len(bnds[0]) == 0:
                    b = np.zeros(len(c), dtype=np.int32)
                else:
                    keys = c.eval_expression_list(by)._columns
                    b = np.minimum(_composite_rank(keys, bnds, desc, nf),
                                   num - 1).astype(np.int32)
            part_buckets.append(b)
            dev_buckets.append((b % n).astype(np.int32) if num > n else b)
            inbounds.append(np.ones(len(c), dtype=bool))
        cap = exchange_capacity(dev_buckets, inbounds, n)
        maxlen = max((len(c) for c in chunks), default=1)
        if multiproc:
            # Negotiate the exchange SHAPE globally: with disjoint
            # contributions the local capacity/slab sizes differ per process,
            # and shard_map needs every process to compile the same program.
            # cap is a per-(src,dst) property so the global value is the max
            # over all sources; a zero GLOBAL row count (not local) skips.
            from jax.experimental import multihost_utils

            agreed = np.asarray(multihost_utils.process_allgather(
                np.array([cap, maxlen, total], dtype=np.int64)))
            cap = int(agreed[:, 0].max())
            maxlen = int(agreed[:, 1].max())
            if int(agreed[:, 2].sum()) == 0:
                return None
        r = size_bucket(max(maxlen, 1))
        names = [f.name for f in schema]
        ncols = len(names)
        ship_lane = num > n  # receivers need the partition id to split
        # Per-device staging: stage one source shard at a time and device_put
        # it straight onto its mesh device. Every chunk here is staged — in
        # multi-process mode `chunks` already covers exactly the LOCAL
        # devices (the global arrays assemble from addressable shards only,
        # standard jax multihost staging).
        b_shards, v_shards, lane_shards = [], [], []
        col_shards = [[] for _ in range(ncols)]
        null_shards = [[] for _ in range(ncols)]
        col_trailing = [()] * ncols
        col_dtypes = [None] * ncols
        ok = True
        try:
            for i, c in enumerate(chunks):
                dev = devs[chunk_dev_idx[i]]
                bm = np.zeros(r, dtype=np.int32)
                vm = np.zeros(r, dtype=bool)
                bm[:len(c)] = dev_buckets[i]
                vm[:len(c)] = True
                b_shards.append(jax.device_put(bm[None], dev))
                v_shards.append(jax.device_put(vm[None], dev))
                if ship_lane:
                    lm = np.zeros(r, dtype=np.int32)
                    lm[:len(c)] = part_buckets[i]
                    lane_shards.append(jax.device_put(lm[None], dev))
                for j, name in enumerate(names):
                    if j in global_dicts:
                        vals, valid = _stage_global_codes(
                            c.get_column(name), global_dicts[j], r)
                    else:
                        vals, valid, _ = stage_np(c.get_column(name), r)
                    col_trailing[j] = tuple(vals.shape[1:])
                    col_dtypes[j] = vals.dtype
                    col_shards[j].append(jax.device_put(vals[None], dev))
                    null_shards[j].append(jax.device_put(valid[None], dev))
        except ValueError:
            # stage_np rejects e.g. int64 values outside int32 range when x64
            # is off (real-TPU mode): fall back to the host shuffle, same as
            # every other device route
            ok = False
        if multiproc:
            # staging failure is DATA-dependent and contributions are
            # disjoint: one process declining while others proceed would
            # deadlock the collective, so agree on the outcome first
            from jax.experimental import multihost_utils

            oks = np.asarray(multihost_utils.process_allgather(
                np.array([1 if ok else 0], dtype=np.int64)))
            if int(oks.min()) == 0:
                return None
        if not ok:
            return None
        lane_cols = ([np.dtype(np.int32)] if ship_lane else [])
        all_dtypes = tuple(col_dtypes) + tuple(np.dtype(bool) for _ in names) + tuple(lane_cols)
        trailing = tuple(col_trailing) + tuple(() for _ in names) + tuple(
            () for _ in lane_cols)
        fn = build_exchange(self.mesh, cap, all_dtypes, trailing)
        dev_args = [self._shard_onto_devices(b_shards, (), r),
                    self._shard_onto_devices(v_shards, (), r)]
        for j in range(ncols):
            dev_args.append(self._shard_onto_devices(col_shards[j], col_trailing[j], r))
        for j in range(ncols):
            dev_args.append(self._shard_onto_devices(null_shards[j], (), r))
        if ship_lane:
            dev_args.append(self._shard_onto_devices(lane_shards, (), r))
        out = fn(*dev_args)
        import jax.numpy as jnp

        if multiproc:
            # SPMD materialization: every process needs every output
            # partition to continue the (duplicated) host control plane, so
            # the exchanged slabs allgather across processes — this IS the
            # DCN data movement (jax.experimental.multihost_utils), the
            # role the reference's Ray object store plays across nodes.
            from jax.experimental import multihost_utils

            gathered = [np.asarray(multihost_utils.process_allgather(
                o, tiled=True)) for o in out]
            valid_all = gathered[0]
            lane_all = gathered[1 + 2 * ncols] if ship_lane else None
            if ship_lane:
                cnts = np.stack([
                    np.bincount(lane_all[d].reshape(-1)[
                        valid_all[d].reshape(-1)], minlength=num)[:num]
                    for d in range(n)])
            else:
                cnts = valid_all.sum(axis=(1, 2))

            def _slab(idx: int, d: int):
                return gathered[idx][d]
        else:
            # Per-partition row counts computed ON DEVICE: one tiny
            # [n(, num)] fetch instead of pulling the full [n, n, cap]
            # valid/lane matrices through the host link.
            if ship_lane:
                def _cnts(v, l):
                    def per_dev(vv, ll):
                        lanes = jnp.where(vv.reshape(-1), ll.reshape(-1), num)
                        return jnp.bincount(lanes, length=num + 1)[:num]
                    return jax.vmap(per_dev)(v, l)

                cnts = np.asarray(jax.device_get(
                    jax.jit(_cnts)(out[0], out[1 + 2 * ncols])))  # [n, num]
            else:
                cnts = np.asarray(jax.device_get(
                    jax.jit(lambda v: jnp.sum(v, axis=(1, 2)))(out[0])))  # [n]

            shard_maps = [
                {s.device: s.data for s in garr.addressable_shards}
                for garr in out]

            def _slab(idx: int, d: int):
                return shard_maps[idx][devs[d]][0]

        self.stats.bump("device_shuffles")

        # Unstage: per OUTPUT PARTITION, pack the received slab's real rows to
        # the front ON ITS OWNING DEVICE (b % n for num > n; b otherwise,
        # trailing devices idle when num < n), then SEED the new partition's
        # HBM residency cache with the packed columns — downstream device ops
        # (join probes, filters, segment aggs) on co-partitioned outputs run
        # without re-staging anything through the host link.
        from ..kernels.device import x64_enabled

        results: List[MicroPartition] = []
        for b in range(num):
            d = b % n
            cnt = int(cnts[d, b]) if ship_lane else int(cnts[b])
            bucket = size_bucket(max(cnt, 1))
            sel = _slab(0, d).reshape(-1)
            if ship_lane:
                sel = sel & (_slab(1 + 2 * ncols, d).reshape(-1) == np.int32(b))
            series_out = []
            staged: List[DeviceColumn] = []
            for j, f in enumerate(schema):
                slab = _slab(1 + j, d)
                flat = slab.reshape((-1,) + tuple(slab.shape[2:]))
                nulls = _slab(1 + ncols + j, d).reshape(-1)
                pv, pn = _pack_slab(flat, nulls, sel, bucket)
                # string columns arrive as codes into the GLOBAL sorted
                # dictionary — decode at unstage, and the seeded residency
                # below hands downstream device string ops exactly the
                # sorted-dictionary shape they expect
                dc = DeviceColumn(pv, pn, cnt, f.dtype,
                                  dictionary=global_dicts.get(j))
                staged.append(dc)
                series_out.append(unstage(dc).rename(f.name))
            part = MicroPartition.from_table(Table(Schema(list(schema)), series_out))
            cache = part.device_stage_cache()
            for f, dc in zip(schema, staged):
                cache[(f.name, bucket, x64_enabled())] = dc
            results.append(part)
        # actual exchanged payload, symmetric with the host path's
        # bucket-append accounting: the rows/bytes THIS process staged onto
        # the collective (post pre-combine) — not the pre-materialization
        # estimate the old device branch reported. Bumped only HERE, after
        # the whole exchange (collective + unstage) succeeded: an earlier
        # bump would double-count with the host fallback's re-count when a
        # late failure makes try_device_shuffle return None.
        if total:
            self.stats.bump("exchange_rows", total)
            mb = merged.size_bytes()
            if mb:
                self.stats.bump("exchange_bytes", mb)
        if precombined:
            self.stats.bump("exchange_precombined_rows", precombined)
        return results

    # ------------------------------------------------------------------
    # sketch subsystem: global stage-2 HLL merges ride ICI as a register
    # all_gather+max instead of a host loop over gathered sketch rows
    # ------------------------------------------------------------------

    def try_sketch_register_merge(self, regs: np.ndarray):
        """Merge [k, m] uint8 HLL register rows into one [m] row with the
        jitted all_gather+max collective (collectives.build_register_allmerge).
        Returns None when ineligible, when the collective breaker is open, or
        when the collective fails (failure recorded against the breaker; the
        caller's host merge takes over). Fault site: collective.sketch."""
        from .. import faults

        n = self.n_devices
        if self._multiproc or regs.ndim != 2 or regs.shape[0] == 0:
            # multi-process stage-2 inputs are process-local after the
            # gather; keep the collective merge single-process for now
            return None
        if not self.collective_health.allow(self.stats):
            self.stats.bump("degraded_sketch_merges")
            return None
        try:
            faults.check("collective.sketch", self.stats)
            from .collectives import build_register_allmerge, shard_to_mesh

            k, m = regs.shape
            if k > n:
                # pre-fold surplus rows so one row rides each device
                pad = (-k) % n
                folded = np.concatenate(
                    [regs, np.zeros((pad, m), np.uint8)])
                regs = folded.reshape(-1, n, m).max(axis=0)
            elif k < n:
                regs = np.concatenate(
                    [regs, np.zeros((n - k, m), np.uint8)])
            fn = build_register_allmerge(self.mesh, m)
            out = np.asarray(jax.device_get(
                fn(shard_to_mesh(np.ascontiguousarray(regs), self.mesh))))[0]
        except Exception as e:
            self.stats.note_device_error("collective.sketch", e)
            self.collective_health.record_failure(self.stats)
            return None
        self.collective_health.record_success(self.stats)
        self.stats.bump("collective_sketch_merges")
        return out

    def _collective_answer(self, step, parts):
        """Global merge_sketch_hll stages (the gathered stage 2 of a
        multi-partition approx_count_distinct) merge on the mesh when
        eligible; the merge resolves synchronously (one tiny all_gather).
        Everything else takes the base routing."""
        from ..physical import AggregateOp

        # no min-rows gate: a stage-2 input is one sketch row per partition
        # BY DESIGN — routing those few wide rows through ICI is the point.
        # Multi-process declines HERE, before the partition materializes and
        # the sketches decode (try_sketch_register_merge would refuse anyway)
        if (isinstance(step, AggregateOp) and not step.groupby
                and self.cfg.use_device_kernels and not self._multiproc):
            return self._sketch_merge_collective(parts[0], step.aggregations)
        return None

    def _sketch_merge_collective(self, part, aggregations):
        from ..datatypes import DataType
        from ..expressions import AggExpr, Alias
        from ..schema import Field, Schema
        from ..series import Series
        from ..sketch.hll import binary_to_registers, registers_to_binary
        from ..table import Table

        nodes = []
        for e in aggregations:
            node = e._node
            while isinstance(node, Alias):
                node = node.child
            if not (isinstance(node, AggExpr)
                    and node.kind == "merge_sketch_hll"):
                return None
            nodes.append((e.name(), node))
        if not nodes:
            return None
        tbl = part.table()
        out_cols = []
        out_fields = []
        for alias, node in nodes:
            child = node.child.evaluate(tbl)
            merged = self.try_sketch_register_merge(
                binary_to_registers(child))
            if merged is None:
                return None
            s = Series.from_arrow(registers_to_binary(merged[None]), alias,
                                  DataType.binary())
            out_cols.append(s)
            out_fields.append(Field(alias, DataType.binary()))
        return MicroPartition.from_table(Table(Schema(out_fields), out_cols))
