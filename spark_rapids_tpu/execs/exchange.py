"""Shuffle exchange exec.

Reference (SURVEY.md §3.4): GpuShuffleExchangeExecBase — device partition
split (GpuPartitioning.sliceInternalOnGpuAndClose), serialized write through
the shuffle manager, then the read side's GpuShuffleCoalesceExec concats a
reduce partition's serialized tables ON HOST to the target size before one
device upload (GpuShuffleCoalesceExec.scala:43-229).

The exec yields batches per reduce partition: oversized partitions split
at the batch target; with adaptive coalescing enabled, adjacent
undersized partitions share output batches (so batch count can be far
below the partition count)."""

from __future__ import annotations

from time import perf_counter
from typing import List, Optional, Sequence

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceTable, HostTable
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.errors import ColumnarProcessingError, MapOutputLostError
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.runtime.faults import RECOVERY
from spark_rapids_tpu.ops.expr import Expression
from spark_rapids_tpu.shuffle.manager import get_shuffle_manager
from spark_rapids_tpu.shuffle.partitioning import (
    HashPartitioner,
    Partitioner,
    RangePartitioner,
    RoundRobinPartitioner,
    SinglePartitioner,
    split_by_partition,
)


def _pad_capacity(table: DeviceTable, new_cap: int) -> DeviceTable:
    """Extend every column with dead tail rows to ``new_cap`` (flat
    columns only — the ICI exchange's equal-shard requirement for
    non-pow2 partition counts)."""
    import jax.numpy as jnp

    extra = new_cap - table.capacity

    def pad(arr):
        # zeros of a bool dtype are False, so validity/live tails are dead
        tail = jnp.zeros((extra,) + arr.shape[1:], dtype=arr.dtype)
        return jnp.concatenate([arr, tail])

    cols = [c.with_arrays(pad(c.data), pad(c.validity))
            for c in table.columns]
    live = pad(table.live) if table.live is not None else None
    return DeviceTable(table.names, cols, table.nrows_dev, new_cap,
                       live=live)


def ici_requested(conf: RapidsConf) -> bool:
    """Did the session ask for collective shuffles — either the legacy
    ``spark.rapids.shuffle.mode=ICI`` or mesh-native execution
    (``spark.rapids.mesh.enabled``)?"""
    from spark_rapids_tpu.conf import SHUFFLE_MANAGER_MODE
    from spark_rapids_tpu.parallel.mesh import MESH_ENABLED
    return (str(conf.get_entry(SHUFFLE_MANAGER_MODE)).upper() == "ICI"
            or bool(conf.get_entry(MESH_ENABLED)))


def collective_applicable(mode: str, num_partitions: int) -> bool:
    """Whether an exchange of this shape has a collective form AT ALL.
    A single output partition is a gather, not an all-to-all — taking
    the host path there is not a demotion, so it neither counts toward
    hostShuffleFallbacks nor earns a fallback note in explain()."""
    return mode != "single" and num_partitions > 1


def ici_demotion_reason(conf: RapidsConf, mode: str, num_partitions: int,
                        schema) -> Optional[str]:
    """Why an ICI-requested exchange takes the host-file shuffle, or
    None when the collective path will run. STATIC facts only (mode,
    partition count, device count, column dtypes), so the overrides
    tagger surfaces the same reason in explain() that the exec acts on
    at execution (the demotion analog for shuffles: the exchange still
    runs on device, just through the host path). Callers gate on
    ``collective_applicable`` first — shapes with no collective form
    are not demotions."""
    import jax
    from spark_rapids_tpu.parallel.mesh import MESH, suppression_reason
    sup = suppression_reason()
    if sup is not None:
        # the degradation ladder suppressed mesh landing for THIS
        # attempt (partial device loss, retry failed): the collective
        # demotes with the ladder's reason so hostShuffleFallbacks and
        # explain() surface WHY the exchange took the host path
        return sup
    if mode != "hash":
        return (f"{mode} partitioning has no deterministic per-row "
                f"device target; host shuffle computes it row-by-row")
    # ONE atomic snapshot: separate enabled/ndev reads racing a
    # concurrent reconfiguration could see enabled=True then ndev=0
    ndev = MESH.effective_ndev()
    if ndev is None:
        ndev = len(jax.devices())
    if num_partitions > ndev:
        return (f"partition count {num_partitions} exceeds the "
                f"{ndev}-device mesh")
    nested = [n for n, dt in schema
              if isinstance(dt, (T.ArrayType, T.StructType, T.MapType))]
    if nested:
        return (f"nested-type columns ({', '.join(nested[:3])}) have no "
                f"collective-exchangeable device layout")
    return None


def make_partitioner(mode: str, keys: Sequence[Expression],
                     num_partitions: int) -> Partitioner:
    mode = mode.lower()
    if mode == "hash":
        if not keys:
            raise ColumnarProcessingError("hash partitioning requires keys")
        return HashPartitioner(keys, num_partitions)
    if mode == "range":
        return RangePartitioner(keys, num_partitions)
    if mode == "roundrobin":
        return RoundRobinPartitioner(num_partitions)
    if mode == "single":
        return SinglePartitioner()
    raise ColumnarProcessingError(f"unknown partitioning {mode}")


class TpuShuffleExchangeExec(TpuExec):
    def __init__(self, child: TpuExec, mode: str, num_partitions: int,
                 keys: Sequence[Expression], conf: RapidsConf,
                 target_batch_bytes: int = 1 << 30):
        super().__init__()
        self.children = (child,)
        self.mode = mode
        self.num_partitions = 1 if mode == "single" else num_partitions
        self.keys = list(keys)
        self.conf = conf
        self.target_batch_bytes = target_batch_bytes
        #: why an ICI-requested exchange demoted to the host shuffle
        #: (None while on the collective path or when never requested)
        self.ici_fallback_reason: Optional[str] = None

    def output_schema(self):
        return self.children[0].output_schema()

    def describe(self):
        extra = (f", hostShuffleFallback={self.ici_fallback_reason!r}"
                 if self.ici_fallback_reason else "")
        return f"TpuShuffleExchange[{self.mode}, n={self.num_partitions}{extra}]"

    def _aqe_coalesce_enabled(self) -> bool:
        from spark_rapids_tpu.conf import AQE_COALESCE_PARTITIONS
        return bool(self.conf.get_entry(AQE_COALESCE_PARTITIONS))

    def _ici_eligible(self) -> bool:
        """The collective path runs when the session asked for it (ICI
        shuffle mode or mesh-native execution), the partitioning is
        hash, and every partition maps onto one mesh device (SURVEY
        §2.6: 'partitions on one slice -> collective, else host
        shuffle'). Supports EVERY non-nested column type — decimal128's
        two-limb layout rides the collective as a trailing dim — and
        non-pow2 partition counts pad the row capacity up to a multiple
        of the mesh size (_pad_capacity). A requested-but-demoted
        exchange counts hostShuffleFallbacks with the reason surfaced
        in explain() (overrides._tag_exchange notes the same static
        reason this check acts on)."""
        if not ici_requested(self.conf):
            return False
        if not collective_applicable(self.mode, self.num_partitions):
            return False
        reason = ici_demotion_reason(self.conf, self.mode,
                                     self.num_partitions,
                                     self.output_schema())
        if reason is not None:
            from spark_rapids_tpu.parallel.mesh import MESH_SCOPE
            self.ici_fallback_reason = reason
            self.add_metric("hostShuffleFallbacks", 1)
            MESH_SCOPE.add("hostShuffleFallbacks", 1)
            return False
        return True

    #: masked batches share the input buffers, but every downstream
    #: kernel still runs at full input capacity PER partition — beyond
    #: this many partitions the host shuffle's compacted batches win
    LOCAL_SPLIT_MAX_PARTITIONS = 32

    def _local_split_eligible(self) -> bool:
        from spark_rapids_tpu.conf import (
            SHUFFLE_LOCAL_DEVICE_SPLIT,
            SHUFFLE_MANAGER_MODE,
        )
        from spark_rapids_tpu.execs.base import MASKED_ENABLED
        mode = str(self.conf.get_entry(SHUFFLE_MANAGER_MODE)).upper()
        # the device split wins over host-shuffle coalescing when both
        # apply: its per-partition masked VIEWS cost no serialization and
        # downstream group-blind consumers mask-union undersized views
        # back together (columnar/table.merge_split_views) — the same
        # sliver-batch problem AQE coalescing solves, without the stats
        return (mode == "MULTITHREADED"
                and bool(self.conf.get_entry(SHUFFLE_LOCAL_DEVICE_SPLIT))
                and MASKED_ENABLED.get()  # masked-batch kill switch
                and self.mode in ("hash", "roundrobin", "single")
                and self.num_partitions <= self.LOCAL_SPLIT_MAX_PARTITIONS)

    produces_masked = True

    def execute(self):
        # base-contract note: execute() must yield PREFIX batches; the
        # masked local split therefore lives in execute_masked() and
        # mask-unaware callers get compacted tables via the base wrapper
        if self._ici_eligible():
            yield from self._execute_ici()
            return
        if self._local_split_eligible():
            for b in self._execute_local_device_split():
                yield b.compacted()
            return
        yield from self._execute_host_shuffle()

    def execute_masked(self):
        if self._ici_eligible():
            yield from self._execute_ici()
            return
        if self._local_split_eligible():
            yield from self._execute_local_device_split()
            return
        yield from self._execute_host_shuffle()

    def _execute_local_device_split(self):
        """Single-process repartition entirely ON DEVICE: one partition-id
        kernel over the coalesced input, then one MASKED batch per
        partition sharing the input buffers — liveness masks instead of
        per-partition compaction scatters (columnar/table.py
        DeviceTable.live). The reference always round-trips the shuffle
        manager because its executors are separate processes; a
        single-chip engine has no wire to cross."""
        import jax.numpy as jnp

        from spark_rapids_tpu.columnar.table import concat_device
        from spark_rapids_tpu.dispatch import tpu_jit
        from spark_rapids_tpu.ops.expr import shared_traces
        from spark_rapids_tpu.runtime.retry import retry_block

        t0 = perf_counter()
        batches = list(self.children[0].execute_masked())
        if not batches:
            return
        table = retry_block(lambda: concat_device(batches)) \
            if len(batches) > 1 else batches[0]
        parter = make_partitioner(self.mode, self.keys, self.num_partitions)
        nparts = self.num_partitions
        pids = parter.partition_ids(table)
        traces = shared_traces(("localsplit", nparts))
        tkey = (table.capacity, table.live is not None)
        fn = traces.get(tkey)
        if fn is None:
            cap = table.capacity

            def masks(pids, nrows, live_in):
                if live_in is not None:
                    live = live_in
                else:
                    live = jnp.arange(cap, dtype=jnp.int32) < nrows
                outs = []
                for p in range(nparts):
                    m = live & (pids == p)
                    outs.append((m, jnp.sum(m.astype(jnp.int32))))
                return outs

            fn = tpu_jit(masks, name="split_masks")
            traces[tkey] = fn
        outs = fn(pids, table.nrows_dev, table.live)
        self.add_metric("localSplitParts", nparts)
        self.add_metric("localSplitTime", perf_counter() - t0)
        from spark_rapids_tpu.columnar.table import mark_shared_view
        split_group = object()  # one token per split: its masks are disjoint
        for mask, cnt in outs:
            out = DeviceTable(table.names, table.columns, cnt,
                              table.capacity, live=mask)
            # coalesce streams capacity-sharing views (and may mask-union
            # same-group views back together for group-blind consumers)
            mark_shared_view(out, split_group)
            yield out

    def _execute_ici(self):
        """ONE all-to-all collective over the device mesh instead of the
        host-file shuffle: coalesce input, evaluate key columns, exchange
        every column's rows to its murmur3 partition's device, emit one
        front-compacted batch per partition (parallel/exchange.py).
        Input shards stay DEVICE-RESIDENT end to end — the only host
        traffic is the per-shard live-count fetch, which doubles as the
        AQE map-output statistic (skew/coalesce decisions see the real
        shard distribution instead of the host path's file sizes)."""
        from spark_rapids_tpu.columnar import DeviceColumn, bucket_for
        from spark_rapids_tpu.columnar.table import concat_device
        from spark_rapids_tpu.ops.expr import compile_project
        from spark_rapids_tpu.parallel.exchange import (
            MeshExchange,
            interned_dict_bytes,
        )
        from spark_rapids_tpu.parallel.mesh import MESH, MESH_SCOPE
        from spark_rapids_tpu.runtime.retry import retry_block

        t0 = perf_counter()
        batches = list(self.children[0].execute())
        if not batches:
            return
        table = retry_block(lambda: concat_device(batches)) \
            if len(batches) > 1 else batches[0]
        ndev = self.num_partitions
        if table.capacity % ndev != 0:
            # non-pow2 partition counts (or tiny tables): pad the row
            # capacity up to a multiple of ndev with dead rows — every
            # column extends with zero/False tails, so the collective's
            # equal per-device shards always exist
            table = _pad_capacity(table, -(-table.capacity // ndev) * ndev)

        key_cols = compile_project(self.keys, table)
        mesh, axis = MESH.exchange_mesh(ndev)
        string_bytes = {}
        for i, c in enumerate(key_cols):
            if isinstance(c.dtype, T.StringType):
                # replicated byte matrix, interned by dictionary
                # identity: repeated exchanges over one dictionary pay
                # the replication upload once
                string_bytes[i] = interned_dict_bytes(c.dictionary, mesh)

        ex = MeshExchange.get(
            mesh,
            tuple(str(c.dtype) for c in table.columns),
            tuple(range(len(key_cols))),
            tuple(c.dtype for c in key_cols),
            tuple(sorted((i, v[0].shape) for i, v in string_bytes.items())),
            table.capacity, axis_name=axis)
        out_d, out_v, counts = ex.run(
            [c.data for c in table.columns],
            [c.validity for c in table.columns],
            [c.data for c in key_cols],
            [c.validity for c in key_cols],
            table.row_mask(),
            string_bytes)
        self.add_metric("iciExchangeTime", perf_counter() - t0)
        self.add_metric("iciPartitions", ndev)
        # exchanged payload bytes (static shapes: no device sync)
        ici_bytes = sum(a.nbytes for a in out_d) + \
            sum(a.nbytes for a in out_v)
        self.add_metric("iciBytes", ici_bytes)
        MESH_SCOPE.add("iciExchanges", 1)
        MESH_SCOPE.add("iciBytes", ici_bytes)

        # AQE exchange statistics from the MEASURED per-shard live
        # counts (MapOutputStatistics analog): rows x packed row bytes
        # approximates per-partition output size, driving the same
        # skew metric the host shuffle records from file sizes
        row_bytes = max(self._packed_row_bytes_for(table), 1)
        live = sorted(int(c) * row_bytes for c in counts if int(c) > 0)
        if live:
            from spark_rapids_tpu.conf import AQE_SKEW_FACTOR
            median = live[len(live) // 2]
            factor = float(self.conf.get_entry(AQE_SKEW_FACTOR))
            skewed = sum(1 for b in live if b > factor * max(median, 1))
            self.add_metric("mapOutputBytesMax", live[-1])
            self.add_metric("mapOutputBytesMedian", median)
            if skewed:
                self.add_metric("skewedPartitions", skewed)

        shard = len(out_d[0]) // ndev if out_d else 0
        for p in range(ndev):
            n = int(counts[p])
            if n == 0:
                continue
            k = min(bucket_for(max(n, 1)), shard)
            cols = []
            for c, d, v in zip(table.columns, out_d, out_v):
                sl = slice(p * shard, p * shard + k)
                cols.append(DeviceColumn(c.dtype, d[sl], v[sl],
                                         dictionary=c.dictionary,
                                         dict_sorted=c.dict_sorted))
            yield DeviceTable(table.names, cols, n, k)

    @staticmethod
    def _packed_row_bytes_for(table: DeviceTable) -> int:
        """Approximate serialized bytes per row of ``table`` (column
        data words + validity) for the AQE map-output statistic."""
        total = 0
        for c in table.columns:
            itemsize = getattr(c.data.dtype, "itemsize", 4)
            if getattr(c.data, "ndim", 1) == 2:
                itemsize *= c.data.shape[1]
            total += itemsize + 1
        return total

    def _shuffle_manager(self):
        """MULTITHREADED -> file-backed manager; P2P -> cached blocks
        served through the client/server transport (UCX-mode analog). Both
        expose the same write/read handle interface."""
        from spark_rapids_tpu.conf import SHUFFLE_MANAGER_MODE
        mode = str(self.conf.get_entry(SHUFFLE_MANAGER_MODE)).upper()
        if mode == "P2P":
            from spark_rapids_tpu.shuffle.p2p import get_p2p_env
            return get_p2p_env(self.conf)
        return get_shuffle_manager(self.conf)

    def _execute_host_shuffle(self, prefetched=None):
        manager = self._shuffle_manager()
        partitioner = make_partitioner(self.mode, self.keys, self.num_partitions)
        handle = manager.new_shuffle(self.num_partitions)
        try:
            t0 = perf_counter()
            batches = (iter(prefetched) if prefetched is not None
                       else self.children[0].execute())
            if isinstance(partitioner, RangePartitioner):
                # range bounds must sample the WHOLE input, not the first
                # batch (Spark samples per-partition across the input)
                batches = list(batches)
                partitioner.compute_bounds_multi(batches)
            from spark_rapids_tpu.runtime.retry import retry_block
            for batch in batches:
                parts = split_by_partition(batch, partitioner)
                # host-memory pressure (CpuRetryOOM from the arbiter)
                # retries through the same framework as device OOM
                retry_block(lambda p=parts: handle.write_partitions(p))
            self.add_metric("shuffleWriteTime", perf_counter() - t0)
            self.add_metric("shuffleBytesWritten", handle.bytes_written)

            reader = manager.reader(handle)

            def read_one_partition(p: int) -> List[HostTable]:
                """Buffer one reduce partition (the recovery unit: nothing
                is emitted downstream until the partition read succeeded,
                so a recompute-and-retry never double-counts rows). A lost
                map output re-runs the missing upstream partitions from
                the RETAINED PLAN LINEAGE (self.children[0]) instead of
                failing the query."""
                for attempt in range(3):
                    bytes_before = reader.bytes_read
                    try:
                        return list(reader.read_partition(p))
                    except MapOutputLostError as e:
                        # a failed attempt's partial reads must not count
                        # toward shuffleBytesRead (the retry re-reads them)
                        reader.bytes_read = bytes_before
                        if attempt == 2:
                            raise
                        self._recompute_maps(handle, partitioner, e.map_ids)

            t0 = perf_counter()
            # AQE partition coalescing (reference: AQE
            # CoalesceShufflePartitions / ShufflePartitionsUtil): with the
            # conf enabled, ADJACENT undersized reduce partitions share
            # output batches, so a 200-partition shuffle of a small dataset
            # emits a handful of full batches instead of 200 slivers. NOTE:
            # a flush can land mid-partition, so batches are NOT
            # partition-aligned in this mode (keyed co-location still holds
            # per ROW, just not per batch). The within-partition target-
            # size split (GpuShuffleCoalesce) applies in both modes.
            coalesce_parts = self._aqe_coalesce_enabled()
            # measured map-output stats (AQE MapOutputStatistics analog):
            # per-partition byte sizes drive the skew metric and make the
            # coalescing decision observable
            part_bytes = [0] * self.num_partitions
            pending: List[HostTable] = []
            pending_bytes = 0
            nonempty_parts = 0
            emitted = 0
            for p in range(self.num_partitions):
                saw_rows = False
                for t in read_one_partition(p):
                    saw_rows = True
                    pending.append(t)
                    nb = t.nbytes()
                    part_bytes[p] += nb
                    pending_bytes += nb
                    if pending_bytes >= self.target_batch_bytes:
                        yield self._upload(pending)
                        emitted += 1
                        pending, pending_bytes = [], 0
                nonempty_parts += saw_rows
                if pending and not coalesce_parts:
                    yield self._upload(pending)
                    emitted += 1
                    pending, pending_bytes = [], 0
            if pending:
                yield self._upload(pending)
                emitted += 1
            if coalesce_parts and nonempty_parts > emitted:
                self.add_metric("aqeCoalescedPartitions",
                                nonempty_parts - emitted)
            live = sorted(b for b in part_bytes if b > 0)
            if live:
                from spark_rapids_tpu.conf import AQE_SKEW_FACTOR
                median = live[len(live) // 2]
                factor = float(self.conf.get_entry(AQE_SKEW_FACTOR))
                skewed = sum(1 for b in live if b > factor * max(median, 1))
                self.add_metric("mapOutputBytesMax", live[-1])
                self.add_metric("mapOutputBytesMedian", median)
                if skewed:
                    # oversized partitions already split into target-size
                    # batches above (OptimizeSkewedJoin's split, from
                    # MEASURED sizes); surface how many were skewed
                    self.add_metric("skewedPartitions", skewed)
            self.add_metric("shuffleReadTime", perf_counter() - t0)
            self.add_metric("shuffleBytesRead", reader.bytes_read)
        finally:
            manager.remove_shuffle(handle)

    def _recompute_maps(self, handle, partitioner, map_ids) -> None:
        """Lost-map-output recovery: re-run the child plan (map output i
        is batch i — partitioning is deterministic, so the recomputed
        blocks are byte-identical to the lost ones) and rewrite the
        missing maps through the manager's write handle. ``map_ids`` None
        means the loss scope is unknown: recompute every map once."""
        wanted = None if map_ids is None else set(map_ids)
        already = getattr(handle, "_recomputed_maps", set())
        # a second loss report for maps we already rewrote means the
        # rewrite itself is unreadable — recomputing again cannot
        # converge, so let the MapOutputLostError surface on the next try
        if wanted is None:
            if getattr(handle, "_recomputed_all", False):
                return
            handle._recomputed_all = True
        elif wanted <= already:
            return
        from spark_rapids_tpu.runtime.retry import retry_block
        total_maps = len(handle.map_outputs)
        rewritten = 0
        for i, batch in enumerate(self.children[0].execute()):
            if i >= total_maps:
                break
            if wanted is not None:
                if wanted <= already:
                    break  # everything lost is rewritten: stop re-running
                if i not in wanted:
                    continue
            parts = split_by_partition(batch, partitioner)
            # host-memory pressure retries like the original write path
            retry_block(lambda i=i, p=parts: handle.rewrite_map(i, p))
            already = already | {i}
            rewritten += 1
        handle._recomputed_maps = already
        RECOVERY.bump("recomputed_maps", rewritten)
        self.add_metric("recomputedMapOutputs", rewritten)

    @staticmethod
    def _upload(tables: List[HostTable]) -> DeviceTable:
        from spark_rapids_tpu.runtime.retry import retry_block
        host = tables[0] if len(tables) == 1 else HostTable.concat(tables)
        # shuffle re-landings are device landings like scans: a budget
        # squeeze (arbiter RetryOOM) spills and replays here instead
        # of failing the query with an unhandled OOM
        return retry_block(lambda: DeviceTable.from_host(host))
