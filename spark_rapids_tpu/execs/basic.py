"""Basic TPU execs: scan, range, project, filter, limit, union, coalesce,
expand (reference: basicPhysicalOperators.scala, GpuCoalesceBatches.scala,
GpuExpandExec.scala — SURVEY.md §2.3)."""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceColumn, DeviceTable, HostTable, bucket_for
from spark_rapids_tpu.columnar.column import MIN_BUCKET
from spark_rapids_tpu.columnar.table import f64_bits_columns
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.ops.expr import (
    DevVal,
    EvalCtx,
    Expression,
    NodePrep,
    PrepCtx,
    _walk_eval,
    _walk_prep,
    _prep_trace_key,
    compile_project,
    output_name,
)


def _scan_sharding(exec_node: TpuExec):
    """(row sharding, cache token) this scan may land under — (None,
    None) when its tree was not converted mesh-aware. Sharded placement
    is BOUND AT CONVERSION: insert_mesh_relands stamps every scan with
    the mesh generation its re-land boundaries were planned against
    (``_mesh_scan_gen``), and an unstamped or stale-stamped scan lands
    single-device — a tree converted with the mesh off carries no
    boundaries, so feeding it physically sharded batches would let
    GSPMD repartition a sort, a join or a sorted-path aggregate as it
    likes when a concurrent session flips the process mesh mid-query,
    outside the contract execs/mesh.py states (exact types bit-identical
    to one chip; DOUBLE sums merged in (batch, shard, slice) order,
    judged against a float64 reference). The token
    keys cached device images to the mesh GENERATION, so a
    reconfiguration invalidates every cached placement. Read atomically
    (MeshRuntime.scan_placement) so a concurrent reconfiguration cannot
    pair an old mesh's sharding with the new generation token."""
    gen = getattr(exec_node, "_mesh_scan_gen", None)
    if gen is None:
        return None, None
    from spark_rapids_tpu.parallel.mesh import MESH
    sharding, token = MESH.scan_placement()
    if token != gen:
        return None, None
    return sharding, token


def _upload_sharded(exec_node: TpuExec, host: HostTable,
                    sharding) -> DeviceTable:
    """Land one scan batch — split per device over the mesh row sharding
    when mesh-native execution is on (one jax.device_put per staged
    column delivers every device exactly its row shard, no single-host
    concat) — and account the dispatched shards on both the exec and
    the mesh scope."""
    dt = DeviceTable.from_host(host, sharding=sharding)
    # count what from_host actually DID: nested-type and zero-column
    # batches bypass the staged split and land single-device (no
    # shard_spec), so they must not claim distributed placement. The
    # shard count comes from the sharding the batch LANDED under — a
    # concurrent reconfiguration between the scan's atomic placement
    # read and this point must not pair the old mesh's placement with
    # the new mesh's device count
    if dt.shard_spec is not None:
        from spark_rapids_tpu.parallel.mesh import MESH_SCOPE
        nshards = int(dt.shard_spec.mesh.devices.size)
        MESH_SCOPE.add("shardsDispatched", nshards)
        exec_node.add_metric("shardsDispatched", nshards)
    return dt


class TpuScanExec(TpuExec):
    """Uploads pre-built host batches (LocalScan analog).

    With ``device_cache`` the uploaded DeviceTable is memoized on the host
    table itself, so repeated queries over one in-memory table skip the
    H2D transfer entirely — the GpuInMemoryTableScanExec / DataFrame.cache
    analog (reference: InMemoryTableScanExec override, GpuOverrides.scala).
    Cached images are dropped on device OOM (columnar.table.
    evict_device_caches, wired into the retry framework)."""

    def __init__(self, batches: Sequence[HostTable], device_cache: bool = True):
        super().__init__()
        self.batches = list(batches)
        self.device_cache = device_cache

    def output_schema(self):
        return self.batches[0].schema()

    def execute(self):
        from spark_rapids_tpu.columnar.table import register_device_cache
        from spark_rapids_tpu.runtime.memory import scan_chunks
        from spark_rapids_tpu.runtime.retry import retry_block
        sharding, shard_token = _scan_sharding(self)
        for b in self.batches:
            # out-of-core scan: a batch whose estimated device bytes
            # exceed its budget share lands as bounded partitions
            # (runtime/memory.py scan_chunks); chunked landings bypass
            # the device cache — a multi-chunk image would pin the very
            # budget the chunking protects. Each landing is wrapped in
            # the OOM retry loop so a budget squeeze spills and
            # replays instead of failing the query at the scan.
            chunks = scan_chunks(b)
            if len(chunks) > 1 or not self.device_cache:
                if len(chunks) > 1:
                    self.add_metric("scanChunks", len(chunks))
                for ch in chunks:
                    yield retry_block(
                        lambda c=ch: _upload_sharded(self, c, sharding))
                continue
            entry = b._cache.get("device")
            # the cached image must match the CURRENT mesh layout — a
            # reconfigured (or newly enabled/disabled) mesh re-lands
            # the shards rather than serving a stale placement
            if entry is not None and entry[1] == shard_token:
                self.add_metric("scanCacheHit", 1)
                yield entry[0]
                continue
            dt = retry_block(
                lambda: _upload_sharded(self, b, sharding))
            b._cache["device"] = (dt, shard_token)
            register_device_cache(b)
            self.add_metric("scanCacheMiss", 1)
            yield dt

    def describe(self):
        return f"TpuScan[{len(self.batches)} batches]"


class TpuFileScanExec(TpuExec):
    """File scan on device: the scan node's reader (with its PERFILE /
    COALESCING / MULTITHREADED prefetch behavior) feeds decoded host batches
    that upload to HBM here (reference: GpuFileSourceScanExec +
    MultiFile*PartitionReader — decode output lands in device memory)."""

    def __init__(self, scan_node):
        super().__init__()
        self.scan_node = scan_node
        #: execution-scoped dynamic partition pruning filters — owned by
        #: THIS converted exec, never by the shared logical scan node
        #: (overrides/rules._maybe_install_dpp)
        self._dynamic_prunes: list = []

    def install_dynamic_pruning(self, part_col: str, provider) -> None:
        self._dynamic_prunes.append((part_col, provider))

    def output_schema(self):
        return self.scan_node.output_schema()

    def execute(self):
        import time
        from spark_rapids_tpu.runtime.memory import scan_chunks
        from spark_rapids_tpu.runtime.retry import retry_block
        sharding, _ = _scan_sharding(self)
        # set, not added: what this execution reads of the files' columns
        # (overrides/pruning.py narrows the node to what the plan reads)
        read = self.scan_node.read_width()
        self.metrics["scanColumnsRead"] = read
        self.metrics["scanColumnsPruned"] = max(
            0, self.scan_node.full_width() - read)
        for batch in self.scan_node.execute_cpu(
                dynamic_prunes=self._dynamic_prunes or None,
                metrics=self.metrics):
            # out-of-core scan: decoded batches over the budget share
            # land as bounded partitions (runtime/memory.py), each
            # upload OOM-retryable (budget squeezes spill and replay)
            chunks = scan_chunks(batch)
            if len(chunks) > 1:
                self.add_metric("scanChunks", len(chunks))
            for ch in chunks:
                # strings the reader encoded as it decoded (io/
                # arrow_convert.py); a chunk, a stitched or partition
                # column encodes at the upload
                self.add_metric("scanStringsPreEncoded", sum(
                    1 for c in ch.columns
                    if isinstance(c.dtype, T.StringType)
                    and "encode" in c._cache))
                # DOUBLE columns handed over as their 64-bit words, split
                # into the f32 pair by the assemble program
                self.add_metric("scanF64SplitOnDevice", f64_bits_columns(ch))
                t0 = time.perf_counter()
                # mesh-native: each decoded file/row-group batch lands
                # SPLIT across the mesh (execs/basic._upload_sharded)
                dt = retry_block(
                    lambda c=ch: _upload_sharded(self, c, sharding))
                self.add_metric("scanUploadTime",
                                time.perf_counter() - t0)
                self.add_metric("scanBatches", 1)
                self.add_metric("scanRows", ch.num_rows)
                yield dt

    def describe(self):
        return f"TpuFileScan[{self.scan_node.describe()}]"


class TpuRangeExec(TpuExec):
    """Device-side range generation (reference: GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int, batch_rows: int, name: str):
        super().__init__()
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows
        self.col_name = name

    def output_schema(self):
        return [(self.col_name, T.LONG)]

    def execute(self):
        total = max(0, -(-(self.end - self.start) // self.step))
        pos = 0
        while True:
            cnt = min(self.batch_rows, total - pos) if total else 0
            cap = bucket_for(max(cnt, 1))
            data = jnp.arange(cap, dtype=jnp.int64) * self.step + (self.start + pos * self.step)
            validity = jnp.arange(cap, dtype=jnp.int32) < cnt
            yield DeviceTable([self.col_name], [DeviceColumn(T.LONG, data, validity)], cnt, cap)
            pos += cnt
            if pos >= total:
                break


class TpuProjectExec(TpuExec):
    def __init__(self, child: TpuExec, exprs: Sequence[Expression], names: Sequence[str]):
        super().__init__()
        self.children = (child,)
        self.exprs = list(exprs)
        self.names = list(names)

    def output_schema(self):
        return [(n, e.data_type) for n, e in zip(self.names, self.exprs)]

    produces_masked = True

    def execute_masked(self):
        from spark_rapids_tpu.ops.expr import has_position_dependent
        from spark_rapids_tpu.runtime.retry import with_retry
        exprs, names = self.exprs, self.names
        # compact first when slot numbering matters (position-dependent
        # exprs) or when outputs are NESTED (array/struct/map columns have
        # no compaction scatter — they must only ever live in prefix
        # batches; TypeSig keeps nested out of mask-producing execs)
        must_compact = (
            any(has_position_dependent(e) for e in exprs)
            or any(isinstance(e.data_type,
                              (T.ArrayType, T.StructType, T.MapType))
                   for e in exprs))

        def run(dt):
            if must_compact:
                dt = dt.compacted()
            cols = compile_project(exprs, dt)
            return DeviceTable(names, cols, dt.nrows_dev, dt.capacity,
                               live=dt.live)

        for batch in self.children[0].execute_masked():
            yield from with_retry(batch, run)

    def describe(self):
        return f"TpuProject{self.names}"


class _FilterKernel:
    """Fused predicate evaluation + row compaction, one jit per
    (schema, predicate, bucket, prep structure).

    Compaction is O(n): scatter kept rows to cumsum positions (dropped rows
    scatter out of bounds with mode='drop') — no sort needed."""

    def __init__(self, condition: Expression):
        self.condition = condition

    def __call__(self, table: DeviceTable, emit_mask: bool = False):
        """``emit_mask=True`` returns a MASKED table (keep-mask + count, no
        compaction scatter — columnar/table.py DeviceTable.live); otherwise
        the classic compacting filter. Masked INPUT is consumed either
        way (the predicate ANDs with the input's liveness)."""
        from spark_rapids_tpu.ops.expr import has_position_dependent, shared_traces
        if table.live is not None and has_position_dependent(self.condition):
            table = table.compacted()  # slot ids must match prefix form
        pctx = PrepCtx(table)
        preps: List[NodePrep] = []
        _walk_prep(self.condition, pctx, preps)
        cols = tuple(DevVal(c.data, c.validity) for c in table.columns)
        from spark_rapids_tpu.dispatch import ANSI_MODE, prep_aux
        aux = prep_aux(pctx)
        capacity = table.capacity
        has_mask = table.live is not None
        ansi = ANSI_MODE.get()

        self._traces = shared_traces(
            ("filter", self.condition.key(), table.schema_key()[0]))
        tkey = (capacity, emit_mask, has_mask, ansi,
                _prep_trace_key(preps))
        got = self._traces.get(tkey)
        if got is None:
            cond = self.condition
            labels: List[str] = []

            def run(cols, aux, nrows, live_in):
                ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in,
                              ansi=ansi)
                ctx._prep_iter = iter(preps)
                pred = _walk_eval(cond, ctx)
                labels.clear()
                labels.extend(lbl for lbl, _ in ctx.ansi_errors)
                errs = tuple(f for _, f in ctx.ansi_errors)
                if live_in is not None:
                    live = live_in
                else:
                    live = jnp.arange(capacity, dtype=jnp.int32) < nrows
                keep = pred.data & pred.validity & live
                new_n = jnp.sum(keep.astype(jnp.int32))
                if emit_mask:
                    return keep, new_n, errs
                from spark_rapids_tpu.ops.scatter32 import compact_pairs
                outs, new_n = compact_pairs([d for d, _ in cols],
                                            [v for _, v in cols],
                                            keep, capacity)
                return outs, new_n, errs

            got = (tpu_jit(run, name="filter"), labels)
            self._traces[tkey] = got
        fn, labels = got

        from spark_rapids_tpu.ops.expr import deliver_ansi_flags
        if emit_mask:
            keep, new_n, errs = fn(cols, aux, table.nrows_dev, table.live)
            deliver_ansi_flags(labels, errs)
            return DeviceTable(table.names, table.columns, new_n, capacity,
                               live=keep)
        outs, new_n, errs = fn(cols, aux, table.nrows_dev, table.live)
        deliver_ansi_flags(labels, errs)
        new_cols = [c.with_arrays(d, v) for c, (d, v) in zip(table.columns, outs)]
        return DeviceTable(table.names, new_cols, new_n, capacity)


class TpuFilterExec(TpuExec):
    produces_masked = True

    def __init__(self, child: TpuExec, condition: Expression):
        super().__init__()
        self.children = (child,)
        self.condition = condition
        self._kernel = _FilterKernel(condition)

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_masked(self):
        from spark_rapids_tpu.execs.base import MASKED_ENABLED
        from spark_rapids_tpu.runtime.retry import with_retry
        emit = MASKED_ENABLED.get()
        for batch in self.children[0].execute_masked():
            yield from with_retry(
                batch, lambda b: self._kernel(b, emit_mask=emit))

    def describe(self):
        return f"TpuFilter[{self.condition!r}]"


class TpuLimitExec(TpuExec):
    def __init__(self, child: TpuExec, limit: int):
        super().__init__()
        self.children = (child,)
        self.limit = limit

    def output_schema(self):
        return self.children[0].output_schema()

    def execute(self):
        remaining = self.limit
        for batch in self.children[0].execute():
            if remaining <= 0:
                return
            n = batch.num_rows  # host sync at the limit boundary only
            take = min(n, remaining)
            if take == n:
                yield batch
            else:
                yield DeviceTable(batch.names, batch.columns, take, batch.capacity)
            remaining -= take
            if remaining <= 0:
                return

    def describe(self):
        return f"TpuLimit[{self.limit}]"


class TpuUnionExec(TpuExec):
    produces_masked = True

    def __init__(self, children: Sequence[TpuExec]):
        super().__init__()
        self.children = tuple(children)

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_masked(self):
        for c in self.children:
            yield from c.execute_masked()


class TpuExpandExec(TpuExec):
    """Each input batch produces one output batch per projection
    (reference: GpuExpandExec)."""

    def __init__(self, child: TpuExec, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str]):
        super().__init__()
        self.children = (child,)
        self.projections = [list(p) for p in projections]
        self.names = list(names)

    def output_schema(self):
        return [(n, e.data_type) for n, e in zip(self.names, self.projections[0])]

    produces_masked = True

    def execute_masked(self):
        from spark_rapids_tpu.ops.expr import has_position_dependent
        pos_dep = any(has_position_dependent(e)
                      for proj in self.projections for e in proj)
        for batch in self.children[0].execute_masked():
            if pos_dep:
                batch = batch.compacted()
            for proj in self.projections:
                cols = compile_project(proj, batch)
                yield DeviceTable(self.names, cols, batch.nrows_dev,
                                  batch.capacity, live=batch.live)


class TpuCoalesceExec(TpuExec):
    """Copy child batches together up to a target size — or into ONE batch
    when ``require_single`` (reference: GpuCoalesceBatches with
    TargetSize/RequireSingleBatch goals).

    What it does, in order:

    - ``columns`` (ordinals of the child's schema, or None for all): only
      these are buffered, copied and yielded — a consumer that has peeled
      its input chain (the aggregate, overrides/rules.py) names what its
      expressions read, so a wide cached table's unread columns (and
      their string dictionaries) never reach the copy. Selecting columns
      of a batch moves no data.
    - the flush rule, under TargetSize: a batch joins the pending ones
      unless the output they would then make — the capacity bucket of the
      capacity sum (what ``concat_device`` allocates) at their row width —
      would pass the target; then the pending ones flush first. So the
      goal is honoured in the bytes the output takes, padding included,
      and a run of equal batches stops at a bucket boundary (four 2^21-row
      batches make exactly 2^23 rows; a fifth would pay for 2^24). Pending
      bytes at or over the target flush at once; a lone batch over the
      target passes through.
    - a multi-batch flush is ONE device program, ``jit_coalesce``
      (columnar/table.concat_device): unmasked inputs are copied at their
      running device offsets, masked inputs fuse their deferred
      compaction into a scatter, row counts stay on the device (no host
      round trip); string columns whose dictionaries are equal keep
      them, unequal ones union on the host (``dictUnions``). The flush is
      the range ``srt.coalesce.flush`` and the query's
      ``phasesS.coalesceS``. Nothing is kept from query to query: the
      copy is redone per execution (PERF.md section 6, PR 27, has the
      readings behind that).

    On a mesh a lone buffered batch is handed on where it lies, sharded
    or not; batches that are concatenated are gathered to one device first
    (execs/mesh.py ``reland``): the copy builds one batch on one device.

    Passthroughs: a lone buffered batch; under TargetSize only,
    capacity-sharing masked VIEWS from a local shuffle split
    (columnar/table.is_shared_view), which stream un-coalesced because
    concatenating views of one table only multiplies capacity; and, on a
    join's probe side (``masked_pass``), every masked batch."""

    def __init__(self, child: TpuExec, target_bytes: int = 1 << 30,
                 require_single: bool = False,
                 columns: Optional[Sequence[int]] = None,
                 masked_pass: bool = False):
        super().__init__()
        self.children = (child,)
        self.target_bytes = target_bytes
        self.require_single = require_single
        self.columns = None if columns is None else tuple(columns)
        #: a join's probe side: a MASKED batch (a filter's output) streams
        #: on as it is. The join probes under the mask, and a copy of
        #: masked batches is sized by their capacities, not their live
        #: rows: it would pay the compaction scatter the mask defers and
        #: shrink nothing
        self.masked_pass = masked_pass and not require_single

    def output_schema(self):
        schema = self.children[0].output_schema()
        if self.columns is None:
            return schema
        return [schema[i] for i in self.columns]

    produces_masked = True

    def execute_masked(self):
        from spark_rapids_tpu.columnar.table import is_shared_view
        from spark_rapids_tpu.runtime.memory import MEMORY
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch

        catalog = BufferCatalog.get()
        # spill-aware TargetSize: the flush target never exceeds the
        # device budget's chunk share, so a coalesce below a streaming
        # consumer cannot re-concatenate chunked scans back into one
        # over-budget resident batch (RequireSingleBatch consumers —
        # join builds — still get their single batch; the join then
        # sub-partitions it spillably)
        target = self.target_bytes
        if not self.require_single:
            target = min(target, MEMORY.scan_chunk_bytes())
        pending: List[SpillableBatch] = []
        pending_bytes = pending_rows = 0
        try:
            for batch in self.children[0].execute_masked():
                if self.columns is not None:
                    batch = batch.select_columns(self.columns)
                if (batch.live is not None and self.masked_pass) or (
                        is_shared_view(batch) and not self.require_single):
                    # capacity-sharing views (a local split's per-partition
                    # masks over ONE table): concatenation would only
                    # multiply capacity and pay the very scatters masking
                    # defers — stream them. Ordinary masked batches
                    # (independent filter outputs) still coalesce.
                    if pending:
                        yield self._flush(pending)
                        pending, pending_bytes, pending_rows = [], 0, 0
                    self.add_metric("maskedPassthrough", 1)
                    yield batch
                    continue
                nbytes = batch.device_nbytes()
                rows = pending_rows + batch.capacity
                # device_nbytes counts capacity rows, so bytes / rows is
                # the row width and the output takes bucket * width
                if (pending and not self.require_single
                        and bucket_for(rows) * (pending_bytes + nbytes)
                        > target * rows):
                    yield self._flush(pending)
                    pending, pending_bytes, rows = [], 0, batch.capacity
                pending_bytes += nbytes
                pending_rows = rows
                # buffered batches are spillable while more input streams in
                # (reference: coalesce inputs are SpillableColumnarBatches)
                pending.append(SpillableBatch(batch, catalog))
                if not self.require_single and pending_bytes >= target:
                    yield self._flush(pending)
                    pending, pending_bytes, pending_rows = [], 0, 0
            if pending:
                yield self._flush(pending)
                pending = []
        finally:
            # abandonment (downstream limit stopped consuming) or an error
            # mid-flush must not leak catalog registrations/spill files
            for b in pending:
                b.release()

    def _flush(self, batches) -> DeviceTable:
        from spark_rapids_tpu.columnar.table import concat_device
        from spark_rapids_tpu.dispatch import phase_span
        from spark_rapids_tpu.runtime.retry import retry_block
        if len(batches) == 1:
            sb = batches[0]
            out = retry_block(sb.get)
            sb.release()
            return out
        self.add_metric("concatBatches", len(batches))
        stats = {"dictUnions": 0}
        # the copy builds ONE batch on one device: batches that lie
        # sharded over a mesh are gathered first (a lone batch, above,
        # is handed on where it lies)
        from spark_rapids_tpu.execs.mesh import reland
        try:
            with phase_span("coalesceS", "flush", "coalesce"):
                out = retry_block(lambda: concat_device(
                    [reland(self, b.get()) for b in batches],
                    coalesce=True, stats=stats))
        finally:
            for b in batches:
                b.release()
        if "coalescedColumns" not in self.metrics:
            # the width of a flush, not a sum over flushes
            self.add_metric("coalescedColumns", len(out.columns))
        self.add_metric("coalescedBytes", out.device_nbytes())
        self.add_metric("dictUnions", stats["dictUnions"])
        return out

    def describe(self):
        goal = "RequireSingleBatch" if self.require_single else f"TargetSize({self.target_bytes})"
        cols = "" if self.columns is None else f", columns={list(self.columns)}"
        return f"TpuCoalesce[{goal}{cols}]"


class TpuSampleExec(TpuExec):
    """Bernoulli sample (reference: GpuSampleExec). The device kernel uses
    the SAME counter-based RNG stream as the CPU path cannot (numpy
    Philox vs threefry differ), so the mask is drawn ON HOST per batch
    from the plan's seeded generator and shipped as a bitmask — tiny
    (1 byte/row) and bit-identical to the CPU oracle."""

    def __init__(self, child: TpuExec, fraction: float, seed: int):
        super().__init__()
        self.children = (child,)
        self.fraction = float(fraction)
        self.seed = int(seed)

    def output_schema(self):
        return self.children[0].output_schema()

    def describe(self):
        return f"TpuSample[{self.fraction}]"

    def execute(self):
        import numpy as _np
        from spark_rapids_tpu.runtime.retry import with_retry
        rng = _np.random.default_rng(self.seed)

        def make_run(keep_host):
            def run(dt):
                keep = jnp.asarray(keep_host)
                kernel = _compaction_kernel(dt.capacity, dt.schema_key()[0])
                outs, new_n = kernel(
                    tuple(c.data for c in dt.columns),
                    tuple(c.validity for c in dt.columns),
                    keep & dt.row_mask())
                cols = [c.with_arrays(d, v)
                        for c, (d, v) in zip(dt.columns, outs)]
                return DeviceTable(dt.names, cols, new_n, dt.capacity)
            return run

        for batch in self.children[0].execute():
            n = batch.num_rows  # host count drives the CPU-identical draw
            keep_host = np.zeros(batch.capacity, dtype=np.bool_)
            keep_host[:n] = rng.random(n) < self.fraction
            yield from with_retry(batch, make_run(keep_host),
                                  splittable=False)


_COMPACT_KERNELS = {}


def _compaction_kernel(capacity: int, schema_key):
    key = (capacity, schema_key)
    fn = _COMPACT_KERNELS.get(key)
    if fn is None:
        def run(datas, valids, keep):
            from spark_rapids_tpu.ops.scatter32 import compact_pairs
            return compact_pairs(datas, valids, keep, capacity)

        fn = tpu_jit(run, name="compact_rows")
        _COMPACT_KERNELS[key] = fn
    return fn
