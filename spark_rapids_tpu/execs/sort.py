"""TPU sort (reference: GpuSortExec.scala / SortUtils.scala — SURVEY.md
§2.3; out-of-core spill variant comes with the memory runtime).

Multi-operand ``lax.sort`` does the lexicographic work directly on the MXU-
adjacent sort network. Each sort key is transformed into ascending operands:
descending order negates/complements the key; nulls-first/last becomes an
explicit leading flag operand; padding rows always sort last. A row-index
payload yields the permutation used to gather every output column."""

from __future__ import annotations

from typing import List, Sequence

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceTable
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.ops.expr import (
    DevVal,
    EvalCtx,
    NodePrep,
    PrepCtx,
    _prep_trace_key,
    _walk_eval,
    _walk_prep,
)
from spark_rapids_tpu.plan.nodes import SortOrder


def _directional(data, validity, ascending: bool, nulls_first: bool, capacity: int):
    """Make (null_flag, *key_operands) for an ascending lax.sort realizing
    the requested direction and null placement. Keys decompose into
    native <=32-bit order-isomorphic operands (ops/ordering.py) — i64/f64
    sort ~1.6x faster than emulated 64-bit compares, and f64 gets -0.0/NaN
    canonicalization (Spark NormalizeFloatingNumbers / NaN-last) for free."""
    from spark_rapids_tpu.ops.ordering import (
        comparable_operands,
        descending_operands,
        zero_invalid,
    )
    ops = comparable_operands(zero_invalid(data, validity))
    if not ascending:
        ops = descending_operands(ops)
    # null flag sorts ahead of the key: 0 sorts first, so invalid rows get 0
    # when nulls_first else 1.
    nf = jnp.where(validity, 1 if nulls_first else 0, 0 if nulls_first else 1)
    return [nf] + ops


class TpuSortExec(TpuExec):
    def __init__(self, child: TpuExec, orders: Sequence[SortOrder]):
        super().__init__()
        self.children = (child,)
        self.orders = list(orders)

    def output_schema(self):
        return self.children[0].output_schema()

    #: set by the overrides conversion from
    #: spark.rapids.sql.sort.outOfCoreThresholdBytes
    ooc_threshold_bytes = 1 << 30

    def execute(self):
        """Multi-batch inputs accumulate as SPILLABLE batches (bounded HBM
        while upstream streams; reference: GpuSortExec pending pool,
        GpuSortExec.scala:281). Small totals concat on device and sort
        once; totals above the out-of-core threshold take the spilled-run
        range merge (``sorted_run_stream``) so peak HBM stays one output
        range — the GpuSortExec.scala:281 merge-of-spilled-runs analog."""
        from spark_rapids_tpu.runtime.retry import retry_block
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch

        it = self.children[0].execute_masked()
        first = next(it, None)
        if first is None:
            return
        second = next(it, None)
        if second is None:
            yield retry_block(lambda: self._sort(first))
            return

        from itertools import chain
        from spark_rapids_tpu.columnar.table import concat_device
        from spark_rapids_tpu.runtime.memory import MEMORY
        catalog = BufferCatalog.get()
        pending = []
        total = 0
        # spill-aware threshold: a multi-batch sort past the device
        # budget's chunk share goes out of core even when the conf
        # threshold is higher — the spilled-run range merge keeps peak
        # HBM at one output range
        threshold = min(self.ooc_threshold_bytes,
                        MEMORY.scan_chunk_bytes())
        all_batches = chain([first, second], it)
        try:
            for batch in all_batches:
                pending.append(SpillableBatch(batch, catalog))
                total += batch.device_nbytes()
                self.add_metric("sortInputBatches", 1)
                if total > threshold:
                    # switch to out-of-core: drain the rest as host runs
                    batches = [sb for sb in pending]
                    pending = []
                    self.add_metric("sortOutOfCore", 1)
                    yield from self._ooc_stream(batches, all_batches,
                                                catalog)
                    return

            def merge_and_sort():
                tables = [sb.get() for sb in pending]
                return self._sort(concat_device(tables))

            yield retry_block(merge_and_sort)
        finally:
            for sb in pending:
                sb.release()

    @classmethod
    def for_orders(cls, orders):
        """Standalone sorter over ``orders`` (used by range merging and
        the window streaming path — no child exec)."""
        ex = cls.__new__(cls)
        ex.orders = list(orders)
        ex.metrics = {}
        return ex

    def _ooc_stream(self, spillables, rest_iter, catalog):
        from spark_rapids_tpu.runtime.retry import retry_block
        runs = []
        try:
            while spillables:
                sb = spillables.pop()
                try:
                    with sb.pinned_batch() as dt:
                        runs.append(retry_block(
                            lambda d=dt: self._sort(d)).to_host())
                finally:
                    sb.release()
            for batch in rest_iter:
                runs.append(retry_block(
                    lambda b=batch: self._sort(b)).to_host())
                self.add_metric("sortInputBatches", 1)
        finally:
            for sb in spillables:  # error mid-loop: drop the rest
                sb.release()
        yield from sorted_run_stream(runs, self.orders)

    def _pos_dep(self) -> bool:
        from spark_rapids_tpu.ops.expr import has_position_dependent
        return any(has_position_dependent(o.expr) for o in self.orders)

    def _sort(self, table: DeviceTable) -> DeviceTable:
        from spark_rapids_tpu.ops.expr import shared_traces
        if table.live is not None and self._pos_dep():
            table = table.compacted()  # slot ids must match prefix form
        self._traces = shared_traces(
            ("sort",
             tuple((o.expr.key(), o.ascending, o.resolved_nulls_first())
                   for o in self.orders),
             table.schema_key()[0]))
        pctx = PrepCtx(table)
        key_preps: List[List[NodePrep]] = []
        for o in self.orders:
            preps: List[NodePrep] = []
            _walk_prep(o.expr, pctx, preps)
            key_preps.append(preps)
        from spark_rapids_tpu.dispatch import prep_aux
        cols = tuple(DevVal(c.data, c.validity) for c in table.columns)
        aux = prep_aux(pctx)
        capacity = table.capacity

        has_mask = table.live is not None
        tkey = (capacity, has_mask,
                tuple(_prep_trace_key(p) for p in key_preps))
        fn = self._traces.get(tkey)
        if fn is None:
            orders = self.orders

            def run(cols, aux, nrows, live_in):
                from spark_rapids_tpu.ops.ordering import lex_sort
                # masked input: dead rows park last via the liveness
                # operand, so the sort doubles as the deferred compaction
                if live_in is not None:
                    live = live_in
                else:
                    live = jnp.arange(capacity, dtype=jnp.int32) < nrows
                operands = [(~live).astype(jnp.int32)]  # padding last
                for o, preps in zip(orders, key_preps):
                    ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in)
                    ctx._prep_iter = iter(preps)
                    kv = _walk_eval(o.expr, ctx)
                    operands.extend(_directional(kv.data, kv.validity, o.ascending,
                                                 o.resolved_nulls_first(), capacity))
                payload = jnp.arange(capacity, dtype=jnp.int32)
                res = lex_sort(operands, payload)
                perm = res[-1]
                return [(d[perm], v[perm]) for d, v in cols]

            fn = tpu_jit(run, name="sort_run")
            self._traces[tkey] = fn

        outs = fn(cols, aux, table.nrows_dev, table.live)
        new_cols = [c.with_arrays(d, v) for c, (d, v) in zip(table.columns, outs)]
        return DeviceTable(table.names, new_cols, table.nrows_dev, capacity)

    def _topk(self, table: DeviceTable, k: int) -> DeviceTable:
        """Top-k rows by sort order at a k-sized capacity: sort ONLY the
        key operands + a row-index payload, then gather the k winning rows
        of every column. The reference's per-batch top-k sorts then slices
        (GpuTakeOrderedAndProjectExec), but on TPU a full-width gather at
        input capacity costs ~10-30ms per 64-bit column (PERF.md) — this
        does O(k) gather work instead and emits a small-capacity batch,
        which also shrinks every downstream kernel."""
        from spark_rapids_tpu.columnar import bucket_for
        from spark_rapids_tpu.ops.expr import shared_traces
        if table.live is not None and self._pos_dep():
            table = table.compacted()  # slot ids must match prefix form
        capacity = table.capacity
        kcap = min(bucket_for(max(k, 1)), capacity)
        self._traces = shared_traces(
            ("topk", kcap,
             tuple((o.expr.key(), o.ascending, o.resolved_nulls_first())
                   for o in self.orders),
             table.schema_key()[0]))
        pctx = PrepCtx(table)
        key_preps: List[List[NodePrep]] = []
        for o in self.orders:
            preps: List[NodePrep] = []
            _walk_prep(o.expr, pctx, preps)
            key_preps.append(preps)
        from spark_rapids_tpu.dispatch import prep_aux
        cols = tuple(DevVal(c.data, c.validity) for c in table.columns)
        aux = prep_aux(pctx)
        has_mask = table.live is not None
        tkey = (capacity, has_mask, k,
                tuple(_prep_trace_key(p) for p in key_preps))
        fn = self._traces.get(tkey)
        if fn is None:
            orders = self.orders

            def run(cols, aux, nrows, live_in):
                if live_in is not None:
                    live = live_in
                    n_live = jnp.sum(live.astype(jnp.int32))
                else:
                    live = jnp.arange(capacity, dtype=jnp.int32) < nrows
                    n_live = nrows
                operands = [(~live).astype(jnp.int32)]  # dead rows last
                for o, preps in zip(orders, key_preps):
                    ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in)
                    ctx._prep_iter = iter(preps)
                    kv = _walk_eval(o.expr, ctx)
                    operands.extend(_directional(
                        kv.data, kv.validity, o.ascending,
                        o.resolved_nulls_first(), capacity))
                from spark_rapids_tpu.ops.ordering import lex_sort
                payload = jnp.arange(capacity, dtype=jnp.int32)
                res = lex_sort(operands, payload)
                idx = res[-1][:kcap]
                n_out = jnp.minimum(n_live, jnp.asarray(k, jnp.int32))
                out_live = jnp.arange(kcap, dtype=jnp.int32) < n_out
                outs = []
                for d, v in cols:
                    outs.append((d[idx], v[idx] & out_live))
                return outs, n_out

            fn = tpu_jit(run, name="sort_topk")
            self._traces[tkey] = fn
        outs, n_out = fn(cols, aux, table.nrows_dev, table.live)
        new_cols = [c.with_arrays(d, v)
                    for c, (d, v) in zip(table.columns, outs)]
        return DeviceTable(table.names, new_cols, n_out, kcap)

    def describe(self):
        return f"TpuSort[{len(self.orders)} keys]"


class TpuTakeOrderedAndProjectExec(TpuExec):
    """ORDER BY + LIMIT n (+ projection): per-batch device top-k via the
    sort kernel, keep only k rows per batch, then one final k*batches
    merge-sort — the reference's GpuTakeOrderedAndProjectExec shape
    (never materializes the full sorted input)."""

    def __init__(self, child: TpuExec, orders, limit: int,
                 project=None, project_names=None):
        super().__init__()
        self.children = (child,)
        self.orders = list(orders)
        self.limit = int(limit)
        self.project = list(project) if project is not None else None
        self.project_names = list(project_names) if project_names else None
        self._sorter = TpuSortExec(child, orders)  # reuse the sort kernel

    def output_schema(self):
        if self.project is None:
            return self.children[0].output_schema()
        return [(n, e.data_type)
                for n, e in zip(self.project_names, self.project)]

    def describe(self):
        return f"TpuTakeOrderedAndProject[limit={self.limit}]"

    def execute(self):
        from spark_rapids_tpu.columnar import bucket_for
        from spark_rapids_tpu.columnar.table import concat_device
        from spark_rapids_tpu.ops.expr import compile_project
        from spark_rapids_tpu.runtime.retry import retry_block

        k = self.limit
        tops = []
        for batch in self.children[0].execute_masked():
            tops.append(retry_block(lambda b=batch: self._sorter._topk(b, k)))

        if not tops:
            return
        merged = tops[0] if len(tops) == 1 else retry_block(
            lambda: concat_device(tops))
        if len(tops) == 1:
            final = merged  # a single _topk batch is already sorted
        else:
            final = retry_block(lambda: self._sorter._sort(merged))
        from spark_rapids_tpu.dispatch import device_scalar
        nrows = jnp.minimum(final.nrows_dev, device_scalar(k))
        out = DeviceTable(final.names, final.columns, nrows, final.capacity)
        if self.project is not None:
            cols = compile_project(self.project, out)
            out = DeviceTable(self.project_names, cols, out.nrows_dev,
                              out.capacity)
        yield out


def sorted_run_stream(runs, orders, target_rows: int = None):
    """Merge HOST-resident sorted runs into a stream of globally ordered
    DEVICE batches without materializing the whole table on device — the
    reference's merge of spilled sorted runs (GpuSortExec.scala:281),
    re-shaped for the TPU: instead of a pointer-chasing k-way merge, the
    FIRST sort key's value space splits into quantile ranges; each range
    gathers its slice from every run (host slicing is O(log n) per run —
    runs are sorted), uploads, and one device sort orders the range. Peak
    HBM = one range. Rows with EQUAL first keys always land in the same
    output batch (bounds are cut points), which also makes the stream
    safe for RANGE-frame window peers (execs/window.py streaming).

    ``runs``: list of HostTable, each fully sorted by ``orders``."""
    import numpy as np
    from spark_rapids_tpu.columnar import DeviceTable, HostTable
    from spark_rapids_tpu.runtime.retry import retry_block

    o0 = orders[0]
    asc = o0.ascending
    nulls_first = o0.resolved_nulls_first()

    # first-key host values + per-run null spans (contiguous by sortedness)
    keys = []
    spans = []
    for run in runs:
        kc = o0.expr.eval_cpu(run)
        n = run.num_rows
        nn = int(kc.validity.sum())
        if nulls_first:
            null_lo, null_hi, lo, hi = 0, n - nn, n - nn, n
        else:
            null_lo, null_hi, lo, hi = nn, n, 0, nn
        vals = kc.data[lo:hi]
        keys.append(vals if asc else vals[::-1])  # ascending view
        spans.append((null_lo, null_hi, lo, hi))

    total = sum(k.shape[0] for k in keys)
    if target_rows is None:
        target_rows = max((r.num_rows for r in runs), default=1)
    nparts = max(1, -(-total // max(target_rows, 1)))
    if total:
        allvals = np.sort(np.concatenate([np.asarray(k) for k in keys]))
        bounds = []
        for i in range(1, nparts):
            b = allvals[(total * i) // nparts]
            if not bounds or b != bounds[-1]:
                bounds.append(b)
    else:
        bounds = []

    def run_slices(part_idx, lo_b, hi_b):
        """HostTable slices of every run for value range [lo_b, hi_b)."""
        parts = []
        for run, k, (null_lo, null_hi, lo, hi) in zip(runs, keys, spans):
            a = 0 if lo_b is None else int(np.searchsorted(k, lo_b, "left"))
            b = k.shape[0] if hi_b is None else int(
                np.searchsorted(k, hi_b, "left"))
            if b <= a:
                continue
            if asc:
                parts.append(run.slice(lo + a, b - a))
            else:
                # ascending view was reversed: map back from the end
                parts.append(run.slice(hi - b, b - a))
        return parts

    ranges = [(bounds[i - 1] if i else None,
               bounds[i] if i < len(bounds) else None)
              for i in range(len(bounds) + 1)]
    if not asc:
        ranges = ranges[::-1]  # larger keys first in the output order

    def null_parts():
        out = []
        for run, (null_lo, null_hi, lo, hi) in zip(runs, spans):
            if null_hi > null_lo:
                out.append(run.slice(null_lo, null_hi - null_lo))
        return out

    emitted_sorter = _RangeSorter(orders)
    if nulls_first:
        np_parts = null_parts()
        if np_parts:
            yield retry_block(lambda p=np_parts: emitted_sorter(p))
    for lo_b, hi_b in ranges:
        parts = run_slices(0, lo_b, hi_b)
        if parts:
            yield retry_block(lambda p=parts: emitted_sorter(p))
    if not nulls_first:
        np_parts = null_parts()
        if np_parts:
            yield retry_block(lambda p=np_parts: emitted_sorter(p))


class _RangeSorter:
    """Upload + device-sort one range's host slices."""

    def __init__(self, orders):
        self._exec = TpuSortExec.for_orders(orders)

    def __call__(self, host_parts):
        from spark_rapids_tpu.columnar import DeviceTable, HostTable
        host = host_parts[0] if len(host_parts) == 1 else \
            HostTable.concat(host_parts)
        return self._exec._sort(DeviceTable.from_host(host))
