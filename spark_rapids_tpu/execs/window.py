"""TPU window exec.

Reference: GpuWindowExec + its specialized iterators (SURVEY.md §2.3,
window/ — running window, batched bounded, unbounded-to-unbounded).

TPU-first design — everything is ONE jitted kernel over a sorted batch:
  1. lax.sort by (live, partition keys, order keys) with a row payload;
  2. partition boundaries -> segment starts via an associative max-scan;
     peer boundaries (order-key ties) -> peer-group ids;
  3. per function:
     row_number   = idx - seg_start + 1
     rank         = peer_start - seg_start + 1 (propagated over peers)
     dense_rank   = segmented cumsum of peer boundaries
     lag/lead     = shifted gather masked to the segment
     whole-part.  = jax.ops.segment_* + gather by segment id
     running      = segmented inclusive prefix (cumsum / scan-min / scan-max),
                    RANGE frames read the value at the LAST PEER row
     bounded rows = prefix-sum differences against clamped segment bounds
                    (sum/count/avg; bounded min/max falls back)
  4. results ride out positionally with the sorted child columns.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceColumn, DeviceTable
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.ops import aggregates as agg
from spark_rapids_tpu.ops.window import (
    NthValue,
    PercentRank,
    DenseRank,
    Lag,
    Lead,
    Rank,
    RowNumber,
    WindowExpression,
)
from spark_rapids_tpu.ops.expr import (
    DevVal,
    EvalCtx,
    NodePrep,
    PrepCtx,
    _prep_trace_key,
    _walk_eval,
    _walk_prep,
)

#: window aggregates with device support
DEVICE_WINDOW_AGGS = (agg.Sum, agg.Count, agg.Min, agg.Max, agg.Average)


def device_window_supported(w: WindowExpression,
                            variable_float_agg: bool = True,
                            rows_frame_max_bound: int = 1 << 16
                            ) -> Tuple[bool, str]:
    fn = w.function
    frame = w.spec.resolved_frame()
    if isinstance(fn, (RowNumber, Rank, DenseRank, PercentRank)):
        if not w.spec.orders:
            return False, "ranking window function requires an ORDER BY"
        return True, ""
    if isinstance(fn, NthValue):
        if fn.ignore_nulls:
            return False, "nth_value IGNORE NULLS is not supported on TPU"
        if frame != ("range", None, 0):
            return False, ("nth_value supports only the default running "
                           "frame on TPU")
        return True, ""
    if isinstance(fn, (Lag, Lead)):
        if fn.default is not None and isinstance(fn.data_type, T.StringType):
            return False, "lag/lead string default value is not supported on TPU"
        return True, ""
    if isinstance(fn, DEVICE_WINDOW_AGGS):
        kind, lo, hi = frame
        if kind == "range" and not (lo is None and (hi in (0, None))):
            return False, "only UNBOUNDED..CURRENT/UNBOUNDED range frames"
        if kind == "rows":
            # sparse-table / unroll widths are bounded by the frame's
            # FINITE endpoints; gate them so table levels can't exhaust HBM
            for bound in (lo, hi):
                if bound is not None and abs(bound) > rows_frame_max_bound:
                    return False, (
                        f"rows frame bound beyond {rows_frame_max_bound} "
                        "is not supported on TPU (spark.rapids.sql."
                        "window.rowsFrameMaxBound)")
            if (lo is not None and hi is not None and (hi - lo + 1) > 512
                    and isinstance(fn, (agg.Sum, agg.Average))
                    and isinstance(fn.data_type, (T.FloatType, T.DoubleType))
                    and not variable_float_agg):
                return False, ("wide float rows frame uses prefix-difference "
                               "sums (reduction-order variance); enable "
                               "spark.rapids.sql.variableFloatAgg.enabled")
        return True, ""
    return False, f"window function {type(fn).__name__} is not supported on TPU"


class _TableExec(TpuExec):
    """Fixed device tables as an exec (two-pass composition plumbing)."""

    def __init__(self, tables, schema):
        super().__init__()
        self.children = ()
        self._tables = list(tables)
        self._schema = list(schema)

    def output_schema(self):
        return self._schema

    def execute(self):
        yield from self._tables


class _ReplayExec(TpuExec):
    """Replays SpillableBatches, pinning each while downstream consumes
    it (the cached-batch source of the double-pass window)."""

    def __init__(self, spills, schema):
        super().__init__()
        self.children = ()
        self._spills = list(spills)
        self._schema = list(schema)

    def output_schema(self):
        return self._schema

    def execute(self):
        for sb in self._spills:
            with sb.pinned_batch() as dt:
                yield dt


def _slice_rows(table: DeviceTable, a: int, b: int) -> DeviceTable:
    """Rows [a, b) of a compacted flat-column table as a fresh
    bucket-capacity table (the bounded-window streaming emit/carry cut)."""
    from spark_rapids_tpu.columnar import bucket_for

    n = b - a
    cap = bucket_for(max(n, 1))

    def cut(arr):
        s = arr[a:b]
        if cap > n:
            pad = jnp.zeros((cap - n,) + s.shape[1:], dtype=s.dtype)
            s = jnp.concatenate([s, pad])
        return s

    cols = [c.with_arrays(cut(c.data), cut(c.validity))
            for c in table.columns]
    return DeviceTable(table.names, cols, n, cap)


def _seg_scan_max(flags_idx):
    return jax.lax.associative_scan(jnp.maximum, flags_idx)


def _segmented_cumsum(v, seg_start_idx):
    """Inclusive prefix sum restarting at each segment: cumsum(v) minus the
    exclusive total at the segment start."""
    c = jnp.cumsum(v, dtype=v.dtype)
    base = c[seg_start_idx] - v[seg_start_idx]
    return c - base


def _segmented_scan(op, v, new_seg):
    """Generic segmented inclusive scan via flagged associative combine."""
    def combine(a, b):
        af, av = a
        bf, bv = b
        return af | bf, jnp.where(bf, bv, op(av, bv))
    _, out = jax.lax.associative_scan(combine, (new_seg, v))
    return out


class TpuWindowExec(TpuExec):
    def __init__(self, child: TpuExec, window_cols: Sequence[Tuple[str, WindowExpression]],
                 per_batch: bool = False, use_split: bool = False,
                 stream_target_rows: int = 0):
        super().__init__()
        self.children = (child,)
        self.window_cols = list(window_cols)
        self.per_batch = per_batch
        self.use_split = use_split
        self.stream_target_rows = stream_target_rows

    def output_schema(self):
        return (self.children[0].output_schema()
                + [(n, w.data_type) for n, w in self.window_cols])

    def describe(self):
        return f"TpuWindow[{[n for n, _ in self.window_cols]}]"

    def execute(self):
        from spark_rapids_tpu.runtime.retry import retry_block
        if self.per_batch:
            # each incoming batch holds COMPLETE partition groups
            # (TpuKeyedBatchExec contract) and windows independently
            for batch in self.children[0].execute():
                yield retry_block(lambda b=batch: self._window(b))
            return
        it = self.children[0].execute()
        if self._streamable():
            # consume ONE batch at a time: each sorts on device and
            # demotes to a host run before the next loads (bounded HBM)
            yield from self._stream_running(it)
            return
        bctx = self._bounded_ctx()
        two_pass = bctx is None and self._two_pass_able()
        if bctx is not None or two_pass:
            first = next(it, None)
            if first is None:
                return
            second = next(it, None)
            if second is None:
                yield retry_block(lambda: self._window(first))
                return
            from itertools import chain
            rest = chain([first, second], it)
            if two_pass:
                yield from self._stream_two_pass(rest)
            else:
                yield from self._stream_bounded(rest, *bctx)
            return
        batches = list(it)
        if not batches:
            return
        if len(batches) == 1:
            yield retry_block(lambda: self._window(batches[0]))
            return
        # multi-batch fallback (whole-partition frames with rank mixes,
        # RANGE frames, lag/lead): device concat (bounded by HBM) + one
        # kernel — the pre-round-4 "requires a single batch" raise is
        # gone; running and finite-rows frames stream above
        from spark_rapids_tpu.columnar.table import concat_device
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch
        catalog = BufferCatalog.get()
        spills = [SpillableBatch(b, catalog) for b in batches]
        try:
            merged = retry_block(
                lambda: concat_device([sb.get() for sb in spills]))
        finally:
            for sb in spills:
                sb.release()
        yield retry_block(lambda: self._window(merged))

    # -- partition-less running-window streaming ----------------------------
    # (reference: GpuRunningWindowExec — per-batch evaluation with carried
    # scalar state; window/GpuWindowExec.scala)

    _RUNNING_FRAMES = (("range", None, 0), ("rows", None, 0))

    def _streamable(self) -> bool:
        """True when every window column is a partition-less running
        window over ONE shared ORDER BY — these stream with cross-batch
        carried state instead of materializing the whole input."""
        first_orders = None
        for _, w in self.window_cols:
            if w.spec.partition_exprs:
                return False
            if not w.spec.orders:
                return False
            okey = tuple((o.expr.key(), o.ascending,
                          o.resolved_nulls_first()) for o in w.spec.orders)
            if first_orders is None:
                first_orders = okey
            elif okey != first_orders:
                return False
            fn = w.function
            if isinstance(fn, (RowNumber, Rank, DenseRank)):
                continue
            if isinstance(fn, DEVICE_WINDOW_AGGS) and \
                    w.spec.resolved_frame() in self._RUNNING_FRAMES:
                continue
            return False
        return True

    def _stream_running(self, batches):
        """Sort the input ONCE into globally ordered range batches (host
        runs + quantile range merge — execs/sort.sorted_run_stream; its
        equal-first-key invariant keeps RANGE-frame peers within one
        batch), then evaluate each batch with carried running state."""
        from spark_rapids_tpu.execs.sort import TpuSortExec, sorted_run_stream
        from spark_rapids_tpu.runtime.retry import retry_block

        orders = self.window_cols[0][1].spec.orders
        sorter = TpuSortExec.for_orders(orders)
        runs = []
        for b in batches:
            runs.append(retry_block(lambda bb=b: sorter._sort(bb)).to_host())
        if not runs:
            return
        state = None
        self.add_metric("runningWindowBatches", len(runs))
        for dt in sorted_run_stream(
                runs, orders,
                target_rows=getattr(self, "stream_target_rows", 0) or None):
            out, state = retry_block(
                lambda d=dt, st=state: self._stream_batch(d, st))
            yield out

    # -- batched bounded-frame streaming ------------------------------------
    # (reference: window/GpuBatchedBoundedWindowExec.scala:1-255 — batches
    # stream with a small carried context instead of materializing the
    # whole input; the TPU shape: globally sort into spill-backed runs,
    # then window each run EXTENDED by `lookback` rows of kept context
    # before it, withholding the last `lookahead` rows until the next run
    # supplies their forward frame.)

    def _bounded_ctx(self, child_schema=None):
        """(lookback, lookahead) when every window column is a device agg
        over a FINITE rows frame sharing one (partition, order) and all
        child columns are flat; None otherwise (-> other paths)."""
        from spark_rapids_tpu import types as T

        if child_schema is None:
            child_schema = self.children[0].output_schema()
        for _, dt in child_schema:
            if isinstance(dt, (T.ArrayType, T.StructType, T.MapType)):
                return None  # row-slicing nested buffers is not supported
        shared = None
        lookback = lookahead = 0
        for _, w in self.window_cols:
            if not isinstance(w.function, DEVICE_WINDOW_AGGS):
                return None
            kind, lo, hi = w.spec.resolved_frame()
            if kind != "rows" or lo is None or hi is None:
                return None
            if not w.spec.partition_exprs and not w.spec.orders:
                return None  # nothing to sort runs by -> concat fallback
            skey = (tuple(e.key() for e in w.spec.partition_exprs),
                    tuple((o.expr.key(), o.ascending,
                           o.resolved_nulls_first()) for o in w.spec.orders))
            if shared is None:
                shared = skey
            elif skey != shared:
                return None
            lookback = max(lookback, -min(lo, 0))
            lookahead = max(lookahead, max(hi, 0))
        if shared is None:
            return None
        return lookback, lookahead

    # -- cached double-pass: whole-partition aggregate windows ---------------
    # (reference: window/GpuCachedDoublePassWindowExec.scala — one pass
    # computes per-partition results while batches cache spillably, a
    # second pass stitches results onto every cached batch. TPU shape:
    # COMPOSE the existing streaming aggregate (pass 1) with a hash join
    # back by partition key (pass 2) — no bespoke caching machinery.)

    def _two_pass_able(self) -> bool:
        """True when every window column is a device agg over the whole
        partition (UNBOUNDED..UNBOUNDED) sharing one non-empty
        partition_by, over flat child columns."""
        from spark_rapids_tpu import types as T

        for _, dt in self.children[0].output_schema():
            if isinstance(dt, (T.ArrayType, T.StructType, T.MapType)):
                return False
        shared = None
        for _, w in self.window_cols:
            if not isinstance(w.function, DEVICE_WINDOW_AGGS):
                return False
            kind, lo, hi = w.spec.resolved_frame()
            if not (lo is None and hi is None):
                return False
            if not w.spec.partition_exprs:
                return False
            skey = tuple(e.key() for e in w.spec.partition_exprs)
            if shared is None:
                shared = skey
            elif skey != shared:
                return False
        return shared is not None

    @staticmethod
    def _null_sentinel(dt):
        from spark_rapids_tpu.ops.expr import Literal
        if isinstance(dt, T.StringType):
            return Literal("", dt)
        if isinstance(dt, T.BooleanType):
            return Literal(False, dt)
        if isinstance(dt, (T.FloatType, T.DoubleType)):
            return Literal(0.0, dt)
        return Literal(0, dt)

    @classmethod
    def _null_safe_keys(cls, exprs):
        """(coalesce(k, sentinel), isnull(k)) pairs — the join kernel has
        Spark null!=null key semantics, but window partitions group nulls
        together; the flag key restores null-safe matching."""
        from spark_rapids_tpu.ops.conditional import Coalesce
        from spark_rapids_tpu.ops.predicates import IsNull
        keys = []
        for k in exprs:
            keys.append(Coalesce(k, cls._null_sentinel(k.data_type)))
            keys.append(IsNull(k))
        return keys

    def _stream_two_pass(self, batches):
        from spark_rapids_tpu.columnar.table import concat_device
        from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
        from spark_rapids_tpu.execs.join import TpuJoinExec
        from spark_rapids_tpu.ops.expr import BoundReference
        from spark_rapids_tpu.runtime.retry import retry_block
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch

        catalog = BufferCatalog.get()
        child_schema = self.children[0].output_schema()
        grouping = list(self.window_cols[0][1].spec.partition_exprs)
        gnames = [f"__wp{i}" for i in range(len(grouping))]
        wnames = [n for n, _ in self.window_cols]
        agg_specs = [(f"__wa{i}", w.function)
                     for i, (_, w) in enumerate(self.window_cols)]

        spills = [SpillableBatch(b, catalog) for b in batches]
        try:
            # pass 1: streaming partial/merge aggregate over the cached
            # batches (bounded HBM — one pinned batch at a time)
            agg_exec = TpuHashAggregateExec(
                _ReplayExec(spills, child_schema), grouping, agg_specs,
                gnames, use_split=self.use_split)
            agg_batches = list(agg_exec.execute())
            self.add_metric("twoPassPartitions", len(agg_batches))
            agg_table = (agg_batches[0] if len(agg_batches) == 1 else
                         retry_block(lambda: concat_device(agg_batches)))
            agg_schema = agg_exec.output_schema()
            right_refs = [BoundReference(i, dt, name_hint=n)
                          for i, (n, dt) in enumerate(agg_schema)]

            # pass 2: ONE probe-streaming join stitches every cached
            # batch to its partition's results by null-safe key, then the
            # key duplicates drop
            join = TpuJoinExec(
                _ReplayExec(spills, child_schema),
                _TableExec([agg_table], agg_schema),
                "inner",
                self._null_safe_keys(grouping),
                self._null_safe_keys(right_refs[:len(grouping)]),
                None, child_schema, agg_schema)
            keep_child = len(child_schema)
            names = [n for n, _ in child_schema] + wnames
            for out in join.execute():
                cols = (list(out.columns[:keep_child])
                        + list(out.columns[keep_child + len(grouping):]))
                yield DeviceTable(names, cols, out.nrows_dev,
                                  out.capacity, live=out.live)
        finally:
            for sb in spills:
                sb.release()

    def _stream_bounded(self, batches, lookback: int, lookahead: int):
        """Sort ONCE into host runs, stream globally ordered ranges, and
        window each range over [kept context ++ range], emitting only the
        rows whose frame is complete: a row emits when `lookahead` rows
        exist after it; `lookback` already-emitted rows stay as context.
        Peak HBM = one range + (lookback+lookahead) rows."""
        from spark_rapids_tpu.columnar.table import concat_device
        from spark_rapids_tpu.execs.sort import TpuSortExec, sorted_run_stream
        from spark_rapids_tpu.plan.nodes import SortOrder
        from spark_rapids_tpu.runtime.retry import retry_block

        spec = self.window_cols[0][1].spec
        all_orders = ([SortOrder(e, True) for e in spec.partition_exprs]
                      + list(spec.orders))
        sorter = TpuSortExec.for_orders(all_orders)
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch
        catalog = BufferCatalog.get()
        # queued inputs stay SPILLABLE while each sorts (the sort exec's
        # _ooc_stream pattern): an OOM mid-sort demotes a queued batch
        spillables = [SpillableBatch(b, catalog) for b in batches]
        runs = []
        try:
            while spillables:
                sb = spillables.pop(0)
                try:
                    with sb.pinned_batch() as dt:
                        runs.append(retry_block(
                            lambda d=dt: sorter._sort(d)).to_host())
                finally:
                    sb.release()
        finally:
            for sb in spillables:
                sb.release()
        if not runs:
            return
        keep = lookback + lookahead
        carry_sb = None    # last `keep`+ rows (SPILLABLE context — an OOM
        # mid-stream can demote it and replay)
        c_n = 0
        unemitted = 0      # trailing carry rows still awaiting lookahead
        try:
            for dt in sorted_run_stream(
                    runs, all_orders,
                    target_rows=self.stream_target_rows or None):
                self.add_metric("boundedWindowBatches", 1)
                b_n = dt.num_rows
                if carry_sb is not None:
                    ext = retry_block(lambda d=dt: concat_device(
                        [carry_sb.get(), d]))
                    ext = DeviceTable(ext.names, ext.columns, c_n + b_n,
                                      ext.capacity)
                else:
                    ext = dt
                ext_n = c_n + b_n
                emit_start = c_n - unemitted
                emit_end = max(ext_n - lookahead, emit_start)
                if emit_end > emit_start:
                    out = retry_block(lambda e=ext: self._window(e))
                    yield _slice_rows(out, emit_start, emit_end)
                unemitted = ext_n - emit_end
                cstart = max(0, ext_n - max(keep, unemitted))
                new_carry = retry_block(
                    lambda e=ext, a=cstart, b=ext_n: _slice_rows(e, a, b))
                if carry_sb is not None:
                    carry_sb.release()
                carry_sb = SpillableBatch(new_carry, catalog)
                c_n = ext_n - cstart
            if unemitted:
                # final rows: no further input, frames clamp at the end
                out = retry_block(
                    lambda: self._window(carry_sb.get()))
                yield _slice_rows(out, c_n - unemitted, c_n)
        finally:
            if carry_sb is not None:
                carry_sb.release()

    def _stream_batch(self, table: DeviceTable, state):
        """One sorted batch through the running-window kernel with carried
        state (tuple of device scalars; None = initial)."""
        from spark_rapids_tpu.dispatch import prep_aux
        from spark_rapids_tpu.ops.expr import shared_traces

        pctx = PrepCtx(table)
        specs = []
        for _, w in self.window_cols:
            op = [self._prep_tree(o.expr, pctx) for o in w.spec.orders]
            vp = self._prep_value(w, pctx)
            specs.append((op, vp))
        cols = tuple(DevVal(c.data, c.validity) for c in table.columns)
        aux = prep_aux(pctx)
        capacity = table.capacity

        self._traces = shared_traces(
            ("runwin", tuple(w.key() for _, w in self.window_cols),
             table.schema_key()[0]))
        tkey = ("stream", capacity, tuple(
            (tuple(_prep_trace_key(p) for p in op),
             tuple(_prep_trace_key(p) for p in vp) if vp else None)
            for op, vp in specs))
        fn = self._traces.get(tkey)
        if fn is None:
            fn = tpu_jit(self._build_stream_kernel(capacity, specs),
                         name="window_stream")
            self._traces[tkey] = fn
        if state is None:
            state = self._initial_state()
        outs, new_state = fn(cols, aux, table.nrows_dev, state)
        out_cols = list(table.columns)
        names = list(table.names)
        for (name, w), (d, v) in zip(self.window_cols, outs):
            out_cols.append(DeviceColumn(w.data_type, d, v))
            names.append(name)
        return (DeviceTable(names, out_cols, table.nrows_dev, capacity),
                new_state)

    def _initial_state(self):
        parts = []
        for _, w in self.window_cols:
            fn = w.function
            if isinstance(fn, (RowNumber, Rank)):
                parts.append((jnp.asarray(0, jnp.int64),))
            elif isinstance(fn, DenseRank):
                parts.append((jnp.asarray(0, jnp.int64),))
            elif isinstance(fn, agg.Count):
                parts.append((jnp.asarray(0, jnp.int64),))
            elif isinstance(fn, (agg.Sum, agg.Average)):
                is_long = (isinstance(fn, agg.Sum)
                           and isinstance(fn.data_type, T.LongType))
                parts.append((jnp.asarray(0, jnp.int64) if is_long
                              else jnp.asarray(0.0, jnp.float64),
                              jnp.asarray(0, jnp.int64)))
            elif isinstance(fn, (agg.Min, agg.Max)):
                dt = fn.children[0].data_type.np_dtype
                ident = self._ident(jnp.dtype(dt), isinstance(fn, agg.Min))
                parts.append((ident, jnp.asarray(0, jnp.int64)))
        return tuple(parts)

    def _build_stream_kernel(self, capacity: int, specs):
        window_cols = self.window_cols

        def kernel(cols, aux, nrows, state):
            live = jnp.arange(capacity, dtype=jnp.int32) < nrows

            def eval_tree(e, preps):
                ctx = EvalCtx(cols, aux, nrows, capacity)
                ctx._prep_iter = iter(preps)
                return _walk_eval(e, ctx)

            # shared ORDER key peer structure (all specs share orders)
            op0 = specs[0][0]
            orders0 = window_cols[0][1].spec.orders
            from spark_rapids_tpu.ops.ordering import comparable_operands
            peer_ops = []
            for o, preps in zip(orders0, op0):
                kv = eval_tree(o.expr, preps)
                # canonical operands: NaNs are peers, -0.0 == 0.0 (the
                # batch kernel's _peer_eq_break invariant)
                from spark_rapids_tpu.ops.ordering import zero_invalid
                peer_ops.append((~kv.validity).astype(jnp.int32))
                peer_ops.extend(comparable_operands(
                    zero_invalid(kv.data, kv.validity)))
            first = jnp.arange(capacity) == 0
            new_peer = first
            for o in peer_ops:
                new_peer = new_peer | (o != jnp.roll(o, 1))
            new_peer = new_peer & live
            peer_id = jnp.cumsum(new_peer.astype(jnp.int32)) - 1
            peer_id = jnp.where(live, peer_id, capacity - 1)
            rows_before = jnp.cumsum(live.astype(jnp.int64)) - 1  # 0-based
            batch_rows = jnp.sum(live.astype(jnp.int64))
            peer_start = _seg_scan_max(
                jnp.where(new_peer, jnp.arange(capacity, dtype=jnp.int32),
                          0))

            outs = []
            new_state = []
            for ((op, vp), (name, w), st) in zip(specs, window_cols, state):
                fn = w.function
                if isinstance(fn, RowNumber):
                    (prev_rows,) = st
                    d = (prev_rows + rows_before + 1).astype(jnp.int64)
                    outs.append((jnp.where(live, d, 0), live))
                    new_state.append((prev_rows + batch_rows,))
                elif isinstance(fn, Rank):
                    (prev_rows,) = st
                    start_rows = rows_before[peer_start]
                    d = (prev_rows + start_rows + 1).astype(jnp.int64)
                    outs.append((jnp.where(live, d, 0), live))
                    new_state.append((prev_rows + batch_rows,))
                elif isinstance(fn, DenseRank):
                    (prev_dense,) = st
                    local = jnp.cumsum(new_peer.astype(jnp.int64))
                    d = prev_dense + local
                    outs.append((jnp.where(live, d, 0), live))
                    new_state.append((prev_dense + local[capacity - 1]
                                      if capacity else prev_dense,))
                else:
                    outs_st = self._stream_agg(
                        fn, vp, eval_tree, w, live, peer_id, capacity, st)
                    outs.append(outs_st[0])
                    new_state.append(outs_st[1])
            return outs, tuple(new_state)

        return kernel

    def _stream_agg(self, fn, vp, eval_tree, w, live, peer_id, capacity,
                    st):
        """Running aggregate over one sorted batch with carry. RANGE
        frames read the running value at the END of the row's peer group
        (per-peer totals + prefix over peers); ROWS frames are plain
        prefixes."""
        frame = w.spec.resolved_frame()
        rows_frame = frame[0] == "rows"
        v = eval_tree(fn.children[0], vp[0]) if fn.children else None
        if isinstance(fn, agg.Count):
            (prev_cnt,) = st
            w_valid = (live if fn.child is None
                       else (live & v.validity)).astype(jnp.int64)
            if rows_frame:
                run = jnp.cumsum(w_valid)
            else:
                per_peer = jax.ops.segment_sum(w_valid, peer_id,
                                               num_segments=capacity)
                run = jnp.cumsum(per_peer)[peer_id]
            d = prev_cnt + run
            return ((jnp.where(live, d, 0), live),
                    (prev_cnt + jnp.sum(w_valid),))
        if isinstance(fn, (agg.Sum, agg.Average)):
            prev_sum, prev_cnt = st
            sv = live & v.validity
            # LongType sums stay exact in int64 (the batch kernel's
            # invariant — f64 emulation would round beyond 2^53)
            int_exact = (isinstance(fn, agg.Sum)
                         and isinstance(fn.data_type, T.LongType))
            if int_exact:
                vv = jnp.where(sv, v.data.astype(jnp.int64), 0)
                prev_sum = prev_sum.astype(jnp.int64)
            else:
                vv = jnp.where(sv, v.data.astype(jnp.float64), 0.0)
            cnt1 = sv.astype(jnp.int64)
            if rows_frame:
                rsum = jnp.cumsum(vv)
                rcnt = jnp.cumsum(cnt1)
            else:
                rsum = jnp.cumsum(jax.ops.segment_sum(
                    vv, peer_id, num_segments=capacity))[peer_id]
                rcnt = jnp.cumsum(jax.ops.segment_sum(
                    cnt1, peer_id, num_segments=capacity))[peer_id]
            tsum = prev_sum + rsum
            tcnt = prev_cnt + rcnt
            has = tcnt > 0
            if isinstance(fn, agg.Average):
                d = tsum / jnp.maximum(tcnt, 1).astype(jnp.float64)
            else:
                d = tsum
            zero = jnp.zeros_like(d)
            return ((jnp.where(has & live, d, zero), has & live),
                    (prev_sum + jnp.sum(vv), prev_cnt + jnp.sum(cnt1)))
        # Min / Max
        prev_m, prev_cnt = st
        is_min = isinstance(fn, agg.Min)
        dt = jnp.dtype(v.data.dtype)
        ident = self._ident(dt, is_min)
        sv = live & v.validity
        vd = jnp.where(sv, v.data, ident)
        op = jnp.minimum if is_min else jnp.maximum
        if frame[0] == "rows":
            run = jax.lax.associative_scan(op, vd)
        else:
            per_peer = (jax.ops.segment_min if is_min
                        else jax.ops.segment_max)(
                vd, peer_id, num_segments=capacity)
            run = jax.lax.associative_scan(op, per_peer)[peer_id]
        cnt1 = sv.astype(jnp.int64)
        if frame[0] == "rows":
            rcnt = jnp.cumsum(cnt1)
        else:
            rcnt = jnp.cumsum(jax.ops.segment_sum(
                cnt1, peer_id, num_segments=capacity))[peer_id]
        total = op(run, prev_m.astype(run.dtype))
        tcnt = prev_cnt + rcnt
        has = tcnt > 0
        zero = jnp.zeros_like(total)
        return ((jnp.where(has & live, total, zero), has & live),
                (op(prev_m.astype(run.dtype),
                    jnp.where(jnp.sum(cnt1) > 0, run[capacity - 1],
                              prev_m.astype(run.dtype))),
                 prev_cnt + jnp.sum(cnt1)))

    # -----------------------------------------------------------------------
    def _window(self, table: DeviceTable) -> DeviceTable:
        # all window exprs share ONE spec sort per distinct spec; v1 sorts
        # once per expr group with identical (partition, order) — common case
        # is a single spec.
        pctx = PrepCtx(table)
        expr_preps = []
        for _, w in self.window_cols:
            pp = [self._prep_tree(e, pctx) for e in w.spec.partition_exprs]
            op = [self._prep_tree(o.expr, pctx) for o in w.spec.orders]
            vp = self._prep_value(w, pctx)
            expr_preps.append((pp, op, vp))

        from spark_rapids_tpu.dispatch import prep_aux
        cols = tuple(DevVal(c.data, c.validity) for c in table.columns)
        aux = prep_aux(pctx)
        capacity = table.capacity

        from spark_rapids_tpu.ops.expr import shared_traces
        self._traces = shared_traces(
            ("window", tuple(w.key() for _, w in self.window_cols),
             table.schema_key()[0]))
        tkey = (capacity, tuple(
            (tuple(_prep_trace_key(p) for p in pp),
             tuple(_prep_trace_key(p) for p in op),
             tuple(_prep_trace_key(p) for p in vp))
            for pp, op, vp in expr_preps))
        fn = self._traces.get(tkey)
        if fn is None:
            fn = tpu_jit(self._build_kernel(capacity, expr_preps),
                         name="window")
            self._traces[tkey] = fn
        col_outs, win_outs = fn(cols, aux, table.nrows_dev)

        out_cols = [c.with_arrays(d, v) for c, (d, v) in zip(table.columns, col_outs)]
        names = list(table.names)
        for (name, w), (d, v), (pp, op, vp) in zip(self.window_cols, win_outs,
                                                   expr_preps):
            dictionary = None
            dict_sorted = True
            if isinstance(w.data_type, T.StringType) and vp:
                # lag/lead of a string expr: the value prep's root carries
                # the output dictionary (same as aggregate outputs)
                dictionary = vp[0][-1].out_dict
                dict_sorted = vp[0][-1].dict_sorted
            out_cols.append(DeviceColumn(w.data_type, d, v,
                                         dictionary=dictionary,
                                         dict_sorted=dict_sorted))
            names.append(name)
        return DeviceTable(names, out_cols, table.nrows_dev, capacity)

    @staticmethod
    def _prep_tree(e, pctx):
        preps: List[NodePrep] = []
        _walk_prep(e, pctx, preps)
        return preps

    def _prep_value(self, w: WindowExpression, pctx):
        fn = w.function
        if isinstance(fn, (Lag, Lead, NthValue)):
            return [self._prep_tree(fn.children[0], pctx)]
        if isinstance(fn, agg.AggregateFunction) and fn.child is not None:
            return [self._prep_tree(fn.child, pctx)]
        return []

    # -----------------------------------------------------------------------
    def _build_kernel(self, capacity: int, expr_preps):
        window_cols = self.window_cols

        def kernel(cols, aux, nrows):
            idx = jnp.arange(capacity, dtype=jnp.int32)
            live = idx < nrows

            def eval_tree(e, preps):
                ctx = EvalCtx(cols, aux, nrows, capacity)
                ctx._prep_iter = iter(preps)
                return _walk_eval(e, ctx)

            outs = []
            for (name, w), (pp, op, vp) in zip(window_cols, expr_preps):
                spec = w.spec
                pvals = [eval_tree(e, p) for e, p in zip(spec.partition_exprs, pp)]
                ovals = [eval_tree(o.expr, p) for o, p in zip(spec.orders, op)]

                # ---- sort by (dead-last, partition, order) ----------------
                operands = [(~live).astype(jnp.int32)]
                for kv in pvals:
                    operands.extend(self._sortable(kv))
                from spark_rapids_tpu.execs.sort import _directional
                for o, kv in zip(spec.orders, ovals):
                    operands.extend(_directional(
                        kv.data, kv.validity, o.ascending,
                        o.resolved_nulls_first(), capacity))
                res = jax.lax.sort(operands + [idx], num_keys=len(operands),
                                   is_stable=True)
                perm = res[-1]
                s_live = live[perm]

                # ---- segment & peer structure -----------------------------
                first = idx == 0
                def _peer_eq_break(kv):
                    """rows[i] != rows[i-1] with Spark peer semantics:
                    -0.0 == 0.0 and NaN == NaN (canonicalize before the
                    compare — raw float != would split NaN ties into
                    singleton peer groups; ADVICE r1)."""
                    d, v = kv.data[perm], kv.validity[perm]
                    if jnp.issubdtype(d.dtype, jnp.floating):
                        d = jnp.where(d == 0.0, jnp.zeros_like(d), d)
                        nan_mask = jnp.isnan(d)
                        d = jnp.where(nan_mask, jnp.zeros_like(d), d)
                        dp, vpv = jnp.roll(d, 1), jnp.roll(v, 1)
                        np_mask = jnp.roll(nan_mask, 1)
                        diff = (d != dp) | (nan_mask != np_mask)
                    elif getattr(d, "ndim", 1) == 2:  # dec128 limbs
                        dp, vpv = jnp.roll(d, 1, axis=0), jnp.roll(v, 1)
                        diff = jnp.any(d != dp, axis=1)
                    else:
                        dp, vpv = jnp.roll(d, 1), jnp.roll(v, 1)
                        diff = d != dp
                    return jnp.where(v & vpv, diff, v != vpv)

                new_seg = first
                for kv in pvals:
                    new_seg = new_seg | _peer_eq_break(kv)
                new_seg = new_seg & s_live | first
                gid = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
                seg_start = _seg_scan_max(jnp.where(new_seg, idx, 0))

                new_peer = new_seg
                for kv in ovals:
                    new_peer = new_peer | _peer_eq_break(kv)
                peer_id = jnp.cumsum(new_peer.astype(jnp.int32)) - 1
                peer_start = _seg_scan_max(jnp.where(new_peer, idx, 0))
                # last row index of each peer group
                peer_last = jax.ops.segment_max(
                    jnp.where(s_live, idx, -1), peer_id,
                    num_segments=capacity)[peer_id]

                d, v = self._eval_window_fn(
                    w, vp, eval_tree, perm, idx, s_live, gid, seg_start,
                    peer_start, peer_last, nrows, capacity)
                # scatter back to INPUT row order so multiple window exprs
                # with different specs stay positionally aligned with the
                # child columns
                from spark_rapids_tpu.ops.scatter32 import scatter_pair
                outs.append(scatter_pair(capacity, perm, d, v))

            col_outs = [(d, v) for d, v in cols]  # original order
            return col_outs, outs

        return kernel

    @staticmethod
    def _sortable(kv):
        d = kv.data
        if getattr(d, "ndim", 1) == 2:
            # dec128 limb keys MUST decompose (no 2-D sort operand);
            # 1-D keys stay whole — extra sort operands cost real wall
            # time in the per-batch window kernel (measured 0.42s ->
            # 1.8s+ on q6 when every key decomposed)
            from spark_rapids_tpu.ops.ordering import (
                comparable_operands,
                zero_invalid,
            )
            return ([(~kv.validity).astype(jnp.int32)]
                    + comparable_operands(zero_invalid(d, kv.validity)))
        if jnp.issubdtype(d.dtype, jnp.floating):
            d = jnp.where(d == 0.0, jnp.zeros_like(d), d)
        if d.dtype == jnp.bool_:
            d = d.astype(jnp.int32)
        return [(~kv.validity).astype(jnp.int32),
                jnp.where(kv.validity, d, jnp.zeros_like(d))]

    @staticmethod
    def _rmq(op, ident, vv, a, b, width: int, capacity: int):
        """Range min/max over [a, b] per row via a doubling sparse table of
        ceil(log2(width))+1 levels. Queries satisfy b - a + 1 <= width and
        stay inside one partition, so table entries crossing partition
        boundaries are never read by a query that could be contaminated."""
        levels = [vv]
        span = 1
        while span < width:
            prev = levels[-1]
            shifted = jnp.concatenate(
                [prev[span:], jnp.full(span, ident, dtype=prev.dtype)])
            levels.append(op(prev, shifted))
            span <<= 1
        table = jnp.stack(levels)  # (L, capacity)
        length = jnp.maximum(b - a + 1, 1)
        k = jnp.floor(jnp.log2(length.astype(jnp.float32))).astype(jnp.int32)
        k = jnp.clip(k, 0, len(levels) - 1)
        pow_k = (jnp.int32(1) << k)
        left = table[k, a]
        right = table[k, jnp.clip(b - pow_k + 1, 0, capacity - 1)]
        return op(left, right)

    def _eval_window_fn(self, w, vp, eval_tree, perm, idx, s_live, gid,
                        seg_start, peer_start, peer_last, nrows, capacity):
        fn = w.function
        kind, lo, hi = w.spec.resolved_frame()

        if isinstance(fn, RowNumber):
            return ((idx - seg_start + 1).astype(jnp.int32), s_live)
        if isinstance(fn, Rank):
            return ((peer_start - seg_start + 1).astype(jnp.int32), s_live)
        if isinstance(fn, DenseRank):
            # segmented count of peer-group starts
            new_peer_int = (peer_start == idx).astype(jnp.int32)
            dense = _segmented_cumsum(new_peer_int, seg_start)
            return (dense.astype(jnp.int32), s_live)
        if isinstance(fn, PercentRank):
            seg_end_pr = jax.ops.segment_max(
                jnp.where(s_live, idx, -1), gid, num_segments=capacity)[gid]
            m = (seg_end_pr - seg_start + 1).astype(jnp.float64)
            rank = (peer_start - seg_start + 1).astype(jnp.float64)
            pr = jnp.where(m > 1, (rank - 1.0) / jnp.maximum(m - 1.0, 1.0),
                           0.0)
            return (pr, s_live)
        if isinstance(fn, NthValue):
            src = eval_tree(fn.children[0], vp[0])
            sd_n, sv_n = src.data[perm], src.validity[perm]
            pos = seg_start + (fn.n - 1)
            safe = jnp.clip(pos, 0, capacity - 1)
            seg_end_nv = jax.ops.segment_max(
                jnp.where(s_live, idx, -1), gid, num_segments=capacity)[gid]
            avail = (pos <= peer_last) & (pos <= seg_end_nv) & s_live
            data = jnp.where(avail, sd_n[safe], jnp.zeros_like(sd_n))
            return (data, avail & sv_n[safe])

        if isinstance(fn, (Lag, Lead)):
            src = eval_tree(fn.children[0], vp[0])
            sd, sv = src.data[perm], src.validity[perm]
            off = fn.offset if isinstance(fn, Lead) else -fn.offset
            j = idx + off
            safe = jnp.clip(j, 0, capacity - 1)
            in_seg = (j >= 0) & (j < capacity) & (gid[safe] == gid) & s_live
            in_seg = in_seg & (safe < nrows)
            data = jnp.where(in_seg, sd[safe], jnp.zeros_like(sd))
            valid = in_seg & sv[safe]
            if fn.default is not None:
                dflt = jnp.asarray(fn.default, dtype=sd.dtype)
                data = jnp.where(~in_seg & s_live, dflt, data)
                valid = valid | (~in_seg & s_live)
            return (data, valid)

        # aggregates
        if isinstance(fn, agg.Count) and fn.child is None:
            v = s_live.astype(jnp.int64)
            sv = s_live
        else:
            src = eval_tree(fn.child, vp[0])
            sd, sv = src.data[perm], src.validity[perm] & s_live
            if isinstance(fn, agg.Count):
                v = sv.astype(jnp.int64)
            elif isinstance(fn.data_type, T.LongType) and isinstance(fn, agg.Sum):
                v = jnp.where(sv, sd.astype(jnp.int64), 0)
            elif isinstance(fn, (agg.Sum, agg.Average)):
                v = jnp.where(sv, sd.astype(jnp.float64), 0.0)
            else:  # min/max keep dtype
                v = sd

        whole = (lo is None and hi is None)
        running = (lo is None and hi == 0)
        new_seg = seg_start == idx

        def seg_prefix(x):
            """Inclusive prefix restarting at each segment — never crosses
            partitions, so float sums cannot catastrophically cancel against
            other partitions' values (int stays exact too)."""
            return _segmented_scan(jnp.add, x, new_seg)

        if isinstance(fn, (agg.Min, agg.Max)):
            op = jnp.minimum if isinstance(fn, agg.Min) else jnp.maximum
            ident = self._ident(v.dtype, isinstance(fn, agg.Min))
            vv = jnp.where(sv, v, ident)
            if whole:
                seg_fn = jax.ops.segment_min if isinstance(fn, agg.Min) else jax.ops.segment_max
                r = seg_fn(vv, gid, num_segments=capacity)[gid]
                nn = jax.ops.segment_sum(sv.astype(jnp.int32), gid,
                                         num_segments=capacity)[gid]
                valid = (nn > 0) & s_live
            elif running:
                new_seg = seg_start == idx
                r = _segmented_scan(op, vv, new_seg)
                cnt = _segmented_scan(jnp.add, sv.astype(jnp.int32), new_seg)
                if kind == "range":
                    r = r[peer_last]
                    cnt = cnt[peer_last]
                valid = (cnt > 0) & s_live
            else:
                # bounded rows min/max (GpuBatchedBoundedWindowExec analog):
                # clip the frame to the partition, then
                #   hi unbounded  -> reverse segmented running scan read at a
                #   lo unbounded  -> forward scan at idx combined with a
                #                    sparse-table query over (idx, b]
                #   both bounded  -> classic RMQ sparse-table query on [a, b]
                seg_end = jax.ops.segment_max(
                    jnp.where(s_live, idx, -1), gid,
                    num_segments=capacity)[gid]
                a = seg_start if lo is None else jnp.maximum(seg_start, idx + lo)
                b = seg_end if hi is None else jnp.minimum(seg_end, idx + hi)
                # emptiness must be judged BEFORE clipping into the index
                # range (clipping turns an empty edge frame into a 1-row one)
                nonempty = (b >= a) & s_live
                a = jnp.clip(a, 0, capacity - 1)
                b = jnp.clip(b, 0, capacity - 1)
                new_seg = seg_start == idx

                prefc = _segmented_scan(jnp.add, sv.astype(jnp.int32), new_seg)
                lo_exclc = jnp.where(a > seg_start,
                                     prefc[jnp.maximum(a - 1, 0)], 0)
                nn = jnp.where(nonempty, prefc[b] - lo_exclc, 0)

                if hi is None:
                    rscan = jnp.flip(_segmented_scan(
                        op, jnp.flip(vv), jnp.flip(idx == seg_end)))
                    r = rscan[a]
                else:
                    width = (hi - (lo if lo is not None else 0)) + 1 \
                        if lo is not None else hi + 1
                    width = max(int(width), 1)
                    qa = a if lo is not None else jnp.minimum(idx + 1, b)
                    r_tab = self._rmq(op, ident, vv, qa, b, width, capacity)
                    if lo is None:
                        fwd = _segmented_scan(op, vv, new_seg)
                        head = fwd[jnp.minimum(idx, b)]
                        tail = jnp.where(b > idx, r_tab, ident)
                        r = op(head, tail)
                    else:
                        r = r_tab
                valid = (nn > 0) & nonempty
            r = jnp.where(valid, r, jnp.zeros_like(r))
            if isinstance(fn.data_type, T.BooleanType):
                r = r.astype(jnp.bool_)
            return (r, valid)

        # sum / count / average via prefix sums
        if isinstance(fn, agg.Count) and fn.child is None:
            cnt_all = s_live.astype(jnp.int64)
        else:
            cnt_all = sv.astype(jnp.int64)
        if whole:
            total = jax.ops.segment_sum(v, gid, num_segments=capacity)[gid]
            nn = jax.ops.segment_sum(cnt_all, gid, num_segments=capacity)[gid]
        elif running:
            total = seg_prefix(v)
            nn = seg_prefix(cnt_all)
            if kind == "range":
                total = total[peer_last]
                nn = nn[peer_last]
        else:
            # bounded rows frame [lo, hi] relative to current row
            seg_end = jax.ops.segment_max(jnp.where(s_live, idx, -1), gid,
                                          num_segments=capacity)[gid]
            a = seg_start if lo is None else jnp.maximum(seg_start, idx + lo)
            b = seg_end if hi is None else jnp.minimum(seg_end, idx + hi)
            # emptiness judged BEFORE clipping (empty edge frames must
            # stay empty)
            nonempty = b >= a
            a = jnp.clip(a, 0, capacity - 1)
            b = jnp.clip(b, 0, capacity - 1)
            is_float = jnp.issubdtype(v.dtype, jnp.floating)

            # counts (int, exact) always go prefix-diff
            prefc = seg_prefix(cnt_all)
            past_start = a > seg_start
            lo_exclc = jnp.where(past_start, prefc[jnp.maximum(a - 1, 0)], 0)
            nn = jnp.where(nonempty, prefc[b] - lo_exclc, 0)

            if not is_float:
                pref = seg_prefix(v)
                lo_excl = jnp.where(past_start, pref[jnp.maximum(a - 1, 0)], 0)
                total = jnp.where(nonempty, pref[b] - lo_excl, 0)
            elif lo is None:
                # frame starts at segment start: prefix read, NO subtraction
                # (prefix-diff on floats can catastrophically cancel)
                total = jnp.where(nonempty, seg_prefix(v)[b], 0.0)
            elif hi is None:
                # frame ends at segment end: reverse segmented prefix
                seg_last = idx == seg_end
                rpref = jnp.flip(_segmented_scan(
                    jnp.add, jnp.flip(v), jnp.flip(seg_last)))
                total = jnp.where(nonempty, rpref[a], 0.0)
            elif (hi - lo + 1) <= 512:
                # both-bounded small frame: exact per-frame unrolled sum
                total = jnp.zeros_like(v)
                for k in range(lo, hi + 1):
                    j = idx + k
                    safe = jnp.clip(j, 0, capacity - 1)
                    inside = (j >= seg_start) & (j <= seg_end) & s_live
                    total = total + jnp.where(inside, v[safe], 0.0)
            else:
                # wide float frame: segmented-prefix DIFFERENCE — same
                # reduction-order float variance class the reference gates
                # with variableFloatAgg (ulp-level, partition-local)
                pref = seg_prefix(v)
                lo_excl = jnp.where(past_start,
                                    pref[jnp.maximum(a - 1, 0)], 0.0)
                total = jnp.where(nonempty, pref[b] - lo_excl, 0.0)

        if isinstance(fn, agg.Count):
            return (nn.astype(jnp.int64), s_live)
        valid = (nn > 0) & s_live
        if isinstance(fn, agg.Average):
            r = total / jnp.maximum(nn, 1).astype(jnp.float64)
        else:
            r = total
        return (jnp.where(valid, r, jnp.zeros_like(r)), valid)

    @staticmethod
    def _ident(dtype, is_min: bool):
        if jnp.issubdtype(dtype, jnp.floating):
            return jnp.asarray(jnp.inf if is_min else -jnp.inf, dtype=dtype)
        if dtype == jnp.bool_:
            return jnp.asarray(True if is_min else False, dtype=dtype)
        info = jnp.iinfo(dtype)
        return jnp.asarray(info.max if is_min else info.min, dtype=dtype)


class TpuKeyedBatchExec(TpuExec):
    """Partition-complete batching for windows (GpuKeyBatchingIterator /
    batched-window analog — reference window/ iterators process bounded
    batches instead of the whole input): a single-batch child passes
    through untouched; a multi-batch child hash-exchanges on the window
    PARTITION keys so every partition group lands whole inside exactly
    one output batch — the window then processes each batch independently
    and peak memory is bounded by the largest reduce partition, not the
    whole input."""

    def __init__(self, child: TpuExec, keys, conf, num_partitions: int = 8):
        super().__init__()
        self.children = (child,)
        self.keys = list(keys)
        self.conf = conf
        self.num_partitions = num_partitions

    def output_schema(self):
        return self.children[0].output_schema()

    def describe(self):
        return f"TpuKeyedBatch[n={self.num_partitions}]"

    def execute(self):
        it = self.children[0].execute()
        first = next(it, None)
        if first is None:
            return
        second = next(it, None)
        if second is None:
            yield first  # common fast path: already one batch, no shuffle
            return
        from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec

        prefix = [first, second]

        class _Replay(TpuExec):
            def __init__(self, schema):
                super().__init__()
                self._schema = schema

            def output_schema(self):
                return self._schema

            def execute(self):
                yield from prefix
                yield from it

        # partition-ALIGNED batches are the contract: no AQE partition
        # coalescing, and one batch per reduce partition (huge target)
        conf = self.conf.set(
            "spark.rapids.sql.adaptive.coalescePartitions.enabled", "false")
        ex = TpuShuffleExchangeExec(
            _Replay(self.output_schema()), "hash", self.num_partitions,
            self.keys, conf, target_batch_bytes=1 << 62)
        self.add_metric("keyBatchedPartitions", self.num_partitions)
        yield from ex.execute()
        self.metrics.update(ex.metrics)


class TpuWindowGroupLimitExec(TpuExec):
    """Pre-window group limit (GpuWindowGroupLimitExec analog): one sort
    kernel ranks rows within their partition and emits a MASKED batch
    keeping rank <= limit — at most limit(+ties) rows per partition reach
    the window/shuffle above. Purely an optimization; the exact rank
    filter above still applies."""

    produces_masked = True

    def __init__(self, child: TpuExec, partition_exprs, orders,
                 rank_kind: str, limit: int):
        super().__init__()
        self.children = (child,)
        self.partition_exprs = list(partition_exprs)
        self.orders = list(orders)
        self.rank_kind = rank_kind
        self.limit = int(limit)

    def output_schema(self):
        return self.children[0].output_schema()

    def describe(self):
        return f"TpuWindowGroupLimit[{self.rank_kind} <= {self.limit}]"

    def execute_masked(self):
        from spark_rapids_tpu.runtime.retry import with_retry
        for batch in self.children[0].execute_masked():
            yield from with_retry(batch, self._limit_batch)

    def _limit_batch(self, table: DeviceTable) -> DeviceTable:
        from spark_rapids_tpu.dispatch import prep_aux, tpu_jit
        from spark_rapids_tpu.ops.expr import shared_traces
        pctx = PrepCtx(table)
        pp = [TpuWindowExec._prep_tree(e, pctx)
              for e in self.partition_exprs]
        op = [TpuWindowExec._prep_tree(o.expr, pctx) for o in self.orders]
        cols = tuple(DevVal(c.data, c.validity) for c in table.columns)
        aux = prep_aux(pctx)
        capacity = table.capacity
        self._traces = shared_traces(
            ("wingrouplimit", self.rank_kind, self.limit,
             tuple(e.key() for e in self.partition_exprs),
             tuple((o.expr.key(), o.ascending, o.resolved_nulls_first())
                   for o in self.orders),
             table.schema_key()[0]))
        has_mask = table.live is not None
        tkey = (capacity, has_mask,
                tuple(_prep_trace_key(x) for x in pp),
                tuple(_prep_trace_key(x) for x in op))
        fn = self._traces.get(tkey)
        if fn is None:
            fn = tpu_jit(self._build_kernel(capacity, pp, op),
                         name="window_group_limit")
            self._traces[tkey] = fn
        keep, nkeep = fn(cols, aux, table.nrows_dev, table.live)
        self.add_metric("groupLimitBatches", 1)
        return DeviceTable(table.names, table.columns, nkeep, capacity,
                           live=keep)

    def _build_kernel(self, capacity: int, pp, op):
        part_exprs = self.partition_exprs
        orders = self.orders
        rank_kind = self.rank_kind
        limit = self.limit

        def kernel(cols, aux, nrows, live_in):
            def eval_tree(e, preps):
                ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in)
                ctx._prep_iter = iter(preps)
                return _walk_eval(e, ctx)

            if live_in is not None:
                live = live_in
            else:
                live = jnp.arange(capacity, dtype=jnp.int32) < nrows
            from spark_rapids_tpu.execs.sort import _directional
            from spark_rapids_tpu.ops.ordering import comparable_operands
            operands = [(~live).astype(jnp.int32)]
            part_ops = []
            for e, preps in zip(part_exprs, pp):
                kv = eval_tree(e, preps)
                from spark_rapids_tpu.ops.ordering import zero_invalid
                part_ops.append((~kv.validity).astype(jnp.int32))
                part_ops.extend(comparable_operands(
                    zero_invalid(kv.data, kv.validity)))
            operands.extend(part_ops)
            n_part_ops = len(part_ops)
            order_ops = []
            for o, preps in zip(orders, op):
                kv = eval_tree(o.expr, preps)
                order_ops.extend(_directional(
                    kv.data, kv.validity, o.ascending,
                    o.resolved_nulls_first(), capacity))
            operands.extend(order_ops)
            payload = jnp.arange(capacity, dtype=jnp.int32)
            res = jax.lax.sort(operands + [payload],
                               num_keys=len(operands))
            perm = res[-1]
            s_live = live[perm]
            first = jnp.arange(capacity) == 0
            new_part = first
            for so in res[1:1 + n_part_ops]:
                new_part = new_part | (so != jnp.roll(so, 1))
            new_peer = new_part
            for so in res[1 + n_part_ops:-1]:
                new_peer = new_peer | (so != jnp.roll(so, 1))
            idx = jnp.arange(capacity, dtype=jnp.int32)
            part_start = _seg_scan_max(jnp.where(new_part, idx, 0))
            if rank_kind == "rownumber":
                rank = idx - part_start + 1
            elif rank_kind == "rank":
                peer_start = _seg_scan_max(jnp.where(new_peer, idx, 0))
                rank = peer_start - part_start + 1
            else:  # denserank
                rank = _segmented_cumsum(
                    new_peer.astype(jnp.int32), part_start)
            keep_sorted = s_live & (rank <= limit)
            keep = jnp.zeros(capacity, jnp.bool_).at[perm].set(keep_sorted)
            return keep, jnp.sum(keep.astype(jnp.int32))

        return kernel
