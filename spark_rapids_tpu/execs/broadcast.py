"""Broadcast exchange + nested-loop join.

Reference: GpuBroadcastExchangeExec.scala (build-side materialization shared
by consumers), GpuBroadcastHashJoinExecBase, GpuBroadcastNestedLoopJoinExec
(conditioned joins without equi keys) — SURVEY.md §2.3.

TPU mapping: a broadcast in the single-controller JAX world is a table that
is materialized once, kept spillable, and (in the sharded plan) replicated
to every device of the mesh rather than partitioned. The nested-loop join
evaluates the join condition over probe-tile x build cross products with a
STATIC pair budget — each tile is one jitted kernel evaluating the bound
condition on gathered pair columns, so memory is bounded regardless of
input sizes."""

from __future__ import annotations

from typing import List, Optional, Sequence

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceColumn, DeviceTable, bucket_for
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.ops.expr import (
    DevVal,
    EvalCtx,
    Expression,
    NodePrep,
    PrepCtx,
    _prep_trace_key,
    _walk_eval,
    _walk_prep,
    shared_traces,
)

#: max probe_tile * build_rows pairs materialized per nested-loop tile
PAIR_BUDGET = 1 << 20


def _materialize_single(child: TpuExec, schema):
    """Materialize a child into ONE device table with spill protection:
    every buffered batch registers as a SpillableBatch so the OOM-retry
    catalog can demote it during the concat (the coalesce path's
    invariant — TpuJoinExec requires a spillable-protected build).
    Returns (table, n_input_batches)."""
    from spark_rapids_tpu.columnar.table import concat_device
    from spark_rapids_tpu.plan.nodes import _empty_table
    from spark_rapids_tpu.runtime.retry import retry_block
    from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch

    catalog = BufferCatalog.get()
    spills = []
    try:
        for b in child.execute():
            spills.append(SpillableBatch(b, catalog))
        if not spills:
            return DeviceTable.from_host(_empty_table(schema)), 0
        if len(spills) == 1:
            return spills[0].get(), 1
        table = retry_block(
            lambda: concat_device([sb.get() for sb in spills]))
        return table, len(spills)
    finally:
        for sb in spills:
            sb.release()


class TpuBroadcastExchangeExec(TpuExec):
    """Materializes the child ONCE into a single spillable table, reused
    across re-executions (multiple consumers / replays). The multi-chip
    plan replicates this table across the mesh instead of partitioning it
    (reference: GpuBroadcastExchangeExec builds the batch on the driver and
    ships it to every executor)."""

    def __init__(self, child: TpuExec):
        super().__init__()
        self.children = (child,)
        self._cached = None
        #: the build side of a join on the mesh's chips, which copies
        #: the table to every chip itself (execs/join.py): the child's
        #: batches are taken where they lie, not gathered first
        #: (execs/mesh.py)
        self.on_chips = False

    def execute(self):
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch

        if self._cached is None:
            table, n = _materialize_single(self.children[0],
                                           self.output_schema())
            self._cached = SpillableBatch(table, BufferCatalog.get())
            self.add_metric("broadcastBatches", n)
            self.add_metric("broadcastBytes", table.device_nbytes())
        yield self._cached.get()

    def output_schema(self):
        return self.children[0].output_schema()

    def describe(self):
        return "TpuBroadcastExchange"


class TpuNestedLoopJoinExec(TpuExec):
    """Conditioned nested-loop join (no equi keys): inner, left, right,
    full, leftsemi, leftanti, cross — the condition is evaluated on device
    over tiled cross products. The probe side streams; the build side is a
    broadcast table. Full outer tracks build-row matches across all tiles
    and batches and emits unmatched build rows last."""

    def __init__(self, left: TpuExec, right: TpuExec, join_type: str,
                 condition: Optional[Expression],
                 left_schema, right_schema):
        super().__init__()
        self.children = (left, right)
        self.join_type = join_type.lower().replace("_", "")
        self.condition = condition
        self._left_schema = list(left_schema)
        self._right_schema = list(right_schema)
        self.left_names = [n for n, _ in left_schema]
        self.right_names = [n for n, _ in right_schema]

    def output_schema(self):
        if self.join_type in ("leftsemi", "leftanti"):
            return list(self._left_schema)
        return list(self._left_schema) + list(self._right_schema)

    def describe(self):
        c = "cond" if self.condition is not None else "nocond"
        return f"TpuNestedLoopJoin[{self.join_type}, {c}]"

    # ------------------------------------------------------------------
    def execute(self):
        from spark_rapids_tpu.runtime.retry import retry_block

        jt = self.join_type
        swapped = jt in ("right", "rightouter")
        build_child = self.children[0] if swapped else self.children[1]
        probe_child = self.children[1] if swapped else self.children[0]

        build_batches = list(build_child.execute())
        if len(build_batches) != 1:
            from spark_rapids_tpu.columnar.table import concat_device
            build = retry_block(lambda: concat_device(build_batches))
        else:
            build = build_batches[0]

        full_outer = jt in ("full", "fullouter", "outer")
        b_matched = None

        for pb in probe_child.execute():
            tile = self._tile_rows(pb.capacity, build.capacity)
            for start in range(0, pb.capacity, tile):
                pt = self._slice(pb, start, tile)
                outs, bm = retry_block(
                    lambda p=pt: self._join_tile(p, build, swapped))
                if full_outer and bm is not None:
                    b_matched = bm if b_matched is None else (b_matched | bm)
                for out in outs:
                    yield out
            self.add_metric("probeBatches", 1)

        if full_outer:
            if b_matched is None:
                b_matched = jnp.zeros(build.capacity, jnp.bool_)
            yield self._unmatched_build(build, b_matched, swapped)

    @staticmethod
    def _tile_rows(cap_p: int, cap_b: int) -> int:
        # round DOWN to a power of two so tile * cap_b never exceeds the
        # pair budget (huge build sides get 1-row tiles — an O(n*m) nested
        # loop over a big build is slow however it is tiled, but it must
        # not OOM)
        t = max(PAIR_BUDGET // max(cap_b, 1), 1)
        b = 1 << (t.bit_length() - 1)
        return min(b, cap_p)

    @staticmethod
    def _slice(table: DeviceTable, start: int, tile: int) -> DeviceTable:
        cols = [c.with_arrays(
            jax.lax.dynamic_slice_in_dim(c.data, start, tile),
            jax.lax.dynamic_slice_in_dim(c.validity, start, tile))
            for c in table.columns]
        nrows = jnp.clip(table.nrows_dev - jnp.int32(start), 0, tile)
        return DeviceTable(table.names, cols, nrows, tile)

    # ------------------------------------------------------------------
    def _join_tile(self, pt: DeviceTable, bt: DeviceTable, swapped: bool):
        """Join one probe tile against the whole build table. Returns
        (list of output DeviceTables, build-match bool array or None)."""
        jt = self.join_type
        cap_p, cap_b = pt.capacity, bt.capacity

        # left/right logical tables in plan order for condition + output
        lt, rt = (bt, pt) if swapped else (pt, bt)

        # condition preps walk over a PAIR context; aux arrays ride as usual
        preps: List[NodePrep] = []
        pair_pctx = _PairPrepCtx(lt, rt)
        if self.condition is not None:
            _walk_prep(self.condition, pair_pctx, preps)

        tkey = ("nlj", jt, swapped, cap_p, cap_b,
                self.condition.key() if self.condition is not None else None,
                tuple((str(c.dtype), c.dictionary is not None)
                      for c in lt.columns),
                tuple((str(c.dtype), c.dictionary is not None)
                      for c in rt.columns),
                _prep_trace_key(preps))
        traces = shared_traces(("nlj-traces",))
        fn = traces.get(tkey)
        if fn is None:
            fn = tpu_jit(self._build_tile_kernel(
                jt, swapped, cap_p, cap_b, preps), name="nlj_tile")
            traces[tkey] = fn

        lcols = tuple((c.data, c.validity) for c in lt.columns)
        rcols = tuple((c.data, c.validity) for c in rt.columns)
        from spark_rapids_tpu.dispatch import prep_aux
        aux = prep_aux(pair_pctx)
        res = fn(lcols, rcols, aux, pt.nrows_dev, bt.nrows_dev)

        outs = []
        if jt in ("leftsemi", "leftanti"):
            cols_arrays, nout = res[0]
            cols = [c.with_arrays(d, v)
                    for c, (d, v) in zip(pt.columns, cols_arrays)]
            outs.append(DeviceTable(pt.names, cols, nout, cap_p))
            return outs, None

        (pair_arrays, n_pairs), (un_arrays, n_un), b_match = res
        names = self.left_names + self.right_names
        all_cols = list(lt.columns) + list(rt.columns)
        pair_cols = [DeviceColumn(c.dtype, d, v, dictionary=c.dictionary,
                                  dict_sorted=c.dict_sorted, domain=c.domain)
                     for c, (d, v) in zip(all_cols, pair_arrays)]
        outs.append(DeviceTable(names, pair_cols, n_pairs,
                                pair_cols[0].capacity))
        if un_arrays is not None:
            un_cols = [DeviceColumn(c.dtype, d, v, dictionary=c.dictionary,
                                    dict_sorted=c.dict_sorted, domain=c.domain)
                       for c, (d, v) in zip(all_cols, un_arrays)]
            outs.append(DeviceTable(names, un_cols, n_un, cap_p))
        return outs, (b_match if jt in ("full", "fullouter", "outer") else None)

    def _build_tile_kernel(self, jt: str, swapped: bool, cap_p: int,
                           cap_b: int, preps):
        condition = self.condition
        npairs = cap_p * cap_b
        out_cap = bucket_for(npairs)

        def kernel(lcols, rcols, aux, n_p, n_b):
            j = jnp.arange(out_cap, dtype=jnp.int32)
            p_idx = jnp.clip(j // cap_b, 0, cap_p - 1)
            b_idx = jnp.clip(j % cap_b, 0, cap_b - 1)
            in_range = j < npairs
            live_pair = in_range & (p_idx < n_p) & (b_idx < n_b)

            l_idx = b_idx if swapped else p_idx
            r_idx = p_idx if swapped else b_idx
            pair_cols = tuple(
                DevVal(d[l_idx], v[l_idx]) for d, v in lcols) + tuple(
                DevVal(d[r_idx], v[r_idx]) for d, v in rcols)

            if condition is not None:
                ctx = EvalCtx(pair_cols, aux, jnp.int32(npairs), out_cap)
                ctx._prep_iter = iter(preps)
                pred = _walk_eval(condition, ctx)
                match = live_pair & pred.data & pred.validity
            else:
                match = live_pair

            # per-probe-row any-match (for outer/semi/anti)
            mk = jnp.zeros(cap_p, jnp.bool_).at[
                jnp.where(match, p_idx, cap_p)].set(True, mode="drop")
            row_any = mk

            if jt in ("leftsemi", "leftanti"):
                keep = (row_any if jt == "leftsemi" else ~row_any)
                keep = keep & (jnp.arange(cap_p, dtype=jnp.int32) < n_p)
                pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
                tgt = jnp.where(keep, pos, cap_p)
                nout = jnp.sum(keep.astype(jnp.int32))
                # probe table IS the left side for semi/anti (never swapped)
                from spark_rapids_tpu.ops.scatter32 import scatter_pair
                outs = []
                for d, v in (lcols if not swapped else rcols):
                    outs.append(scatter_pair(cap_p, tgt, d, v))
                return ((tuple(outs), nout),)

            # matched pairs -> compact to the front
            pos = jnp.cumsum(match.astype(jnp.int32)) - 1
            tgt = jnp.where(match, pos, out_cap)
            n_pairs = jnp.sum(match.astype(jnp.int32))
            from spark_rapids_tpu.ops.scatter32 import scatter_pair
            pair_out = []
            for pv in pair_cols:
                pair_out.append(
                    scatter_pair(out_cap, tgt, pv.data, pv.validity))

            b_match = jnp.zeros(cap_b, jnp.bool_).at[
                jnp.where(match, b_idx, cap_b)].set(True, mode="drop")

            if jt == "inner" or jt == "cross":
                return ((tuple(pair_out), n_pairs),
                        (None, jnp.int32(0)), b_match)

            # outer: unmatched live probe rows emit one null-build row each
            p_live = jnp.arange(cap_p, dtype=jnp.int32) < n_p
            un = p_live & ~row_any
            upos = jnp.cumsum(un.astype(jnp.int32)) - 1
            utgt = jnp.where(un, upos, cap_p)
            n_un = jnp.sum(un.astype(jnp.int32))
            probe_cols = rcols if swapped else lcols
            probe_out = []
            for d, v in probe_cols:
                probe_out.append(scatter_pair(cap_p, utgt, d, v))
            null_build = []
            for d, v in (lcols if swapped else rcols):
                zd = jnp.zeros((cap_p,) + d.shape[1:], dtype=d.dtype)
                null_build.append((zd, jnp.zeros(cap_p, jnp.bool_)))
            if swapped:
                un_out = tuple(null_build) + tuple(probe_out)
            else:
                un_out = tuple(probe_out) + tuple(null_build)
            return ((tuple(pair_out), n_pairs), (un_out, n_un), b_match)

        return kernel

    def _unmatched_build(self, bt: DeviceTable, b_matched, swapped: bool):
        """Full-outer tail: build rows never matched, null probe side."""
        live = bt.row_mask()
        keep = live & ~b_matched
        pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
        tgt = jnp.where(keep, pos, bt.capacity)
        nout = jnp.sum(keep.astype(jnp.int32))
        from spark_rapids_tpu.ops.scatter32 import scatter_pair
        build_cols = []
        for c in bt.columns:
            od, ov = scatter_pair(bt.capacity, tgt, c.data, c.validity)
            build_cols.append(c.with_arrays(od, ov))
        probe_schema = self._right_schema if swapped else self._left_schema
        null_cols = []
        for _, dt in probe_schema:
            if isinstance(dt, T.StringType):
                data = jnp.zeros(bt.capacity, dtype=jnp.int32)
                null_cols.append(DeviceColumn(
                    dt, data, jnp.zeros(bt.capacity, jnp.bool_),
                    dictionary=np.array([], dtype=object)))
            else:
                from spark_rapids_tpu.columnar.column import null_data_array
                null_cols.append(DeviceColumn(
                    dt, null_data_array(dt, bt.capacity),
                    jnp.zeros(bt.capacity, jnp.bool_)))
        names = self.left_names + self.right_names
        cols = (build_cols + null_cols) if swapped else (null_cols + build_cols)
        return DeviceTable(names, cols, nout, bt.capacity)


class _PairPrepCtx(PrepCtx):
    """PrepCtx whose table view is the concatenated (left, right) pair
    schema — BoundReference.prep reads dictionaries by ordinal."""

    def __init__(self, lt: DeviceTable, rt: DeviceTable):
        self.table = _PairTableView(lt, rt)
        self.aux_arrays = []
        self.aux_intern = []


class _PairTableView:
    def __init__(self, lt: DeviceTable, rt: DeviceTable):
        self.columns = list(lt.columns) + list(rt.columns)


class TpuAdaptiveBuildExec(TpuExec):
    """AQE runtime join-strategy conversion (reference: AQE's
    DynamicJoinSelection + GpuOverrides AQE integration,
    GpuOverrides.scala:4577-4638): when the STATIC size estimate could
    not prove the build side small, the decision is deferred to RUNTIME —
    the build materializes, its ACTUAL bytes are measured, and a build
    under the broadcast threshold is cached as a broadcast table (reused
    across replays/consumers exactly like TpuBroadcastExchangeExec);
    otherwise it flows on as the ordinary single-batch build feeding the
    sub-partitioned join path."""

    def __init__(self, child: TpuExec, threshold_bytes: int):
        super().__init__()
        self.children = (child,)
        self.threshold_bytes = threshold_bytes
        self._cached = None
        self.converted: Optional[bool] = None

    def output_schema(self):
        return self.children[0].output_schema()

    def execute(self):
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch

        if self._cached is not None:
            yield self._cached.get()
            return
        table, _n = _materialize_single(self.children[0],
                                        self.output_schema())
        measured = table.device_nbytes()
        if self.converted is None:  # record the decision metrics ONCE
            self.add_metric("aqeMeasuredBuildBytes", measured)
            if measured <= self.threshold_bytes:
                self.add_metric("aqeBroadcastConverted", 1)
        if measured <= self.threshold_bytes:
            # runtime conversion to broadcast: cache for reuse
            self.converted = True
            self._cached = SpillableBatch(table, BufferCatalog.get())
            yield self._cached.get()
        else:
            self.converted = False
            yield table

    def describe(self):
        state = {None: "undecided", True: "->broadcast",
                 False: "->shuffle"}[self.converted]
        return f"TpuAdaptiveBuild[{state}]"
