"""TPU Generate exec (explode / posexplode [outer]).

Reference: GpuGenerateExec.scala (~1,600 LoC) — SURVEY.md §2.3 / VERDICT r1
item 6. TPU-first shape: the array column already lives flattened as
(offsets, elements, element-validity), so "explode" is a GATHER, not a
loop — each element slot finds its source row with one searchsorted over
the offsets, the other columns gather by that row id, and one compaction
scatter drops dead slots. Outer mode appends one null row per null/empty
array with the same unmatched-row trick the joins use. All static shapes:
output capacity = element capacity (+ row capacity when outer)."""

from __future__ import annotations

from typing import List, Sequence

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceColumn, DeviceTable, bucket_for
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.ops.expr import (
    DevVal,
    Expression,
    NodePrep,
    PrepCtx,
    EvalCtx,
    _prep_trace_key,
    _walk_eval,
    _walk_prep,
    shared_traces,
)


class TpuGenerateExec(TpuExec):
    def __init__(self, child: TpuExec, gen_child: Expression,
                 pos: bool, outer: bool, out_names: Sequence[str],
                 required: Sequence[str] = ()):
        super().__init__()
        self.children = (child,)
        self.gen_child = gen_child
        self.pos = pos
        self.outer = outer
        self.out_names = list(out_names)
        self.required = list(required)

    def output_schema(self):
        child_schema = dict(self.children[0].output_schema())
        out = [(n, child_schema[n]) for n in self.required]
        i = 0
        if self.pos:
            out.append((self.out_names[i], T.INT))
            i += 1
        out.append((self.out_names[i],
                    self.gen_child.data_type.element_type))
        return out

    def describe(self):
        kind = ("posexplode" if self.pos else "explode") + \
            ("_outer" if self.outer else "")
        return f"TpuGenerate[{kind}]"

    def execute(self):
        from spark_rapids_tpu.runtime.retry import with_retry
        for batch in self.children[0].execute():
            yield from with_retry(batch, self._generate, splittable=False)

    def _generate(self, full: DeviceTable) -> DeviceTable:
        # evaluate the generator over the FULL child table, pass through
        # only the required (pruned) columns
        keep = [full.names.index(n) for n in self.required]
        table = DeviceTable([full.names[i] for i in keep],
                            [full.columns[i] for i in keep],
                            full.nrows_dev, full.capacity)
        pctx = PrepCtx(full)
        preps: List[NodePrep] = []
        _walk_prep(self.gen_child, pctx, preps)
        gen_cols = tuple(DevVal(c.data, c.validity) for c in full.columns)
        cols = tuple(DevVal(c.data, c.validity) for c in table.columns)
        from spark_rapids_tpu.dispatch import prep_aux
        aux = prep_aux(pctx)
        cap = table.capacity

        # element capacity comes from the evaluated array column; for a
        # plain column ref it is the upload's bucket
        traces = shared_traces(
            ("generate", self.gen_child.key(), self.pos, self.outer,
             table.schema_key()[0]))

        # learn ecap via ABSTRACT evaluation (no device compute; the jitted
        # kernel evaluates for real inside its trace)
        gen_child = self.gen_child

        def _shape_probe(gc, a, n):
            ctx = EvalCtx(gc, a, n, cap)
            ctx._prep_iter = iter(preps)
            return _walk_eval(gen_child, ctx)

        shaped = jax.eval_shape(_shape_probe, gen_cols, aux, table.nrows_dev)
        ecap = shaped.data[1].shape[0]
        out_cap = bucket_for(ecap + (cap if self.outer else 0))

        tkey = (cap, ecap, out_cap, _prep_trace_key(preps),
                table.schema_key()[0])
        fn = traces.get(tkey)
        if fn is None:
            fn = tpu_jit(self._build_kernel(cap, ecap, out_cap, preps),
                         name="generate")
            traces[tkey] = fn
        out_arrays, nout = fn(gen_cols, cols, aux, table.nrows_dev)

        out_cols = []
        names = []
        for c, name, (d, v) in zip(table.columns, table.names, out_arrays):
            out_cols.append(DeviceColumn(c.dtype, d, v,
                                         dictionary=c.dictionary,
                                         dict_sorted=c.dict_sorted))
            names.append(name)
        i = len(table.columns)
        oni = 0
        if self.pos:
            d, v = out_arrays[i]
            out_cols.append(DeviceColumn(T.INT, d, v))
            names.append(self.out_names[oni])
            i += 1
            oni += 1
        d, v = out_arrays[i]
        out_cols.append(DeviceColumn(
            self.gen_child.data_type.element_type, d, v))
        names.append(self.out_names[oni])
        return DeviceTable(names, out_cols, nout, out_cap)

    def _build_kernel(self, cap: int, ecap: int, out_cap: int, preps):
        gen_child = self.gen_child
        pos = self.pos
        outer = self.outer

        def kernel(gen_cols, cols, aux, nrows):
            ctx = EvalCtx(gen_cols, aux, nrows, cap)
            ctx._prep_iter = iter(preps)
            arr = _walk_eval(gen_child, ctx)
            off, ed, ev = arr.data
            row_ok = arr.validity & (jnp.arange(cap, dtype=jnp.int32) < nrows)

            j = jnp.arange(ecap, dtype=jnp.int32)
            rid_raw = jnp.searchsorted(off, j, side="right").astype(jnp.int32) - 1
            rid = jnp.clip(rid_raw, 0, cap - 1)
            live = (j < off[-1]) & row_ok[rid]
            pos_val = j - off[rid]

            # compact live element slots to the front of out_cap
            cpos = jnp.cumsum(live.astype(jnp.int32)) - 1
            tgt = jnp.where(live, cpos, out_cap)
            n_elems = jnp.sum(live.astype(jnp.int32))

            from spark_rapids_tpu.ops.scatter32 import scatter_pair
            outs = []
            for data, valid in cols:
                outs.append(list(scatter_pair(out_cap, tgt, data[rid],
                                              valid[rid])))
            if pos:
                pd = jnp.zeros(out_cap, dtype=jnp.int32).at[tgt].set(
                    pos_val, mode="drop")
                pv = jnp.zeros(out_cap, dtype=jnp.bool_).at[tgt].set(
                    True, mode="drop")
                outs.append([pd, pv])
            vd, vv = scatter_pair(
                out_cap, tgt, jnp.where(ev, ed, jnp.zeros_like(ed)), ev)
            outs.append([vd, vv])
            nout = n_elems

            if outer:
                # rows with null/empty arrays emit ONE all-columns row with
                # null pos/element, appended after the element rows
                in_bounds = jnp.arange(cap, dtype=jnp.int32) < nrows
                empty = in_bounds & (~arr.validity | (off[1:] - off[:-1] == 0))
                epos = jnp.cumsum(empty.astype(jnp.int32)) - 1
                etgt = jnp.where(empty, n_elems + epos, out_cap)
                n_extra = jnp.sum(empty.astype(jnp.int32))
                for ci, (data, valid) in enumerate(cols):
                    outs[ci][0] = outs[ci][0].at[etgt].set(data, mode="drop")
                    outs[ci][1] = outs[ci][1].at[etgt].set(valid, mode="drop")
                # pos/element columns stay null on the appended rows
                nout = n_elems + n_extra

            return [tuple(o) for o in outs], nout

        return kernel
