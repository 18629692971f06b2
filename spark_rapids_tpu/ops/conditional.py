"""Conditional expressions (reference: If CaseWhen Coalesce Least Greatest
NaNvl — conditionalExpressions.scala; SURVEY.md Appendix A).

String results are handled by merging branch dictionaries host-side and
remapping branch codes on device (see ops/common.py)."""

from __future__ import annotations

from typing import Sequence

import numpy as np
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.ops.common import (
    align_string_dicts_many,
    dev_remap_codes,
)
from spark_rapids_tpu.ops.expr import DevVal, Expression, NodePrep


def _is_string(e: Expression) -> bool:
    return isinstance(e.data_type, T.StringType)


class If(Expression):
    def __init__(self, pred: Expression, if_true: Expression, if_false: Expression):
        self.children = (pred, if_true, if_false)

    @property
    def data_type(self):
        return self.children[1].data_type

    def with_children(self, children):
        return If(*children)

    def eval_cpu(self, table: HostTable) -> HostColumn:
        from spark_rapids_tpu.dispatch import ANSI_MODE
        p = self.children[0].eval_cpu(table)
        take_a = p.validity & p.data.astype(np.bool_)
        if ANSI_MODE.get():
            # Spark evaluates branches lazily: only selected rows may
            # raise — evaluate each branch on its row subset
            a = _eval_branch_cpu(self.children[1], table, take_a,
                                 self.data_type)
            b = _eval_branch_cpu(self.children[2], table, ~take_a,
                                 self.data_type)
        else:
            a = self.children[1].eval_cpu(table)
            b = self.children[2].eval_cpu(table)
        data = np.where(take_a, a.data, b.data)
        validity = np.where(take_a, a.validity, b.validity)
        return HostColumn(self.data_type, data, validity)

    def eval_walk(self, ctx):
        """Custom device walk: branch values evaluate under an ANSI guard
        so unselected rows cannot raise (ops/expr._walk_eval hook)."""
        from spark_rapids_tpu.ops.expr import _walk_eval
        p = _walk_eval(self.children[0], ctx)
        take_a = p.validity & p.data
        if ctx.ansi:
            with ctx.guarded(take_a):
                a = _walk_eval(self.children[1], ctx)
            with ctx.guarded(~take_a):
                b = _walk_eval(self.children[2], ctx)
        else:
            a = _walk_eval(self.children[1], ctx)
            b = _walk_eval(self.children[2], ctx)
        prep = ctx.next_prep()
        return self.eval_dev_branches(ctx, p, a, b, prep, take_a)

    def prep(self, pctx, child_preps):
        if child_preps[1].out_dict is not None:
            return align_string_dicts_many(pctx, child_preps[1:3])
        return NodePrep()

    def eval_dev(self, ctx, child_vals, prep):
        p, a, b = child_vals
        return self.eval_dev_branches(ctx, p, a, b, prep,
                                      p.validity & p.data)

    def eval_dev_branches(self, ctx, p, a, b, prep, take_a):
        ad, bd = a.data, b.data
        if prep.aux_slots:
            ad = dev_remap_codes(ctx, prep.aux_slots[0], ad)
            bd = dev_remap_codes(ctx, prep.aux_slots[1], bd)
        # a decimal128's data is a (rows, 2) limb matrix: the row's choice
        # covers both limbs (the streaming decimal sum's If(.., NULL, sum))
        rows = take_a.reshape(take_a.shape + (1,) * (ad.ndim - take_a.ndim))
        return DevVal(jnp.where(rows, ad, bd), jnp.where(take_a, a.validity, b.validity))


class CaseWhen(Expression):
    """children = [cond0, val0, cond1, val1, ..., (else)]. An odd child count
    means the last child is the else branch; otherwise else is NULL."""

    def __init__(self, *children: Expression):
        self.children = tuple(children)

    @property
    def has_else(self) -> bool:
        return len(self.children) % 2 == 1

    @property
    def data_type(self):
        return self.children[1].data_type

    def with_children(self, children):
        return CaseWhen(*children)

    def _branches(self):
        n = len(self.children) - (1 if self.has_else else 0)
        return [(self.children[i], self.children[i + 1]) for i in range(0, n, 2)]

    def _value_child_indices(self):
        n = len(self.children) - (1 if self.has_else else 0)
        idx = list(range(1, n, 2))
        if self.has_else:
            idx.append(len(self.children) - 1)
        return idx

    def eval_walk(self, ctx):
        """Device walk with branch guards: each value expression (and the
        else) evaluates only-raising-for rows its predicate selects."""
        from spark_rapids_tpu.ops.expr import _walk_eval
        if not ctx.ansi:
            vals = [_walk_eval(c, ctx) for c in self.children]
            return self.eval_dev(ctx, vals, ctx.next_prep())
        vals = []
        decided = None
        n_branch = len(self.children) - (1 if self.has_else else 0)
        for i in range(0, n_branch, 2):
            c = _walk_eval(self.children[i], ctx)
            vals.append(c)
            take = c.validity & c.data
            if decided is not None:
                take = take & ~decided
            with ctx.guarded(take):
                vals.append(_walk_eval(self.children[i + 1], ctx))
            decided = take if decided is None else (decided | take)
        if self.has_else:
            with ctx.guarded(~decided if decided is not None
                             else jnp.ones(ctx.capacity, jnp.bool_)):
                vals.append(_walk_eval(self.children[-1], ctx))
        return self.eval_dev(ctx, vals, ctx.next_prep())

    def _eval_cpu_ansi(self, table):
        """Lazy-branch CPU evaluation: each value expression runs only on
        the rows its predicate (first-match) selects."""
        n = table.num_rows
        decided = np.zeros(n, dtype=np.bool_)
        dtype = self.data_type
        npdt = np.int32 if False else None
        data = None
        validity = np.zeros(n, dtype=np.bool_)
        for cond, val in self._branches():
            c = cond.eval_cpu(table)
            take = ~decided & c.validity & c.data.astype(np.bool_)
            part = _eval_branch_cpu(val, table, take, dtype)
            if data is None:
                data = part.data.copy()
            else:
                data = np.where(take, part.data, data)
            validity = np.where(take, part.validity, validity)
            decided |= take
        if self.has_else:
            part = _eval_branch_cpu(self.children[-1], table, ~decided,
                                    dtype)
            if data is None:
                data = part.data.copy()
            else:
                data = np.where(~decided, part.data, data)
            validity = np.where(~decided, part.validity, validity)
        return HostColumn(dtype, data, validity)

    def eval_cpu(self, table):
        from spark_rapids_tpu.dispatch import ANSI_MODE
        if ANSI_MODE.get():
            return self._eval_cpu_ansi(table)
        n = table.num_rows
        dtype = self.data_type
        if isinstance(dtype, T.StringType):
            data = np.full(n, "", dtype=object)
        else:
            data = np.zeros(n, dtype=dtype.np_dtype)
        validity = np.zeros(n, dtype=np.bool_)
        decided = np.zeros(n, dtype=np.bool_)
        for cond, val in self._branches():
            c = cond.eval_cpu(table)
            v = val.eval_cpu(table)
            take = ~decided & c.validity & c.data.astype(np.bool_)
            data = np.where(take, v.data, data)
            validity = np.where(take, v.validity, validity)
            decided |= take
        if self.has_else:
            v = self.children[-1].eval_cpu(table)
            data = np.where(~decided, v.data, data)
            validity = np.where(~decided, v.validity, validity)
        return HostColumn(dtype, data, validity)

    def prep(self, pctx, child_preps):
        vidx = self._value_child_indices()
        if child_preps[vidx[0]].out_dict is not None:
            return align_string_dicts_many(pctx, [child_preps[i] for i in vidx])
        return NodePrep()

    def eval_dev(self, ctx, child_vals, prep):
        vidx = self._value_child_indices()
        remapped = {}
        if prep.aux_slots:
            for slot, i in zip(prep.aux_slots, vidx):
                remapped[i] = dev_remap_codes(ctx, slot, child_vals[i].data)
        cap = ctx.capacity
        dtype = self.data_type
        data = jnp.zeros(cap, dtype=jnp.int32 if isinstance(dtype, T.StringType) else dtype.np_dtype)
        validity = jnp.zeros(cap, dtype=jnp.bool_)
        decided = jnp.zeros(cap, dtype=jnp.bool_)
        n_branch = len(self.children) - (1 if self.has_else else 0)
        for i in range(0, n_branch, 2):
            c = child_vals[i]
            v = child_vals[i + 1]
            vd = remapped.get(i + 1, v.data)
            take = ~decided & c.validity & c.data
            data = jnp.where(take, vd, data)
            validity = jnp.where(take, v.validity, validity)
            decided = decided | take
        if self.has_else:
            i = len(self.children) - 1
            v = child_vals[i]
            vd = remapped.get(i, v.data)
            data = jnp.where(decided, data, vd)
            validity = jnp.where(decided, validity, v.validity)
        return DevVal(data, validity)


def _eval_branch_cpu(expr, table, mask, dtype):
    """Evaluate ``expr`` over only the mask-selected rows (ANSI lazy-branch
    semantics), scattering results back to full length."""
    from spark_rapids_tpu.columnar import HostTable as _HT
    idx = np.nonzero(mask)[0]
    sub = _HT(table.names,
              [HostColumn(c.dtype, c.data[idx], c.validity[idx])
               for c in table.columns])
    part = expr.eval_cpu(sub)
    n = table.num_rows
    data = np.zeros(n, dtype=part.data.dtype) \
        if part.data.dtype != object else np.full(n, None, dtype=object)
    validity = np.zeros(n, dtype=np.bool_)
    data[idx] = part.data
    validity[idx] = part.validity
    return HostColumn(part.dtype, data, validity)


class Coalesce(Expression):
    def __init__(self, *children: Expression):
        self.children = tuple(children)

    @property
    def data_type(self):
        return self.children[0].data_type

    def with_children(self, children):
        return Coalesce(*children)

    def eval_cpu(self, table):
        cols = [c.eval_cpu(table) for c in self.children]
        data = cols[0].data.copy()
        validity = cols[0].validity.copy()
        for c in cols[1:]:
            take = ~validity & c.validity
            data = np.where(take, c.data, data)
            validity |= c.validity
        return HostColumn(self.data_type, data, validity)

    def prep(self, pctx, child_preps):
        if child_preps[0].out_dict is not None:
            return align_string_dicts_many(pctx, child_preps)
        return NodePrep()

    def eval_dev(self, ctx, child_vals, prep):
        datas = [v.data for v in child_vals]
        if prep.aux_slots:
            datas = [dev_remap_codes(ctx, s, d) for s, d in zip(prep.aux_slots, datas)]
        data = datas[0]
        validity = child_vals[0].validity
        for v, d in zip(child_vals[1:], datas[1:]):
            take = ~validity & v.validity
            data = jnp.where(take, d, data)
            validity = validity | v.validity
        return DevVal(data, validity)


class _MinMaxN(Expression):
    """Least/Greatest: skip nulls; null only when every input is null."""

    _pick_cpu = None
    _pick_dev = None

    def __init__(self, *children: Expression):
        self.children = tuple(children)

    @property
    def data_type(self):
        return self.children[0].data_type

    def with_children(self, children):
        return type(self)(*children)

    def prep(self, pctx, child_preps):
        if child_preps[0].out_dict is not None:
            return align_string_dicts_many(pctx, child_preps)
        return NodePrep()

    def eval_cpu(self, table):
        cols = [c.eval_cpu(table) for c in self.children]
        string = isinstance(self.data_type, T.StringType)
        data = cols[0].data.copy()
        if string:
            data = np.where(cols[0].validity, data, "")
        validity = cols[0].validity.copy()
        for c in cols[1:]:
            cd = np.where(c.validity, c.data, "") if string else c.data
            better = c.validity & (~validity | type(self)._pick_cpu(cd, data))
            data = np.where(better, cd, data)
            validity |= c.validity
        if string:
            data = data.astype(object)
            out = np.empty(len(data), dtype=object)
            out[:] = data
            out[~validity] = None
            data = out
        return HostColumn(self.data_type, data, validity)

    def eval_dev(self, ctx, child_vals, prep):
        datas = [v.data for v in child_vals]
        if prep.aux_slots:
            datas = [dev_remap_codes(ctx, s, d) for s, d in zip(prep.aux_slots, datas)]
        data = datas[0]
        validity = child_vals[0].validity
        for v, d in zip(child_vals[1:], datas[1:]):
            better = v.validity & (~validity | type(self)._pick_dev(d, data))
            data = jnp.where(better, d, data)
            validity = validity | v.validity
        return DevVal(jnp.where(validity, data, jnp.zeros_like(data)), validity)


class Least(_MinMaxN):
    _pick_cpu = staticmethod(lambda new, cur: new < cur)
    _pick_dev = staticmethod(lambda new, cur: new < cur)


class Greatest(_MinMaxN):
    _pick_cpu = staticmethod(lambda new, cur: new > cur)
    _pick_dev = staticmethod(lambda new, cur: new > cur)


class NaNvl(Expression):
    """NaNvl(a, b): a if a is not NaN else b (types already double/float)."""

    def __init__(self, left: Expression, right: Expression):
        self.children = (left, right)

    @property
    def data_type(self):
        return self.children[0].data_type

    def with_children(self, children):
        return NaNvl(*children)

    def eval_cpu(self, table):
        a = self.children[0].eval_cpu(table)
        b = self.children[1].eval_cpu(table)
        take_b = a.validity & np.isnan(a.data)
        data = np.where(take_b, b.data, a.data)
        validity = np.where(take_b, b.validity, a.validity)
        return HostColumn(self.data_type, data, validity)

    def eval_dev(self, ctx, child_vals, prep):
        a, b = child_vals
        take_b = a.validity & jnp.isnan(a.data)
        return DevVal(jnp.where(take_b, b.data, a.data),
                      jnp.where(take_b, b.validity, a.validity))
