"""Predicates & boolean logic (reference rules: EqualTo, EqualNullSafe,
LessThan, LessThanOrEqual, GreaterThan, GreaterThanOrEqual, And, Or, Not,
IsNull, IsNotNull, IsNaN, In, InSet — GpuOverrides.scala expression registry,
SURVEY.md Appendix A)."""

from __future__ import annotations

import operator
from typing import Sequence

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.ops.common import (
    BinaryExpression,
    UnaryExpression,
    align_string_dicts,
    coerce_numeric_pair,
    dev_aligned_codes,
    is_string_pair,
    null_and,
)
from spark_rapids_tpu.ops.expr import DevVal, EvalCtx, Expression, NodePrep, PrepCtx


def _spark_float_cmp(op, ld, rd, xp):
    """Spark total-order float comparison: NaN == NaN is TRUE and NaN is
    greater than every other value (SQL ref 'NaN semantics'); raw IEEE
    compares would return false for all NaN comparisons."""
    nl, nr = xp.isnan(ld), xp.isnan(rd)
    if op is operator.eq:
        return (ld == rd) | (nl & nr)
    if op is operator.lt:
        return (~nl & nr) | (ld < rd)
    if op is operator.le:
        return (~nl & nr) | (nl & nr) | (ld <= rd)
    if op is operator.gt:
        return (nl & ~nr) | (ld > rd)
    if op is operator.ge:
        return (nl & ~nr) | (nl & nr) | (ld >= rd)
    return op(ld, rd)


def _cpu_cmp_data(left: HostColumn, right: HostColumn, op):
    ld, rd = left.data, right.data
    if isinstance(left.dtype, T.StringType):
        # Invalid slots may hold None; substitute "" so object comparison
        # (Python str, code-point order == Spark UTF-8 byte order) is safe.
        ld = np.where(left.validity, ld, "")
        rd = np.where(right.validity, rd, "")
    elif np.issubdtype(np.asarray(ld).dtype, np.floating):
        return _spark_float_cmp(op, ld, rd, np)
    return op(ld, rd)


def _dec128_sign(l, r):
    """Three-way compare of (n, 2) int64 two-limb decimals: -1/0/+1 as
    i32. High limbs compare signed; low limbs compare as unsigned via a
    top-bit flip (no u64 bitcasts: the x64 rewrite lacks them)."""
    top = jnp.int64(-0x8000000000000000)
    lhi, llo = l[:, 0], l[:, 1] ^ top
    rhi, rlo = r[:, 0], r[:, 1] ^ top
    hi_cmp = jnp.where(lhi < rhi, -1, jnp.where(lhi > rhi, 1, 0)
                       ).astype(jnp.int32)
    lo_cmp = jnp.where(llo < rlo, -1, jnp.where(llo > rlo, 1, 0)
                       ).astype(jnp.int32)
    return jnp.where(hi_cmp != 0, hi_cmp, lo_cmp)


class BinaryComparison(BinaryExpression):
    op = None  # numpy/python operator
    jop = None  # jnp operator (same symbol works)

    @property
    def data_type(self):
        return T.BOOLEAN

    def resolve(self, bound):
        left, right = bound
        if is_string_pair(left, right) or left.data_type == right.data_type:
            return type(self)(left, right)
        left, right, _ = coerce_numeric_pair(left, right)
        return type(self)(left, right)

    def eval_cpu(self, table: HostTable) -> HostColumn:
        l = self.left.eval_cpu(table)
        r = self.right.eval_cpu(table)
        data = _cpu_cmp_data(l, r, type(self).op).astype(np.bool_)
        validity = l.validity & r.validity
        return HostColumn(T.BOOLEAN, np.where(validity, data, False), validity)

    def prep(self, pctx: PrepCtx, child_preps) -> NodePrep:
        lp, rp = child_preps
        if lp.out_dict is not None and rp.out_dict is not None:
            p = align_string_dicts(pctx, lp, rp)
            return NodePrep(aux_slots=p.aux_slots, extra={"string": True})
        return NodePrep()

    def eval_dev(self, ctx: EvalCtx, child_vals, prep) -> DevVal:
        lval, rval = child_vals
        if prep.extra.get("string"):
            ld, rd = dev_aligned_codes(ctx, prep, lval, rval)
        else:
            ld, rd = lval.data, rval.data
        validity = null_and(lval.validity, rval.validity)
        if jnp.issubdtype(ld.dtype, jnp.floating):
            data = _spark_float_cmp(type(self).op, ld, rd, jnp)
        elif getattr(ld, "ndim", 1) == 2:
            # DECIMAL128 two-limb storage: compare the three-way sign
            data = type(self).op(_dec128_sign(ld, rd),
                                 jnp.zeros(ld.shape[0], jnp.int32))
        else:
            data = type(self).op(ld, rd)
        return DevVal(jnp.where(validity, data, False), validity)


class EqualTo(BinaryComparison):
    op = staticmethod(operator.eq)


class LessThan(BinaryComparison):
    op = staticmethod(operator.lt)


class LessThanOrEqual(BinaryComparison):
    op = staticmethod(operator.le)


class GreaterThan(BinaryComparison):
    op = staticmethod(operator.gt)


class GreaterThanOrEqual(BinaryComparison):
    op = staticmethod(operator.ge)


class EqualNullSafe(BinaryComparison):
    """<=> : never null; null <=> null is true."""

    op = staticmethod(operator.eq)

    @property
    def nullable(self):
        return False

    def eval_cpu(self, table):
        l = self.left.eval_cpu(table)
        r = self.right.eval_cpu(table)
        both_valid = l.validity & r.validity
        both_null = ~l.validity & ~r.validity
        eq = _cpu_cmp_data(l, r, operator.eq).astype(np.bool_)
        data = np.where(both_valid, eq, both_null)
        return HostColumn(T.BOOLEAN, data, np.ones(len(l), dtype=np.bool_))

    def eval_dev(self, ctx, child_vals, prep):
        lval, rval = child_vals
        if prep.extra.get("string"):
            ld, rd = dev_aligned_codes(ctx, prep, lval, rval)
        else:
            ld, rd = lval.data, rval.data
        if jnp.issubdtype(ld.dtype, jnp.floating):
            eq_data = _spark_float_cmp(operator.eq, ld, rd, jnp)
        elif getattr(ld, "ndim", 1) == 2:  # DECIMAL128 two-limb
            eq_data = _dec128_sign(ld, rd) == 0
        else:
            eq_data = ld == rd
        both_valid = lval.validity & rval.validity
        both_null = ~lval.validity & ~rval.validity
        data = jnp.where(both_valid, eq_data, both_null)
        return DevVal(data, jnp.ones_like(data, dtype=jnp.bool_))


class And(BinaryExpression):
    """Kleene logic: false AND null = false."""

    @property
    def data_type(self):
        return T.BOOLEAN

    def eval_cpu(self, table):
        l = self.left.eval_cpu(table)
        r = self.right.eval_cpu(table)
        lv, rv = l.validity, r.validity
        ld = l.data.astype(np.bool_) & lv
        rd = r.data.astype(np.bool_) & rv
        data = ld & rd
        # valid iff: both valid, or either side is a definite false
        validity = (lv & rv) | (lv & ~l.data.astype(np.bool_)) | (rv & ~r.data.astype(np.bool_))
        return HostColumn(T.BOOLEAN, np.where(validity, data, False), validity)

    def eval_dev(self, ctx, child_vals, prep):
        lval, rval = child_vals
        ld = lval.data & lval.validity
        rd = rval.data & rval.validity
        data = ld & rd
        validity = (lval.validity & rval.validity) | (lval.validity & ~lval.data) | (rval.validity & ~rval.data)
        return DevVal(jnp.where(validity, data, False), validity)


class Or(BinaryExpression):
    """Kleene logic: true OR null = true."""

    @property
    def data_type(self):
        return T.BOOLEAN

    def eval_cpu(self, table):
        l = self.left.eval_cpu(table)
        r = self.right.eval_cpu(table)
        lv, rv = l.validity, r.validity
        ld = l.data.astype(np.bool_) & lv
        rd = r.data.astype(np.bool_) & rv
        data = ld | rd
        validity = (lv & rv) | ld | rd
        return HostColumn(T.BOOLEAN, np.where(validity, data, False), validity)

    def eval_dev(self, ctx, child_vals, prep):
        lval, rval = child_vals
        ld = lval.data & lval.validity
        rd = rval.data & rval.validity
        data = ld | rd
        validity = (lval.validity & rval.validity) | ld | rd
        return DevVal(jnp.where(validity, data, False), validity)


class Not(UnaryExpression):
    @property
    def data_type(self):
        return T.BOOLEAN

    def eval_cpu(self, table):
        c = self.child.eval_cpu(table)
        data = ~c.data.astype(np.bool_)
        return HostColumn(T.BOOLEAN, np.where(c.validity, data, False), c.validity.copy())

    def eval_dev(self, ctx, child_vals, prep):
        (c,) = child_vals
        return DevVal(jnp.where(c.validity, ~c.data, False), c.validity)


class IsNull(UnaryExpression):
    @property
    def data_type(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def eval_cpu(self, table):
        c = self.child.eval_cpu(table)
        return HostColumn(T.BOOLEAN, ~c.validity, np.ones(len(c), dtype=np.bool_))

    def eval_dev(self, ctx, child_vals, prep):
        (c,) = child_vals
        # Padding rows carry validity False; mask with live-row mask so the
        # result is deterministic there (consumers mask anyway).
        return DevVal(~c.validity, jnp.ones_like(c.validity))


class IsNotNull(UnaryExpression):
    @property
    def data_type(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def eval_cpu(self, table):
        c = self.child.eval_cpu(table)
        return HostColumn(T.BOOLEAN, c.validity.copy(), np.ones(len(c), dtype=np.bool_))

    def eval_dev(self, ctx, child_vals, prep):
        (c,) = child_vals
        return DevVal(c.validity, jnp.ones_like(c.validity))


class IsNaN(UnaryExpression):
    @property
    def data_type(self):
        return T.BOOLEAN

    @property
    def nullable(self):
        return False

    def eval_cpu(self, table):
        c = self.child.eval_cpu(table)
        data = np.isnan(c.data) & c.validity
        return HostColumn(T.BOOLEAN, data, np.ones(len(c), dtype=np.bool_))

    def eval_dev(self, ctx, child_vals, prep):
        (c,) = child_vals
        return DevVal(jnp.isnan(c.data) & c.validity, jnp.ones_like(c.validity))


class In(Expression):
    """value IN (literals...). Spark semantics: true if match; null if no
    match and (value is null or any list element is null); else false."""

    def __init__(self, value: Expression, items: Sequence[Expression]):
        self.children = (value,) + tuple(items)

    @property
    def value(self):
        return self.children[0]

    @property
    def items(self):
        return self.children[1:]

    @property
    def data_type(self):
        return T.BOOLEAN

    def with_children(self, children):
        return In(children[0], children[1:])

    def key(self):
        return ("in", tuple(c.key() for c in self.children))

    def eval_cpu(self, table):
        from spark_rapids_tpu.ops.expr import Literal
        v = self.value.eval_cpu(table)
        n = len(v)
        has_null_item = any(isinstance(i, Literal) and i.value is None for i in self.items)
        match = np.zeros(n, dtype=np.bool_)
        vd = v.data
        if isinstance(v.dtype, T.StringType):
            vd = np.where(v.validity, vd, "")
        for item in self.items:
            i = item.eval_cpu(table)
            idata = i.data
            if isinstance(v.dtype, T.StringType):
                idata = np.where(i.validity, idata, "")
            match |= (vd == idata) & i.validity
        validity = v.validity & (match | ~np.full(n, has_null_item))
        return HostColumn(T.BOOLEAN, np.where(validity, match, False), validity)

    def prep(self, pctx, child_preps):
        vp = child_preps[0]
        slots = []
        if vp.out_dict is not None:
            for ip in child_preps[1:]:
                p = align_string_dicts(pctx, vp, ip)
                slots.extend(p.aux_slots)
            return NodePrep(aux_slots=tuple(slots), extra={"string": True})
        return NodePrep()

    def eval_dev(self, ctx, child_vals, prep):
        from spark_rapids_tpu.ops.expr import Literal
        v = child_vals[0]
        has_null_item = any(isinstance(i, Literal) and i.value is None for i in self.items)
        match = jnp.zeros_like(v.validity)
        for idx, iv in enumerate(child_vals[1:]):
            if prep.extra.get("string"):
                lmap = ctx.aux[prep.aux_slots[2 * idx]]
                rmap = ctx.aux[prep.aux_slots[2 * idx + 1]]
                ld = lmap[jnp.clip(v.data, 0, lmap.shape[0] - 1)]
                rd = rmap[jnp.clip(iv.data, 0, rmap.shape[0] - 1)]
            else:
                ld, rd = v.data, iv.data
            match = match | ((ld == rd) & iv.validity)
        validity = v.validity & (match | (not has_null_item))
        return DevVal(jnp.where(validity, match, False), validity)


class InSet(In):
    """Optimized IN over a large literal set (Spark converts In -> InSet
    past spark.sql.optimizer.inSetConversionThreshold). Identical
    semantics; the device evaluation inherits In's chain, which XLA
    fuses into one vectorized membership test."""
