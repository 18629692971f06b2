"""Order-isomorphic native-width sort operands.

TPU ALUs are 32-bit: i64 and f64 are storage-native but every compare/sort
op decomposes into emulated multi-op sequences, making ``lax.sort`` over
64-bit keys ~2x slower (and f64 bitcasts are NOT supported under the x64
rewrite at all — a single-operand f64 sort key cannot even be built the
cuDF way). Every sort/rank/compare in the engine therefore decomposes each
logical key into a LIST of <=32-bit operands whose lexicographic order
equals the value order:

  i64  -> (hi = x >> 32 as i32, lo = x & 0xffffffff as u32)
  f64  -> canonicalize (-0.0 -> 0.0, NaN -> one pattern), exact hi/lo f32
          split (TPU f64 IS an (f32, f32) pair), each component mapped to a
          monotone u32 (sign-flip trick; NaN sorts greater than +inf, which
          is Spark's NaN-last total order)
  f32  -> canonicalize + monotone u32
  bool -> i32
  <=32-bit ints / dictionary codes -> unchanged

On CPU backends f64 is native and the pair decomposition would LOSE
precision (two distinct f64 can share one (f32, f32) pair), so f64 there
uses the classic single-operand sortable-bits i64 bitcast instead.

(reference: SortUtils.scala / cuDF lexicographic comparators; the
decomposition itself is the TPU-native replacement for cuDF's typed
comparators.)"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from spark_rapids_tpu.ops.limbs import (
    f32_sortable_u32 as _f32_sortable_u32,
    split_f64_hi_lo,
    split_i64_hi_lo,
)


def _canon_float(d):
    d = jnp.where(d == 0.0, jnp.zeros_like(d), d)  # -0.0 == 0.0
    return jnp.where(jnp.isnan(d), jnp.full_like(d, jnp.nan), d)


def zero_invalid(data, validity):
    """jnp.where(validity, data, 0) with 2-D (dec128 limb) broadcasting."""
    v = validity[:, None] if getattr(data, "ndim", 1) == 2 else validity
    return jnp.where(v, data, jnp.zeros_like(data))


def comparable_operands(data) -> List[jax.Array]:
    """Decompose one key column into ascending-order operands. Callers add
    their own null-placement flag operand; invalid slots should be zeroed
    first (zero_invalid)."""
    d = data
    if getattr(d, "ndim", 1) == 2 and d.dtype == jnp.int64:
        # DECIMAL128 two-limb storage: signed high limb orders first,
        # then the unsigned low limb as two u32 words
        hi, lo = d[:, 0], d[:, 1]
        return [(hi >> 32).astype(jnp.int32),
                (hi & 0xFFFFFFFF).astype(jnp.uint32),
                ((lo >> 32) & 0xFFFFFFFF).astype(jnp.uint32),
                (lo & 0xFFFFFFFF).astype(jnp.uint32)]
    if d.dtype == jnp.int64:
        return list(split_i64_hi_lo(d))
    if d.dtype == jnp.float64:
        d = _canon_float(d)
        if jax.default_backend() == "cpu":
            # classic sortable-bits over the exact f64 pattern (CPU f64 is
            # native; the f32-pair split would merge distinct values):
            # negatives complement, positives flip the sign bit -> u64
            # order, emitted as a (u32 hi, u32 lo) word pair
            raw = jax.lax.bitcast_convert_type(d, jnp.int64)
            bits = jnp.where(raw < 0, ~raw,
                             raw ^ jnp.int64(-0x8000000000000000))
            return [((bits >> 32) & 0xFFFFFFFF).astype(jnp.uint32),
                    (bits & 0xFFFFFFFF).astype(jnp.uint32)]
        hi, lo = split_f64_hi_lo(d)
        return [_f32_sortable_u32(hi), _f32_sortable_u32(lo)]
    if d.dtype == jnp.float32:
        return [_f32_sortable_u32(_canon_float(d))]
    if d.dtype == jnp.bool_:
        return [d.astype(jnp.int32)]
    return [d]


def descending_operands(ops: List[jax.Array]) -> List[jax.Array]:
    """Order-reverse a comparable-operand list: bitwise complement reverses
    both signed i32 and unsigned u32 order component-wise, and equal tuples
    stay equal — so lexicographic order reverses exactly."""
    return [~o for o in ops]


def lex_sort(operands: List[jax.Array], payload: jax.Array) -> List[jax.Array]:
    """THE engine-wide lexicographic sort point: a stable sort of the
    rows by the operand tuple, the payload carried along. Every call
    site passes ``jnp.arange(capacity)`` as ``payload``, so the last
    output is the stable sorting permutation."""
    return jax.lax.sort(list(operands) + [payload],
                        num_keys=len(operands))


def operands_equal_adjacent(ops: List[jax.Array]) -> jax.Array:
    """rows[i] == rows[i-1] over the operand tuple (row 0 compares against
    the rolled-around last row; callers mask it)."""
    eq = None
    for o in ops:
        e = o == jnp.roll(o, 1)
        eq = e if eq is None else (eq & e)
    return eq
