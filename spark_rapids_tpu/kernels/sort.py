"""Pallas multi-column sort: a bitonic network over packed key limbs.

The HLO path (``jax.lax.sort`` with the ops/ordering.py operand
decomposition) already avoids emulated 64-bit COMPARES, but XLA still
materializes every operand between comparator stages in HBM. This
kernel runs the whole bitonic network over all key operands + the
payload in ONE fused program: operands stay resident (VMEM within the
``spark.rapids.tpu.kernels.vmemBudgetBytes`` envelope), each
compare-exchange is a vectorized lexicographic compare over the ≤32-bit
limb tuple, and the payload permutation rides the same swaps — no
per-stage HBM round trips and no separate gather pass.

Bit-identity with ``lax.sort``: callers pass a UNIQUE i32 row-index
iota as the payload (ops/ordering.lex_sort contract). The kernel sorts
with the payload as the FINAL tiebreak key, which makes every row tuple
unique — and a total-order bitonic sort of unique tuples produces
exactly the stable sort lax.sort defines. Shapes outside the envelope
(non-power-of-two capacity, >32-bit operands, over-budget working sets)
raise :class:`~spark_rapids_tpu.kernels.KernelIneligible` and the call
falls back to lax.sort.
"""

from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from spark_rapids_tpu.kernels import (
    KernelIneligible,
    compiler_params,
    config,
    interpret_mode,
)
from spark_rapids_tpu.runtime.faults import fault_point


def _lex_cmp(a_list, b_list):
    """(a > b, a == b) over the lexicographic operand tuple."""
    gt = None
    eq = None
    for a, b in zip(a_list, b_list):
        g = a > b
        e = a == b
        gt = g if gt is None else gt | (eq & g)
        eq = e if eq is None else eq & e
    return gt, eq


def _substage(arrs, n, k, j):
    """One compare-exchange substage of the bitonic network: partner
    distance d = 2^j inside (ascending/descending alternating) blocks
    of 2^k elements. Element i pairs with i^d via the (n/2d, 2, d)
    reshape; the direction bit of the pair is bit (k-j-1) of the major
    index."""
    d = 1 << j
    half = n // (2 * d)
    r = jax.lax.broadcasted_iota(jnp.int32, (half, d), 0)
    asc = ((r >> (k - j - 1)) & 1) == 0
    a_list, b_list = [], []
    for x in arrs:
        xr = x.reshape(half, 2, d)
        a_list.append(xr[:, 0, :])
        b_list.append(xr[:, 1, :])
    gt, eq = _lex_cmp(a_list, b_list)
    swap = jnp.where(asc, gt, (~gt) & (~eq))
    out = []
    for a, b in zip(a_list, b_list):
        na = jnp.where(swap, b, a)
        nb = jnp.where(swap, a, b)
        out.append(jnp.stack([na, nb], axis=1).reshape(n))
    return out


def _build(n: int, dtypes):
    log2n = n.bit_length() - 1
    n_arr = len(dtypes)

    def kernel(*refs):
        arrs = [refs[i][:] for i in range(n_arr)]
        for k in range(1, log2n + 1):
            for j in range(k - 1, -1, -1):
                arrs = _substage(arrs, n, k, j)
        for i, x in enumerate(arrs):
            refs[n_arr + i][:] = x

    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct((n,), dt) for dt in dtypes],
        compiler_params=compiler_params(),
        interpret=interpret_mode())


def sort_with_payload(operands: List[jax.Array],
                      payload: jax.Array) -> List[jax.Array]:
    """``lax.sort(operands + [payload], num_keys=len(operands))``,
    fused. ``payload`` must be a unique i32 iota (see module doc)."""
    fault_point("kernels.sort")
    arrs = list(operands) + [payload]
    n = payload.shape[0]
    if n < 2 or (n & (n - 1)) != 0:
        raise KernelIneligible(f"capacity {n} is not a power of two")
    for a in arrs:
        if getattr(a, "ndim", 1) != 1:
            raise KernelIneligible("sort operands must be 1-D")
        if a.dtype.itemsize > 4:
            raise KernelIneligible(f"operand dtype {a.dtype} is wider "
                                   "than one 32-bit limb")
    # in + out + one compare-exchange working copy
    if 3 * sum(a.dtype.itemsize * n for a in arrs) > config().vmem_budget:
        raise KernelIneligible("sort working set exceeds the VMEM budget")
    from spark_rapids_tpu.dispatch import pallas_program
    key = ("sort", n, tuple(str(a.dtype) for a in arrs),
           config().vmem_budget)
    fn = pallas_program(key, lambda: _build(n, [a.dtype for a in arrs]))
    return list(fn(*arrs))
