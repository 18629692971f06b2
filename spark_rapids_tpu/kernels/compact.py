"""Pallas row compaction: mask -> one gather kernel over every column.

Row compaction — in every filter, join output, aggregate output pack
and split — is THE cost PERF.md's round-4 measurement pinned: the HLO
path scatters every column, and every 64-bit column scatters as 2-3
32-bit passes plus a recombine chain (ops/scatter32.py), so an
8-column table pays ~20 scatter passes over HBM.

This kernel inverts the data movement: ONE i32 scatter builds the
gather map (``sel[j]`` = source row of output slot j — the scatter's
payload is row indices, never column data), and a single fused kernel
then gathers every column's 32-bit limb streams through ``sel`` in one
pass, zeroing the dead tail exactly like the scatter path's zero-init
does. Scatter passes no longer scale with column count or width.

The limb policy matches ops/scatter32.py: 64-bit streams split on
backends where 64-bit scatter/gather serializes (non-CPU), and ride
natively on the CPU backend — where splitting f64 would be lossy and
the native gather is free. Either way the result is bit-identical to
the scatter_pair loop (pinned by tests/test_kernels.py).
"""

from __future__ import annotations

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


def _split_streams(datas, valids):
    """Flatten (data, validity) pairs into ≤32-bit gather streams plus
    a recombine recipe. Streams for one column: its validity plus
    either the raw array (narrow dtypes / CPU backend) or the two limb
    halves."""
    from spark_rapids_tpu.ops.limbs import split_f64_hi_lo, split_i64_hi_lo
    from spark_rapids_tpu.ops.scatter32 import _split_worthwhile
    streams = []
    recipe = []  # (kind, dtype) per column, kinds: raw | f64 | i64
    for d, v in zip(datas, valids):
        if not _split_worthwhile(d.dtype):
            streams.append(d)
            recipe.append(("raw", d.dtype))
        elif d.dtype == jnp.float64:
            hi, lo = split_f64_hi_lo(d)
            streams.extend((hi, lo))
            recipe.append(("f64", d.dtype))
        else:
            hi, lo = split_i64_hi_lo(d)
            streams.extend((hi, lo))
            recipe.append(("i64", d.dtype))
        streams.append(v)
    return streams, recipe


def _recombine(outs, recipe):
    from spark_rapids_tpu.ops.limbs import combine_f64, combine_i64
    pairs = []
    i = 0
    for kind, dtype in recipe:
        if kind == "raw":
            data = outs[i]
            i += 1
        elif kind == "f64":
            data = combine_f64(outs[i], outs[i + 1])
            i += 2
        else:
            data = combine_i64(outs[i], outs[i + 1]).astype(dtype)
            i += 2
        pairs.append((data, outs[i]))
        i += 1
    return pairs


def gather_compact(datas, valids, keep, pos, new_n, capacity: int):
    """[(data, validity)...] compacted to the row prefix — bit-identical
    to the per-column scatter_pair loop. ``pos`` is the exclusive-style
    cumsum position (cumsum(keep)-1) the caller already computed; the
    gather map inverts it with ONE i32 scatter."""
    fault_point("kernels.compact")
    nbytes = 0
    for d in datas:
        nbytes += d.dtype.itemsize * d.size + capacity  # data + validity
    if 3 * nbytes > config().vmem_budget:
        raise KernelIneligible("compaction working set exceeds the VMEM "
                               "budget")
    tgt = jnp.where(keep, pos, capacity)
    sel = jnp.zeros((capacity,), jnp.int32).at[tgt].set(
        jnp.arange(capacity, dtype=jnp.int32), mode="drop")
    out_live = jnp.arange(capacity, dtype=jnp.int32) < new_n

    streams, recipe = _split_streams(datas, valids)
    shapes = tuple((s.shape, str(s.dtype)) for s in streams)

    from spark_rapids_tpu.dispatch import pallas_program
    key = ("compact", capacity, shapes, config().vmem_budget)

    def build():
        def kernel(*refs):
            n_in = len(streams)
            sel_v = refs[0][:]
            live_v = refs[1][:]
            for i in range(n_in):
                x = refs[2 + i][:]
                g = jnp.take(x, sel_v, axis=0)
                mask = live_v if x.ndim == 1 else live_v[:, None]
                refs[2 + n_in + i][:] = jnp.where(mask, g,
                                                  jnp.zeros_like(g))

        return pl.pallas_call(
            kernel,
            out_shape=[jax.ShapeDtypeStruct(s.shape, s.dtype)
                       for s in streams],
            compiler_params=compiler_params(),
            interpret=interpret_mode())

    fn = pallas_program(key, build)
    outs = fn(sel, out_live, *streams)
    return _recombine(list(outs), recipe)
