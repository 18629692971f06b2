"""32-bit-safe columnar scatters.

XLA's TPU scatter for 64-bit element types is ~25x slower than for
32-bit (measured on v5e: 120ms vs 5ms for a 1M-row scatter-set — the
emulated wide type serializes; PERF.md). Every row compaction in the
engine (filter, join gather/compact, aggregate output packing, concat)
is a scatter of column payloads, and LONG/DOUBLE columns are the common
case — so every 64-bit payload is split into exact 32-bit halves,
scattered natively, and recombined. f64 splits via
ops/segsum.split_f64_hi_lo (exact on TPU where f64 storage IS an
(f32, f32) pair); i64 splits into sign-preserving hi/lo words.

The CPU backend (virtual-mesh tests) scatters 64-bit natively and skips
the split. Gathers don't need this treatment (64-bit gathers are only
~2x a 32-bit gather)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _split_worthwhile(dtype) -> bool:
    return (jax.default_backend() != "cpu"
            and dtype in (jnp.float64, jnp.int64, jnp.uint64))


def scatter_set(out_len: int, tgt, data, mode: str = "drop"):
    """``zeros(out_len, data.dtype).at[tgt].set(data, mode=mode)`` with
    64-bit payloads scattered as two 32-bit streams. Trailing dims ride
    along (a DECIMAL128 column is a (rows, 2) int64 limb matrix)."""
    shape = (out_len,) + data.shape[1:]
    if not _split_worthwhile(data.dtype):
        return jnp.zeros(shape, data.dtype).at[tgt].set(data, mode=mode)
    from spark_rapids_tpu.ops.limbs import (
        combine_f64,
        combine_i64,
        split_f64_hi_lo,
        split_i64_hi_lo,
    )
    if data.dtype == jnp.float64:
        hi, lo = split_f64_hi_lo(data)
        ohi = jnp.zeros(shape, jnp.float32).at[tgt].set(hi, mode=mode)
        olo = jnp.zeros(shape, jnp.float32).at[tgt].set(lo, mode=mode)
        return combine_f64(ohi, olo)
    hi, lo = split_i64_hi_lo(data)
    ohi = jnp.zeros(shape, jnp.int32).at[tgt].set(hi, mode=mode)
    olo = jnp.zeros(shape, jnp.uint32).at[tgt].set(lo, mode=mode)
    return combine_i64(ohi, olo).astype(data.dtype)


def scatter_pair(out_len: int, tgt, data, validity, mode: str = "drop"):
    """Scatter one column's (data, validity) to ``tgt`` slots."""
    od = scatter_set(out_len, tgt, data, mode=mode)
    ov = jnp.zeros(out_len, jnp.bool_).at[tgt].set(validity, mode=mode)
    return od, ov


def compact_pairs(datas, valids, keep, capacity: int):
    """THE row-compaction point: compact every column's (data,
    validity) to the kept-row prefix, one scatter_pair a column.
    Returns ([(data, validity)...], new_n)."""
    keep_i = keep.astype(jnp.int32)
    new_n = jnp.sum(keep_i)
    tgt = jnp.where(keep, jnp.cumsum(keep_i) - 1, capacity)
    return [scatter_pair(capacity, tgt, d, v)
            for d, v in zip(datas, valids)], new_n
