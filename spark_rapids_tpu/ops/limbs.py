"""Two-limb 64-bit layout — the single source of truth.

TPU ALUs are 32-bit: f64 storage IS an (f32, f32) pair and i64 compute
emulates through 32-bit word sequences, so every hot path in the engine
represents a 64-bit value as TWO native 32-bit limbs. What that means
for a double on the ``tpu`` backend (chip_smoke.py's representation
probe on a v5e, jax 0.9.0 / libtpu 0.0.34; PERF.md "the number
format"): about 48 mantissa bits (1+2^-52 and 2^53-1 survive; 1/3 and
0.1 come back a few 1e-16 off), f32's exponent range (|x| > f32 max is
+-inf; below f32's normal range the value is a denormal high limb or 0),
-0.0 comes back +0.0, NaN and +-inf are kept. i64 is exact.

  f64 -> (hi = f32(x), lo = f32(x - hi)) — EXACT on TPU because the
         storage itself is the pair; hi rounds monotonically, so
         (hi, lo) also orders lexicographically like the value.
  i64 -> (hi = x >> 32 as i32, lo = x & 0xffffffff as u32) — the
         (signed high word, unsigned low word) pair orders
         lexicographically like the value.

Before this module the split/recombine recipes were hand-rolled in
three places (ops/scatter32.py, ops/segsum.py, segment_minmax_64) and
had started to drift; now the scatter/sort/segment paths and the d2h
pack all import the one definition here. The numpy staging variant
(host-side upload split) remains in columnar/column.py stage_upload —
it runs on host buffers before any device array exists.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

#: low-word mask, usable against i64 without promotion surprises
M32 = 0xFFFFFFFF


def split_f64_hi_lo(x):
    """EXACT hi/lo f32 decomposition of a device f64 array (TPU f64
    storage is an (f32, f32) pair, so x == hi + lo exactly). Non-finite
    hi (inf from overflow, NaN) gets lo=0 so hi+lo reproduces the
    special value instead of inf-inf=NaN. Signed zero: -0.0 - (-0.0) =
    +0.0 and -0.0 + 0.0 = +0.0 would lose the sign on reconstruction,
    so the signed zero is carried in lo too."""
    hi = x.astype(jnp.float32)
    lo = jnp.where(jnp.isfinite(hi),
                   (x - hi.astype(jnp.float64)).astype(jnp.float32), 0.0)
    lo = jnp.where(x == 0.0, hi, lo)
    return hi, lo


def combine_f64(hi, lo):
    """Reassemble a split f64: exact for every value split_f64_hi_lo
    produced on a backend where the split round-trips (TPU always; CPU
    backends with the split forced on can lose values outside f32
    range — callers there guard with a reconstruction check)."""
    return hi.astype(jnp.float64) + lo.astype(jnp.float64)


def split_i64_hi_lo(x):
    """(hi i32, lo u32) two-limb decomposition of an integer array.
    value == (hi << 32) | lo, and (signed hi, unsigned lo) orders
    lexicographically like the i64 value."""
    d = x.astype(jnp.int64)
    return ((d >> 32).astype(jnp.int32),
            (d & jnp.int64(M32)).astype(jnp.uint32))


def combine_i64(hi, lo):
    """Reassemble a split i64 from its (i32 hi, u32 lo) limbs."""
    return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)


def f32_sortable_u32(x) -> jax.Array:
    """Monotone map f32 -> u32 (IEEE sortable-bits trick): negatives
    complement, non-negatives set the top bit, so unsigned order equals
    the float total order with NaN (canonicalized positive pattern)
    greatest — Spark's NaN-last ordering."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return jnp.where(b < 0,
                     (~b).astype(jnp.uint32),
                     b.astype(jnp.uint32) | jnp.uint32(0x80000000))
