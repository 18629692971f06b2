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
pack all import the one definition here. The upload's split is here
too: columnar/column.py stage_upload hands a DOUBLE column over as its
raw 64-bit words and the assemble program (columnar/table.py) splits
them with f64_bits_hi_lo, by integer operations, into the pair the host
split used to make.
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


def _round_up(q, round_bit, sticky):
    """1 where a right shift with quotient ``q`` rounds up to nearest
    even: the dropped bits are over half (the round bit and any bit
    below it), or exactly half and ``q`` odd; else 0 (all u32)."""
    return round_bit & (sticky | (q & 1))


def _low_mask(k):
    """u32 mask of the ``k`` low bits, 0 <= k <= 31."""
    return (jnp.uint32(1) << k) - 1


def f64_bits_hi_lo(bits):
    """The (hi, lo) f32 pair of f64 values handed over as their IEEE
    bits (an int64 array), computed by 32-bit integer operations alone,
    so no f64 arithmetic (an emulated pair on TPU) and no float rounding
    mode or denormal flush of the device takes part. Bit for bit the
    split a host makes in IEEE arithmetic: ``hi = f32(x)`` rounded to
    nearest even (into f32's subnormals, to zero, to +-inf past f32's
    range; a NaN keeps its sign and the top 23 bits of its payload and
    is made quiet), ``lo = f32(x - hi)`` the same way, ``lo = +0`` where
    hi is not finite, ``lo = hi`` where x is +-0 (split_f64_hi_lo's
    rules, so combine_f64 and the signed-zero select reassemble it).
    A bitcast of the words to f64 followed by split_f64_hi_lo compiles
    on a v5e but gives other bits for subnormals, ties and most low
    halves (PERF.md, PR 40), hence the integer path."""
    h32, low = split_i64_hi_lo(bits)
    high = jax.lax.bitcast_convert_type(h32, jnp.uint32)
    sign = high >> 31
    biased = ((high >> 20) & 0x7FF).astype(jnp.int32)
    mant_high = high & 0xFFFFF
    sig_high = jnp.where(biased != 0, mant_high | 0x100000, mant_high)
    # the significand's top 24 bits, and the 29 below them
    top24 = (sig_high << 3) | (low >> 29)
    rest29 = low & 0x1FFFFFFF

    # hi: an f32-normal result (biased 897..1150) drops the 29 bits; a
    # subnormal one drops k more of top24 (an f64 subnormal: all of it)
    k = jnp.where(biased == 0, 25,
                  jnp.clip(897 - biased, 0, 25)).astype(jnp.uint32)
    km1 = jnp.maximum(k, 1) - 1
    q = top24 >> k
    round_bit = jnp.where(k == 0, (rest29 >> 28) & 1, (top24 >> km1) & 1)
    below = jnp.where(k == 0, rest29 & 0x0FFFFFFF,
                      (top24 & _low_mask(km1)) | rest29)
    sticky = (below != 0).astype(jnp.uint32)
    up = _round_up(q, round_bit, sticky)
    # the biased f32 exponent less one: a carry of the rounding into bit
    # 24 bumps it (and past 2^128 makes exactly +inf's bits)
    exp_field = jnp.maximum(biased - 897, 0).astype(jnp.uint32)
    hi_mag = q + up + (exp_field << 23)
    hi_mag = jnp.where(biased > 1150, jnp.uint32(0x7F800000), hi_mag)
    is_nan = (mant_high | low) != 0
    hi_mag = jnp.where(
        biased == 0x7FF,
        jnp.where(is_nan, 0x7FC00000 | (top24 & 0x7FFFFF),
                  jnp.uint32(0x7F800000)),
        hi_mag)
    hi = (sign << 31) | hi_mag

    # lo: where hi is f32-normal (k == 0) the residual x - hi is exactly
    # r * 2^(biased - 1075) with r < 2^29 of the opposite sign if hi
    # rounded up; where hi is subnormal or zero |x - hi| <= 2^-150, which
    # rounds to a zero of the residual's sign
    r_sign = sign ^ up
    r = jnp.where(up != 0, (jnp.uint32(1) << 29) - rest29, rest29)
    lsb = biased - 1075
    top = 31 - jax.lax.clz(r).astype(jnp.int32)
    shift = jnp.maximum(top - 23, -149 - lsb)  # bits r drops (< 0: gains)
    s = jnp.clip(shift, 1, 29).astype(jnp.uint32)
    lq = r >> s
    lo_round = jnp.where(
        shift <= 0, r << jnp.clip(-shift, 0, 31).astype(jnp.uint32),
        lq + _round_up(lq, (r >> (s - 1)) & 1,
                       ((r & _low_mask(s - 1)) != 0).astype(jnp.uint32)))
    lo_mag = lo_round + (
        jnp.maximum(lsb + top + 126, 0).astype(jnp.uint32) << 23)
    r_nonzero = jnp.where(k == 0, r != 0, (round_bit | sticky) != 0)
    lo = jnp.where(r_nonzero,
                   (r_sign << 31) | jnp.where(k == 0, lo_mag, 0),
                   jnp.uint32(0))
    lo = jnp.where(hi_mag < 0x7F800000, lo, jnp.uint32(0))
    lo = jnp.where(((high & 0x7FFFFFFF) | low) == 0, hi, lo)
    return (jax.lax.bitcast_convert_type(hi, jnp.float32),
            jax.lax.bitcast_convert_type(lo, jnp.float32))


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
