"""Fast f64 segmented sums for the TPU.

On TPU, float64 storage is native but every compute op is emulated (XLA
rewrites f64 into (f32, f32) pair arithmetic), and the scatter-add inside an
emulated-f64 ``segment_sum`` dominates aggregation time (~5x the cost of the
f32 one). ``segment_sum_f64`` computes the same reduction through an EXACT
hi/lo f32 decomposition — on TPU every f64 value is exactly ``f32(x) +
f32(x - f32(x))`` because the storage itself is an f32 pair:

  1. per-(segment, block) partial sums of ``hi`` and ``lo`` run as plain f32
     scatter-adds (a block of 1024 rows bounds f32 accumulation error);
  2. the (num_segments * num_blocks) partials combine in emulated f64 —
     tiny compared to the input.

Accuracy: the decomposition is exact; the only rounding is f32 accumulation
within one block. That error scales with the segment's ABSOLUTE mass
(sum |x|), so the kernel self-checks at runtime: alongside the split sums it
accumulates per-segment |hi| mass and reroutes the whole batch to the exact
emulated path (``lax.cond``) whenever the estimated error could exceed 1e-6
relative — which catches both huge magnitudes (|x| > 1e34 would overflow an
f32 block partial) and catastrophic cancellation (mass >> |sum|). On
well-conditioned data (TPC-style positive measures) the observed error is
~1e-9 relative (tests/test_agg_fastpath.py).

Per-group ROW COUNTS ride a one-hot contraction too (``segment_counts``,
below) whenever the sums do: 0/1 masks against the 0/1 one-hot are exact
in any MXU operand type, so they cost one int8 pass into int32, not
'highest''s six, and no scatter is left on the small-segment path. One
predicate (``takes_contraction``) decides "small segment count, whole
blocks -> contraction" for both.

This is the same class of trade the reference makes for float aggregation:
GPU float sums differ from CPU Spark in ULPs by reduction order and are
gated by ``spark.rapids.sql.variableFloatAgg.enabled``
(reference: aggregate.scala GpuSum, RapidsConf.scala). The exact emulated
path stays available via ``spark.rapids.tpu.sum.splitF64=false``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# the limb split/recombine recipes live in ops/limbs.py (their single
# source of truth); re-exported here because half the engine
# historically imported them from this module
from spark_rapids_tpu.ops.limbs import (  # noqa: F401
    combine_f64,
    combine_i64,
    split_f64_hi_lo,
    split_i64_hi_lo,
)

#: rows per f32 partial-sum block — bounds f32 accumulation error
BLOCK = 1024

#: batches with |x| above this could overflow an f32 block partial
SPLIT_MAX_ABS = 1e34

#: error estimate per unit of absolute segment mass (eps_f32 with an 8x
#: safety margin over the random-walk expectation)
ERR_PER_MASS = 4.8e-7

#: the split result is accepted when est. error <= RTOL * |sum| + ATOL
RTOL = 1e-6
ATOL = 1e-12

#: don't let (num_segments * num_blocks) partials outgrow the input
MAX_PARTIALS = 1 << 22


def trace_key():
    """Tuning values that change the shape of a traced kernel — any
    trace cache keyed on split-sum behavior must include this (a cached
    trace would silently keep a superseded conf value otherwise)."""
    return (BLOCK, MAX_PARTIALS, MATMUL_MAX_SEGMENTS, float(SPLIT_MAX_ABS))


def resolve_split_mode(conf) -> bool:
    """Resolve spark.rapids.tpu.sum.splitF64 ('auto' = split on non-CPU
    backends, where f64 is emulated; CPU f64 is native and exact)."""
    from spark_rapids_tpu.conf import SPLIT_F64_SUM
    mode = str(conf.get_entry(SPLIT_F64_SUM)).strip().lower()
    if mode in ("true", "1", "on"):
        return True
    if mode in ("false", "0", "off"):
        return False
    return jax.default_backend() != "cpu"


#: one-hot MXU matmul partials when num_segments is at most this (the
#: materialized one-hot costs capacity*num_segments*4 bytes of HBM traffic)
MATMUL_MAX_SEGMENTS = 32


def _blocking(capacity: int):
    """(rows per block, whole blocks in ``capacity``): the blocked
    reductions apply when their product is ``capacity``."""
    block = min(BLOCK, capacity)
    return block, max(capacity // block, 1)


def takes_contraction(num_segments: int, capacity: int) -> bool:
    """True where a segmented reduction runs as the blocked one-hot
    contraction on the MXU instead of a scatter: few segments (the
    one-hot is ``capacity * num_segments`` entries) over whole blocks."""
    block, nb = _blocking(capacity)
    return (num_segments <= MATMUL_MAX_SEGMENTS and nb * block == capacity
            and nb * num_segments <= MAX_PARTIALS)


def segment_counts(masks, gid, num_segments: int, capacity: int):
    """Rows per segment under each of several boolean masks, in one pass.

    ``masks``: list of (capacity,) bool arrays; ``gid`` int32 in
    [0, num_segments). Returns (num_segments, len(masks)) int32, column i
    the per-segment count of ``masks[i]``'s true rows: the integers
    ``numpy.bincount(gid, weights=masks[i])`` gives.

    Where ``takes_contraction`` holds (the sums' own condition: one
    predicate decides), the counts are the contraction of the masks
    against the one-hot of ``gid`` over the rows: no scatter, which the
    TPU serialises on duplicate indices. They are EXACT, and need none of
    the f32 sums' 'highest' (six MXU passes): mask and one-hot entries
    are 0 or 1, so every product is exact in any operand type the MXU
    has, and int8 operands accumulate in int32, where a count of at most
    ``capacity`` < 2^31 rows cannot round. So the counts need no blocks
    either (the sums' blocks bound f32 accumulation error; bf16 or
    one-pass f32 operands would need them, a partial being exact in an
    f32 accumulator only up to 2^24): on a v5e the one unblocked int8 dot
    ran 8% under the blocked forms inside Q1's program and kept its time
    when they moved with XLA's layout choices (PERF.md, PR 26). Rows stay
    on the lane axis ((k, capacity), never (capacity, k): a minor
    dimension of k pads to the 128-lane tile).

    Elsewhere: one 2-D scatter up to 4096 segments (the lane padding of
    its OUTPUT is cheap there), per-mask 1-D scatters above (the padded
    (num_segments, 128-lane) output would dwarf the input re-reads)."""
    k = len(masks)
    if k == 0:
        return jnp.zeros((num_segments, 0), dtype=jnp.int32)
    if takes_contraction(num_segments, capacity):
        return jnp.einsum(
            'kc,cg->gk', jnp.stack(masks, axis=0).astype(jnp.int8),
            jax.nn.one_hot(gid, num_segments, dtype=jnp.int8),
            preferred_element_type=jnp.int32)
    if num_segments <= 4096:
        return jax.ops.segment_sum(
            jnp.stack(masks, axis=1).astype(jnp.int32), gid,
            num_segments=num_segments)
    return jnp.stack(
        [jax.ops.segment_sum(mk.astype(jnp.int32), gid,
                             num_segments=num_segments)
         for mk in masks], axis=1)


def batched_segment_sum_f64(cols, gid, num_segments: int, capacity: int,
                            use_split: bool, counts=None):
    """Segmented sums of several f64 columns in ONE device pass.

    ``cols``: list of (capacity,) f64 arrays, invalid slots zeroed. Returns
    (num_segments, len(cols)) f64. Small segment counts reduce hi/lo/|hi|
    f32 streams with one blocked one-hot einsum on the MXU; medium counts
    use blocked 2-D scatter partials; large counts (beyond MAX_PARTIALS)
    take _batched_unblocked_split's per-stream 1-D scatters with the
    count-scaled guard. All paths share the exact-fallback guard (the
    whole batch reroutes if ANY column is risky); ``counts`` optionally
    feeds the unblocked guard a precomputed row-count bound."""
    m = len(cols)
    if m == 0:
        return jnp.zeros((num_segments, 0), dtype=jnp.float64)
    block, nb = _blocking(capacity)
    if not use_split or cols[0].dtype != jnp.float64 or nb * block != capacity:
        return jax.ops.segment_sum(jnp.stack(cols, axis=1), gid,
                                   num_segments=num_segments)
    if nb * num_segments > MAX_PARTIALS:
        # large segment counts (int-domain fast-path group-bys): per-block
        # partials would outgrow the input, but the emulated-f64 scatter
        # fallback is the single most expensive op on TPU — run the
        # UNBLOCKED split instead (f32 scatters + count-scaled guard)
        return _batched_unblocked_split(cols, gid, num_segments,
                                        counts=counts)

    with jax.named_scope("split_sums"):
        his, los, abss = [], [], []
        for c in cols:
            hi, lo = split_f64_hi_lo(c)
            his.append(hi)
            los.append(lo)
            abss.append(jnp.abs(hi))
        streams = his + los + abss  # 3m f32 streams of (capacity,)

    with jax.named_scope("block_partials"):
        if takes_contraction(num_segments, capacity):
            parts = _onehot_block_partials(streams, gid, num_segments,
                                           nb, block)
        else:
            blk = jnp.arange(capacity, dtype=jnp.int32) // block
            ids = blk * num_segments + gid
            parts = jax.ops.segment_sum(
                jnp.stack(streams, axis=1), ids,
                num_segments=nb * num_segments
            ).reshape(nb, num_segments, 3 * m)
    with jax.named_scope("merge"):
        p64 = parts.astype(jnp.float64).sum(axis=0)  # (num_segments, 3m)
        shi, slo, mass = p64[:, :m], p64[:, m:2 * m], p64[:, 2 * m:]
        split_sum = shi + slo

        err_est = mass * ERR_PER_MASS
        risky = err_est > (jnp.abs(split_sum) * RTOL + ATOL)
        has_big = jnp.any(mass * 0 != 0) | jnp.any(
            jnp.stack([jnp.max(a) for a in abss]) > SPLIT_MAX_ABS)
        bad = jnp.any(risky) | has_big

        def exact(_):
            # per-column 1-D scatters: a stacked (capacity, m) f64 operand
            # pads m to the 128-lane tile, 1 KiB a row, and XLA sizes the
            # program for this branch whether or not it ever runs (at
            # 2^24 rows it was two 8 GiB allocations, "Used 17.13G of
            # 15.75G hbm": the aggregate did not compile there)
            return jnp.stack(
                [jax.ops.segment_sum(c, gid, num_segments=num_segments)
                 for c in cols], axis=1)

        return jax.lax.cond(bad, exact, lambda _: split_sum,
                            jnp.zeros((), dtype=jnp.int32))


def _onehot_block_partials(streams, gid, num_segments: int, nb: int,
                           block: int):
    """Per-(block, segment) f32 partial sums of each stream, shape
    (nb, num_segments, len(streams)): the one-hot contraction over a
    block's rows at 'highest' (f32-faithful) precision. How the operands
    are written does not matter (XLA lays them out itself); what does is
    that nothing around it materialises a (capacity, k) array, 512 B a
    row once the minor dimension pads to the 128-lane tile."""
    k = len(streams)
    x = jnp.stack(streams, axis=0).reshape(k, nb, block)
    oh = jax.nn.one_hot(gid.reshape(nb, block), num_segments,
                        dtype=jnp.float32)
    return jnp.einsum('knb,nbg->ngk', x, oh, precision='highest')


def _batched_unblocked_split(cols, gid, num_segments: int, counts=None):
    """Unblocked split for SEVERAL f64 columns at a large segment count.

    Every 1-D scatter pass over the input costs ~100ms at 4M rows on TPU
    (XLA scatter with duplicate indices serializes), so the pass count IS
    the cost model here:
      - hi and lo streams: one scatter each (unavoidable — the sums);
      - |hi| mass for the error guard: SKIPPED when every value is
        globally non-negative (then mass == hi sum exactly — the
        TPC-measure common case), else one scatter per column via
        lax.cond;
      - per-segment row count for the guard's scale term: callers that
        already scattered nonnull counts (the aggregate kernels) pass
        them via ``counts`` ((num_segments,) or (num_segments, m) i32,
        an UPPER bound on contributing rows) and the scatter is skipped.
    Per-stream 1-D scatters, never a (capacity, 3m) 2-D scatter: the TPU
    lane width is 128 and a 2-D scatter pads the tiny minor dim to it."""
    m = len(cols)
    with jax.named_scope("split_sums"):
        his, tops, rests, los = [], [], [], []
        for c in cols:
            hi, lo = split_f64_hi_lo(c)
            # hi goes in as its leading 12 significant bits and the 12
            # that follow, each exactly: an f32 scatter-add of the
            # leading parts is EXACT while a segment's rows span under
            # 2^12 in count and magnitude together (a group of a join's
            # few rows always), and the roundings of the two small
            # streams lie 2^-12 lower, so such a sum is good to about
            # 2^-36 where one stream of hi rounded at 2^-24 an add, as
            # float32 arithmetic does. Larger segments round as before.
            top = jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(hi, jnp.uint32)
                & jnp.uint32(0xFFFFF000), jnp.float32)
            his.append(hi)
            tops.append(top)
            rests.append(jnp.where(jnp.isfinite(hi), hi - top, 0.0))
            los.append(lo)
        parts = jnp.stack(
            [jax.ops.segment_sum(st, gid, num_segments=num_segments)
             for st in tops + rests + los], axis=1)
    if counts is None:
        any_nz = jnp.zeros(cols[0].shape, dtype=jnp.bool_)
        for c in cols:
            any_nz = any_nz | (c != 0.0)
        cnt2 = jax.ops.segment_sum(any_nz.astype(jnp.int32), gid,
                                   num_segments=num_segments)[:, None]
    else:
        cnt2 = counts if counts.ndim == 2 else counts[:, None]
    with jax.named_scope("merge"):
        p64 = parts.astype(jnp.float64)
        # small streams first: their sum loses nothing to the large one
        slow = p64[:, m:2 * m] + p64[:, 2 * m:]
        shi = p64[:, :m] + p64[:, m:2 * m]
        split_sum = p64[:, :m] + slow

        all_nonneg = jnp.ones((), dtype=jnp.bool_)
        for hi in his:
            all_nonneg = all_nonneg & jnp.all(hi >= 0)

        def mass_from_hi(_):
            return shi

        def mass_scatter(_):
            return jnp.stack(
                [jax.ops.segment_sum(jnp.abs(hi), gid,
                                     num_segments=num_segments)
                 for hi in his], axis=1).astype(jnp.float64)

        mass = jax.lax.cond(all_nonneg, mass_from_hi, mass_scatter,
                            jnp.zeros((), dtype=jnp.int32))

        scale = jnp.sqrt(jnp.maximum(cnt2.astype(jnp.float64) / BLOCK, 1.0))
        err_est = ERR_PER_MASS * scale * mass
        risky = err_est > (jnp.abs(split_sum) * RTOL + ATOL)
        has_big = jnp.zeros((), dtype=jnp.bool_)
        for c in cols:
            has_big = has_big | jnp.any(jnp.abs(c) > SPLIT_MAX_ABS)
        has_nonfinite = ~jnp.all(jnp.isfinite(mass))
        bad = jnp.any(risky) | has_big | has_nonfinite

        def exact(_):
            return jax.ops.segment_sum(jnp.stack(cols, axis=1), gid,
                                       num_segments=num_segments)

        return jax.lax.cond(bad, exact, lambda _: split_sum,
                            jnp.zeros((), dtype=jnp.int32))


def segment_minmax_64(is_min: bool, sd, sv, gid, num_segments: int):
    """Exact 64-bit segment min/max through NATIVE 32-bit scatters.

    The emulated-64-bit compare-select inside a scatter is the most
    expensive segment op on TPU (~100ms at 1M rows x 32k segments, vs
    sub-ms for a 32-bit scatter). Both 64-bit dtypes order
    lexicographically by (high limb, low limb):

      f64: x == hi + lo with hi = f32(x) (monotone rounding) and the
           residual lo carrying the tie-break — reduce hi with a native
           f32 scatter, then reduce lo over rows whose hi equals the
           winner; mhi + mlo reconstructs the winning f64 EXACTLY.
      i64: (top 32 bits signed, low 32 bits unsigned).

    Float NaN follows Spark's ordering (NaN greatest): max yields NaN if
    any NaN; min ignores NaN unless the segment is all-NaN. Returns
    per-segment values with EMPTY segments undefined (callers mask by
    their own has_any). reference: GpuMin/GpuMax in aggregate.scala run
    cudf device reductions; this is the TPU-shaped equivalent."""
    red = jax.ops.segment_min if is_min else jax.ops.segment_max

    def _limb_minmax(hi, lo, use, hi_ident, lo_ident):
        """(per-segment hi winner, lo tiebreak among the rows that
        hold it): two native 32-bit segment reductions."""
        mhi = red(jnp.where(use, hi, hi_ident), gid,
                  num_segments=num_segments)
        cand = use & (hi == mhi[gid])
        mlo = red(jnp.where(cand, lo, lo_ident), gid,
                  num_segments=num_segments)
        return mhi, mlo

    if sd.dtype == jnp.float64:
        isnan = jnp.isnan(sd) & sv
        use = sv & ~isnan
        hi, lo = split_f64_hi_lo(sd)

        def fast(_):
            ident = np.float32(np.inf if is_min else -np.inf)
            mhi, mlo = _limb_minmax(hi, lo, use, ident, ident)
            return combine_f64(mhi, mlo)

        def exact(_):
            ident = jnp.float64(jnp.inf if is_min else -jnp.inf)
            return red(jnp.where(use, sd, ident), gid,
                       num_segments=num_segments)

        # On TPU f64 IS an (f32, f32) pair so the split is exact for every
        # representable value; on CPU backends with split forced on, values
        # outside f32 range (overflow to inf) or below it (subnormal /
        # underflow-to-zero) don't round-trip — reroute to the emulated-64
        # reduction whenever hi+lo fails to reconstruct any used input.
        recon = hi.astype(jnp.float64) + lo.astype(jnp.float64)
        lossy = jnp.any(use & ~jnp.isnan(sd) & (recon != sd))
        out = jax.lax.cond(lossy, exact, fast,
                           jnp.zeros((), dtype=jnp.int32))
        any_nan = jax.ops.segment_max(isnan.astype(jnp.int32), gid,
                                      num_segments=num_segments) > 0
        if is_min:
            n_use = jax.ops.segment_sum(use.astype(jnp.int32), gid,
                                        num_segments=num_segments)
            return jnp.where(any_nan & (n_use == 0), jnp.float64(jnp.nan), out)
        return jnp.where(any_nan, jnp.float64(jnp.nan), out)
    hi, lo = split_i64_hi_lo(sd)
    info = jnp.iinfo(jnp.int32)
    hi_ident = np.int32(info.max if is_min else info.min)
    lo_ident = np.uint32(0xFFFFFFFF if is_min else 0)
    mhi, mlo = _limb_minmax(hi, lo, sv, hi_ident, lo_ident)
    return combine_i64(mhi, mlo)


def _unblocked_split_segment_sum(v, gid, num_segments: int):
    """Split path for LARGE segment counts (sorted-path aggregates run
    with num_segments == capacity, where per-block partials would outgrow
    the input): the m=1 case of _batched_unblocked_split — ONE guard
    implementation serves both (code-review r5: three hand-rolled copies
    of the error model drifted apart)."""
    return _batched_unblocked_split([v], gid, num_segments)[:, 0]


def segment_sum_f64(v, gid, num_segments: int, capacity: int,
                    use_split: bool, counts=None):
    """segment_sum for f64 ``v`` (invalid slots must already be zeroed).

    ``gid`` must be int32 in [0, num_segments). Non-f64 dtypes and
    disabled split configurations take the plain jax.ops.segment_sum
    path; oversized configurations (num_segments*blocks would outgrow
    the input) and a segment a row (the sorted-path aggregate, whose
    groups are a few rows each) take the guarded UNBLOCKED split path:
    per-block f32 partials would round a group's sum once in f32, as
    float32 arithmetic does, where the unblocked split keeps it to the
    pair's precision (at 2^16 rows and fewer the blocked partials were
    taken, 64 times the input's slots). ``counts``: an optional
    caller-scattered per-segment row-count upper bound — the unblocked
    guard reuses it instead of scattering its own."""
    if v.dtype != jnp.float64 or not use_split:
        return jax.ops.segment_sum(v, gid, num_segments=num_segments)
    block = min(BLOCK, capacity)
    nb = max(capacity // block, 1)
    if nb * block != capacity or nb * num_segments > MAX_PARTIALS \
            or num_segments >= capacity:
        if counts is not None:
            return _batched_unblocked_split([v], gid, num_segments,
                                            counts=counts)[:, 0]
        return _unblocked_split_segment_sum(v, gid, num_segments)

    with jax.named_scope("block_partials"):
        hi, lo = split_f64_hi_lo(v)
        blk = jnp.arange(capacity, dtype=jnp.int32) // block
        ids = blk * num_segments + gid
        phi = jax.ops.segment_sum(hi, ids, num_segments=nb * num_segments)
        plo = jax.ops.segment_sum(lo, ids, num_segments=nb * num_segments)
        pabs = jax.ops.segment_sum(jnp.abs(hi), ids, num_segments=nb * num_segments)
    with jax.named_scope("merge"):
        parts = phi.astype(jnp.float64) + plo.astype(jnp.float64)
        split_sum = parts.reshape(nb, num_segments).sum(axis=0)
        mass = pabs.reshape(nb, num_segments).sum(axis=0).astype(jnp.float64)

        err_est = mass * ERR_PER_MASS
        risky = err_est > (jnp.abs(split_sum) * RTOL + ATOL)
        has_big = jnp.any(jnp.abs(v) > SPLIT_MAX_ABS)
        has_nonfinite = ~jnp.all(jnp.isfinite(mass))
        bad = jnp.any(risky) | has_big | has_nonfinite

        def exact(x):
            return jax.ops.segment_sum(x, gid, num_segments=num_segments)

        return jax.lax.cond(bad, exact, lambda x: split_sum, v)
