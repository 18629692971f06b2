"""Pallas segmented reductions over two-limb 64-bit values.

Two kernels, replacing the HLO workarounds where the fused form wins:

* ``fused_minmax`` — the hi-limb-native / lo-limb-tiebreak reduction
  (ops/segsum.segment_minmax_64) in ONE two-pass program. The HLO form
  is 4+ separate passes over the input (hi scatter-reduce, a gather of
  the per-segment winner, the candidate mask, the lo scatter-reduce);
  here the input streams through twice (grid phase 0 reduces the high
  limbs into a VMEM accumulator, phase 1 re-reads each block and
  reduces the low limbs among winner ties) and the accumulators never
  leave VMEM. Segment counts are bounded by
  ``spark.rapids.tpu.kernels.segreduce.maxSegments`` (the accumulator
  and the per-block (rows x segments) compare tile are VMEM-resident).

* ``onehot_partials`` — the blocked one-hot matmul of the split-f64
  segment sum (ops/segsum.batched_segment_sum_f64's small-domain
  path). The HLO form MATERIALIZES the (blocks, block, segments)
  one-hot in HBM before the einsum; here each block's one-hot is built
  in VMEM from an iota compare and contracted immediately — the input
  is read once and nothing segment-shaped touches HBM but the partial
  sums themselves. The contraction is the same highest-precision f32
  dot the einsum lowers to, so results are bit-identical.

Reductions here are min/max (exactly associative) and the same-order
blocked f32 dot — NOT reorderings of float addition — so bit-identity
with the HLO path holds on every backend (pinned by
tests/test_kernels.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from spark_rapids_tpu.kernels import (
    KernelIneligible,
    compiler_params,
    config,
    decline_if_auto_off,
    interpret_mode,
)
from spark_rapids_tpu.runtime.faults import fault_point


def _pick_block(capacity: int, nseg: int, budget: int) -> int:
    """Largest row block whose (block x nseg) compare tile fits the
    budget; capacity must tile evenly (capacities are multiples of the
    128-lane minimum bucket)."""
    for blk in (1024, 512, 256, 128):
        if capacity % blk == 0 and blk * nseg * 4 * 3 <= budget:
            return blk
    if capacity < 128 and capacity * nseg * 4 * 3 <= budget:
        return capacity
    raise KernelIneligible(
        f"no block tiling for capacity {capacity} x {nseg} segments "
        "inside the VMEM budget")


def fused_minmax(is_min: bool, hi, lo, valid, gid, nseg: int,
                 hi_ident, lo_ident):
    """(per-segment hi winner, per-segment lo tiebreak) with the exact
    semantics of the two segment_min/segment_max passes in
    ops/segsum.segment_minmax_64: empty segments hold the identity."""
    fault_point("kernels.segreduce")
    cfg = config()
    if nseg > cfg.max_segments:
        raise KernelIneligible(
            f"{nseg} segments > kernels.segreduce.maxSegments "
            f"({cfg.max_segments})")
    capacity = int(hi.shape[0])
    blk = _pick_block(capacity, nseg, cfg.vmem_budget)
    nb = capacity // blk
    # Mosaic reduces signed integers only: an unsigned low limb rides
    # sign-biased (u ^ 2^31 viewed as i32 orders exactly like u)
    unsigned_lo = lo.dtype == jnp.uint32
    if unsigned_lo:
        lo = jax.lax.bitcast_convert_type(lo ^ jnp.uint32(1 << 31),
                                          jnp.int32)
        lo_ident = int(lo_ident) - (1 << 31)
    hi_dt, lo_dt = hi.dtype, lo.dtype

    from spark_rapids_tpu.dispatch import pallas_program
    key = ("segminmax", bool(is_min), capacity, nseg, blk,
           str(hi_dt), str(lo_dt), cfg.vmem_budget)

    def build():
        red = jnp.minimum if is_min else jnp.maximum
        axred = jnp.min if is_min else jnp.max

        # Mosaic layout: row streams arrive as lane-dense (1, blk) ROWS
        # and the per-segment accumulators are (nseg, 1) COLUMNS, so the
        # (nseg, blk) one-hot tile forms by plain broadcasting — no
        # in-kernel relayout (a 1-D -> column shape cast does not lower)
        def kernel(hi_ref, lo_ref, valid_ref, gid_ref, mhi_ref, mlo_ref):
            p = pl.program_id(0)
            b = pl.program_id(1)
            # typed in-trace constants: a bare Python scalar would enter
            # as a weak 64-bit value under x64, which Mosaic cannot narrow
            hi_id = jnp.asarray(hi_ident, hi_dt)
            lo_id = jnp.asarray(lo_ident, lo_dt)

            @pl.when((p == 0) & (b == 0))
            def _init():
                mhi_ref[...] = jnp.full((nseg, 1), hi_id)
                mlo_ref[...] = jnp.full((nseg, 1), lo_id)

            onseg = gid_ref[...] == jax.lax.broadcasted_iota(
                jnp.int32, (nseg, blk), 0)
            valid = valid_ref[...] != 0

            @pl.when(p == 0)
            def _hi_pass():
                contrib = jnp.where(onseg & valid, hi_ref[...], hi_id)
                mhi_ref[...] = red(mhi_ref[...],
                                   axred(contrib, axis=1, keepdims=True))

            @pl.when(p == 1)
            def _lo_pass():
                # each row's segment winner, selected through the one-hot
                # tile already built (Mosaic has no 1-D gather)
                win = axred(jnp.where(onseg, mhi_ref[...], hi_id),
                            axis=0, keepdims=True)
                cand = valid & (hi_ref[...] == win)
                contrib = jnp.where(onseg & cand, lo_ref[...], lo_id)
                mlo_ref[...] = red(mlo_ref[...],
                                   axred(contrib, axis=1, keepdims=True))

        # index maps return jnp.int32(0), never a bare 0: under x64 that
        # is an i64 the Mosaic compiler cannot legalize
        return pl.pallas_call(
            kernel,
            grid=(2, nb),
            in_specs=[pl.BlockSpec(
                (1, blk), lambda p, b: (jnp.int32(0), b))] * 4,
            out_specs=[pl.BlockSpec(
                (nseg, 1), lambda p, b: (jnp.int32(0), jnp.int32(0)))] * 2,
            out_shape=[jax.ShapeDtypeStruct((nseg, 1), hi_dt),
                       jax.ShapeDtypeStruct((nseg, 1), lo_dt)],
            compiler_params=compiler_params(),
            interpret=interpret_mode())

    fn = pallas_program(key, build)
    # validity rides as i32: Mosaic has no packed-bool memory layout
    mhi, mlo = fn(*(a.reshape(1, capacity) for a in
                    (hi, lo, valid.astype(jnp.int32), gid)))
    mhi, mlo = mhi[:, 0], mlo[:, 0]
    if unsigned_lo:
        mlo = (jax.lax.bitcast_convert_type(mlo, jnp.uint32)
               ^ jnp.uint32(1 << 31))
    return mhi, mlo


def onehot_partials(x, gid, nseg: int, nb: int, block: int):
    """Per-(block, segment) f32 partial sums, shape (nb, nseg, c) —
    bit-compatible with ``einsum('nbc,nbg->ngc', x.reshape(nb, block,
    c), one_hot(gid.reshape(nb, block), nseg), precision='highest')``
    but with the one-hot built in VMEM per block."""
    fault_point("kernels.segreduce")
    decline_if_auto_off("segreduce.onehot_partials")
    cfg = config()
    c = int(x.shape[1])
    if (block * nseg + block * c + nseg * c) * 4 * 2 > cfg.vmem_budget:
        raise KernelIneligible("one-hot partial tile exceeds the VMEM "
                               "budget")

    from spark_rapids_tpu.dispatch import pallas_program
    key = ("onehotsum", nb, block, nseg, c, str(x.dtype), cfg.vmem_budget)

    def build():
        def kernel(x_ref, gid_ref, out_ref):
            oh = (gid_ref[:][:, None] == jax.lax.broadcasted_iota(
                jnp.int32, (block, nseg), 1)).astype(x_ref.dtype)
            # contract the row axis: (block, nseg)^T . (block, c)
            out_ref[0] = jax.lax.dot_general(
                oh, x_ref[:], (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST)

        return pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[pl.BlockSpec((block, c),
                                   lambda b: (b, jnp.int32(0))),
                      pl.BlockSpec((block,), lambda b: (b,))],
            out_specs=pl.BlockSpec(
                (1, nseg, c), lambda b: (b, jnp.int32(0), jnp.int32(0))),
            out_shape=jax.ShapeDtypeStruct((nb, nseg, c), x.dtype),
            compiler_params=compiler_params(),
            interpret=interpret_mode())

    fn = pallas_program(key, build)
    return fn(x, gid)
