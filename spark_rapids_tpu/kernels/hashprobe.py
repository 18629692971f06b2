"""Pallas hash-probe for the equi-join build/probe.

The sort-based probe (execs/join.JoinKernel) dense-ranks BOTH sides
through one shared code space — two full multi-operand sorts plus the
bincount/prefix chain — on every probe batch. For the dominant join
shape (a fact table probing a build side with UNIQUE keys: every
foreign-key join) none of that is needed: a bounded-attempt
open-addressing table over the two-limb key delivers each probe row's
build match in one pass.

  * BUILD (plain XLA, 32-bit scatters — scatters are the op Pallas is
    worst at): each valid build row tries ``attempts`` alternative
    slots (per-attempt multiplicative hashes over the (hi, lo) u32
    limbs); scatter-max arbitration picks one winner per slot per
    round. Rows still homeless after the last attempt, or duplicate
    build keys (detected by a self-probe: a placed row whose probe
    finds a DIFFERENT row holds a duplicated key), raise the device
    ``fail`` flag — the join validates it speculatively and replays on
    the sort-based probe, exactly the _DirectJoinKernel protocol.

  * PROBE (the Pallas kernel): the table lives in VMEM; each probe
    block computes its ``attempts`` candidate slots, gathers
    (rowid, key limbs) per attempt, and keeps the first limb-exact
    match — one pass over the probe side, zero sorts.

Outputs are shaped exactly like JoinKernel.probe's range form
(lo = matched build rowid, counts in {0,1}, rs_perm = identity), so
gather-map expansion, outer-join null handling and the full-outer
match bitmap all run unchanged — and, with unique build keys, produce
bit-identical join output.
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

#: per-attempt hash salts (odd multiplicative constants; 8 attempts max)
_SALTS = ((0x9E3779B1, 0x85EBCA77), (0xC2B2AE3D, 0x27D4EB2F),
          (0x165667B1, 0x9E3779B9), (0xD6E8FEB9, 0xCA9B0A93),
          (0x2545F491, 0x8F4C2D17), (0xB5297A4D, 0x68E31DA5),
          (0x1B56C4E9, 0x7FEB352D), (0x846CA68B, 0xC2B2AE35))

MAX_ATTEMPTS = len(_SALTS)


def _slot(hi_u, lo_u, attempt: int, mask: int):
    """Slot for one attempt: a multiplicative mix of the two limbs.
    Pure u32 arithmetic — identical under XLA (build) and Pallas
    (probe). The hi limb arrives as i32 (ops/limbs.py layout); it is
    VIEWED as u32 first — mixed i32*u32 arithmetic would promote the
    whole chain to i64 under x64, which Mosaic cannot lower (and which
    is the exact emulation tax this layer exists to avoid)."""
    c1 = jnp.uint32(_SALTS[attempt][0])
    c2 = jnp.uint32(_SALTS[attempt][1])
    h = (hi_u.astype(jnp.uint32) * c1) ^ (lo_u.astype(jnp.uint32) * c2)
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    return (h & jnp.uint32(mask)).astype(jnp.int32)


def build_table(hi_u, lo_u, valid, H: int, attempts: int):
    """Open-addressing build in plain XLA. Returns (table_row i32 with
    -1 empties, table_hi, table_lo, fail_build)."""
    cap = hi_u.shape[0]
    mask = H - 1
    rowid = jnp.arange(cap, dtype=jnp.int32)
    table_row = jnp.full((H,), -1, jnp.int32)
    placed = jnp.zeros((cap,), jnp.bool_)
    myslot = jnp.zeros((cap,), jnp.int32)
    for a in range(attempts):
        slots = _slot(hi_u, lo_u, a, mask)
        occupied = table_row[slots] >= 0
        want = valid & ~placed & ~occupied
        tgt = jnp.where(want, slots, H)
        table_row = table_row.at[tgt].max(rowid, mode="drop")
        won = want & (table_row[slots] == rowid)
        placed = placed | won
        myslot = jnp.where(won, slots, myslot)
    fail_build = jnp.any(valid & ~placed)
    tslot = jnp.where(placed, myslot, H)
    table_hi = jnp.zeros((H,), hi_u.dtype).at[tslot].set(hi_u, mode="drop")
    table_lo = jnp.zeros((H,), lo_u.dtype).at[tslot].set(lo_u, mode="drop")
    return table_row, table_hi, table_lo, fail_build


def probe_rowids(p_hi, p_lo, valid, table_row, table_hi, table_lo,
                 attempts: int):
    """Pallas probe: per probe row the matching build rowid, -1 when
    unmatched. The (rowid, hi, lo) table is VMEM-resident per block."""
    fault_point("kernels.hashprobe")
    cfg = config()
    if attempts > MAX_ATTEMPTS:
        raise KernelIneligible(f"{attempts} attempts > {MAX_ATTEMPTS}")
    cap = int(p_hi.shape[0])
    H = int(table_row.shape[0])
    blk = cap
    for cand in (2048, 1024, 512, 256, 128):
        if cap % cand == 0:
            blk = cand
            break
    if cap % blk != 0:
        raise KernelIneligible(f"probe capacity {cap} does not tile")
    if (H * 12 + blk * 16) * 2 > cfg.vmem_budget:
        raise KernelIneligible("hash table exceeds the VMEM budget")
    nb = cap // blk
    mask = H - 1

    from spark_rapids_tpu.dispatch import pallas_program
    key = ("hashprobe", cap, H, blk, attempts, str(p_hi.dtype),
           str(p_lo.dtype), cfg.vmem_budget)

    def build():
        def kernel(phi_ref, plo_ref, pvalid_ref, trow_ref, thi_ref,
                   tlo_ref, ri_ref):
            phi = phi_ref[:]
            plo = plo_ref[:]
            pvalid = pvalid_ref[:]
            trow = trow_ref[:]
            thi = thi_ref[:]
            tlo = tlo_ref[:]
            ri = jnp.full((blk,), -1, jnp.int32)
            found = jnp.zeros((blk,), jnp.bool_)
            for a in range(attempts):
                slots = _slot(phi, plo, a, mask)
                r = jnp.take(trow, slots)
                hit = (pvalid & ~found & (r >= 0)
                       & (jnp.take(thi, slots) == phi)
                       & (jnp.take(tlo, slots) == plo))
                ri = jnp.where(hit, r, ri)
                found = found | hit
            ri_ref[:] = ri

        return pl.pallas_call(
            kernel,
            grid=(nb,),
            in_specs=[pl.BlockSpec((blk,), lambda b: (b,))] * 3
            + [pl.BlockSpec((H,), lambda b: (jnp.int32(0),))] * 3,
            out_specs=pl.BlockSpec((blk,), lambda b: (b,)),
            out_shape=jax.ShapeDtypeStruct((cap,), jnp.int32),
            compiler_params=compiler_params(),
            interpret=interpret_mode())

    fn = pallas_program(key, build)
    from spark_rapids_tpu.kernels import note_used
    note_used("hashprobe")  # execute-time failure attribution (tpu_jit)
    return fn(p_hi, p_lo, valid, table_row, table_hi, table_lo)


def probe_ranges(lkey, rkey, live_l, live_r, H: int, attempts: int):
    """Build + probe + range-form packaging (see module doc). Returns
    (lo, counts, total, matched_l, rs_perm, fail)."""
    if not 1 <= attempts <= MAX_ATTEMPTS:
        # checked BEFORE build_table touches _SALTS[attempts-1]: an
        # out-of-range conf value is an ineligible call (clean HLO
        # fallback), never an IndexError that demotes the primitive
        raise KernelIneligible(
            f"kernels.hashprobe.attempts={attempts} outside "
            f"[1, {MAX_ATTEMPTS}]")
    (ld, lv), (rd, rv) = lkey, rkey
    from spark_rapids_tpu.ops.limbs import split_i64_hi_lo
    l_hi, l_lo = split_i64_hi_lo(ld)
    r_hi, r_lo = split_i64_hi_lo(rd)
    valid_r = rv & live_r
    valid_l = lv & live_l
    trow, thi, tlo, fail_build = build_table(r_hi, r_lo, valid_r, H,
                                             attempts)
    # duplicate-key detection: a placed row whose own probe resolves to
    # a DIFFERENT row shares its key with that row
    self_ri = probe_rowids(r_hi, r_lo, valid_r, trow, thi, tlo, attempts)
    rowid_r = jnp.arange(rd.shape[0], dtype=jnp.int32)
    dup = jnp.any(valid_r & (self_ri >= 0) & (self_ri != rowid_r))
    ri = probe_rowids(l_hi, l_lo, valid_l, trow, thi, tlo, attempts)
    matched = ri >= 0
    counts = matched.astype(jnp.int32)
    lo = jnp.where(matched, ri, 0)
    total = jnp.sum(counts.astype(jnp.int64))
    rs_perm = jnp.arange(rd.shape[0], dtype=jnp.int32)
    return lo, counts, total, matched, rs_perm, fail_build | dup
