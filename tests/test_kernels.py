"""Pallas kernel layer (ISSUE 11 tentpole): per-primitive bit-identity
vs the HLO paths, demotion-on-crash, and executable-cache isolation.

Everything runs in Pallas INTERPRET mode on the CPU backend (the
kernels resolve interpret=True there), which is exactly what makes the
bit-identity contract testable in tier-1 without TPU hardware: the
interpreter evaluates the same jnp program the kernel traces, so any
divergence from the HLO path is an algorithmic bug, not a backend
artifact."""

import contextlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import kernels
from spark_rapids_tpu.kernels import KernelsConfig
from spark_rapids_tpu.runtime.faults import FAULTS
from spark_rapids_tpu.session import TpuSession

pytestmark = pytest.mark.kernels

ON = {f"spark.rapids.tpu.kernels.{n}.enabled": "true"
      for n in kernels.PRIMITIVES}
OFF = {f"spark.rapids.tpu.kernels.{n}.enabled": "false"
       for n in kernels.PRIMITIVES}


@pytest.fixture(autouse=True)
def _clean_kernel_state():
    kernels.reset()
    FAULTS.disarm()
    yield
    kernels.reset()
    FAULTS.disarm()


class _forced:
    """Force the kernel enablement contextvar for direct (no-session)
    primitive calls. ``_forced()`` with no names means ALL HLO."""

    def __init__(self, *names, **kw):
        self.cfg = KernelsConfig(enabled=names, **kw)

    def __enter__(self):
        self.tok = kernels.KERNELS_ENABLED.set(self.cfg)

    def __exit__(self, *exc):
        kernels.KERNELS_ENABLED.reset(self.tok)


@contextlib.contextmanager
def _ran_on_pallas():
    """The forced-on call inside must have traced a Pallas kernel and
    demoted nothing: guarded() turns a kernel failure into a silent HLO
    fallback, and comparing HLO with HLO proves no identity."""
    from spark_rapids_tpu.dispatch import COMPILE_SCOPE
    before = COMPILE_SCOPE.get("pallasKernels", 0)
    yield
    assert kernels.demoted_ops() == {}
    assert COMPILE_SCOPE.get("pallasKernels", 0) > before, \
        "the forced-on call took the HLO path"


def _edge_i64(n, rng):
    x = rng.integers(-(2 ** 62), 2 ** 62, n).astype(np.int64)
    x[:6] = [2 ** 63 - 1, -(2 ** 63), 0, -1, 1, -(2 ** 31)]
    return x


def _edge_f64(n, rng):
    x = rng.standard_normal(n) * 1e18
    # NaN / signed zero / infinities / subnormal / beyond-f32 magnitude
    x[:8] = [np.nan, -0.0, 0.0, np.inf, -np.inf, 5e-324, 1e300, -1e300]
    return x


def _eq(a, b):
    a, b = np.asarray(a), np.asarray(b)
    if a.dtype.kind == "f":
        return ((a == b) | (np.isnan(a) & np.isnan(b))).all()
    return (a == b).all()


# ---------------------------------------------------------------------------
# per-primitive bit-identity
# ---------------------------------------------------------------------------


def test_sort_bit_identity_vs_lax_sort():
    from spark_rapids_tpu.ops.ordering import (
        comparable_operands,
        descending_operands,
        lex_sort,
    )
    rng = np.random.default_rng(0)
    n = 64
    i64 = _edge_i64(n, rng)
    f64 = _edge_f64(n, rng)
    dup = (rng.integers(0, 4, n)).astype(np.int32)  # ties -> stability
    # ONE program covers everything (each extra pallas build costs
    # seconds of tier-1 XLA compile): heavy ties (stability via the
    # payload tiebreak), ascending i64 limb pairs with extremes, and a
    # DESCENDING f64 limb pair with NaN/±0/±inf/subnormal edges
    ops = ([jnp.asarray(dup)] + comparable_operands(jnp.asarray(i64))
           + descending_operands(comparable_operands(jnp.asarray(f64))))
    payload = jnp.arange(n, dtype=jnp.int32)
    ref = jax.lax.sort(list(ops) + [payload], num_keys=len(ops))
    with _forced("sort"), _ran_on_pallas():
        got = lex_sort(ops, payload)
    for r, g in zip(ref, got):
        assert _eq(r, g)


def test_sort_ineligible_shape_falls_back_bit_identically():
    from spark_rapids_tpu.ops.ordering import lex_sort
    n = 384  # 3 * 128: a valid bucket under explicit lists, not pow2
    ops = [jnp.asarray(np.arange(n)[::-1].copy().astype(np.int32))]
    payload = jnp.arange(n, dtype=jnp.int32)
    ref = jax.lax.sort(list(ops) + [payload], num_keys=1)
    with _forced("sort"):
        got = lex_sort(ops, payload)
    for r, g in zip(ref, got):
        assert _eq(r, g)
    assert kernels.demoted_ops() == {}  # ineligible != demoted


def test_segment_minmax_bit_identity():
    from spark_rapids_tpu.ops.segsum import segment_minmax_64
    rng = np.random.default_rng(1)
    n, nseg = 128, 8
    gid = jnp.asarray(rng.integers(0, nseg - 2, n), jnp.int32)  # 2 empty
    sv = jnp.asarray(rng.random(n) > 0.25)
    i64 = jnp.asarray(_edge_i64(n, rng))
    # f64 edges PLUS an all-NaN segment (Spark: min ignores NaN unless
    # the segment is all-NaN)
    f64_np = _edge_f64(n, rng)
    f64_np[np.asarray(gid) == 3] = np.nan
    for vals in (i64, jnp.asarray(f64_np)):
        for is_min in (True, False):
            with _forced("segreduce"), _ran_on_pallas():
                got = segment_minmax_64(is_min, vals, sv, gid, nseg)
            with _forced():  # empty set = all HLO
                ref = segment_minmax_64(is_min, vals, sv, gid, nseg)
            assert _eq(got, ref), (str(vals.dtype), is_min)


def test_split_sum_onehot_bit_identity():
    from spark_rapids_tpu.ops.segsum import batched_segment_sum_f64
    rng = np.random.default_rng(2)
    n, nseg = 1024, 8
    gid = jnp.asarray(rng.integers(0, nseg, n), jnp.int32)
    well = [jnp.asarray(np.abs(rng.standard_normal(n))),
            jnp.asarray(rng.standard_normal(n) * 1e6)]
    # catastrophic cancellation: the runtime guard must reroute BOTH
    # paths to the exact sum identically
    cancel = np.zeros(n)
    cancel[0::2], cancel[1::2] = 1e16, -1e16
    cancel[0] += 1.0
    for cols in (well, [jnp.asarray(cancel)]):
        with _forced("segreduce"), _ran_on_pallas():
            got = batched_segment_sum_f64(cols, gid, nseg, n, True)
        with _forced():
            ref = batched_segment_sum_f64(cols, gid, nseg, n, True)
        assert _eq(got, ref)


def test_compact_bit_identity_dtype_zoo():
    from spark_rapids_tpu.ops.scatter32 import compact_pairs
    rng = np.random.default_rng(3)
    n = 256
    sv = jnp.asarray(rng.random(n) > 0.3)
    dec128 = jnp.asarray(
        rng.integers(-(2 ** 62), 2 ** 62, (n, 2)).astype(np.int64))
    datas = [jnp.asarray(_edge_i64(n, rng)),
             jnp.asarray(_edge_f64(n, rng)),
             jnp.asarray(rng.integers(0, 99, n), jnp.int32),
             jnp.asarray(rng.random(n) > 0.5),
             dec128]
    valids = [sv] * len(datas)
    for keep_np in (rng.random(n) > 0.5, np.ones(n, bool),
                    np.zeros(n, bool)):
        keep = jnp.asarray(keep_np)
        with _forced("compact"), _ran_on_pallas():
            got, n_got = compact_pairs(datas, valids, keep, n)
        with _forced():
            ref, n_ref = compact_pairs(datas, valids, keep, n)
        assert int(n_got) == int(n_ref)
        for (gd, gv), (rd, rv) in zip(got, ref):
            assert _eq(gd, rd) and _eq(gv, rv)


def test_hashprobe_matches_and_flags_duplicates():
    from spark_rapids_tpu.kernels import hashprobe as khash
    rng = np.random.default_rng(4)
    cap_l, cap_r, H = 256, 128, 512
    rkeys = (rng.choice(10 ** 9, cap_r, replace=False).astype(np.int64)
             - 5 * 10 ** 8)
    lkeys = np.concatenate([
        rkeys[rng.integers(0, cap_r, cap_l // 2)],
        rng.integers(10 ** 10, 10 ** 11, cap_l - cap_l // 2),
    ]).astype(np.int64)
    lv = rng.random(cap_l) > 0.1  # some null probe keys
    with _forced("hashprobe"):
        lo, counts, total, matched, rs_perm, fail = khash.probe_ranges(
            (jnp.asarray(lkeys), jnp.asarray(lv)),
            (jnp.asarray(rkeys), jnp.ones(cap_r, bool)),
            jnp.ones(cap_l, bool), jnp.ones(cap_r, bool), H, 4)
        assert not bool(fail)
        m, lo_n = np.asarray(matched), np.asarray(lo)
        for i in range(cap_l):
            hits = np.nonzero(rkeys == lkeys[i])[0] if lv[i] else []
            assert m[i] == (len(hits) > 0)
            if m[i]:
                assert lo_n[i] == hits[0]
        assert int(total) == int(m.sum())
        # a duplicated build key must raise the device fail flag
        rdup = rkeys.copy()
        rdup[5] = rdup[7]
        *_, fail2 = khash.probe_ranges(
            (jnp.asarray(lkeys), jnp.asarray(lv)),
            (jnp.asarray(rdup), jnp.ones(cap_r, bool)),
            jnp.ones(cap_l, bool), jnp.ones(cap_r, bool), H, 4)
        assert bool(fail2)
    # probe_ranges IS the kernel (no guarded() in between, so a failure
    # raises instead of falling back); nothing may have demoted either
    assert kernels.demoted_ops() == {}


# ---------------------------------------------------------------------------
# end-to-end: the same queries with kernels on vs off
# ---------------------------------------------------------------------------


def _tables(n=600, seed=5, tag=""):
    """``tag`` renames the columns: exec kernel traces are shared
    process-wide by STRUCTURE, so a test that needs cold traces (to
    observe trace-time counters or fire a trace-time fault) must use a
    structurally distinct plan."""
    rng = np.random.default_rng(seed)
    fact = {f"k{tag}": rng.integers(0, 40, n).astype(np.int64),
            f"v{tag}": rng.standard_normal(n) * 1e9,
            f"q{tag}": rng.integers(-(2 ** 40), 2 ** 40, n).astype(np.int64)}
    dim = {f"k{tag}": np.arange(40, dtype=np.int64),
           f"name{tag}": np.asarray([f"n{i}" for i in range(40)], object)}
    return fact, dim


def _pipeline(s, fact, dim, tag=""):
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.ops.expr import col, lit
    df = s.create_dataframe(dict(fact))
    dd = s.create_dataframe(dict(dim))
    return (df.filter(col(f"v{tag}") > lit(-1e9))
              .join(dd, on=f"k{tag}", how="inner")
              .group_by(f"name{tag}")
              .agg(F.sum(f"v{tag}").alias("s"),
                   F.min(f"q{tag}").alias("mn"),
                   F.max(f"q{tag}").alias("mx"),
                   F.count(f"v{tag}").alias("c"))
              .order_by(f"name{tag}"))


def _collect(s, fact, dim, tag=""):
    return _pipeline(s, fact, dim, tag).collect_table().to_pydict()


def test_kernel_path_counters_surface_in_compile_scope():
    from spark_rapids_tpu.dispatch import COMPILE_SCOPE
    from spark_rapids_tpu.ops.ordering import lex_sort
    # trace-time resolution counters, pinned on a fresh shape: the
    # kernel path books pallasKernels, the disabled path hloFallbacks
    ops = [jnp.asarray(np.arange(64)[::-1].copy().astype(np.int32))]
    payload = jnp.arange(64, dtype=jnp.int32)
    before = dict(COMPILE_SCOPE)
    with _forced("sort"):
        lex_sort(ops, payload)
    assert (COMPILE_SCOPE.get("pallasKernels", 0)
            > before.get("pallasKernels", 0))
    before = dict(COMPILE_SCOPE)
    with _forced():
        lex_sort(ops, payload)
    assert (COMPILE_SCOPE.get("hloFallbacks", 0)
            > before.get("hloFallbacks", 0))
    # ...and the per-query event record carries the same counters (the
    # offline `tools profile` surface). Cold structure (tag) so the
    # query actually traces — scope deltas are zero on warm replays.
    import tempfile
    rng = np.random.default_rng(6)
    s = TpuSession({**ON, "spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": tempfile.mkdtemp()})
    from spark_rapids_tpu.ops.expr import col, lit
    df = s.create_dataframe(
        {"cnt": rng.integers(0, 9, 256).astype(np.int64)})
    df.filter(col("cnt") > lit(4)).collect_table()
    scopes = s.last_event_record["scopes"]
    assert scopes.get("compile", {}).get("pallasKernels", 0) > 0


# ---------------------------------------------------------------------------
# demotion on crash (the PR-3 circuit-breaker contract, per primitive)
# ---------------------------------------------------------------------------


def test_seeded_kernel_crash_demotes_and_query_completes():
    # a COLD capacity bucket (n=1100 -> 2048 vs the other tests' 1024):
    # the fault point fires at TRACE time, and exec kernel traces are
    # shared process-wide by structure + capacity — column names alone
    # don't cold them (expressions bind to ordinals)
    fact, dim = _tables(n=1100, seed=7, tag="c")
    ref = _collect(TpuSession(dict(OFF)), fact, dim, tag="c")
    import tempfile
    crashy = TpuSession({
        **ON, "spark.rapids.test.faults": "kernels.compact:crash:1",
        "spark.rapids.sql.eventLog.enabled": "true",
        "spark.rapids.sql.eventLog.dir": tempfile.mkdtemp()})
    got = _collect(crashy, fact, dim, tag="c")
    assert got == ref or all(
        a == b or (isinstance(a, float) and np.isnan(a) and np.isnan(b))
        for k in ref for a, b in zip(got[k], ref[k]))
    # demoted for the process, with the reason surfaced...
    assert "pallas:compact" in kernels.demoted_ops()
    reason = kernels.demoted_ops()["pallas:compact"]
    assert "demoted to HLO" in reason and "KernelCrashError" in reason
    # ...in the event record's demotions map...
    assert "pallas:compact" in crashy.last_event_record["demotions"]
    # ...and in explain() as a root note
    text = _pipeline(crashy, fact, dim, tag="c").explain()
    assert "demoted to HLO" in text
    # the demoted primitive stays off; the others keep their kernels
    assert not kernels.enabled("compact")
    with _forced(*kernels.PRIMITIVES):
        assert kernels.enabled("sort") and not kernels.enabled("compact")


# ---------------------------------------------------------------------------
# cache isolation: enablement + demotions fold into every cache key
# ---------------------------------------------------------------------------


def test_fingerprints_never_cross_kernel_paths():
    from spark_rapids_tpu.plan.fingerprint import template_fingerprint
    fact, dim = _tables(seed=8)
    s_on, s_off = TpuSession(dict(ON)), TpuSession(dict(OFF))
    fp_on = template_fingerprint(_pipeline(s_on, fact, dim).plan,
                                 s_on.conf)
    fp_off = template_fingerprint(_pipeline(s_off, fact, dim).plan,
                                  s_off.conf)
    assert fp_on is not None and fp_on != fp_off
    # a runtime demotion re-keys cached trees even under identical conf
    kernels.demote("sort", RuntimeError("synthetic"))
    fp_dem = template_fingerprint(_pipeline(s_on, fact, dim).plan,
                                  s_on.conf)
    assert fp_dem != fp_on


def test_execute_time_failure_demotes_captured_primitives():
    """Mosaic lowering / backend compile happens when the ENCLOSING jit
    first runs, outside the kernels layer's guarded() — tpu_jit's
    trace-capture frame must demote the embedded primitives and convert
    the failure into a replayable KernelCrashError."""
    from spark_rapids_tpu.dispatch import tpu_jit
    from spark_rapids_tpu.errors import KernelCrashError

    def body(x):
        kernels.note_used("sort")  # what guarded() records on success
        raise RuntimeError("synthetic backend-compile failure")

    with _forced("sort", "compact"):
        with pytest.raises(KernelCrashError, match="demoted"):
            tpu_jit(body, name="test_body")(jnp.arange(8))
    assert "pallas:sort" in kernels.demoted_ops()
    assert "pallas:compact" not in kernels.demoted_ops()


def test_hashprobe_attempts_out_of_range_is_ineligible_not_a_crash():
    from spark_rapids_tpu.kernels import KernelIneligible
    from spark_rapids_tpu.kernels import hashprobe as khash
    k = (jnp.arange(8, dtype=jnp.int64), jnp.ones(8, bool))
    with _forced("hashprobe"):
        with pytest.raises(KernelIneligible):
            khash.probe_ranges(k, k, jnp.ones(8, bool), jnp.ones(8, bool),
                               32, 9)
    assert kernels.demoted_ops() == {}


def test_trace_token_tracks_enablement_and_demotion():
    with _forced("sort", "compact"):
        t0 = kernels.trace_token()
        kernels.demote("sort", RuntimeError("synthetic"))
        t1 = kernels.trace_token()
    assert t0 != t1
    with _forced():
        assert kernels.trace_token()[0] == ()
