"""Decimal arithmetic tests (reference: DecimalUtils JNI +
DecimalArithmeticOverrides + decimal integration suites): two-limb device
kernels vs Python-int oracle, Spark precision/scale rules, overflow
nulls, casts, engine integration."""

import decimal as pydec

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.ops import decimal as D
from spark_rapids_tpu.ops.expr import col, lit


# -- two-limb kernels vs python ints -----------------------------------------

def test_i64_mul_to_i128_exact(session):
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    a = rng.integers(-(10**18), 10**18, 300, dtype=np.int64)
    b = rng.integers(-(10**18), 10**18, 300, dtype=np.int64)
    hi, lo = D.i64_mul_to_i128(jnp.asarray(a), jnp.asarray(b))
    hi = np.asarray(hi).astype(object)
    lo = np.asarray(lo).astype(object)
    got = [int(h) * (1 << 64) + int(l) for h, l in zip(hi, lo)]
    want = [int(x) * int(y) for x, y in zip(a, b)]
    assert got == want


@pytest.mark.parametrize("d", [1, 4, 9, 13, 18])
def test_i128_div_pow10_half_up(session, d):
    import jax.numpy as jnp
    rng = np.random.default_rng(d)
    a = rng.integers(-(10**18), 10**18, 200, dtype=np.int64)
    b = rng.integers(-(10**18), 10**18, 200, dtype=np.int64)
    hi, lo = D.i64_mul_to_i128(jnp.asarray(a), jnp.asarray(b))
    qhi, qlo = D.i128_div_pow10_half_up(hi, lo, d)
    got = [int(h) * (1 << 64) + int(l)
           for h, l in zip(np.asarray(qhi).astype(object),
                           np.asarray(qlo).astype(object))]
    m = 10 ** d
    for g, x, y in zip(got, a, b):
        v = int(x) * int(y)
        q, r = divmod(abs(v), m)
        if 2 * r >= m:
            q += 1
        want = -q if v < 0 else q
        assert g == want, (x, y, d, g, want)


def test_u128_div_u64_big(session):
    import jax.numpy as jnp
    rng = np.random.default_rng(7)
    nums = [int(x) for x in rng.integers(0, 10**18, 100, dtype=np.int64)]
    ups = [int(x) for x in rng.integers(0, 10**18, 100, dtype=np.int64)]
    divs = [int(x) for x in rng.integers(1 << 31, 1 << 62, 100,
                                         dtype=np.int64)]
    vals = [n * u for n, u in zip(nums, ups)]
    hi = jnp.asarray([v >> 64 for v in vals], dtype=jnp.uint64)
    lo = jnp.asarray([v & ((1 << 64) - 1) for v in vals], dtype=jnp.uint64)
    dd = jnp.asarray(divs, dtype=jnp.uint64)
    q, r = D._u128_div_u64_big(hi, lo, dd)
    for i, (v, m) in enumerate(zip(vals, divs)):
        assert int(np.asarray(q)[i]) == v // m, (i, v, m)
        assert int(np.asarray(r)[i]) == v % m


# -- result-type rules -------------------------------------------------------

def test_spark_result_type_rules():
    a, b = T.DecimalType(10, 2), T.DecimalType(8, 4)
    assert D.add_result_type(a, b) == T.DecimalType(13, 4)
    assert D.mul_result_type(a, b) == T.DecimalType(19, 6)
    # divide: s = max(6, 2+8+1)=11, p = 10-2+4+11 = 23
    assert D.div_result_type(a, b) == T.DecimalType(23, 11)
    # precision-loss adjustment kicks in past 38
    big = T.DecimalType(38, 10)
    r = D.mul_result_type(big, big)
    assert r.precision == 38


# -- engine integration ------------------------------------------------------

def _dec_df(s, values, ptype, n_batches=1):
    unscaled = np.array([int(v.scaleb(ptype.scale)) for v in values],
                        dtype=np.int64)
    return s.create_dataframe({"d": unscaled}, dtypes={"d": ptype})


def _pd(x):
    return pydec.Decimal(x)


def test_engine_decimal_add_mul_div(session, cpu_session):
    from tests.asserts import assert_runs_on_tpu
    # (6,2) keeps every result type within the decimal64 device tier:
    # add -> (7,2), mul -> (13,4), div-by-int-literal -> (17,13)
    ptype = T.DecimalType(6, 2)
    rng = np.random.default_rng(1)
    vals = [_pd(int(x)) / 100 for x in
            rng.integers(-10**5, 10**5, 2000)]

    def q(s):
        df = _dec_df(s, vals, ptype)
        return df.select(
            (col("d") + col("d")).alias("a"),
            (col("d") * col("d")).alias("m"),
            (col("d") / lit(100)).alias("q"))

    got = q(session).collect()
    want = q(cpu_session).collect()
    assert got == want  # decimals must be BIT-exact between paths
    assert_runs_on_tpu(q, session)
    # spot-check against python Decimal
    a0, m0, q0 = got[0]
    schema = dict(q(session).plan.output_schema())
    sm = schema["m"]
    d0 = vals[0]
    assert a0 == int((d0 + d0).scaleb(schema["a"].scale))
    want_m = (d0 * d0).quantize(
        pydec.Decimal(1).scaleb(-sm.scale), rounding=pydec.ROUND_HALF_UP)
    assert m0 == int(want_m.scaleb(sm.scale))


def test_engine_decimal_overflow_nulls(session, cpu_session):
    ptype = T.DecimalType(18, 0)
    big = 10 ** 17

    def q(s):
        df = s.create_dataframe(
            {"d": np.array([big, 5, -big], dtype=np.int64)},
            dtypes={"d": ptype})
        # d * d overflows decimal(38,0)-capped result for big values
        return df.select((col("d") * col("d")).alias("m"))

    got = q(session).collect()
    want = q(cpu_session).collect()
    assert got == want
    assert got[1][0] == 25
    # 10^34 fits decimal(37,0) -> on host path valid; device must agree
    # (both paths computed it identically above)


def test_engine_int_decimal_mixing(session, cpu_session):
    ptype = T.DecimalType(12, 3)

    def q(s):
        df = _dec_df(s, [_pd("1.250"), _pd("-7.125")], ptype)
        return df.select((col("d") + lit(2)).alias("a"),
                         (col("d") * lit(3)).alias("m"))

    assert q(session).collect() == q(cpu_session).collect()


def test_decimal_casts(session, cpu_session):
    src = T.DecimalType(10, 4)

    def q(s):
        df = _dec_df(s, [_pd("12.3456"), _pd("-0.5000"), _pd("99.9999")],
                     src)
        from spark_rapids_tpu.ops.cast import Cast
        return df.select(
            Cast(col("d"), T.DecimalType(8, 2)).alias("rescale"),
            Cast(col("d"), T.LONG).alias("l"),
            Cast(col("d"), T.DOUBLE).alias("f"))

    got = q(session).collect()
    want = q(cpu_session).collect()
    # decimal/integral results bit-exact; the double column is subject to
    # the emulated-f64 division ulp on the device (same carve-out as
    # splitF64)
    for g, w in zip(got, want):
        assert g[:2] == w[:2]
        assert abs(g[2] - w[2]) <= 1e-12 * max(1.0, abs(w[2]))
    assert got[0][:2] == (1235, 12)        # HALF_UP to 2dp; trunc to long
    assert abs(got[0][2] - 12.3456) < 1e-12
    assert got[1][0] == -50 and got[1][1] == 0
    assert got[2][0] == 10000              # 99.9999 -> 100.00


def test_decimal_to_from_string_cpu(cpu_session):
    from spark_rapids_tpu.ops.cast import Cast
    df = cpu_session.create_dataframe(
        {"s": np.array(["12.345", "-0.5", "oops", "1e2"], dtype=object)})
    rows = df.select(
        Cast(col("s"), T.DecimalType(10, 2)).alias("d")).collect()
    assert rows[0][0] == 1235   # HALF_UP at scale 2 (unscaled)
    assert rows[1][0] == -50
    assert rows[2][0] is None
    assert rows[3][0] == 10000  # 1e2 == 100.00

    back = cpu_session.create_dataframe(
        {"d": np.array([1235, -50], dtype=np.int64)},
        dtypes={"d": T.DecimalType(10, 2)})
    srows = back.select(Cast(col("d"), T.STRING).alias("s")).collect()
    assert srows == [("12.35",), ("-0.50",)]


def test_decimal_divide_by_zero_null(session, cpu_session):
    ptype = T.DecimalType(6, 2)

    def q(s):
        df = _dec_df(s, [_pd("4.00"), _pd("9.00")], ptype)
        return df.select((col("d") / (col("d") - col("d"))).alias("q"))

    got = q(session).collect()
    assert got == q(cpu_session).collect()
    assert got == [(None,), (None,)]


def test_p_gt_18_falls_back_but_correct(session, cpu_session):
    """Operands driving the result past decimal64 tag device fallback;
    the host path computes exactly (python ints)."""
    ptype = T.DecimalType(18, 6)

    def q(s):
        df = _dec_df(s, [_pd("123456789012.345678")], ptype)
        return df.select((col("d") * col("d")).alias("m"))

    # result type decimal(37, 12) > decimal64 -> CPU path both sessions
    got = q(session).collect()
    want = q(cpu_session).collect()
    assert got == want
    v = _pd("123456789012.345678")
    with pydec.localcontext() as ctx:
        ctx.prec = 50  # default 28-digit context would round the product
        assert got[0][0] == int((v * v).scaleb(12))


def test_unscaled_value_and_make_decimal(session, cpu_session):
    ptype = T.DecimalType(9, 3)

    def q(s):
        df = _dec_df(s, [_pd("1.500"), _pd("-2.250")], ptype)
        return df.select(D.UnscaledValue(col("d")).alias("u"))

    got = q(session).collect()
    assert got == q(cpu_session).collect() == [(1500,), (-2250,)]


def test_double_to_decimal_cast_rounds_half_up(cpu_session, session):
    from spark_rapids_tpu.ops.cast import Cast

    def q(s):
        df = s.create_dataframe({"f": np.array([2.5, 2.555, -1.005, np.inf])})
        return df.select(Cast(col("f"), T.DecimalType(10, 2)).alias("d"))

    rows = q(cpu_session).collect()
    assert rows[0][0] == 250    # 2.50
    assert rows[1][0] == 256    # HALF_UP, not truncation
    assert rows[2][0] == -101   # -1.01 (repr half-up on magnitude)
    assert rows[3][0] is None   # inf -> null
    # device session takes the CPU fallback for float->decimal but must
    # produce the same values
    assert q(session).collect() == rows


def test_decimal_mixed_with_double_promotes(session, cpu_session):
    ptype = T.DecimalType(8, 2)

    def q(s):
        df = _dec_df(s, [_pd("2.50"), _pd("-4.00")], ptype)
        return df.select((col("d") * lit(1.5)).alias("m"))

    got = q(session).collect()
    want = q(cpu_session).collect()
    for g, w in zip(got, want):
        assert abs(g[0] - w[0]) <= 1e-12 * max(1.0, abs(w[0]))
    assert abs(got[0][0] - 3.75) < 1e-12
    # result is DOUBLE (Spark: decimal x double -> double)
    assert dict(q(session).plan.output_schema())["m"] == T.DOUBLE


def test_decimal_remainder_and_pmod(session, cpu_session):
    ptype = T.DecimalType(8, 2)

    def q(s):
        df = _dec_df(s, [_pd("7.50"), _pd("-7.50")], ptype)
        return df.select((col("d") % lit(2)).alias("r"))

    got = q(session).collect()
    assert got == q(cpu_session).collect()
    assert got[0][0] == 150    # 1.50 (unscaled at scale 2)
    assert got[1][0] == -150   # Java %: dividend sign


def test_decimal_pmod_negative_dividend(session, cpu_session):
    from spark_rapids_tpu.ops.arithmetic import Pmod
    ptype = T.DecimalType(8, 2)

    def q(s):
        df = _dec_df(s, [_pd("-7.50"), _pd("7.50")], ptype)
        return df.select(Pmod(col("d"), lit(2)).alias("p"))

    got = q(session).collect()
    assert got == q(cpu_session).collect()
    assert got[0][0] == 50     # pmod(-7.5, 2) = 0.50
    assert got[1][0] == 150    # pmod(7.5, 2) = 1.50


def test_decimal_divided_by_double_promotes(session, cpu_session):
    ptype = T.DecimalType(8, 2)

    def q(s):
        df = _dec_df(s, [_pd("5.00")], ptype)
        return df.select((col("d") / lit(2.0)).alias("q"))

    got = q(session).collect()
    want = q(cpu_session).collect()
    assert abs(got[0][0] - 2.5) < 1e-12
    assert abs(got[0][0] - want[0][0]) <= 1e-12
    assert dict(q(session).plan.output_schema())["q"] == T.DOUBLE


def test_decimal_add_19_digit_boundary(session, cpu_session):
    """decimal(18,0) + decimal(18,0) -> decimal(19,0): 10^18 is a VALID
    19-digit value; device must not null it (review fix)."""
    ptype = T.DecimalType(18, 0)
    v = 10**18 - 1

    def q(s):
        df = s.create_dataframe({"d": np.array([v], dtype=np.int64)},
                                dtypes={"d": ptype})
        return df.select((col("d") + lit(1)).alias("a"))

    got = q(session).collect()
    assert got == q(cpu_session).collect()
    assert got[0][0] == 10**18


def test_mixed_scale_decimal_comparison(session, cpu_session):
    a = T.DecimalType(6, 2)

    def q(s):
        df = s.create_dataframe(
            {"x": np.array([150, 149, 151], dtype=np.int64),
             "y": np.array([1500, 1500, 1500], dtype=np.int64)},
            dtypes={"x": a, "y": T.DecimalType(8, 3)})
        return df.select((col("x") == col("y")).alias("eq"),
                         (col("x") < col("y")).alias("lt"))

    got = q(session).collect()
    assert got == q(cpu_session).collect()
    assert got == [(True, False), (False, True), (False, False)]


def test_integral_divide_decimal(session, cpu_session):
    ptype = T.DecimalType(6, 1)

    def q(s):
        df = _dec_df(s, [_pd("7.5"), _pd("-7.5")], ptype)
        from spark_rapids_tpu.ops.arithmetic import IntegralDivide
        return df.select(
            IntegralDivide(col("d"), lit(5, T.DecimalType(2, 1))).alias("q"))

    got = q(session).collect()
    assert got == q(cpu_session).collect()
    assert got[0][0] == 15 and got[1][0] == -15  # 7.5 div 0.5
