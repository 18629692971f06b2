"""The engine's device primitives against an independent oracle.

``ops/ordering.lex_sort``, ``ops/segsum.segment_minmax_64`` and
``ops/scatter32.compact_pairs`` are the single sort / 64-bit segment
min-max / row-compaction points every exec goes through. Each case
here is checked against plain numpy or Python arithmetic on the host
(never against another engine path), on the edge inputs 64-bit limb
code gets wrong first: int64 extremes, NaN, signed zeros, infinities,
a subnormal, magnitudes beyond f32."""

import math

import numpy as np
import pytest

import jax.numpy as jnp


def _edge_i64(n, rng):
    x = rng.integers(-(2 ** 62), 2 ** 62, n).astype(np.int64)
    x[:6] = [2 ** 63 - 1, -(2 ** 63), 0, -1, 1, -(2 ** 31)]
    return x


def _edge_f64(n, rng):
    x = rng.standard_normal(n) * 1e18
    # NaN / signed zero / infinities / subnormal / beyond-f32 magnitude
    x[:8] = [np.nan, -0.0, 0.0, np.inf, -np.inf, 5e-324, 1e300, -1e300]
    # repeats of the specials, so ties among them meet the payload
    x[8:12] = [np.nan, 0.0, -0.0, np.inf]
    return x


# ---------------------------------------------------------------------------
# lex_sort
# ---------------------------------------------------------------------------

# Spark's total order on doubles as a Python sort key: NaN greatest,
# -0.0 and 0.0 one value (Python compares them equal). A subnormal
# double is zero to the engine on every backend: XLA's CPU code
# compares denormals as zero, and on the TPU a double is an (f32, f32)
# pair that cannot hold one (ops/limbs.py).

_F64_MIN_NORMAL = 2.2250738585072014e-308


def _f64_asc(v):
    if math.isnan(v):
        return (1, 0.0)
    return (0, 0.0 if abs(v) < _F64_MIN_NORMAL else v)


def _f64_desc(v):
    flag, val = _f64_asc(v)
    return (1 - flag, -val)


def _sort_case(name):
    """(operands, oracle permutation) for one named case. The oracle is
    Python's stable ``sorted`` over row indices with an exact key."""
    from spark_rapids_tpu.ops.ordering import (
        comparable_operands,
        descending_operands,
        zero_invalid,
    )
    rng = np.random.default_rng(7)
    n = 384 if name == "capacity_384" else 64
    i64 = _edge_i64(n, rng)
    f64 = _edge_f64(n, rng)
    dup = rng.integers(0, 4, n).astype(np.int32)
    asc = lambda a: comparable_operands(jnp.asarray(a))          # noqa: E731
    desc = lambda a: descending_operands(asc(a))                 # noqa: E731
    if name == "i32_heavy_ties":
        return [jnp.asarray(dup)], lambda i: int(dup[i])
    if name == "i64_asc":
        return asc(i64), lambda i: int(i64[i])
    if name == "i64_desc":
        return desc(i64), lambda i: -int(i64[i])
    if name == "f64_asc":
        return asc(f64), lambda i: _f64_asc(float(f64[i]))
    if name == "f64_desc":
        return desc(f64), lambda i: _f64_desc(float(f64[i]))
    if name == "nulls_by_validity_first":
        valid = rng.random(n) > 0.3
        zeroed = zero_invalid(jnp.asarray(i64), jnp.asarray(valid))
        ops = [jnp.asarray(~valid).astype(jnp.int32)] \
            + comparable_operands(zeroed)
        return ops, lambda i: (0, int(i64[i])) if valid[i] else (1, 0)
    if name == "three_keys":
        coarse = _edge_i64(n, rng) >> 61  # few distinct values: ties
        return ([jnp.asarray(dup)] + desc(coarse) + asc(f64),
                lambda i: (int(dup[i]), -int(coarse[i]),
                           _f64_asc(float(f64[i]))))
    if name == "capacity_384":  # 3 * 128: a bucket, not a power of two
        return asc(i64), lambda i: int(i64[i])
    raise AssertionError(name)


@pytest.mark.parametrize("name", [
    "i32_heavy_ties", "i64_asc", "i64_desc", "f64_asc", "f64_desc",
    "nulls_by_validity_first", "three_keys", "capacity_384"])
def test_lex_sort_is_the_stable_sort_by_the_keys(name):
    from spark_rapids_tpu.ops.ordering import lex_sort
    ops, key = _sort_case(name)
    n = int(ops[0].shape[0])
    res = lex_sort(ops, jnp.arange(n, dtype=jnp.int32))
    assert len(res) == len(ops) + 1
    perm = np.asarray(res[-1])
    want = sorted(range(n), key=key)  # stable: ties keep row order
    assert perm.tolist() == want
    # the operands come back permuted with the payload
    for o, r in zip(ops, res):
        assert np.array_equal(np.asarray(o)[perm], np.asarray(r))


# ---------------------------------------------------------------------------
# segment_minmax_64
# ---------------------------------------------------------------------------


def _minmax_oracle(is_min, data, valid, gid, nseg):
    """{segment: value} for the segments that hold a valid row, Spark's
    order on doubles (NaN greatest): max is NaN if any valid NaN, min
    ignores NaN unless every valid row is NaN."""
    out = {}
    for s in range(nseg):
        vals = [data[i] for i in range(len(data)) if valid[i] and gid[i] == s]
        if not vals:
            continue
        if data.dtype.kind == "f":
            nans = [v for v in vals if np.isnan(v)]
            rest = [v for v in vals if not np.isnan(v)]
            if is_min:
                out[s] = min(rest) if rest else np.nan
            else:
                out[s] = np.nan if nans else max(rest)
        else:
            out[s] = min(vals) if is_min else max(vals)
    return out


def _minmax_inputs(kind):
    rng = np.random.default_rng(11)
    n, nseg = 128, 8
    gid = rng.integers(0, nseg - 2, n).astype(np.int32)  # 6 and 7: no row
    valid = rng.random(n) > 0.25
    if kind == "i64":
        data = _edge_i64(n, rng)
        valid[:6] = True
    elif kind == "f64":
        data = _edge_f64(n, rng)
        valid[:12] = True
        gid[:12] = [0, 1, 1, 2, 2, 3, 3, 3, 4, 4, 4, 0]
        # segment 4 holds NaN, 0.0, -0.0; segment 5: every valid row NaN
        data[gid == 5] = np.nan
    else:  # f32-exact doubles: the (f32, f32) limb path, not its guard
        data = rng.standard_normal(n).astype(np.float32).astype(np.float64)
        data[:4] = [np.inf, -np.inf, 0.0, np.nan]
        valid[:4] = True
    return data, valid, gid, nseg


@pytest.mark.parametrize("is_min", [True, False], ids=["min", "max"])
@pytest.mark.parametrize("kind", ["i64", "f64", "f64_f32_exact"])
def test_segment_minmax_64_against_numpy(kind, is_min):
    from spark_rapids_tpu.ops.segsum import segment_minmax_64
    data, valid, gid, nseg = _minmax_inputs(kind)
    got = np.asarray(segment_minmax_64(
        is_min, jnp.asarray(data), jnp.asarray(valid), jnp.asarray(gid),
        nseg))
    assert got.shape == (nseg,) and got.dtype == data.dtype
    want = _minmax_oracle(is_min, data, valid, gid, nseg)
    assert len(want) == 6
    for s, w in want.items():
        if isinstance(w, float) and np.isnan(w):
            assert np.isnan(got[s]), (s, got[s])
        else:
            assert got[s] == w, (s, got[s], w)


def test_segment_minmax_64_segment_without_a_valid_row():
    """A segment whose rows are all invalid is undefined by contract
    (callers mask it by their own count); it must not leak into its
    neighbours, whatever the dead rows hold."""
    from spark_rapids_tpu.ops.segsum import segment_minmax_64
    data = np.array([5, -(2 ** 63), 2 ** 63 - 1, 7, 3], np.int64)
    valid = np.array([True, False, False, True, True])
    gid = np.array([0, 1, 1, 2, 2], np.int32)
    for is_min, want in ((True, {0: 5, 2: 3}), (False, {0: 5, 2: 7})):
        got = np.asarray(segment_minmax_64(
            is_min, jnp.asarray(data), jnp.asarray(valid),
            jnp.asarray(gid), 4))
        assert got.shape == (4,)
        assert {s: int(got[s]) for s in want} == want


# ---------------------------------------------------------------------------
# compact_pairs
# ---------------------------------------------------------------------------


def _compact_column(name, n, rng):
    if name == "i32":
        return rng.integers(-2 ** 31, 2 ** 31, n).astype(np.int32)
    if name == "i64":
        return _edge_i64(n, rng)
    if name == "f32":
        with np.errstate(over="ignore"):  # +-1e300 -> +-inf, wanted
            return _edge_f64(n, rng).astype(np.float32)
    if name == "f64":
        return _edge_f64(n, rng)
    if name == "bool":
        return rng.random(n) > 0.5
    if name == "decimal128_limbs":  # (rows, 2) int64 limb matrix
        return np.stack([_edge_i64(n, rng), _edge_i64(n, rng)[::-1]], axis=1)
    raise AssertionError(name)


def _same(a, b):
    if a.dtype.kind == "f":
        # bit-level: NaN stays NaN, -0.0 keeps its sign
        return np.array_equal(a.view(f"u{a.dtype.itemsize}"),
                              b.view(f"u{b.dtype.itemsize}"))
    return np.array_equal(a, b)


@pytest.mark.parametrize("name", [
    "i32", "i64", "f32", "f64", "bool", "decimal128_limbs",
    "keep_none", "keep_all"])
def test_compact_pairs_is_the_kept_rows_in_order(name):
    from spark_rapids_tpu.ops.scatter32 import compact_pairs
    rng = np.random.default_rng(3)
    n = 96
    keep = rng.random(n) > 0.4
    keep[:12] = True  # the edge values are kept
    if name == "keep_none":
        keep[:] = False
    elif name == "keep_all":
        keep[:] = True
    kinds = (["i64", "f64"] if name in ("keep_none", "keep_all")
             else [name])
    datas = [_compact_column(k, n, rng) for k in kinds]
    valids = [rng.random(n) > 0.2 for _ in kinds]
    outs, new_n = compact_pairs(
        [jnp.asarray(d) for d in datas], [jnp.asarray(v) for v in valids],
        jnp.asarray(keep), n)
    k = int(keep.sum())
    assert int(new_n) == k
    assert len(outs) == len(datas)
    for (od, ov), d, v in zip(outs, datas, valids):
        od, ov = np.asarray(od), np.asarray(ov)
        assert od.shape == d.shape and od.dtype == d.dtype
        assert _same(od[:k], d[keep])
        assert np.array_equal(ov[:k], v[keep])
        assert not ov[k:].any()  # the dead tail is null


# ---------------------------------------------------------------------------
# tpu_jit, and a removed option
# ---------------------------------------------------------------------------


def test_tpu_jit_raises_what_the_program_raised():
    """An exception from a program leaves the dispatch as it was
    raised, and the dispatch is counted."""
    from spark_rapids_tpu.dispatch import dispatch_count, tpu_jit

    def refuses(x):
        raise ValueError("this shape is not mine")

    fn = tpu_jit(refuses, name="refuses")
    before = dispatch_count()
    with pytest.raises(ValueError, match="this shape is not mine") as ei:
        fn(jnp.arange(4))
    assert type(ei.value) is ValueError
    assert dispatch_count() == before + 1


def test_removed_kernels_option_is_an_unregistered_key():
    """spark.rapids.tpu.kernels.* left with the Pallas layer (ISSUE
    29): given anyway, it is stored as any unregistered key is and
    changes no answer."""
    from spark_rapids_tpu.conf import RapidsConf, registry
    from spark_rapids_tpu.ops.expr import col
    from spark_rapids_tpu.session import TpuSession
    key = "spark.rapids.tpu.kernels.sort.enabled"
    assert key not in registry()
    with pytest.raises(KeyError):
        RapidsConf().get(key)
    data = {"k": _edge_i64(40, np.random.default_rng(5)).tolist(),
            "v": list(range(40))}

    def answer(session):
        return session.create_dataframe(data).sort(
            col("k"), ascending=False).collect()

    given = TpuSession({key: "true"})
    assert given.conf.get(key) == "true"
    rows = answer(given)
    assert rows == answer(TpuSession())
    assert [r[0] for r in rows] == sorted(data["k"], reverse=True)
