"""A DOUBLE column lands as its raw 64-bit words (recipe ``f64bits``) and
the assemble program splits them into the device's (f32, f32) pair by
integer operations (ops/limbs.f64_bits_hi_lo). Held here: the pair equals,
bit for bit, the host split the upload made before (kept below as the
oracle) on random 64-bit patterns and on every edge of the rounding;
``DeviceTable.from_host`` with the split forced lands the arrays the host
split's path landed, on one device and row-sharded over the CPU test
mesh; and a file scan counts the columns that took the path
(``scanF64SplitOnDevice``). The CPU backend lands f64 as it is, so the
tests force the split by standing in for ``split_f64_on_device``."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.columnar import table as table_mod
from spark_rapids_tpu.columnar.column import stage_upload
from spark_rapids_tpu.columnar.table import DeviceTable
from spark_rapids_tpu.ops.expr import col
from spark_rapids_tpu.ops.limbs import f64_bits_hi_lo
from tests.asserts import plan_metric_total


def _host_split(x):
    """The split the upload made on the host until the assemble program
    took it over: hi = f32(x), lo = f32(x - hi), lo = 0 where hi is not
    finite, lo = hi where x is +-0."""
    with np.errstate(invalid="ignore", over="ignore"):
        hi = x.astype(np.float32)
        lo = np.where(np.isfinite(hi), x - hi.astype(np.float64),
                      0.0).astype(np.float32)
        lo = np.where(x == 0.0, hi, lo)
    return hi, lo


def _bits(values):
    return np.asarray(values, dtype=np.uint64).view(np.int64)


def _of(biased, mantissa, negative=False):
    return (np.uint64(negative) << np.uint64(63)
            | np.uint64(biased) << np.uint64(52) | np.uint64(mantissa))


MANT = (1 << 52) - 1


def _random_patterns(rng):
    return rng.integers(-(1 << 63), (1 << 63) - 1, 1 << 20, dtype=np.int64,
                        endpoint=True)


def _f32_range(rng):
    """Exponents from below f32's subnormals to past its overflow."""
    n = 1 << 18
    sign = rng.integers(0, 2, n, dtype=np.uint64) << np.uint64(63)
    exp = rng.integers(840, 1170, n, dtype=np.uint64) << np.uint64(52)
    mant = rng.integers(0, MANT, n, dtype=np.uint64, endpoint=True)
    return (sign | exp | mant).view(np.int64)


def _specials(rng):
    v = [0.0, -0.0, np.inf, -np.inf, 1.0, -1.0, 0.5, 2.0 ** 52]
    words = [_of(2047, 1 << 51), _of(2047, 1 << 51, True),  # quiet NaN
             _of(2047, 1), _of(2047, 1, True),  # signalling, least payload
             _of(2047, (1 << 51) | 0x123456789),
             _of(2047, 0x4000123456789), _of(2047, MANT),
             _of(2047, MANT, True), _of(2047, 1 << 29),
             _of(2047, (1 << 29) - 1)]
    return np.concatenate([np.asarray(v).view(np.int64), _bits(words)])


def _f64_subnormals(rng):
    mant = np.concatenate([
        [1, 2, 3, MANT, MANT - 1, 1 << 51],
        rng.integers(1, MANT, 4096, dtype=np.uint64)]).astype(np.uint64)
    return np.concatenate([_bits(mant), _bits(mant | (np.uint64(1) << 63))])


def _around(value):
    """The 64 patterns nearest ``value`` on either side, both signs."""
    b = int(np.float64(value).view(np.int64))
    out = np.arange(b - 64, b + 65, dtype=np.int64)
    return np.concatenate([out, out | np.int64(-(1 << 63))])


def _flt_max_edges(rng):
    fmax = float(np.finfo(np.float32).max)
    half_ulp = 2.0 ** (127 - 24)
    return np.concatenate([_around(fmax), _around(fmax + half_ulp),
                           _around(fmax + 2 * half_ulp),
                           _around(2.0 ** 128)])


def _f32_subnormal_edges(rng):
    return np.concatenate([_around(v) for v in (
        2.0 ** -126, 2.0 ** -126 - 2.0 ** -150, 2.0 ** -127,
        2.0 ** -149, 2.0 ** -150, 3 * 2.0 ** -150, 2.0 ** -151,
        2.0 ** -149 * 1.5, 2.0 ** -140, 2.0 ** -178, 2.0 ** -179)])


def _hi_ties(rng):
    """hi exactly halfway between two f32s: the 29 dropped bits are
    100...0, with an even and an odd kept part, at every exponent of
    f32's normal range."""
    exps = np.arange(897, 1151, dtype=np.uint64)
    kept = rng.integers(0, 1 << 23, exps.size, dtype=np.uint64)
    out = []
    for odd in (0, 1):
        m = ((kept & ~np.uint64(1)) | np.uint64(odd)) << np.uint64(29)
        out.append((exps << np.uint64(52)) | m | np.uint64(1 << 28))
    ties = np.concatenate(out)
    return _bits(np.concatenate(
        [ties, ties | (np.uint64(1) << np.uint64(63))]))


def _with_residual(rng, exps, r):
    """Patterns whose residual x - f32(x) is r (< 2^28) units of the last
    place: hi rounded down (the low 29 bits are r) and, with the opposite
    sign, hi rounded up (they are 2^29 - r)."""
    high = rng.integers(0, 1 << 23, r.size,
                        dtype=np.uint64) << np.uint64(29)
    head = (exps << np.uint64(52)) | high
    return [head | r, head | (np.uint64(1 << 29) - r)]


def _lo_ties(rng):
    """lo halfway between two f32s. Where the residual is f32-normal
    (exponents from 930): 25 to 28 significant bits whose dropped low bits
    are 10...0. Where it is f32-subnormal (exponents 897 to 924, residuals
    under 2^-126, its quantum 2^-149 = 2^(926 - biased) units): bits from
    that position down are 10...0."""
    n = 1 << 14
    out = []
    for width in (25, 26, 27, 28):
        dropped = width - 24
        body = rng.integers(1 << 23, 1 << 24, n, dtype=np.uint64)
        r = (body << np.uint64(dropped)) | np.uint64(1 << (dropped - 1))
        out += _with_residual(
            rng, rng.integers(930, 1151, n, dtype=np.uint64), r)
    for biased in range(897, 925):
        s = 926 - biased
        k = rng.integers(0, 1 << max(28 - s, 0), 64, dtype=np.uint64,
                         endpoint=s >= 28)
        r = (k << np.uint64(s)) | np.uint64(1 << (s - 1))
        r = r[r < (1 << min(28, 949 - biased))]  # under 2^-126
        out += _with_residual(
            rng, np.full(r.size, biased, dtype=np.uint64), r)
    return _bits(np.concatenate(out))


def _tpch_prices(rng):
    """TPC-H decimals as DOUBLE: money rounded to cents and discounts."""
    price = np.round(rng.uniform(900.0, 105000.0, 1 << 16), 2)
    qty = np.round(rng.integers(1, 51, 1 << 16) * price, 2)
    disc = np.round(rng.integers(0, 11, 1 << 16) / 100.0, 2)
    charge = price * (1 - disc) * (1 + disc)
    return np.concatenate([price, -price, qty, disc, charge]).view(np.int64)


def _case(name):
    """A case's patterns, the same whichever cases ran before it."""
    return CASES[name](np.random.default_rng(20261018))


CASES = {
    "random_patterns": _random_patterns,
    "f32_range": _f32_range,
    "specials": _specials,
    "f64_subnormals": _f64_subnormals,
    "flt_max_edges": _flt_max_edges,
    "f32_subnormal_edges": _f32_subnormal_edges,
    "hi_ties": _hi_ties,
    "lo_ties": _lo_ties,
    "tpch_prices": _tpch_prices,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_device_split_equals_the_host_split(case):
    words = _case(case)
    hi, lo = jax.jit(f64_bits_hi_lo)(words)
    want_hi, want_lo = _host_split(words.view(np.float64))
    got_hi = np.asarray(hi).view(np.uint32)
    got_lo = np.asarray(lo).view(np.uint32)
    for name, got, want in (("hi", got_hi, want_hi.view(np.uint32)),
                            ("lo", got_lo, want_lo.view(np.uint32))):
        bad = np.nonzero(got != want)[0]
        assert bad.size == 0, (name, [
            (hex(int(words[i]) & (2 ** 64 - 1)), hex(got[i]), hex(want[i]))
            for i in bad[:5]])


def test_the_random_patterns_are_a_million():
    assert _case("random_patterns").size >= 10 ** 6


def test_the_tie_cases_are_ties():
    """The tie generators make what they say: the host split of each
    pattern is exactly halfway for hi, or has a halfway lo."""
    x = _case("hi_ties").view(np.float64)
    hi = x.astype(np.float32).astype(np.float64)
    ulp = np.spacing(np.abs(hi.astype(np.float32))).astype(np.float64)
    assert np.all(np.abs(x - hi) * 2 == ulp)
    x = _case("lo_ties").view(np.float64)
    r = x - x.astype(np.float32).astype(np.float64)
    lo = r.astype(np.float32)
    assert np.all(np.abs(r - lo.astype(np.float64)) * 2
                  == np.spacing(np.abs(lo)).astype(np.float64))


# --------------------------------------------------------------------------
# The landing: from_host with the split forced lands the host split's arrays
# --------------------------------------------------------------------------

ROWS = 1000


def _mixed_table():
    rng = np.random.default_rng(7)
    price = np.round(rng.uniform(900.0, 105000.0, ROWS), 2)
    price[:6] = [0.0, -0.0, np.inf, -np.inf, 1e-40, 3.4028235677973366e38]
    nullable = rng.normal(size=ROWS) * 1e5
    nullable[7] = np.nan
    valid = rng.random(ROWS) > 0.2
    flags = np.empty(ROWS, dtype=object)
    flags[:] = list(rng.choice(list("ANR"), ROWS))
    cols = [
        HostColumn(T.DOUBLE, price),
        HostColumn(T.DOUBLE, nullable, valid),
        HostColumn(T.DATE, rng.integers(8000, 10600, ROWS).astype(np.int32)),
        HostColumn(T.INT, rng.integers(-5, 50, ROWS).astype(np.int32),
                   rng.random(ROWS) > 0.1),
        HostColumn(T.LONG, rng.integers(0, 1 << 40, ROWS)),
        HostColumn(T.STRING, flags),
    ]
    return HostTable(["price", "nullable", "date", "int", "long", "flag"],
                     cols)


@jax.jit
def _parent_combine(hi, lo):
    """The parent assemble program's ``f64split`` branch."""
    h64, l64 = hi.astype(jnp.float64), lo.astype(jnp.float64)
    return jnp.where((h64 == 0.0) & (l64 == 0.0), h64, h64 + l64)


def _parent_double(column: HostColumn, cap: int):
    """The DOUBLE the host split's path landed: the halves staged on the
    host, summed on the device (whose f32 denormals flush as they did)."""
    padded = np.zeros(cap, dtype=np.float64)
    padded[:len(column)] = column.data
    return np.asarray(_parent_combine(*_host_split(padded)))


def _row_sharding():
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from spark_rapids_tpu.parallel.mesh import ensure_cpu_test_mesh
    if ensure_cpu_test_mesh(8) < 8:
        pytest.fail("the CPU test mesh has fewer than 8 devices")
    mesh = Mesh(np.array(jax.devices()[:8]), ("rows",))
    return NamedSharding(mesh, P("rows"))


@pytest.mark.parametrize("sharded", [False, True],
                         ids=["one_device", "row_sharded"])
def test_from_host_with_the_split_forced_lands_the_parent_arrays(
        monkeypatch, sharded):
    host = _mixed_table()
    sharding = _row_sharding() if sharded else None
    direct = DeviceTable.from_host(host, sharding=sharding)
    monkeypatch.setattr(table_mod, "split_f64_on_device", lambda: True)
    split = DeviceTable.from_host(host, sharding=sharding)
    assert split.capacity == direct.capacity
    if sharded:
        assert split.shard_spec is sharding
        for c in split.columns:
            assert c.data.sharding.is_equivalent_to(sharding, 1)
    for name, c, got, want in zip(host.names, host.columns, split.columns,
                                  direct.columns):
        assert np.array_equal(np.asarray(got.validity),
                              np.asarray(want.validity)), name
        data = np.asarray(got.data)
        if isinstance(c.dtype, T.DoubleType):
            parent = _parent_double(c, split.capacity)
            assert np.array_equal(data.view(np.int64),
                                  parent.view(np.int64)), name
        else:
            assert data.dtype == np.asarray(want.data).dtype, name
            assert np.array_equal(data, np.asarray(want.data)), name
            assert got.dictionary is want.dictionary or list(
                got.dictionary) == list(want.dictionary), name


def test_a_double_stages_as_its_padded_words():
    column = _mixed_table().columns[1]
    cap = 1024
    recipe, arrays, dictionary = stage_upload(column, cap, split_f64=True)
    assert recipe == ("f64bits", "i8", "double")
    words, validity = arrays
    assert words.dtype == np.int64 and words.shape == (cap,)
    assert np.array_equal(words[:ROWS], column.data.view(np.int64))
    assert not words[ROWS:].any() and not validity[ROWS:].any()
    assert np.array_equal(validity[:ROWS], column.validity)
    assert dictionary is None
    recipe, arrays, _ = stage_upload(column, cap, split_f64=False)
    assert recipe[0] == "direct" and arrays[0].dtype == np.float64


# --------------------------------------------------------------------------
# The counter: scanF64SplitOnDevice on a file scan
# --------------------------------------------------------------------------

FILES = 3
FILE_ROWS = 400


def _write(directory):
    os.makedirs(directory, exist_ok=True)
    rows = []
    for f in range(FILES):
        rng = np.random.default_rng(f)
        part = {
            "l_returnflag": list(rng.choice(list("ANR"), FILE_ROWS)),
            "l_quantity": np.round(rng.random(FILE_ROWS) * 50, 2),
            "l_extendedprice": np.round(rng.random(FILE_ROWS) * 9e4, 2),
            "l_orderkey": rng.integers(0, 1 << 20, FILE_ROWS),
        }
        pq.write_table(pa.table(part), os.path.join(
            directory, f"part-{f}.parquet"))
        rows.append(part)
    return {k: [v for p in rows for v in list(p[k])] for k in rows[0]}


@pytest.fixture(scope="module")
def logged_session(tmp_path_factory):
    """A device session whose queries leave an event record."""
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({
        "spark.rapids.sql.eventLog.enabled": "true",
        "spark.rapids.sql.eventLog.dir": str(tmp_path_factory.mktemp("ev"))})


def _sums(df):
    return (df.group_by("l_returnflag")
            .agg(F.sum(col("l_quantity")).alias("q"),
                 F.sum(col("l_extendedprice")).alias("p"),
                 F.count().alias("n")))


def _keys(df):
    return (df.group_by("l_returnflag")
            .agg(F.sum(col("l_orderkey")).alias("k"), F.count().alias("n")))


def _same(got, want):
    got, want = sorted(got, key=repr), sorted(want, key=repr)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g == pytest.approx(w, rel=1e-12)


@pytest.mark.parametrize("forced,query,doubles", [
    (True, _sums, 2), (True, _keys, 0), (False, _sums, 0)],
    ids=["forced_doubles", "forced_no_double", "cpu_doubles"])
def test_the_scan_counts_doubles_split_on_the_device(
        tmp_path, logged_session, cpu_session, monkeypatch, forced, query,
        doubles):
    """DOUBLE columns read × batches landed, in the record's plan tree."""
    rows = _write(str(tmp_path / "p"))
    if forced:
        monkeypatch.setattr(table_mod, "split_f64_on_device", lambda: True)
    got = query(logged_session.read_parquet(
        str(tmp_path / "p"), reader_type="PERFILE")).collect()
    assert plan_metric_total(logged_session, "scanBatches") == FILES
    assert plan_metric_total(
        logged_session, "scanF64SplitOnDevice") == doubles * FILES
    want = query(cpu_session.create_dataframe(
        {k: [x.item() if hasattr(x, "item") else x for x in v]
         for k, v in rows.items()},
        {"l_returnflag": T.STRING, "l_quantity": T.DOUBLE,
         "l_extendedprice": T.DOUBLE, "l_orderkey": T.LONG})).collect()
    _same(got, want)
