"""Dictionary-code aggregation fast path + split-f64 sums + input fusion
(reference analog: hash_aggregate_test.py; the fast path is the TPU-first
no-sort grouping of execs/aggregate.py, split sums are ops/segsum.py)."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.ops.expr import col, lit

from tests.asserts import assert_tpu_and_cpu_are_equal
from tests.asserts import plan_metric_total as _metric_total
from tests.data_gen import (
    BooleanGen, DoubleGen, IntGen, LongGen, StringGen, gen_table,
)


def _df(sess, gens, n=800, seed=11, num_batches=1):
    from spark_rapids_tpu.plan import from_host_table
    return from_host_table(gen_table(gens, n, seed), sess, num_batches)


GENS = {"s": StringGen(cardinality=7), "b": BooleanGen(),
        "v": LongGen(min_val=-1000, max_val=1000), "d": DoubleGen()}

ALL_AGGS = [
    F.count().alias("cnt"), F.count(col("v")).alias("cntv"),
    F.sum(col("v")).alias("sumv"), F.sum(col("d")).alias("sumd"),
    F.avg(col("d")).alias("avgd"), F.min(col("d")).alias("mind"),
    F.max(col("v")).alias("maxv"), F.first(col("v")).alias("fv"),
    F.last(col("d")).alias("ld"),
]


@pytest.fixture(scope="module")
def split_session():
    """Force the split-f64 sum path even on the CPU backend."""
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.tpu.sum.splitF64": "true"})


@pytest.fixture(scope="module")
def sorted_session():
    """Disable the dict fast path to pin the sort-segment path."""
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.tpu.agg.maxDictGroups": "0"})


def test_fast_path_string_key(session, cpu_session):
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("s").agg(*ALL_AGGS),
        session, cpu_session)


def test_fast_path_bool_key(session, cpu_session):
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("b").agg(*ALL_AGGS),
        session, cpu_session)


def test_fast_path_string_bool_multi_key(session, cpu_session):
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("s", "b").agg(*ALL_AGGS),
        session, cpu_session)


def test_fast_path_matches_sorted_path(session, sorted_session):
    """The no-sort dict path and the general sort-segment path must agree."""
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("s", "b").agg(*ALL_AGGS),
        session, sorted_session)


def test_fast_path_with_fused_filter_project(session, cpu_session):
    def build(s):
        return (
            _df(s, GENS)
            .filter(col("v") > lit(-500))
            .select(col("s"), col("b"), col("v"),
                    (col("d") * lit(2.0)).alias("d2"))
            .filter(col("v") < lit(500))
            .group_by("s", "b")
            .agg(F.count().alias("cnt"), F.sum(col("d2")).alias("sd2"),
                 F.avg(col("v")).alias("av"))
        )
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def _exec_tree(e):
    """Every exec of a converted plan, transitions' links included."""
    yield e
    for c in getattr(e, "children", ()):
        yield from _exec_tree(c)
    for attr in ("source", "tpu_exec", "cpu_node"):
        nxt = getattr(e, attr, None)
        if nxt is not None:
            yield from _exec_tree(nxt)


def test_fusion_peels_project_and_filter(session):
    """The converted exec tree should contain no Project/Filter above the
    scan once fusion inlines them into the aggregate."""
    from spark_rapids_tpu.overrides import apply_overrides
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.execs.basic import TpuFilterExec, TpuProjectExec

    df = (_df(session, GENS)
          .filter(col("v") > lit(0))
          .select(col("s"), (col("d") + lit(1.0)).alias("d1"))
          .group_by("s").agg(F.sum(col("d1")).alias("sd")))
    executable, _ = apply_overrides(df.plan, session.conf)

    execs = list(_exec_tree(executable))
    aggs = [e for e in execs if isinstance(e, TpuHashAggregateExec)]
    others = [e for e in execs
              if isinstance(e, (TpuFilterExec, TpuProjectExec))]
    assert len(aggs) == 1
    assert aggs[0].filters, "filter should be fused into the aggregate"
    assert not others, f"unfused execs remain: {others}"


def test_split_sum_accuracy(split_session, cpu_session):
    """Split-f64 sums must stay within ~1e-7 relative of the exact path."""
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, {"s": StringGen(cardinality=5), "d": DoubleGen()},
                      n=5000)
        .group_by("s").agg(F.sum(col("d")).alias("sd"),
                           F.avg(col("d")).alias("ad")),
        split_session, cpu_session, approximate_float=True)


def test_split_sum_huge_values_reroute_exact(split_session, cpu_session):
    """|x| > 1e34 must reroute to the exact path at runtime (lax.cond)."""
    from spark_rapids_tpu.plan import from_host_table
    from spark_rapids_tpu.columnar import HostColumn, HostTable
    from spark_rapids_tpu import types as T

    n = 512
    vals = np.full(n, 1e300)
    vals[::2] = -1e300
    vals[0] = 12345.0
    keys = np.array(["a"] * n, dtype=object)
    table = HostTable(["s", "d"], [HostColumn(T.STRING, keys),
                                   HostColumn(T.DOUBLE, vals)])

    def build(s):
        return from_host_table(table, s).group_by("s").agg(
            F.sum(col("d")).alias("sd"))

    assert_tpu_and_cpu_are_equal(build, split_session, cpu_session)


def test_split_segment_sum_unit():
    """Direct unit check of segment_sum_f64 against numpy, forced split."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.segsum import segment_sum_f64

    rng = np.random.default_rng(3)
    cap = 4096
    vals = rng.random(cap) * 1e5 - 5e4
    gid = (rng.random(cap) * 11).astype(np.int32)
    got = np.asarray(segment_sum_f64(
        jnp.asarray(vals), jnp.asarray(gid), 16, cap, use_split=True))
    ref = np.zeros(16)
    np.add.at(ref, gid, vals)
    np.testing.assert_allclose(got, ref, rtol=1e-7, atol=1e-4)


@pytest.mark.parametrize("cap", [1 << 12, 1 << 16, 1 << 17])
def test_split_segment_sum_a_segment_a_row_keeps_the_pairs_precision(cap):
    """The sorted-path aggregate's segments are its groups, a few rows
    each (num_segments == capacity): the split keeps a group's sum to
    the pair's precision at every capacity, where per-block f32
    partials (taken at 2^16 rows and fewer) rounded it once in f32."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.segsum import segment_sum_f64

    rng = np.random.default_rng(42)
    # Q3's shape: up to 7 products of a price and (1 - discount) a group
    price = np.round(rng.uniform(900, 105000, cap), 2)
    vals = price * (1 - np.round(rng.uniform(0, 0.1, cap), 2))
    gid = np.minimum(np.arange(cap) // 7, cap - 1).astype(np.int32)
    got = np.asarray(segment_sum_f64(
        jnp.asarray(vals), jnp.asarray(gid), cap, cap, use_split=True))
    ref = np.zeros(cap)
    np.add.at(ref, gid, vals)
    live = ref != 0
    assert np.max(np.abs(got[live] - ref[live]) / np.abs(ref[live])) < 1e-12


def test_sorted_path_nulls_in_keys(session, cpu_session):
    gens = {"s": StringGen(cardinality=4), "b": BooleanGen(),
            "v": IntGen(min_val=-50, max_val=50, null_prob=0.3)}
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, gens).group_by("s", "b").agg(
            F.count().alias("c"), F.sum(col("v")).alias("sv")),
        session, cpu_session)


def test_large_dict_falls_back_to_sorted(session, cpu_session):
    """Key domain above maxDictGroups must take the sort-segment path and
    still be correct."""
    from spark_rapids_tpu.session import TpuSession
    limited = TpuSession({"spark.rapids.tpu.agg.maxDictGroups": 4})
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("s").agg(F.count().alias("c")),
        limited, cpu_session)


def test_unblocked_split_guard_skewed_segment():
    """A single huge all-positive segment must reroute to the exact path:
    the unblocked split guard scales with per-segment row count (review
    fix — a mass-only guard calibrated for 1024-row blocks under-counts
    sqrt(n/1024)x)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_tpu.ops.segsum import _unblocked_split_segment_sum

    n = 1 << 17
    v = jnp.asarray(np.full(n, 1.0 + 2**-26))  # low bits shred in f32 sums
    gid = jnp.zeros(n, dtype=jnp.int32)
    got = jax.jit(
        lambda v, g: _unblocked_split_segment_sum(v, g, n))(v, gid)
    want = jax.ops.segment_sum(v, gid, num_segments=n)
    rel = abs(float(got[0]) - float(want[0])) / float(want[0])
    assert rel <= 1e-6, rel


def test_ungrouped_agg_fast_path_empty_input(session):
    """Global aggregates yield exactly ONE row on empty input: count=0,
    sum NULL (Spark semantics through the new zero-key fast path)."""
    import numpy as np

    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.ops.expr import col, lit

    df = (session.create_dataframe(
        {"v": np.arange(50, dtype=np.int64)})
        .filter(col("v") > lit(10**9))
        .agg(F.count("v").alias("c"), F.sum("v").alias("s"),
             F.avg("v").alias("a"), F.max("v").alias("m")))
    rows = df.collect()
    assert rows == [(0, None, None, None)]


# ---------------------------------------------------------------------------
# per-group counts by contraction (ops/segsum.segment_counts; the
# valid_counts section of the fast kernel)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("capacity,num_segments,k,contraction", [
    (4096, 16, 3, True),      # four blocks
    (4096, 8, 1, True),       # a single mask
    (4096, 32, 6, True),      # the widest one-hot it takes
    (8192, 16, 2, True),      # one group holds all rows: partials = BLOCK
    (512, 16, 3, True),       # one block under BLOCK rows
    (1536, 16, 2, False),     # ragged capacity: the 2-D scatter
    (4096, 64, 3, False),     # past MATMUL_MAX_SEGMENTS: the 2-D scatter
    (8192, 5000, 2, False),   # past 4096 segments: per-mask scatters
], ids=["g16", "g8-k1", "g32-k6", "one-group-full-blocks", "sub-block",
        "ragged", "g64-scatter", "g5000-per-mask"])
def test_segment_counts_unit(request, capacity, num_segments, k, contraction):
    """segment_counts against numpy.bincount with weights: exact int32
    on the contraction and on both scatter forms."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops import segsum

    rng = np.random.default_rng(capacity + num_segments + k)
    one_group = "one-group" in request.node.name
    if one_group:
        gid = np.full(capacity, 3, dtype=np.int32)
        masks = [np.ones(capacity, dtype=np.bool_) for _ in range(k)]
    else:
        gid = rng.integers(0, num_segments, capacity).astype(np.int32)
        masks = [rng.random(capacity) < p
                 for p in np.linspace(0.2, 1.0, k)]
    got = jax.jit(lambda ms, g: segsum.segment_counts(
        list(ms), g, num_segments, capacity))(
            tuple(jnp.asarray(m) for m in masks), jnp.asarray(gid))
    want = np.stack([np.bincount(gid, weights=m, minlength=num_segments)
                     for m in masks], axis=1)
    assert got.dtype == jnp.int32 and got.shape == (num_segments, k)
    np.testing.assert_array_equal(np.asarray(got), want.astype(np.int32))
    assert segsum.takes_contraction(num_segments, capacity) == contraction
    if one_group:
        assert int(got[3, 0]) == capacity


@pytest.mark.parametrize("num_segments,scatters", [(16, False), (64, True)])
def test_segment_counts_lowering(num_segments, scatters):
    """At a small segment count the counts lower with no scatter (the
    helper alone: the sums' exact fallback inside lax.cond keeps one)."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.segsum import segment_counts

    cap, k = 4096, 3
    text = jax.jit(lambda ms, g: segment_counts(
        list(ms), g, num_segments, cap)).lower(
            tuple(jax.ShapeDtypeStruct((cap,), jnp.bool_) for _ in range(k)),
            jax.ShapeDtypeStruct((cap,), jnp.int32)).compile().as_text()
    assert ("scatter" in text) == scatters
    assert ("dot(" in text or "convolution(" in text) != scatters


def _counts_table(n, groups, seed, null_keys=True):
    """k: `groups` distinct strings (+ NULLs); x double and y long with
    NULLs at different rows; w for a filter."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar import HostColumn, HostTable
    rng = np.random.default_rng(seed)
    k = np.array([f"g{i:02d}" for i in range(groups)],
                 dtype=object)[rng.integers(0, groups, n)]
    kvalid = (rng.random(n) > 0.1) if null_keys else np.ones(n, np.bool_)
    k[~kvalid] = None
    return HostTable(["k", "x", "y", "w"], [
        HostColumn(T.STRING, k, kvalid),
        HostColumn(T.DOUBLE, rng.random(n) * 100, rng.random(n) > 0.3),
        HostColumn(T.LONG, rng.integers(-9, 9, n).astype(np.int64),
                   rng.random(n) > 0.1),
        HostColumn(T.LONG, rng.integers(0, 100, n).astype(np.int64))])


#: three specs over x (one mask), two over y (a second), count(*) (live)
COUNT_AGGS = [
    F.count().alias("n"), F.count(col("x")).alias("nx"),
    F.sum(col("x")).alias("sx"), F.avg(col("x")).alias("ax"),
    F.count(col("y")).alias("ny"), F.sum(col("y")).alias("sy"),
]


def _logged(event_dir, **conf):
    """Event log on: the exec metrics come from the query's record."""
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({**conf,
                       "spark.rapids.sql.eventLog.enabled": "true",
                       "spark.rapids.sql.eventLog.dir": str(event_dir)})


@pytest.fixture(scope="module")
def logged_session(tmp_path_factory):
    return _logged(tmp_path_factory.mktemp("agg_events"))


def _counts_query(s, table, num_batches=1, filtered=False):
    from spark_rapids_tpu.plan import from_host_table
    df = from_host_table(table, s, num_batches)
    if filtered:
        df = df.filter(col("w") < lit(40))
    return df.group_by("k").agg(*COUNT_AGGS)


@pytest.mark.parametrize("groups,contraction", [
    (5, True),    # 5 + null slot = 6 -> gpad 8
    (12, True),   # 13 -> gpad 16
    (25, True),   # 26 -> gpad 32
    (40, False),  # 41 -> gpad 64: the scatter
], ids=["gpad8", "gpad16", "gpad32", "gpad64"])
@pytest.mark.parametrize("filtered", [False, True], ids=["all", "filtered"])
def test_fast_counts_match_oracle(logged_session, cpu_session, groups,
                                  contraction, filtered):
    """NULLs in the counted values and in the keys, with and without a
    fused filter that drops rows; contraction up to gpad 32, scatter at 64."""
    table = _counts_table(3000, groups, seed=groups)
    assert_tpu_and_cpu_are_equal(
        lambda s: _counts_query(s, table, filtered=filtered),
        logged_session, cpu_session, approximate_float=True)
    assert _metric_total(logged_session, "countsByContraction") == int(
        contraction)


@pytest.mark.parametrize("buckets,contraction", [
    ("pow2", True),    # 1200 rows -> capacity 2048: two whole blocks
    ("1536", False),   # capacity 1536: not whole blocks, the scatter
], ids=["whole-blocks", "ragged"])
def test_fast_counts_capacity_blocks(tmp_path, cpu_session, buckets,
                                     contraction):
    sess = _logged(tmp_path, **{"spark.rapids.sql.shapeBuckets": buckets})
    table = _counts_table(1200, 12, seed=2)
    try:
        assert_tpu_and_cpu_are_equal(
            lambda s: _counts_query(s, table), sess, cpu_session,
            approximate_float=True)
        assert _metric_total(sess, "countsByContraction") == int(contraction)
    finally:
        # the bucket policy is process-wide: put the default back
        from spark_rapids_tpu.columnar.column import set_bucket_policy
        set_bucket_policy("pow2")


def test_fast_counts_one_group_fills_blocks(logged_session, cpu_session):
    """Every row of four blocks in one group: each block partial is 1024."""
    n = 4096
    data = {"k": np.array(["only"] * n, dtype=object),
            "v": np.arange(n, dtype=np.int64)}

    def build(s):
        return s.create_dataframe(data).group_by("k").agg(
            F.count().alias("n"), F.count(col("v")).alias("nv"))
    assert build(logged_session).collect() == [("only", n, n)]
    assert _metric_total(logged_session, "countsByContraction") == 1
    assert build(cpu_session).collect() == [("only", n, n)]


def _fast_agg_exec(session, df):
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.overrides import apply_overrides
    executable, _ = apply_overrides(df.plan, session.conf)
    found = [e for e in _exec_tree(executable)
             if isinstance(e, TpuHashAggregateExec)]
    assert len(found) == 1
    return found[0]


def test_fast_counts_live_mask(session, cpu_session):
    """A deferred-compaction batch (rows live at their own slots) counts
    the same as the CPU oracle over the rows the mask keeps."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.plan import from_host_table

    table = _counts_table(3000, 12, seed=9)
    keep = np.random.default_rng(4).random(3000) < 0.6
    aggx = _fast_agg_exec(
        session, from_host_table(table, session).group_by("k")
        .agg(*COUNT_AGGS))
    batch = next(iter(aggx.children[0].execute_masked()))
    live = np.zeros(batch.capacity, dtype=np.bool_)
    live[:3000] = keep
    masked = DeviceTable(batch.names, batch.columns, int(keep.sum()),
                         batch.capacity, live=jnp.asarray(live))
    got = aggx._aggregate(masked, aggx.grouping, aggx.agg_specs,
                          aggx.grouping_names, aggx.filters).to_host()
    assert aggx.metrics.get("countsByContraction") == 1

    from spark_rapids_tpu.columnar import HostColumn, HostTable
    kept = HostTable(table.names, [
        HostColumn(c.dtype, c.data[keep], c.validity[keep])
        for c in table.columns])
    want = from_host_table(kept, cpu_session).group_by("k").agg(
        *COUNT_AGGS).collect_table()
    g, w = got.to_pydict(), want.to_pydict()
    order_g = sorted(range(got.num_rows), key=lambda i: repr(g["k"][i]))
    order_w = sorted(range(want.num_rows), key=lambda i: repr(w["k"][i]))
    for name in ("k", "n", "nx", "ny", "sy"):
        assert [g[name][i] for i in order_g] == \
            [w[name][i] for i in order_w], name


def test_fast_counts_dedup_fans_out(logged_session, cpu_session, monkeypatch):
    """Specs over one column share a mask; specs over different columns
    do not: five counted specs over three columns make four masks (with
    live), and every spec still reads its own column's count."""
    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.plan import from_host_table

    seen = []
    real = A.segment_counts

    def spy(masks, gid, num_segments, capacity):
        seen.append(len(masks))
        return real(masks, gid, num_segments, capacity)
    monkeypatch.setattr(A, "segment_counts", spy)

    t = _counts_table(2000, 5, seed=21)
    # fresh column names: a fresh trace, so the spy sees the kernel built
    t = type(t)(["dk", "dx", "dy", "dz"], t.columns)

    def build(s):
        return from_host_table(t, s).group_by("dk").agg(
            F.count(col("dx")).alias("nx"), F.avg(col("dx")).alias("ax"),
            F.count(col("dy")).alias("ny"), F.count(col("dz")).alias("nz"),
            F.sum(col("dy")).alias("sy"), F.count().alias("n"))
    assert_tpu_and_cpu_are_equal(build, logged_session, cpu_session,
                                 approximate_float=True)
    assert seen == [4], seen


def test_fast_counts_rand_child_is_not_shared(session, cpu_session):
    """One rand-bearing expression under two specs draws twice (each spec
    preps its own stream), so its two counts differ: computed children
    with equal key() must not share a mask (string literals are the
    other such case, test_expr_tail's pivot)."""
    data = {"k": np.array(["a", "b"] * 500, dtype=object),
            "x": np.arange(1000, dtype=np.int64)}

    def build(s):
        e = F.when(F.rand(3) < lit(0.5), col("x")).otherwise(lit(None))
        return s.create_dataframe(data).group_by("k").agg(
            F.count(e).alias("c1"), F.sum(e).alias("s1"),
            F.count(e).alias("c2"))
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)
    assert any(r[1] != r[3] for r in build(session).collect())


@pytest.mark.parametrize("n", [50, 5000], ids=["one-block", "five-blocks"])
def test_ungrouped_counts_empty_input(logged_session, n):
    """An ungrouped aggregate (gpad 8) whose filter keeps no row: one
    row, counts 0."""
    df = (logged_session.create_dataframe(
        {"v": np.arange(n, dtype=np.int64)})
        .filter(col("v") > lit(10**9))
        .agg(F.count().alias("n"), F.count("v").alias("c"),
             F.sum("v").alias("s"), F.avg("v").alias("a")))
    assert df.collect() == [(0, 0, None, None)]
    assert _metric_total(logged_session, "countsByContraction") == 1


def test_counts_by_contraction_per_aggregate_call(tmp_path, cpu_session,
                                                  monkeypatch):
    """A multi-batch group-by bumps countsByContraction once per
    _aggregate call (the partials and the merge), and the lowered fast
    kernel keeps the valid_counts scope around the counting."""
    import jax
    from spark_rapids_tpu.execs import aggregate as A

    calls = []
    real_aggregate = A.TpuHashAggregateExec._aggregate

    def counting(self, *a, **k):
        calls.append(1)
        return real_aggregate(self, *a, **k)
    monkeypatch.setattr(A.TpuHashAggregateExec, "_aggregate", counting)

    lowered = []
    real_jit = A.tpu_jit

    def jit_spy(fn, *, name, **kw):
        jf = real_jit(fn, name=name, **kw)
        if name != "agg_fast":
            return jf

        def call(*args):
            if not lowered:
                lowered.append(jax.jit(fn).lower(*args).as_text(
                    debug_info=True))
            return jf(*args)
        return call
    monkeypatch.setattr(A, "tpu_jit", jit_spy)

    # a batch target under one batch: the coalesce passes batches through
    logged_session = _logged(
        tmp_path, **{"spark.rapids.sql.batchSizeBytes": "1024"})
    t = _counts_table(4000, 12, seed=33)
    t = type(t)(["mk", "mx", "my", "mw"], t.columns)  # a fresh trace

    def build(s):
        from spark_rapids_tpu.plan import from_host_table
        return from_host_table(t, s, 4).group_by("mk").agg(
            F.count().alias("n"), F.count(col("mx")).alias("nx"),
            F.avg(col("mx")).alias("ax"), F.sum(col("my")).alias("sy"))
    assert_tpu_and_cpu_are_equal(build, logged_session, cpu_session,
                                 approximate_float=True)
    batches = _metric_total(logged_session, "partialAggBatches")
    assert batches == 4
    assert len(calls) == batches + 1
    assert _metric_total(logged_session, "countsByContraction") == len(calls)
    assert lowered and "valid_counts" in lowered[0]
    for scope in ("live_mask", "group_ids", "agg_values", "compact_groups"):
        assert scope in lowered[0], scope


# ---------------------------------------------------------------------------
# the streaming loop: a partial's row count stays on the device unless
# reading it can shrink something (DeviceTable.shrink, the capacity rule in
# TpuHashAggregateExec.execute); tests/test_memory.py holds the run-ahead
# bound
# ---------------------------------------------------------------------------

def _unknown_count_table(capacity, n, masked=False):
    """One LONG column 0..capacity-1 whose first n rows are live and
    whose count is a device scalar the host has not read."""
    import jax.numpy as jnp
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar import DeviceColumn, DeviceTable
    live = jnp.arange(capacity) < n
    col_ = DeviceColumn(T.LONG, jnp.arange(capacity, dtype=jnp.int64),
                        jnp.ones(capacity, dtype=jnp.bool_))
    return DeviceTable(["v"], [col_], jnp.asarray(n, jnp.int32), capacity,
                       live=live if masked else None)


@pytest.mark.parametrize("capacity,masked,reads", [
    (16, False, False),    # a fast-path partial: under the smallest bucket
    (128, False, False),   # the smallest bucket itself
    (128, True, False),    # compacted first, still nothing to shrink to
    (256, False, True),    # one bucket above: the count decides
    (4096, False, True),
    (4096, True, True),
], ids=["cap16", "cap128", "cap128-masked", "cap256", "cap4096",
        "cap4096-masked"])
def test_shrink_reads_the_count_only_for_a_smaller_bucket(capacity, masked,
                                                          reads):
    from spark_rapids_tpu import dispatch
    t = _unknown_count_table(capacity, 5, masked)
    before = dispatch.host_fetch_count()
    out = t.shrink()
    assert dispatch.host_fetch_count() - before == int(reads)
    assert out.num_rows_known == reads
    if reads:
        assert out.capacity == 128 and out.num_rows == 5
    else:
        assert out.capacity == capacity
        assert (out is t) != masked   # a masked table comes back compacted
    assert out.live is None
    assert out.to_host().to_pydict() == {"v": [0, 1, 2, 3, 4]}


def _per_batch_shrink(monkeypatch):
    """Test-only: every _aggregate output's count is read and the table
    shrunk before the loop sees it, as the loop did for each partial
    before the capacity rule."""
    from spark_rapids_tpu.execs import aggregate as A
    real = A.TpuHashAggregateExec._aggregate

    def forced(self, *a, **k):
        out = real(self, *a, **k)
        out.num_rows
        return out.shrink()
    monkeypatch.setattr(A.TpuHashAggregateExec, "_aggregate", forced)


def _stream_table(n, groups, seed, tag):
    """_counts_table under column names of its own (a fresh trace)."""
    t = _counts_table(n, groups, seed)
    return type(t)([f"{tag}{c}" for c in "kxyw"], t.columns)


def _stream_query(s, table, tag, batches, shape):
    from spark_rapids_tpu.plan import from_host_table
    k, x, y, w = (col(f"{tag}{c}") for c in "kxyw")
    aggs = [F.count().alias("n"), F.count(x).alias("nx"),
            F.sum(x).alias("sx"), F.avg(x).alias("ax"),
            F.sum(y).alias("sy"), F.min(y).alias("my")]
    if shape == "masked":
        # filters under a union are not fused into the aggregate: its
        # batches arrive with a deferred-compaction live mask
        half = batches // 2
        df = (from_host_table(table, s, half).filter(w < lit(40))
              .union(from_host_table(table, s, batches - half)
                     .filter(w >= lit(70))))
    else:
        df = from_host_table(table, s, batches)
        if shape == "filtered":
            df = df.filter(w < lit(40))
    return df.group_by(f"{tag}k").agg(*aggs)


@pytest.mark.parametrize("batches", [4, 16])
@pytest.mark.parametrize("groups,shape", [
    (5, "all"), (12, "all"), (25, "all"), (40, "all"),
    (5, "filtered"), (12, "filtered"), (25, "filtered"), (40, "filtered"),
    (12, "masked"), (40, "masked"),
], ids=["gpad8", "gpad16", "gpad32", "gpad64", "gpad8-filtered",
        "gpad16-filtered", "gpad32-filtered", "gpad64-filtered",
        "gpad16-masked", "gpad64-masked"])
def test_streaming_fast_path_reads_no_partial_count(
        tmp_path, cpu_session, monkeypatch, batches, groups, shape):
    """NULL keys, NULL values, a fused filter, masked batches: one host
    sync a query (the root result), every partial kept with its count on
    the device, answers equal to the CPU's and bit-equal to the same
    query with the per-batch count read and shrink forced."""
    from spark_rapids_tpu.execs import aggregate as A
    masks = []
    real = A.TpuHashAggregateExec._aggregate

    def spy(self, table, *a, **k):
        masks.append(table.live is not None)
        return real(self, table, *a, **k)
    monkeypatch.setattr(A.TpuHashAggregateExec, "_aggregate", spy)

    sess = _logged(tmp_path, **{"spark.rapids.sql.batchSizeBytes": "1024"})
    tag = f"s{batches}g{groups}{shape[0]}"
    table = _stream_table(1000 * batches, groups, seed=groups + batches, tag=tag)

    def build(s):
        return _stream_query(s, table, tag, batches, shape)
    assert_tpu_and_cpu_are_equal(build, sess, cpu_session,
                                 approximate_float=True)
    rec = sess.last_event_record
    assert rec["hostSyncs"] == 1
    assert _metric_total(sess, "partialAggBatches") == batches
    assert _metric_total(sess, "partialCountReads") == 0
    assert _metric_total(sess, "runAheadWaits") == 0
    # the partials' inputs, then the merge's unmasked concat
    assert masks[:batches] == [shape == "masked"] * batches
    got = sorted(build(sess).collect(), key=repr)

    _per_batch_shrink(monkeypatch)
    forced = sorted(build(sess).collect(), key=repr)
    assert sess.last_event_record["hostSyncs"] > batches
    assert _metric_total(sess, "partialCountReads") == batches
    assert got == forced


@pytest.mark.parametrize("rows,batches", [
    (1000, 4), (1000, 6),
    (140000, 2),   # 2^18-row inputs: partials at the speculative 2^16
], ids=["4", "6", "speculative-quarter"])
def test_streaming_sorted_path_shrinks_every_partial(
        tmp_path, cpu_session, monkeypatch, rows, batches):
    """A sorted-path partial has its input's capacity (or, past
    EMBED_NROWS_CAP, a speculative quarter of it): the capacity rule
    keeps none, each is shrunk to its live bucket at one count read, and
    the merge concat is as small as before."""
    from spark_rapids_tpu.columnar import bucket_for
    from spark_rapids_tpu.columnar import table as TB
    concats = []
    real = TB.concat_device

    def spy(tables, **kw):
        out = real(tables, **kw)
        concats.append(([(t.capacity, t.num_rows) for t in tables],
                        out.capacity))
        return out
    monkeypatch.setattr(TB, "concat_device", spy)

    sess = _logged(tmp_path, **{"spark.rapids.sql.batchSizeBytes": "1024",
                                "spark.rapids.tpu.agg.maxDictGroups": "0"})
    tag = f"o{rows}x{batches}"
    table = _stream_table(rows * batches, 12, seed=batches, tag=tag)

    def build(s):
        return _stream_query(s, table, tag, batches, "all")
    assert_tpu_and_cpu_are_equal(build, sess, cpu_session,
                                 approximate_float=True)
    assert _metric_total(sess, "partialAggBatches") == batches
    assert _metric_total(sess, "partialCountReads") == batches
    assert _metric_total(sess, "countsByContraction") == 0
    assert sess.last_event_record["hostSyncs"] == batches + 1
    (parts, out_capacity), = concats
    assert len(parts) == batches
    assert all(cap == bucket_for(max(n, 1)) for cap, n in parts), parts
    assert out_capacity == bucket_for(sum(cap for cap, _ in parts))


@pytest.mark.parametrize("rows,groups,batches,reads", [
    (1000, 200, 4, 0),   # 4 x 256 slots = one input batch's 1024: all kept
    (1000, 200, 5, 1),   # the fifth would pass it: shrunk, one count read
    (1000, 200, 7, 3),
    (200, 2000, 4, 4),   # 256 slots against a 256-row input: none is kept
], ids=["at-the-boundary", "one-over", "three-over", "not-under-its-input"])
def test_streaming_capacity_rule_boundary(tmp_path, cpu_session, rows,
                                          groups, batches, reads):
    """Partials of 256 slots (129 to 255 keys + the null slot): kept
    while their capacities together stay within one input batch's, and
    only while a partial is smaller than its input."""
    sess = _logged(tmp_path, **{"spark.rapids.sql.batchSizeBytes": "1024"})
    tag = f"b{rows}x{batches}"
    table = _stream_table(rows * batches, groups, seed=rows + batches,
                          tag=tag)
    exec_ = _fast_agg_exec(sess, _stream_query(sess, table, tag, batches,
                                               "all"))
    batch = next(iter(exec_.children[0].execute_masked()))
    assert exec_._fast_layout(
        exec_.grouping, exec_._prep_all(batch, exec_.grouping,
                                        exec_.agg_specs, exec_.filters)[2],
        batch.capacity)[3] == 256

    assert_tpu_and_cpu_are_equal(
        lambda s: _stream_query(s, table, tag, batches, "all"),
        sess, cpu_session, approximate_float=True)
    assert _metric_total(sess, "partialAggBatches") == batches
    assert _metric_total(sess, "partialCountReads") == reads
    assert sess.last_event_record["hostSyncs"] == 1 + reads


@pytest.mark.parametrize("k", [1, 3, 4], ids=["first", "third", "last"])
def test_streaming_injected_oom_replays_the_partial(
        tmp_path, cpu_session, monkeypatch, k):
    """An OOM injected into the k-th partial's retry block is replayed
    there (one retry, no count read by the loop) and the answer does not
    change."""
    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.runtime.retry import RMM_TPU
    calls = []
    real = A.TpuHashAggregateExec._aggregate

    def failing(self, *a, **kw):
        calls.append(1)
        if len(calls) == k:
            RMM_TPU.force_retry_oom(1)
            RMM_TPU.maybe_inject()
        return real(self, *a, **kw)

    sess = _logged(tmp_path, **{"spark.rapids.sql.batchSizeBytes": "1024"})
    table = _stream_table(4000, 12, seed=k, tag=f"f{k}")

    def build(s):
        return _stream_query(s, table, f"f{k}", 4, "all")
    want = sorted(build(sess).collect(), key=repr)
    monkeypatch.setattr(A.TpuHashAggregateExec, "_aggregate", failing)
    retries = RMM_TPU.retry_count
    got = sorted(build(sess).collect(), key=repr)
    assert RMM_TPU.retry_count == retries + 1
    assert len(calls) == 4 + 1 + 1   # the partials, the replay, the merge
    rec = sess.last_event_record
    # the replay's spill pass reads what it demotes: the k - 1 partials
    # buffered so far, and nothing else but the root result
    assert rec["oomRetries"] == 1 and rec["hostSyncs"] <= k
    assert _metric_total(sess, "partialCountReads") == 0
    assert got == want
    assert_tpu_and_cpu_are_equal(build, sess, cpu_session,
                                 approximate_float=True)


# ---------------------------------------------------------------------------
# a batch of several slices is aggregated slice by slice inside one
# program (AGG_SLICE patched small: 8 slices under a 16,384-row batch)
# ---------------------------------------------------------------------------

SLICED_AGGS = [
    F.count().alias("n"), F.count(col("x")).alias("nx"),
    F.sum(col("x")).alias("sx"), F.avg(col("x")).alias("ax"),
    F.min(col("x")).alias("mnx"), F.max(col("y")).alias("mxy"),
    F.sum(col("y")).alias("sy"), F.variance(col("x")).alias("vx"),
]


def _rows_close(got, want):
    """Two HostTables hold the same rows in any order: floats to 1e-6
    relative (sums and moments merged in another order), the rest equal."""
    got = sorted(zip(*got.to_pydict().values()), key=repr)
    want = sorted(zip(*want.to_pydict().values()), key=repr)
    assert len(got) == len(want), (got, want)
    for g, w in zip(got, want):
        for gv, wv in zip(g, w):
            if isinstance(gv, float) and isinstance(wv, float):
                assert abs(gv - wv) <= 1e-6 * max(1.0, abs(wv)), (g, w)
            else:
                assert gv == wv, (g, w)


def _feed(aggx, batches):
    """`aggx` over the given device batches in place of its child."""
    from spark_rapids_tpu.execs.base import TpuExec

    class Batches(TpuExec):
        def execute(self):
            return iter(batches)
    aggx.children = (Batches(),)
    return [t.to_host() for t in aggx.execute()]


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "ungrouped"])
@pytest.mark.parametrize("masked", [False, True], ids=["prefix", "masked"])
@pytest.mark.parametrize("nrows", [1500, 4096, 15000, 0], ids=[
    "first-slice-only", "on-a-boundary", "partial-last-slice", "empty"])
def test_sliced_aggregate_matches_oracle(session, cpu_session, monkeypatch,
                                         nrows, masked, grouped):
    """NULL keys and values, a fused filter, sum, count, avg, min, max
    and a variance: one program a batch, its slices' partials merged by
    the streaming path's plan, equal to the CPU over the live rows."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import DeviceTable, HostColumn, HostTable
    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.plan import from_host_table
    monkeypatch.setattr(A, "AGG_SLICE", 2048)
    table = _counts_table(15000, 12, seed=21)

    def build(s, t):
        df = from_host_table(t, s).filter(col("w") < lit(80))
        return (df.group_by("k") if grouped else df).agg(*SLICED_AGGS)
    aggx = _fast_agg_exec(session, build(session, table))
    assert aggx.filters
    batch = next(iter(aggx.children[0].execute_masked()))
    assert batch.capacity == 16384
    keep = np.arange(15000) < nrows
    live = None
    if masked:
        keep &= np.random.default_rng(5).random(15000) < 0.6
        live = np.zeros(batch.capacity, dtype=np.bool_)
        live[:15000] = keep
        live = jnp.asarray(live)
    cut = DeviceTable(batch.names, batch.columns, int(keep.sum()),
                      batch.capacity, live=live)
    assert aggx._slices_of(cut)[0] == 8
    got, = _feed(aggx, [cut])
    assert aggx.metrics.get("slicedAggBatches") == 1
    assert aggx.metrics.get("aggSlices") == 8
    assert aggx.metrics.get("partialCountReads") == 0

    kept = HostTable(table.names, [
        HostColumn(c.dtype, c.data[keep], c.validity[keep])
        for c in table.columns])
    want = build(cpu_session, kept).collect_table()
    if not grouped:
        assert got.num_rows == 1    # also from an empty input
    _rows_close(got, want)


@pytest.mark.parametrize("case", ["position-dependent", "no-multiple",
                                  "wide-domain", "sorted"])
def test_unsliceable_batch_takes_the_whole_capacity_body(
        tmp_path, cpu_session, monkeypatch, case):
    """What cannot be sliced is decided from the expressions and the
    shape: a position-dependent child, a capacity that is no whole
    multiple of the slice, a domain above the contraction's (its
    partials a slice would outgrow the saving), the sorted path."""
    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.plan import from_host_table
    monkeypatch.setattr(A, "AGG_SLICE",
                        6144 if case == "no-multiple" else 2048)
    sess = _logged(tmp_path)
    table = _counts_table(9000, 40 if case == "wide-domain" else 12,
                          seed=8, null_keys=case != "sorted")

    def build(s):
        x = col("x")
        if case == "position-dependent":
            x = x + F.rand(11)
        key = col("y") * lit(3) if case == "sorted" else col("k")
        return from_host_table(table, s).group_by(key.alias("g")).agg(
            F.count().alias("n"), F.sum(x).alias("sx"),
            F.avg(col("x")).alias("ax"))
    assert_tpu_and_cpu_are_equal(build, sess, cpu_session,
                                 approximate_float=True)
    assert _metric_total(sess, "slicedAggBatches") == 0
    assert _metric_total(sess, "aggSlices") == 0
    assert _metric_total(sess, "partialAggBatches") == 0


@pytest.mark.parametrize("slice_rows,mode,program,batches,slices", [
    (16384, ("fast", ("str",), 16), "agg_fast", 0, 0),
    (2048, ("fast_sliced", ("str",), 16, 8), "agg_fast_sliced", 1, 8),
], ids=["one-slice", "eight-slices"])
def test_one_slice_is_todays_program(tmp_path, cpu_session, monkeypatch,
                                     slice_rows, mode, program, batches,
                                     slices):
    """A batch of exactly one slice builds the kernel it always built,
    at its capacity, under the name and the trace key it always had;
    a batch of several builds the sliced program under a key of its own."""
    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.ops import segsum as S
    from spark_rapids_tpu.plan import from_host_table
    monkeypatch.setattr(A, "AGG_SLICE", slice_rows)
    built, traces = [], []
    real_jit = A.tpu_jit

    def jit_spy(fn, *, name, **kw):
        built.append(name)
        return real_jit(fn, name=name, **kw)
    monkeypatch.setattr(A, "tpu_jit", jit_spy)
    real_build = A.TpuHashAggregateExec._build_fast_kernel

    def build_spy(self, capacity, *a, **k):
        traces.append((self._traces, self.use_split))
        built.append(capacity)
        return real_build(self, capacity, *a, **k)
    monkeypatch.setattr(A.TpuHashAggregateExec, "_build_fast_kernel",
                        build_spy)

    sess = _logged(tmp_path)
    t = _counts_table(9000, 12, seed=17)

    def build(s):
        # a literal of the case's own: traces no earlier test has built
        return (from_host_table(t, s).filter(col("w") < lit(900 + slices))
                .group_by("k").agg(F.count().alias("n"),
                                   F.sum(col("x")).alias("sx")))
    assert_tpu_and_cpu_are_equal(build, sess, cpu_session,
                                 approximate_float=True)
    assert _metric_total(sess, "slicedAggBatches") == batches
    assert _metric_total(sess, "aggSlices") == slices
    # the batch's kernel first: at the body's rows, under the program's name
    assert built[:2] == [slice_rows, program]
    assert ("agg_fast_sliced" in built) == bool(slices)
    (batch_traces, use_split), *_ = traces
    # (capacity, use_split, segsum's tuning, mode, masked, the preps...)
    assert [k[:5] for k in batch_traces] == [
        (16384, use_split, S.trace_key(), mode, False)]
