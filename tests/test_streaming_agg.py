"""Streaming multi-batch aggregation: partial-per-batch + merge
(reference analog: GpuAggregateExec partial/merge modes,
HashAggregateRetrySuite). A tiny batchSizeBytes forces the coalesce to
stream batches so the merge path actually runs."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.ops.expr import col, lit

from tests.asserts import assert_tpu_and_cpu_are_equal
from tests.data_gen import (
    BooleanGen, DoubleGen, IntGen, LongGen, StringGen, gen_table,
)


@pytest.fixture(scope="module")
def stream_session():
    """Batch target of 1 byte => every input batch streams separately."""
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.sql.batchSizeBytes": 1})


def _df(sess, gens, n=900, seed=23, num_batches=4):
    from spark_rapids_tpu.plan import from_host_table
    return from_host_table(gen_table(gens, n, seed), sess, num_batches)


# corner_prob=0: +/-1e30 corner values make f64 sums ORDER-DEPENDENT (a
# small running sum absorbs into 1e30 and is lost when the pair cancels), so
# partial-per-batch order legitimately differs from the oracle's sequential
# order — the exact variance the reference gates with variableFloatAgg.
GENS = {"k": StringGen(cardinality=6), "b": BooleanGen(),
        "i": IntGen(min_val=-100, max_val=100),
        "v": LongGen(min_val=-1000, max_val=1000),
        "d": DoubleGen(corner_prob=0.0)}


def test_streaming_all_aggs(stream_session, cpu_session):
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("k").agg(
            F.count().alias("cnt"), F.count(col("v")).alias("cntv"),
            F.sum(col("v")).alias("sv"), F.sum(col("d")).alias("sd"),
            F.min(col("d")).alias("mn"), F.max(col("v")).alias("mx"),
            F.first(col("v")).alias("fv"), F.last(col("d")).alias("ld"),
        ),
        stream_session, cpu_session, approximate_float=True)


def test_streaming_order_insensitive_aggs_corner_doubles(
        stream_session, cpu_session):
    """Corner-heavy doubles (inf/1e30/-0.0): count/min/max/first/last are
    order-insensitive and must match bit-for-bit even when streamed."""
    gens = {"k": StringGen(cardinality=5), "d": DoubleGen()}
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, gens, num_batches=5).group_by("k").agg(
            F.count(col("d")).alias("c"), F.min(col("d")).alias("mn"),
            F.max(col("d")).alias("mx"), F.first(col("d")).alias("f"),
            F.last(col("d")).alias("l")),
        stream_session, cpu_session)


def test_streaming_avg(stream_session, cpu_session):
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("k", "b").agg(
            F.avg(col("d")).alias("ad"), F.avg(col("i")).alias("ai")),
        stream_session, cpu_session, approximate_float=True)


def test_streaming_stddev_variance(stream_session, cpu_session):
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("k").agg(
            F.stddev(col("d")).alias("sd"),
            F.stddev_pop(col("d")).alias("sp"),
            F.variance(col("d")).alias("vr"),
            F.var_pop(col("d")).alias("vp"),
        ),
        stream_session, cpu_session, approximate_float=True)


def test_streaming_global_agg(stream_session, cpu_session):
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).agg(
            F.count().alias("c"), F.sum(col("v")).alias("sv"),
            F.min(col("i")).alias("mn"), F.avg(col("d")).alias("ad")),
        stream_session, cpu_session, approximate_float=True)


def test_streaming_with_fused_filter(stream_session, cpu_session):
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS)
        .filter(col("v") > lit(-500))
        .select(col("k"), (col("d") * lit(3.0)).alias("d3"), col("v"))
        .group_by("k")
        .agg(F.sum(col("d3")).alias("s3"), F.count().alias("c")),
        stream_session, cpu_session, approximate_float=True)


def test_streaming_sorted_path_int_keys(stream_session, cpu_session):
    """Int keys take the sort-segment path per batch; merge still works."""
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("i").agg(
            F.count().alias("c"), F.sum(col("d")).alias("sd"),
            F.max(col("v")).alias("mx")),
        stream_session, cpu_session, approximate_float=True)


def test_streaming_with_injected_oom(cpu_session):
    """Partials replay after injected OOM (HashAggregateRetrySuite analog)."""
    from spark_rapids_tpu.session import TpuSession
    inj = TpuSession({"spark.rapids.sql.batchSizeBytes": 1,
                      "spark.rapids.sql.test.injectRetryOOM": "retry:2"})
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, GENS).group_by("k").agg(
            F.count().alias("c"), F.sum(col("v")).alias("sv")),
        inj, cpu_session)


def test_streaming_nulls_in_keys_and_values(stream_session, cpu_session):
    gens = {"k": StringGen(cardinality=4),
            "v": IntGen(min_val=-50, max_val=50, null_prob=0.4),
            "d": DoubleGen(corner_prob=0.0)}
    assert_tpu_and_cpu_are_equal(
        lambda s: _df(s, gens, num_batches=6).group_by("k").agg(
            F.count(col("v")).alias("cv"), F.sum(col("v")).alias("sv"),
            F.avg(col("v")).alias("av"), F.first(col("v")).alias("fv")),
        stream_session, cpu_session, approximate_float=True)


def test_variance_large_mean_stability(stream_session, cpu_session):
    """|mean| >> stddev is the catastrophic case for naive moment merging;
    the MergeMoments Chan combination and exact variance means must hold
    (code-review r2 finding: M + Q - S^2/N cancelled to garbage)."""
    import numpy as np
    from spark_rapids_tpu.plan import from_host_table
    from spark_rapids_tpu.columnar import HostColumn, HostTable
    from spark_rapids_tpu import types as T

    n = 2000
    vals = 1e9 + np.arange(n) * 1e-6
    true_std = float(np.std(vals, ddof=1))
    t = HostTable(["k", "d"],
                  [HostColumn(T.STRING, np.array(["g"] * n, dtype=object)),
                   HostColumn(T.DOUBLE, vals)])
    for nb in (1, 4):
        got = from_host_table(t, stream_session, nb).group_by("k").agg(
            F.stddev(col("d")).alias("sd")).collect()[0][1]
        assert abs(got - true_std) <= 1e-3 * true_std, (nb, got, true_std)


# ---------------------------------------------------------------------------
# resident batches aggregate several to a program (execs/aggregate.py
# AGG_GROUP): a cached table's batches are resident from the second
# query on (the first uploads them), and each group of them is ONE
# enqueue
# ---------------------------------------------------------------------------

def _grouping_session(event_dir, **conf):
    from tests.test_agg_fastpath import _logged
    # a goal below one batch: the coalesce passes cached batches through
    return _logged(event_dir, **{"spark.rapids.sql.batchSizeBytes": "1024",
                                 **conf})


def _cached_view(sess, table, batches, name):
    sess.create_dataframe(table, num_batches=batches) \
        .create_or_replace_temp_view(name)


def _group_query(sess, name, key="k"):
    from tests.test_agg_fastpath import SLICED_AGGS
    return sess.table(name).group_by(key).agg(*SLICED_AGGS)


def _same_answers(got, want):
    """Exact columns bit-identical, doubles inside the engine's contract
    (the merge adds the same partials, in the same order)."""
    got, want = sorted(got, key=repr), sorted(want, key=repr)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for gv, wv in zip(g, w):
            if isinstance(wv, float):
                assert gv == pytest.approx(wv, rel=1e-9, abs=1e-12), (g, w)
            else:
                assert gv == wv, (g, w)


def _matches_cpu(sess, cpu_session, table, batches, name):
    _cached_view(cpu_session, table, batches, name)
    assert_tpu_and_cpu_are_equal(lambda s: _group_query(s, name), sess,
                                 cpu_session, approximate_float=True)


def _group_counts(sess):
    from tests.asserts import plan_metric_total as total
    return (total(sess, "groupedAggPrograms"),
            total(sess, "groupedAggBatches"),
            total(sess, "partialAggBatches"))


@pytest.mark.parametrize("batches,cap,programs,held,saved", [
    (16, 4, 4, 16, 12),
    (5, 4, 1, 4, 3),    # the cap does not divide the stream: 4, then 1 alone
    (6, 3, 2, 6, 4),
    (8, 8, 1, 8, 8),    # one partial: the merge's concat goes too
    (3, 2, 1, 2, 1),
], ids=["16-by-4", "5-by-4", "6-by-3", "8-by-8", "3-by-2"])
def test_resident_batches_aggregate_several_to_a_program(
        tmp_path, cpu_session, monkeypatch, batches, cap, programs, held,
        saved):
    """Grouped = one at a time, and the record counts what was grouped:
    `groupedAggPrograms` / `groupedAggBatches`, and `dispatches` down by
    the enqueues saved."""
    from spark_rapids_tpu.execs import aggregate as A
    from tests.test_agg_fastpath import _counts_table
    monkeypatch.setattr(A, "AGG_GROUP", cap)
    sess = _grouping_session(tmp_path)
    name = f"grp{batches}x{cap}"
    table = _counts_table(700 * batches, 9, seed=batches + cap)
    _cached_view(sess, table, batches, name)

    # the first query uploads every batch in the pull: nothing is grouped
    first = _group_query(sess, name).collect()
    assert _group_counts(sess) == (0, 0, batches)
    grouped = _group_query(sess, name).collect()
    rec = sess.last_event_record
    assert _group_counts(sess) == (programs, held, batches)
    assert rec["hostSyncs"] == 1

    monkeypatch.setattr(A, "AGG_GROUP", 1)
    alone = _group_query(sess, name).collect()
    assert _group_counts(sess) == (0, 0, batches)
    assert sess.last_event_record["dispatches"] - rec["dispatches"] == saved
    _same_answers(grouped, alone)
    _same_answers(grouped, first)
    monkeypatch.setattr(A, "AGG_GROUP", cap)
    _matches_cpu(sess, cpu_session, table, batches, name)


@pytest.mark.parametrize("cut,programs,held", [
    ("halves", 2, 4),        # {a, b} {a, b} {a, c} {a, c}
    ("alternating", 0, 0),   # {a, b} {a, c} {a, b} {a, c}
])
def test_a_changed_key_dictionary_closes_the_group(
        tmp_path, cpu_session, cut, programs, held):
    """Batches whose key dictionaries differ (same size, other strings)
    share a trace key but not their codes: each run of equal
    dictionaries is a group of its own."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar import HostColumn, HostTable
    rng = np.random.default_rng(5)
    per, keys = 500, []
    for b in range(4):
        other = "b" if (b < 2 if cut == "halves" else b % 2 == 0) else "c"
        keys.append(np.array(["a", other], dtype=object)[
            rng.integers(0, 2, per)])
    n = 4 * per
    table = HostTable(["k", "x", "y", "w"], [
        HostColumn(T.STRING, np.concatenate(keys)),
        HostColumn(T.DOUBLE, rng.random(n) * 10, rng.random(n) > 0.2),
        HostColumn(T.LONG, rng.integers(-5, 5, n).astype(np.int64)),
        HostColumn(T.LONG, rng.integers(0, 100, n).astype(np.int64))])
    sess = _grouping_session(tmp_path)
    name = f"dict_{cut}"
    _cached_view(sess, table, 4, name)
    _group_query(sess, name).collect()
    _matches_cpu(sess, cpu_session, table, 4, name)
    assert _group_counts(sess) == (programs, held, 4)


def _resident_batches(sess, batches, seed):
    """(the aggregate exec of a group-by over a cached table, its
    batches already on the device, the host batches)."""
    from spark_rapids_tpu.plan import from_host_table
    from tests.test_agg_fastpath import (
        SLICED_AGGS, _counts_table, _fast_agg_exec)
    table = _counts_table(600 * batches, 9, seed=seed)
    aggx = _fast_agg_exec(sess, from_host_table(table, sess, batches)
                          .group_by("k").agg(*SLICED_AGGS))
    return aggx, list(aggx.children[0].execute_masked())


def _fed(sess, batches, seed, stream):
    """The rows of the aggregate over ``stream(resident batches)`` and
    its exec's metrics."""
    from tests.test_agg_fastpath import _feed
    aggx, resident = _resident_batches(sess, batches, seed)
    out, = _feed(aggx, stream(resident))
    return out, aggx.metrics


def test_a_masked_batch_among_unmasked_ones_is_a_group_of_its_own(
        stream_session, monkeypatch):
    """A live mask is in the trace key: b0 | b1 (masked) | b2 b3."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.execs import aggregate as A
    from tests.test_agg_fastpath import _rows_close

    def stream(resident):
        b = resident[1]
        keep = np.zeros(b.capacity, dtype=np.bool_)
        keep[:b.num_rows:2] = True
        resident[1] = DeviceTable(b.names, b.columns, int(keep.sum()),
                                  b.capacity, live=jnp.asarray(keep))
        return resident
    got, metrics = _fed(stream_session, 4, 31, stream)
    assert (metrics.get("groupedAggPrograms"),
            metrics.get("groupedAggBatches")) == (1, 2)
    assert metrics.get("partialAggBatches") == 4
    monkeypatch.setattr(A, "AGG_GROUP", 1)
    want, metrics = _fed(stream_session, 4, 31, stream)
    assert metrics.get("groupedAggPrograms") == 0
    _rows_close(got, want)


def test_a_batch_the_pull_built_closes_the_group(stream_session,
                                                 monkeypatch):
    """b0 b1 resident, then one that the pull uploads (an uncached
    scan's), then b2 b3: the built one is aggregated alone, at once."""
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.execs import aggregate as A
    from tests.test_agg_fastpath import _counts_table, _rows_close
    calls = []
    real = A.TpuHashAggregateExec._aggregate

    def spy(self, table, *a, members=None, **k):
        calls.append(len(members) if members else 1)
        return real(self, table, *a, members=members, **k)
    monkeypatch.setattr(A.TpuHashAggregateExec, "_aggregate", spy)

    def stream(resident):
        yield from resident[:2]
        yield DeviceTable.from_host(_counts_table(600, 9, seed=77))
        yield from resident[2:]
    got, metrics = _fed(stream_session, 4, 41, stream)
    assert calls == [2, 1, 2, 1]   # the partials in order, then the merge
    assert (metrics.get("groupedAggPrograms"),
            metrics.get("groupedAggBatches")) == (2, 4)
    assert metrics.get("partialAggBatches") == 5
    monkeypatch.setattr(A, "AGG_GROUP", 1)
    want, _ = _fed(stream_session, 4, 41, stream)
    _rows_close(got, want)


def test_a_coalesce_flush_is_never_grouped(tmp_path, cpu_session):
    """Under a goal of several batches the coalesce BUILDS what the
    aggregate pulls: groups of one, on a warm table too."""
    from tests.test_agg_fastpath import _counts_table
    sess = _grouping_session(
        tmp_path, **{"spark.rapids.sql.batchSizeBytes": str(64 << 10)})
    table = _counts_table(8 * 700, 9, seed=3)
    _cached_view(sess, table, 8, "flushed")
    _group_query(sess, "flushed").collect()
    _matches_cpu(sess, cpu_session, table, 8, "flushed")
    from tests.asserts import plan_metric_total as total
    assert total(sess, "concatBatches") > 0
    programs, held, partials = _group_counts(sess)
    assert (programs, held) == (0, 0) and 1 < partials < 8


@pytest.mark.parametrize("path", ["sorted", "sliced"])
def test_the_sorted_path_and_a_sliced_batch_are_never_grouped(
        tmp_path, cpu_session, monkeypatch, path):
    from spark_rapids_tpu.execs import aggregate as A
    from tests.asserts import plan_metric_total as total
    from tests.test_agg_fastpath import _counts_table
    conf = {}
    if path == "sorted":
        conf["spark.rapids.tpu.agg.maxDictGroups"] = "0"
    else:
        monkeypatch.setattr(A, "AGG_SLICE", 512)   # 1024-row batches: 2
    sess = _grouping_session(tmp_path, **conf)
    table = _counts_table(4 * 700, 9, seed=13)
    name = f"never_{path}"
    _cached_view(sess, table, 4, name)
    _group_query(sess, name).collect()
    _matches_cpu(sess, cpu_session, table, 4, name)
    assert _group_counts(sess) == (0, 0, 4)
    assert total(sess, "slicedAggBatches") == (4 if path == "sliced" else 0)


@pytest.mark.parametrize("ooms,retries,programs", [
    (1, 1, 1),   # replayed once, as a group
    (3, 2, 0),   # it persists after the replays: the members, one by one
], ids=["replayed", "falls-back-to-its-members"])
def test_an_oom_on_a_group_is_replayed_then_falls_back_to_its_members(
        tmp_path, monkeypatch, ooms, retries, programs):
    """A group is one retry block: an OOM inside it is spilled for and
    replayed as a group; one that persists after the replays sends the
    members through one at a time, each in a retry block of its own."""
    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.runtime.retry import RMM_TPU
    from tests.test_agg_fastpath import _counts_table
    sess = _grouping_session(tmp_path)
    table = _counts_table(4 * 700, 9, seed=17)
    name = f"oom{ooms}"
    _cached_view(sess, table, 4, name)
    _group_query(sess, name).collect()
    want = _group_query(sess, name).collect()
    assert _group_counts(sess) == (1, 4, 4)
    dispatches = sess.last_event_record["dispatches"]

    real = A.TpuHashAggregateExec._aggregate
    left = [ooms]

    def failing(self, table, *a, members=None, **k):
        if members and len(members) > 1 and left[0]:
            left[0] -= 1
            RMM_TPU.force_retry_oom(1)
            RMM_TPU.maybe_inject()
        return real(self, table, *a, members=members, **k)
    monkeypatch.setattr(A.TpuHashAggregateExec, "_aggregate", failing)
    got = _group_query(sess, name).collect()
    rec = sess.last_event_record
    assert left == [0] and rec["oomRetries"] == retries
    assert _group_counts(sess) == (programs, 4 * programs, 4)
    # four programs for one, and the concat that one partial did not need
    assert rec["dispatches"] == dispatches + (0 if programs else 3 + 1)
    _same_answers(got, want)
