"""Device join tests vs the CPU oracle (reference: integration_tests
join_test.py matrix — SURVEY.md §4)."""

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostTable
from spark_rapids_tpu.ops.expr import col, lit
from tests.asserts import assert_runs_on_tpu, assert_tpu_and_cpu_are_equal
from tests.data_gen import (
    BooleanGen,
    DateGen,
    DoubleGen,
    IntGen,
    LongGen,
    StringGen,
    gen_table,
)

ALL_JOIN_TYPES = ["inner", "left", "right", "full", "leftsemi", "leftanti"]


def _join_inputs(key_gen, n_left=300, n_right=200, seed=11):
    left = gen_table({"k": key_gen, "lv": LongGen()}, n_left, seed=seed)
    right = gen_table({"k": key_gen, "rv": LongGen()}, n_right, seed=seed + 1)
    return left, right


def _build_join(left, right, how, on="k"):
    def build(s):
        ldf = s.create_dataframe(left)
        rdf = s.create_dataframe(right)
        return ldf.join(rdf, on=on, how=how)
    return build


@pytest.mark.parametrize("how", ALL_JOIN_TYPES)
@pytest.mark.parametrize("keygen", [
    IntGen(min_val=0, max_val=50),          # many matches
    LongGen(),                               # mostly no matches
    StringGen(cardinality=30),
    DateGen(),
    BooleanGen(),
], ids=["int_dense", "long_sparse", "string", "date", "bool"])
def test_join_types_and_keys(session, cpu_session, how, keygen):
    left, right = _join_inputs(keygen)
    assert_tpu_and_cpu_are_equal(_build_join(left, right, how),
                                 session, cpu_session)


@pytest.mark.parametrize("how", ["inner", "left", "full"])
def test_join_multi_key(session, cpu_session, how):
    left = gen_table({"a": IntGen(min_val=0, max_val=10),
                      "b": StringGen(cardinality=5), "lv": LongGen()}, 250, seed=3)
    right = gen_table({"a": IntGen(min_val=0, max_val=10),
                       "b": StringGen(cardinality=5), "rv": DoubleGen()}, 150, seed=4)
    assert_tpu_and_cpu_are_equal(
        _build_join(left, right, how, on=["a", "b"]), session, cpu_session,
        approximate_float=True)


def test_join_runs_on_tpu(session):
    left, right = _join_inputs(IntGen(min_val=0, max_val=20))
    assert_runs_on_tpu(_build_join(left, right, "inner"), session)


def test_join_nan_keys_match(session, cpu_session):
    """Spark join keys: NaN == NaN, -0.0 == 0.0."""
    left = HostTable.from_pydict(
        {"k": [float("nan"), 0.0, 1.5, None], "lv": [1, 2, 3, 4]},
        dtypes={"k": T.DOUBLE})
    right = HostTable.from_pydict(
        {"k": [float("nan"), -0.0, 2.5, None], "rv": [10, 20, 30, 40]},
        dtypes={"k": T.DOUBLE})
    assert_tpu_and_cpu_are_equal(_build_join(left, right, "inner"),
                                 session, cpu_session)
    assert_tpu_and_cpu_are_equal(_build_join(left, right, "full"),
                                 session, cpu_session)


def test_join_null_keys_never_match(session, cpu_session):
    left = HostTable.from_pydict({"k": [1, None, 3], "lv": [1, 2, 3]})
    right = HostTable.from_pydict({"k": [None, 1, 3], "rv": [10, 20, 30]})
    for how in ALL_JOIN_TYPES:
        assert_tpu_and_cpu_are_equal(_build_join(left, right, how),
                                     session, cpu_session)


def test_join_type_promotion(session, cpu_session):
    """INT keys join LONG keys through an implicit cast."""
    left = HostTable.from_pydict({"k": [1, 2, 3], "lv": [1, 2, 3]},
                                 dtypes={"k": T.INT, "lv": T.LONG})
    right = HostTable.from_pydict({"k": [2, 3, 4], "rv": [20, 30, 40]},
                                  dtypes={"k": T.LONG, "rv": T.LONG})

    def build(s):
        ldf = s.create_dataframe(left)
        rdf = s.create_dataframe(right)
        from spark_rapids_tpu.plan import nodes as P
        return ldf._wrap(P.Join(ldf.plan, rdf.plan, "inner",
                                [col("k")], [col("k")]))
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def test_cross_join(session, cpu_session):
    left = HostTable.from_pydict({"a": [1, 2, 3]})
    right = HostTable.from_pydict({"b": ["x", "y"]})
    assert_tpu_and_cpu_are_equal(
        lambda s: s.create_dataframe(left).join(s.create_dataframe(right)),
        session, cpu_session)


def test_inner_join_with_condition(session, cpu_session):
    left, right = _join_inputs(IntGen(min_val=0, max_val=10))

    def build(s):
        from spark_rapids_tpu.plan import nodes as P
        ldf = s.create_dataframe(left)
        rdf = s.create_dataframe(right)
        cond = col("lv") < col("rv")
        return ldf._wrap(P.Join(ldf.plan, rdf.plan, "inner",
                                [col("k")], [col("k")], cond))
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def test_outer_join_with_condition_falls_back(session, cpu_session):
    left, right = _join_inputs(IntGen(min_val=0, max_val=10), 50, 50)

    def build(s):
        from spark_rapids_tpu.plan import nodes as P
        ldf = s.create_dataframe(left)
        rdf = s.create_dataframe(right)
        cond = col("lv") < col("rv")
        return ldf._wrap(P.Join(ldf.plan, rdf.plan, "left",
                                [col("k")], [col("k")], cond))

    from spark_rapids_tpu.overrides import wrap_plan
    meta = wrap_plan(build(session).plan, session.conf)
    assert not meta.can_run_on_tpu
    assert any("non-equi condition" in r for r in meta.reasons)
    # correctness still holds through the CPU fallback
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def test_join_empty_sides(session, cpu_session):
    empty = HostTable.from_pydict({"k": [], "lv": []},
                                  dtypes={"k": T.INT, "lv": T.LONG})
    data = HostTable.from_pydict({"k": [1, 2], "rv": [10, 20]},
                                 dtypes={"k": T.INT, "rv": T.LONG})
    for how in ALL_JOIN_TYPES:
        assert_tpu_and_cpu_are_equal(_build_join(empty, data, how),
                                     session, cpu_session)
        assert_tpu_and_cpu_are_equal(_build_join(data, empty, how),
                                     session, cpu_session)


def test_join_then_aggregate(session, cpu_session):
    """Joins compose with downstream device aggregation."""
    from spark_rapids_tpu import functions as F
    left, right = _join_inputs(IntGen(min_val=0, max_val=5, null_prob=0.0))

    def build(s):
        j = _build_join(left, right, "inner")(s)
        return j.group_by("k").agg(F.count("rv").alias("c"),
                                   F.sum("lv").alias("sl"))
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def test_join_duplicate_build_keys(session, cpu_session):
    """Multiple matches per probe row expand correctly."""
    left = HostTable.from_pydict({"k": [1, 1, 2], "lv": [1, 2, 3]})
    right = HostTable.from_pydict({"k": [1, 1, 1, 2, 2], "rv": [1, 2, 3, 4, 5]})
    for how in ["inner", "left", "full"]:
        assert_tpu_and_cpu_are_equal(_build_join(left, right, how),
                                     session, cpu_session)


# -- the build side and the join body are chosen from what is known ---------

def _join_execs(session, df):
    from spark_rapids_tpu.execs.join import TpuJoinExec
    from spark_rapids_tpu.overrides.rules import apply_overrides
    executable, _ = apply_overrides(df.plan, session.conf)
    found, stack = [], [executable.tpu_exec]
    while stack:
        node = stack.pop()
        if isinstance(node, TpuJoinExec):
            found.append(node)
        stack.extend(node.children)
    return found


def _sized(n_small=40, n_big=4000, seed=3):
    rng = np.random.default_rng(seed)
    small = {"k": np.arange(n_small, dtype=np.int64),
             "sv": rng.integers(0, 9, n_small).astype(np.int64)}
    big = {"k": rng.integers(-5, n_small + 5, n_big).astype(np.int64),
           "bv": rng.random(n_big)}
    return small, big


@pytest.mark.parametrize("small_on", ["left", "right"])
def test_inner_join_builds_its_smaller_child(session, cpu_session, small_on):
    """An inner equi join builds the child with the smaller size estimate
    whichever side it is written on (Spark's JoinSelection); the output
    columns keep the written order."""
    small, big = _sized()

    def build(s):
        sdf, bdf = s.create_dataframe(small), s.create_dataframe(big)
        left, right = (sdf, bdf) if small_on == "left" else (bdf, sdf)
        return left.join(right, on="k", how="inner")

    (join,) = _join_execs(session, build(session))
    assert join.build_left == (small_on == "left")
    assert f"build={small_on}" in join.describe()
    names = [n for n, _ in build(session).plan.output_schema()]
    assert names == (["k", "sv", "k", "bv"] if small_on == "left"
                     else ["k", "bv", "k", "sv"])
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def test_equal_sizes_and_outer_joins_keep_their_build_side(session):
    small, big = _sized()
    sdf, bdf = session.create_dataframe(small), session.create_dataframe(big)
    (tie,) = _join_execs(session, sdf.join(sdf, on="k", how="inner"))
    assert not tie.build_left
    (left,) = _join_execs(session, sdf.join(bdf, on="k", how="left"))
    assert not left.build_left
    (right,) = _join_execs(session, bdf.join(sdf, on="k", how="right"))
    assert right.build_left


def test_join_and_filter_size_estimates():
    """What the choice reads: a keyed join is taken to be no larger than
    its sides together, a filter no larger than its input, a keyless
    join unknown."""
    from spark_rapids_tpu.plan import nodes as P
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession()
    small, big = _sized()
    sp = s.create_dataframe(small).plan
    bp = s.create_dataframe(big).plan
    assert sp.estimate_bytes() < bp.estimate_bytes()
    keyed = P.Join(sp, bp, "inner", [col("k")], [col("k")])
    assert keyed.estimate_bytes() == sp.estimate_bytes() + bp.estimate_bytes()
    assert P.Join(sp, bp, "cross", [], []).estimate_bytes() is None
    assert P.Join(bp, sp, "leftsemi", [col("k")], [col("k")]) \
        .estimate_bytes() == bp.estimate_bytes()
    filtered = s.create_dataframe(big).filter(col("bv") > lit(0.5)).plan
    assert filtered.estimate_bytes() == bp.estimate_bytes()


@pytest.mark.parametrize("small_on", ["left", "right"])
@pytest.mark.parametrize("keys", ["duplicate", "null", "missing"])
def test_swapped_inner_join_equals_cpu(session, cpu_session, small_on, keys):
    rng = np.random.default_rng(17)
    if keys == "duplicate":
        sk = np.array([1, 1, 2, 3, 3, 3], dtype=object)
    elif keys == "null":
        sk = np.array([1, None, 2, None, 3, 4], dtype=object)
    else:
        sk = np.array([100, 200, 300, 400, 500, 600], dtype=object)
    small = HostTable.from_pydict(
        {"k": list(sk), "sv": list(range(len(sk)))}, {"k": T.LONG})
    bk = rng.integers(0, 6, 500).astype(object)
    bk[::7] = None
    big = HostTable.from_pydict(
        {"k": list(bk), "bv": list(range(500))}, {"k": T.LONG})
    left, right = (small, big) if small_on == "left" else (big, small)
    assert_tpu_and_cpu_are_equal(_build_join(left, right, "inner"),
                                 session, cpu_session)


def _logged_session(tmp_path):
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                       "spark.rapids.sql.eventLog.dir": str(tmp_path)})


def _join_metrics(record):
    out, stack = [], [record["plan"]]
    while stack:
        node = stack.pop()
        if node["op"] == "TpuJoinExec":
            out.append({k: v["value"] for k, v in node["metrics"].items()})
        stack.extend(node["children"])
    return out


def test_sparse_unique_build_keys_take_the_direct_body(tmp_path, cpu_session):
    """dbgen's o_orderkey pattern (the first 8 of every 32 values: a range
    of four times the rows) on a build side far smaller than its range:
    the join reads the range and takes the direct-address body for every
    probe batch; nothing is guessed, so nothing fails or replays, and the
    record says which body ran, on what, and for how long."""
    from spark_rapids_tpu.runtime import speculation as spec
    n = 20_000
    i = np.arange(1, n + 1, dtype=np.int64)
    orderkey = ((i >> 3) << 5) | (i & 7)
    rng = np.random.default_rng(5)
    keep = np.sort(rng.choice(n, size=n // 10, replace=False))
    orders = {"k": orderkey[keep],
              "o_x": rng.integers(0, 5, len(keep)).astype(np.int64)}
    lines = {"k": orderkey[rng.integers(0, n, 60_000)],
             "l_v": rng.random(60_000)}
    assert orders["k"].max() - orders["k"].min() > 30 * len(keep)

    def build(s):
        # a filter's masked batches pass the probe side's coalesce as
        # they are: four probe batches reach the join
        return (s.create_dataframe(lines, num_batches=4)
                .filter(col("l_v") >= lit(0.0))
                .join(s.create_dataframe(orders), on="k", how="inner")
                .select(col("o_x"), col("l_v")))

    s = _logged_session(tmp_path)
    blocked = set(spec._BLOCKLIST)
    want = sorted(build(cpu_session).collect())
    for _ in range(2):
        assert sorted(build(s).collect()) == want
        record = s.last_event_record
        (m,) = _join_metrics(record)
        assert m["directJoinBatches"] == 4 and "sortJoinBatches" not in m
        assert m["probeBatches"] == 4 and m["buildSideSwapped"] == 0
        assert m["buildRows"] == len(keep)
        assert m["joinOutputRows"] == len(want)
        assert record["phasesS"]["joinS"] > 0
        assert record["phasesS"]["joinS"] <= record["phasesS"]["executeS"]
        assert "speculationReplays" not in s.last_metrics()
        assert not record["faultReplays"]
    assert set(spec._BLOCKLIST) == blocked


@pytest.mark.parametrize("how", ["inner", "left", "leftsemi", "leftanti"])
@pytest.mark.parametrize("order", ["clustered", "shuffled"])
def test_clustered_probe_keys_read_windows_of_the_direct_table(
        tmp_path, cpu_session, order, how):
    """A probe side in its key's order (dbgen's lineitem against orders)
    reads the direct table a window a block of rows, a shuffled one an
    element a row: the same rows either way, NULL keys, keys outside the
    table, rows a filter dropped and repeated keys among them, and for an
    inner join the record says which it was."""
    n = 20_000
    i = np.arange(1, n + 1, dtype=np.int64)
    orderkey = ((i >> 3) << 5) | (i & 7)
    rng = np.random.default_rng(11)
    keep = np.sort(rng.choice(n, size=n // 4, replace=False))
    orders = {"k": orderkey[keep],
              "o_x": rng.integers(0, 5, len(keep)).astype(np.int64)}
    lk = np.repeat(orderkey, rng.integers(1, 8, n))[:60_000]
    if order == "shuffled":
        lk = rng.permutation(lk)
    lk = lk.astype(object)
    lk[::97] = None
    lines = HostTable.from_pydict(
        {"k": list(lk), "l_v": list(rng.random(len(lk)))}, {"k": T.LONG})

    def build(s):
        return (s.create_dataframe(lines, num_batches=4)
                .filter(col("l_v") >= lit(0.3))
                .join(s.create_dataframe(orders), on="k", how=how))

    s = _logged_session(tmp_path)
    assert sorted(build(s).collect(), key=repr) \
        == sorted(build(cpu_session).collect(), key=repr)
    (m,) = _join_metrics(s.last_event_record)
    assert m["directJoinBatches"] == 4 and "sortJoinBatches" not in m
    if how == "inner":
        assert m["clusteredProbeBatches"] == (4 if order == "clustered" else 0)


def test_a_query_without_a_join_records_no_join_time(tmp_path):
    s = _logged_session(tmp_path)
    s.create_dataframe({"k": np.arange(10, dtype=np.int64)}) \
        .filter(col("k") > lit(3)).collect()
    assert s.last_event_record["phasesS"]["joinS"] == 0.0


def test_a_selective_direct_join_hands_on_a_bucket_its_rows_need(session):
    """Few probe rows survive: the inner join's output is gathered into
    the bucket their count needs (what follows sorts thousands of rows,
    not the probe batch's capacity); where most survive it stays in
    place under the mask."""
    from spark_rapids_tpu.columnar import bucket_for
    n = 50_000
    probe = {"k": np.arange(n, dtype=np.int64), "v": np.arange(n) * 1.0}
    few = {"k": np.arange(0, n, 100, dtype=np.int64),
           "w": np.arange(0, n, 100, dtype=np.int64)}
    most = {"k": np.arange(n - 7, dtype=np.int64),
            "w": np.arange(n - 7, dtype=np.int64)}
    for build, rows in ((few, n // 100), (most, n - 7)):
        df = session.create_dataframe(probe).join(
            session.create_dataframe(build), on="k", how="inner")
        (join,) = _join_execs(session, df)
        (out,) = list(join.execute_masked())
        assert out.num_rows == rows
        assert out.capacity == bucket_for(rows)
        assert (out.live is None) == (bucket_for(rows) < bucket_for(n))
