"""Broadcast exchange + conditioned nested-loop joins (reference analog:
GpuBroadcastExchangeExec / GpuBroadcastNestedLoopJoinExec)."""

import pytest

from spark_rapids_tpu.ops.expr import col, lit

from tests.asserts import assert_tpu_and_cpu_are_equal
from tests.data_gen import DoubleGen, IntGen, StringGen, gen_table


def _dfs(sess, n_left=300, n_right=40, nb=3, seed=53):
    from spark_rapids_tpu.plan import from_host_table
    lg = {"a": IntGen(min_val=0, max_val=60), "lv": DoubleGen(corner_prob=0.0)}
    rg = {"b": IntGen(min_val=0, max_val=60), "rv": IntGen(min_val=0, max_val=60)}
    left = from_host_table(gen_table(lg, n_left, seed), sess, nb)
    right = from_host_table(gen_table(rg, n_right, seed + 1), sess, 1)
    return left, right


@pytest.mark.parametrize("how", ["inner", "left", "right", "full",
                                 "leftsemi", "leftanti"])
def test_nlj_condition_join_types(session, cpu_session, how):
    def build(s):
        left, right = _dfs(s)
        return left.join(right, on=col("a") < col("rv"), how=how)
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def test_nlj_range_band_condition(session, cpu_session):
    """Band join: a BETWEEN b-5 AND b+5 — the classic NLJ workload."""
    def build(s):
        left, right = _dfs(s)
        cond = (col("a") >= col("b") - lit(5)) & (col("a") <= col("b") + lit(5))
        return left.join(right, on=cond, how="inner")
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def test_nlj_condition_with_nulls(session, cpu_session):
    def build(s):
        from spark_rapids_tpu.plan import from_host_table
        lg = {"a": IntGen(min_val=0, max_val=20, null_prob=0.3)}
        rg = {"b": IntGen(min_val=0, max_val=20, null_prob=0.3)}
        left = from_host_table(gen_table(lg, 120, 5), s, 2)
        right = from_host_table(gen_table(rg, 30, 6), s, 1)
        return left.join(right, on=col("a") == col("b") + lit(1), how="full")
    assert_tpu_and_cpu_are_equal(build, session, cpu_session)


def test_nlj_runs_on_device(session):
    from tests.asserts import assert_runs_on_tpu
    def build(s):
        left, right = _dfs(s)
        return left.join(right, on=col("a") < col("rv"), how="left")
    assert_runs_on_tpu(build, session)




def _collect_execs(root, cls):
    found = []

    def walk(e):
        if isinstance(e, cls):
            found.append(e)
        for c in getattr(e, "children", ()):
            walk(c)
        for attr in ("source", "tpu_exec", "cpu_node"):
            nxt = getattr(e, attr, None)
            if nxt is not None:
                walk(nxt)

    walk(root)
    return found


def test_broadcast_exchange_selected_for_small_build(session):
    """Small build sides (LocalScan size estimate) go through the broadcast
    exchange; the table materializes once and is reused."""
    from spark_rapids_tpu.overrides import apply_overrides
    from spark_rapids_tpu.execs.broadcast import TpuBroadcastExchangeExec

    from spark_rapids_tpu.plan import from_host_table
    l2 = {"k": IntGen(min_val=0, max_val=9), "x": IntGen()}
    r2 = {"k": IntGen(min_val=0, max_val=9), "y": IntGen()}
    left = from_host_table(gen_table(l2, 200, 1), session, 1)
    right = from_host_table(gen_table(r2, 50, 2), session, 1)
    j = left.join(right, on="k", how="inner")
    executable, _ = apply_overrides(j.plan, session.conf)

    found = _collect_execs(executable, TpuBroadcastExchangeExec)
    assert len(found) == 1, "build side should broadcast"
    list(executable.execute_cpu())
    assert found[0]._cached is not None
    cached = found[0]._cached
    list(executable.execute_cpu())
    assert found[0]._cached is cached  # reused, not rebuilt


def test_broadcast_disabled_by_threshold(session):
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.overrides import apply_overrides
    from spark_rapids_tpu.execs.broadcast import TpuBroadcastExchangeExec
    from spark_rapids_tpu.plan import from_host_table

    off = TpuSession({"spark.rapids.sql.broadcastSizeBytes": 0})
    l2 = {"k": IntGen(min_val=0, max_val=9)}
    left = from_host_table(gen_table(l2, 100, 1), off, 1)
    right = from_host_table(gen_table(l2, 20, 2), off, 1)
    executable, _ = apply_overrides(
        left.join(right, on="k", how="inner").plan, off.conf)

    found = _collect_execs(executable, TpuBroadcastExchangeExec)
    assert not found


# -- AQE runtime broadcast conversion ---------------------------------------

def _find_adaptive(e):
    """Locate the TpuAdaptiveBuildExec in a converted plan tree."""
    from spark_rapids_tpu.execs.broadcast import TpuAdaptiveBuildExec
    found = _collect_execs(e, TpuAdaptiveBuildExec)
    return found[0] if found else None


def test_aqe_runtime_broadcast_conversion(session, cpu_session):
    """A build side with NO static estimate converts to broadcast at
    runtime when measured under the threshold (DynamicJoinSelection
    analog); the decision is visible in the exec tree + metrics."""
    import numpy as np
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.execs.broadcast import TpuAdaptiveBuildExec
    from spark_rapids_tpu.overrides.rules import apply_overrides
    from spark_rapids_tpu.plan import nodes as P
    from spark_rapids_tpu.plan import from_host_table
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.ops.expr import col

    rng = np.random.default_rng(0)
    big = HostTable.from_pydict(
        {"k": rng.integers(0, 50, 5000).astype(np.int64),
         "v": rng.standard_normal(5000)})
    small = HostTable.from_pydict(
        {"k": np.arange(50, dtype=np.int64),
         "w": np.arange(50, dtype=np.int64) * 10})

    # hide the static estimates so the planner cannot prove broadcast
    # (both: an inner join builds the side it KNOWS to be smaller, and
    # with neither known it keeps the right one)
    scan = P.LocalScan([small])
    scan.estimate_bytes = lambda: None
    probe = P.LocalScan([big])
    probe.estimate_bytes = lambda: None

    join = P.Join(probe, scan, "inner", [col("k")], [col("k")])
    executable, _meta = apply_overrides(join, session.conf)

    ab = _find_adaptive(executable)
    assert ab is not None, "AQE adaptive build not planned"
    assert ab.converted is None  # undecided before execution

    rows = HostTable.concat(list(executable.execute_cpu()))
    assert rows.num_rows == 5000
    assert ab.converted is True  # runtime-measured small -> broadcast
    assert ab.metrics.get("aqeBroadcastConverted") == 1

    # oracle: result matches CPU join
    want = (from_host_table(big, cpu_session)
            .join(from_host_table(small, cpu_session), on=["k"])
            .count())
    assert rows.num_rows == want


def test_aqe_large_build_stays_shuffle(session):
    import numpy as np
    from spark_rapids_tpu.execs.broadcast import TpuAdaptiveBuildExec
    from spark_rapids_tpu.overrides.rules import apply_overrides
    from spark_rapids_tpu.plan import nodes as P
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.ops.expr import col
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession({"spark.rapids.sql.broadcastSizeBytes": "64"})
    rng = np.random.default_rng(1)
    left = HostTable.from_pydict(
        {"k": rng.integers(0, 20, 500).astype(np.int64)})
    right = HostTable.from_pydict(
        {"k": np.arange(20, dtype=np.int64),
         "w": np.arange(20, dtype=np.int64)})
    scan = P.LocalScan([right])
    scan.estimate_bytes = lambda: None
    join = P.Join(P.LocalScan([left]), scan, "inner", [col("k")], [col("k")])
    executable, _ = apply_overrides(join, s.conf)

    ab = _find_adaptive(executable)
    assert ab is not None
    out = list(executable.execute_cpu())
    assert sum(t.num_rows for t in out) == 500
    assert ab.converted is False  # 20-row build > 64-byte threshold
