"""TPC-H Q3 on the mesh (a 2x2 of the CPU backend's eight virtual
devices), planned as Spark plans it under a broadcast threshold: the
customer join a broadcast replicated to every chip, the lineitem join a
shuffled hash join whose two inputs are hash-exchanged over the mesh, the
grouped aggregate and the top 10 on each chip. Against the benchmark's
plain reference (benchmarks/reference/q3.py) and against one chip; the
plan and its counters; the shapes off the fast path; a planted fault.

At the cell's scale (SF5) the customer keys measure 9.4 MB and
customer-orders 19.9 MB against the shipped threshold of 10 MiB. At SF
0.01 they measure 18,432 and 38,912 bytes (2^11 rows x 9 and x 19), so
the tests put the threshold between those."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmarks import compare, sut, traffic  # noqa: E402
from benchmarks.datagen import tpch_tables  # noqa: E402
from benchmarks.reference import q3  # noqa: E402

PARAMS = {"SEGMENT": "BUILDING", "DATE": "1995-03-15"}
SCALE = 0.01
BETWEEN = "30000"
MESH_KEYS = ("spark.rapids.mesh.enabled", "spark.rapids.mesh.shape")


def _config(mesh=True, threshold=BETWEEN, one_chip_batches=False):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tpch-q3-tables-mesh4.json")) as f:
        config = json.load(f)
    conf = dict(config["session_conf"])
    if not mesh:
        for key in MESH_KEYS:
            conf.pop(key)
    if threshold is not None:
        conf["spark.rapids.sql.broadcastSizeBytes"] = threshold
    config = dict(config, scale_factor=SCALE, session_conf=conf)
    if one_chip_batches:
        config["batches"] = {"customer": 1, "orders": 4, "lineitem": 16}
    return config


def _limit():
    with open(os.path.join(ROOT, "benchmarks", "limits",
                           "q3-mesh4.json")) as f:
        return float(json.load(f)["max_rel_err"]["q3"])


def _queries(config, tables, text=None, times=2):
    """(answers, full event records) of ``times`` runs of a statement."""
    engine = sut.Engine(config)
    engine.register(tables)
    text = text or traffic.statement("q3", PARAMS)
    answers, records = [], []
    try:
        for _ in range(times):
            answer, slim = engine.query(text)
            assert not sut.off_device_path(slim), slim
            answers.append(answer)
            records.append(engine.session.last_event_record)
    finally:
        engine.close()
    return answers, records


def _nodes(plan, op):
    found, stack = [], [plan]
    while stack:
        node = stack.pop(0)
        if node["op"] == op:
            found.append(node)
        stack.extend(node.get("children") or ())
    return found


def _value(node, key):
    return (node["metrics"].get(key) or {}).get("value", 0)


@pytest.fixture(scope="module", params=[42, 2 ** 31 + 42, 7])
def seeded(request):
    """A seed's tables, and Q3's answers and records on the mesh."""
    tables = tpch_tables.generate(_config(), request.param)
    answers, records = _queries(_config(), tables)
    return tables, answers, records


def test_q3_on_the_mesh_equals_the_reference_and_one_chip(seeded):
    tables, answers, _records = seeded
    want = q3.run(tables, PARAMS)
    one_chip, _ = _queries(_config(mesh=False, threshold=None), tables,
                           times=1)
    for answer in answers:
        mismatches, gap = compare.compare_answer(answer, want)
        assert mismatches == 0 and gap < _limit(), (answer, want)
        for column in ("l_orderkey", "o_orderdate", "o_shippriority"):
            assert answer[column] == one_chip[0][column]
        _m, gap = compare.compare_answer(answer, one_chip[0])
        assert gap < _limit()


def test_the_lineitem_join_exchanges_both_inputs_and_nothing_is_gathered(
        seeded):
    """The outer join ran on the chips as a shuffled hash join, both its
    exchanges ran, one program an input batch; the customer join ran as a
    broadcast copied to every chip, its exchanges never ran; the live
    rows sent are the rows of the two inputs."""
    _tables, _answers, records = seeded
    for record in records:
        assert record["meshRelandRows"] == 0
        outer, inner = _nodes(record["plan"], "TpuJoinExec")
        assert _value(outer, "joinOnChips") == 1
        assert _value(inner, "joinReplicatedBuild") == 1
        exchanges = [c for c in outer["children"]
                     if c["op"] == "TpuShuffleExchangeExec"]
        assert len(exchanges) == 2
        assert sorted(_value(e, "meshExchanges") for e in exchanges) \
            == [1, 4]
        sent = [_value(e["children"][0], "numOutputRows") for e in exchanges]
        assert [_value(e, "meshExchangeRows") for e in exchanges] == sent
        assert record["meshExchangeRows"] == sum(sent) > 0
        assert record["meshExchanges"] == 5
        assert record["meshExchangeBytes"] > record["meshExchangeRows"]
        assert all(_value(e, "meshExchanges") == 0
                   for e in _nodes(inner, "TpuShuffleExchangeExec"))
        (agg,) = _nodes(record["plan"], "TpuHashAggregateExec")
        assert _value(agg, "meshChipAggBatches") == 4
        (top,) = _nodes(record["plan"], "TpuTakeOrderedAndProjectExec")
        assert _value(top, "chipTopKBatches") == 1
        assert record["phasesS"]["exchangeS"] > 0
    # the first run measured the customer keys; the next reuses them
    assert _value(_nodes(records[0]["plan"], "TpuJoinExec")[1],
                  "aqeBroadcastConverted") == 1


def test_explain_shows_the_exchanges_and_why():
    config = _config()
    tables = tpch_tables.generate(dict(config, scale_factor=0.002), 3)
    engine = sut.Engine(config)
    engine.register(tables)
    try:
        text = engine.session.sql(traffic.statement("q3", PARAMS)).explain()
    finally:
        engine.close()
    assert text.count("hash TpuShuffleExchange[n=4] over ICI") == 2
    assert "shuffled hash join: build side estimate" in text
    assert "broadcastSizeBytes 30000 B" in text


def test_a_threshold_above_customer_orders_exchanges_nothing():
    config = _config(threshold=str(1 << 30))
    tables = tpch_tables.generate(config, 42)
    answers, records = _queries(config, tables)
    want = q3.run(tables, PARAMS)
    for answer, record in zip(answers, records):
        mismatches, gap = compare.compare_answer(answer, want)
        assert mismatches == 0 and gap < _limit()
        assert record["meshExchanges"] == 0
        assert _nodes(record["plan"], "TpuShuffleExchangeExec") == []
    # both joins are broadcasts copied to every chip (the outer one's
    # broadcast is kept from the first run: the customer join under it
    # runs once)
    assert [_value(j, "joinReplicatedBuild") for r in records
            for j in _nodes(r["plan"], "TpuJoinExec")] == [1, 1, 1, 0]


def test_with_the_mesh_off_q3_join_plans_and_dispatches_as_before():
    """The one-chip cell's configuration at SF 0.01: the operators and
    the dispatch count of the parent tree, to the program."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tpch-q3-tables.json")) as f:
        config = dict(json.load(f), scale_factor=SCALE)
    tables = tpch_tables.generate(config, 2 ** 31 + 34)
    _answers, records = _queries(config, tables)
    ops = []

    def walk(node):
        ops.append(node["op"])
        for child in node.get("children") or ():
            walk(child)
    walk(records[0]["plan"])
    assert ops == [
        "DeviceToHost", "TpuTakeOrderedAndProjectExec", "TpuProjectExec",
        "TpuHashAggregateExec", "TpuCoalesceExec", "TpuJoinExec",
        "TpuBroadcastExchangeExec", "TpuProjectExec", "TpuJoinExec",
        "TpuBroadcastExchangeExec", "TpuProjectExec", "TpuFilterExec",
        "TpuProjectExec", "TpuScanExec", "TpuCoalesceExec",
        "TpuFilterExec", "TpuProjectExec", "TpuScanExec",
        "TpuCoalesceExec", "TpuProjectExec", "TpuFilterExec",
        "TpuProjectExec", "TpuScanExec"]
    assert [(r["dispatches"], r["hostSyncs"]) for r in records] \
        == [(157, 8), (104, 5)]
    for record in records:
        assert record["meshExchanges"] == 0 and record["meshShape"] is None
        assert all("on chips" not in j["describe"]
                   for j in _nodes(record["plan"], "TpuJoinExec"))


def _mesh_session(threshold, event_dir):
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.mesh.enabled": "true",
                       "spark.rapids.mesh.shape": "2x2",
                       "spark.rapids.sql.broadcastSizeBytes": threshold,
                       "spark.rapids.sql.eventLog.enabled": "true",
                       "spark.rapids.sql.eventLog.dir": str(event_dir)})


def _rows(df):
    return sorted(df.collect(), key=repr)


def test_duplicate_build_keys_on_the_mesh_give_the_oracles_answer(
        tmp_path):
    """A build side whose key repeats has no direct table: the shuffled
    join gathers its inputs to one device and takes the sorted body."""
    from spark_rapids_tpu.session import TpuSession
    rng = np.random.default_rng(5)
    left = {"k": rng.integers(0, 300, 4000).astype(np.int64),
            "a": rng.integers(0, 9, 4000).astype(np.int64)}
    right = {"k": rng.integers(0, 300, 2500).astype(np.int64),
             "b": rng.integers(0, 9, 2500).astype(np.int64)}

    def run(session):
        lt = session.create_dataframe(left, num_batches=2)
        rt = session.create_dataframe(right, num_batches=1)
        return _rows(lt.join(rt, on=["k"]))

    mesh = _mesh_session("1", tmp_path)
    got = run(mesh)
    rec = mesh.last_event_record
    assert got == run(TpuSession({"spark.rapids.sql.enabled": "false"}))
    assert len(got) > 4000
    assert rec["meshExchanges"] == 3
    assert any(_value(j, "sortJoinBatches") > 0
               for j in _nodes(rec["plan"], "TpuJoinExec"))


def test_groups_that_span_chips_give_the_one_chip_answer():
    """Q3 grouped by the order date alone: the exchange partitions by
    the order key, so a date's rows lie on every chip and the aggregate
    does not run chip by chip; the answer is one chip's."""
    text = traffic.statement("q3", PARAMS) \
        .replace("group by\n\tl_orderkey,\n\to_orderdate,\n\to_shippriority",
                 "group by\n\to_orderdate,\n\to_shippriority") \
        .replace("\tl_orderkey,\n\tsum(", "\tsum(")
    assert "group by\n\to_orderdate" in text
    config = _config()
    tables = tpch_tables.generate(config, 11)
    got, records = _queries(config, tables, text)
    want, _ = _queries(_config(mesh=False, threshold=None), tables, text,
                       times=1)
    for answer, record in zip(got, records):
        assert answer["o_orderdate"] == want[0]["o_orderdate"]
        _m, gap = compare.compare_answer(answer, want[0])
        assert gap < 1e-9
        (agg,) = _nodes(record["plan"], "TpuHashAggregateExec")
        assert _value(agg, "meshChipAggBatches") == 0
        assert record["meshExchanges"] == 5


def test_ties_across_chips_in_the_top_10_keep_one_chips_order(tmp_path):
    """Forty rows share the largest value, ten on each chip: each chip's
    ten candidates tie, and the ten kept are the first in row order, as
    on one chip."""
    from spark_rapids_tpu.session import TpuSession
    n = 4096
    values = np.arange(n, dtype=np.int64) % 97
    ties = np.concatenate([np.arange(10) + i * (n // 4) for i in range(4)])
    values[ties] = 1000
    data = {"id": np.arange(n, dtype=np.int64), "v": values}

    def top(session):
        session.create_dataframe(data, num_batches=1) \
            .create_or_replace_temp_view("t")
        return session.sql(
            "select id, v from t order by v desc limit 10").collect()

    mesh = _mesh_session(str(10 << 20), tmp_path)
    got = top(mesh)
    rec = mesh.last_event_record
    assert got == top(TpuSession())
    assert [r[0] for r in got] == list(range(10))
    (node,) = _nodes(rec["plan"], "TpuTakeOrderedAndProjectExec")
    assert _value(node, "chipTopKBatches") == 1
    assert rec["meshRelandRows"] == 0


def test_a_partition_dropped_before_the_join_gives_a_wrong_answer(
        monkeypatch):
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.parallel.shards import chips_of
    real = TpuShuffleExchangeExec.exchanged

    def dropping(self, batches):
        for out in real(self, batches):
            if "l_orderkey" in out.names:
                chips = chips_of(out)
                keep = jnp.arange(out.capacity) >= chips.rows
                out = DeviceTable(out.names, out.columns, out.nrows_dev,
                                  out.capacity, live=out.live & keep,
                                  shard_spec=out.shard_spec)
            yield out

    config = _config()
    tables = tpch_tables.generate(config, 42)
    want = q3.run(tables, PARAMS)
    sound, _ = _queries(config, tables, times=1)
    assert compare.compare_answer(sound[0], want)[0] == 0
    monkeypatch.setattr(TpuShuffleExchangeExec, "exchanged", dropping)
    broken, _ = _queries(config, tables, times=1)
    assert compare.compare_answer(broken[0], want)[0] > 0


@pytest.mark.parametrize("file, only", [
    ("test_q3_mesh4.py", None), ("test_correct.py", "q3-mesh4")],
    ids=["cell", "correct"])
def test_the_benchmarks_own_tests_of_the_cell_pass(file, only):
    """benchmarks/tests/ sits outside tier 1: the new cell's own tests,
    and the correctness tests every cell is held to, run here in a
    process of their own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    args = [sys.executable, "-m", "pytest",
            os.path.join(ROOT, "benchmarks", "tests", file), "-q",
            "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"]
    if only:
        args += ["-k", only]
    done = subprocess.run(args, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
    assert " passed" in done.stdout and " failed" not in done.stdout
