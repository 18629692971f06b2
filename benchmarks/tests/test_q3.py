"""The cell `q3-join` through the harness's own `run_cell` on the CPU
backend at a tiny scale (no timing claimed): a sound run is correct, runs
both joins by the direct-address body with the smaller side built, and
records the join's phase; the float64 reference in the program's place is
correct; not correct are a `revenue` altered by one part in a million, an
order key altered by one, two rows swapped, an answer of 9 rows, and a
fault planted in the mechanism the cell exists for: a join that drops the
orders of one customer. The float32 control is held to what section 2 of
PERF.md says of it on this cell.

    python3 -m pytest benchmarks/tests -q        (or benchmarks/selfcheck.py)
"""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import costs, costs_join  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.tests.test_correct import (  # noqa: E402
    BENCH, CPU_DEVICE, TINY_SCALE, reference_engine)
from benchmarks.tests.test_defaultconf import (  # noqa: E402
    keeping_full_records, metric)

CELL = "q3-join"


def drive(engine_factory, seed=2 ** 31 + 34, scale=TINY_SCALE):
    cell, config, mix, limits = bench_run.resolve_cell(BENCH, CELL)
    config = dict(config, scale_factor=scale)
    return bench_run.run_cell(
        cell, config, mix, limits, BENCH, seed, 0.3, 0, CPU_DEVICE,
        engine_factory, memory_reader=lambda: {"in_use": 1, "peak": 1})


def altering(alter):
    """The engine, each answer passed through `alter` where it is made."""
    from benchmarks import sut

    class Altered(sut.Engine):
        def query(self, text, annotate=None):
            answer, record = super().query(text, annotate)
            alter(answer)
            return answer, record

    return Altered


def test_the_configuration_is_the_three_tables_whole():
    from benchmarks.datagen import tpch_tables
    _cell, config, mix, _limits = bench_run.resolve_cell(BENCH, CELL)
    assert {t: len(c) for t, c in config["tables"].items()} \
        == {"customer": 8, "orders": 9, "lineitem": 16}
    assert config["tables"] == {t: list(c) for t, c
                                in tpch_tables.COLUMN_TYPES.items()}
    assert config["batches"] == {"customer": 1, "orders": 4, "lineitem": 16}
    assert config["session_conf"] == {
        "spark.rapids.sql.batchSizeBytes": "134217728"}
    sf5 = bench_run.load_json(ROOT, "benchmarks", "configs",
                              "tpch-sf5-lineitem.json")
    assert config["guarantees"] == sf5["guarantees"]
    assert config["tables"]["lineitem"] == sf5["tables"]["lineitem"]
    entry = {c["name"]: c for c in BENCH["configs"]}[config["name"]]
    assert entry["reduced"] == list(config["reduced"])
    assert mix["queries"] == [{"id": "q3", "params": {
        "SEGMENT": "BUILDING", "DATE": "1995-03-15"}}]


def test_sound_run_is_correct_and_joins_by_the_body_it_chose():
    engine = keeping_full_records()
    result = drive(engine)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["checks"]["q3.exact_mismatches"]["value"] == 0
    first = engine.records[0]  # later ones may reuse a broadcast build
    assert metric(first["plan"], "sortJoinBatches", []) == []
    assert sorted(metric(first["plan"], "directJoinBatches", [])) == [4, 16]
    assert metric(first["plan"], "buildSideSwapped", []) == [1, 1]
    assert sum(metric(first["plan"], "joinOutputRows", [])) > 0
    for record in engine.records:
        assert record["phasesS"]["joinS"] > 0
        assert not record.get("faultReplays")


def test_float64_reference_in_place_is_correct():
    result = drive(reference_engine(CELL, np.float64))
    assert result["correct"] is True, result["checks"]


def first_revenue_off_by_a_millionth(answer):
    answer["revenue"][0] *= 1.0 + 1e-6


def first_key_off_by_one(answer):
    answer["l_orderkey"][0] += 1


def rows_swapped(answer):
    for values in answer.values():
        values[0], values[-1] = values[-1], values[0]


def nine_rows(answer):
    for values in answer.values():
        del values[-1]


@pytest.mark.parametrize("alter, check", [
    (first_revenue_off_by_a_millionth, "q3.max_rel_err"),
    (first_key_off_by_one, "q3.exact_mismatches"),
    (rows_swapped, "q3.exact_mismatches"),
    (nine_rows, "q3.exact_mismatches"),
], ids=lambda v: getattr(v, "__name__", v))
def test_an_altered_answer_is_not_correct(alter, check):
    result = drive(altering(alter))
    assert result["correct"] is False, result["checks"]
    held = result["checks"][check]
    assert held["value"] > held["limit"]


def test_a_join_that_drops_one_customers_orders_is_not_correct(monkeypatch):
    """The customer whose order leads the answer loses its rows in the
    customer-orders join's output: the answer's first row goes."""
    from benchmarks import sut, traffic
    from benchmarks.datagen import tpch_tables
    from benchmarks.reference import q3
    from spark_rapids_tpu.execs.join import TpuJoinExec
    cell, config, mix, _limits = bench_run.resolve_cell(BENCH, CELL)
    seed = 2 ** 31 + 34
    tables = tpch_tables.generate(dict(config, scale_factor=TINY_SCALE), seed)
    ((_query_id, params),) = traffic.distinct_statements(mix)
    top_order = q3.run(tables, params)["l_orderkey"][0]
    orders = tables["orders"]["columns"]
    victim = int(orders["o_custkey"].values[
        orders["o_orderkey"].values == top_order][0])
    real = TpuJoinExec._direct_finish

    def dropping(self, ahead, rt, swapped):
        outs = real(self, ahead, rt, swapped)
        if "o_custkey" not in self.left_names + self.right_names:
            return outs
        import jax.numpy as jnp
        from spark_rapids_tpu.columnar import DeviceTable
        kept = []
        for out in outs:
            keep = out.row_mask() & (out.column("o_custkey").data != victim)
            kept.append(DeviceTable(out.names, out.columns,
                                    jnp.sum(keep.astype(jnp.int32)),
                                    out.capacity, live=keep))
        return kept

    monkeypatch.setattr(TpuJoinExec, "_direct_finish", dropping)
    result = drive(sut.Engine, seed=seed)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["q3.exact_mismatches"]["value"] > 0


def test_the_join_readers_read_what_the_program_names():
    from benchmarks import traffic
    from benchmarks.datagen import tpch_tables
    from benchmarks.layer_metrics import (
        join_device_ms_per_query, join_ms_per_query, join_roofline,
        sort_agg_device_ms_per_query)
    _cell, config, mix, _limits = bench_run.resolve_cell(BENCH, CELL)
    tables = tpch_tables.generate(dict(config, scale_factor=0.002), 5)
    ((query_id, params),) = traffic.distinct_statements(mix)
    text = traffic.statement(query_id, params)
    rows = {t: tables[t]["num_rows"] for t in tables}
    assert costs.rows_read(tables, text) == sum(rows.values())
    # Q3 names a key and a dictionary-coded string of customer; two keys,
    # a date and an INT of orders; a key, two DOUBLE and a date of lineitem
    assert costs.scan_bytes(tables, text) == (
        rows["customer"] * (8 + 4) + rows["orders"] * (8 + 8 + 4 + 4)
        + rows["lineitem"] * (8 + 8 + 8 + 4))
    peaks = {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}}
    run = {"scan_bytes_per_query": {query_id: 819_000_000},
           "device": {"kind": "TPU v5 lite"}, "peaks": peaks,
           "queries": [
               {"id": query_id, "traced": True,
                "record": {"phasesS": {"joinS": 0.004}}},
               {"id": query_id, "traced": True,
                "record": {"phasesS": {"joinS": 0.002}}},
               {"id": query_id, "record": {"phasesS": {"joinS": 0.003}}},
               {"error": "x"}],
           "trace": {"queries": 2, "device_ops": [
               ["jit_join_direct_probe %gather.1 gather s32[8]", 0.006],
               ["jit_join_direct_build %scatter.2 scatter s32[8]", 0.002],
               ["jit_agg_sorted %sort.1 sort s32[8]", 0.03],
               ["jit_sort_topk %sort.4 sort s32[8]", 0.01],
               ["jit_agg_fast %fusion.1 fusion f32[8]", 4.0]]}}
    assert costs_join.programs_seconds(
        run["trace"], costs_join.JOIN_PROGRAMS) == 0.008
    assert join_ms_per_query.read(run) == 3.0
    assert join_device_ms_per_query.read(run) == pytest.approx(4.0)
    assert sort_agg_device_ms_per_query.read(run) == pytest.approx(20.0)
    # 2 traced queries x 819 MB at 819 GB/s = 2 ms least, over 8 ms
    assert join_roofline.read(run) == pytest.approx(25.0)
    # a program without the phase gives nothing; a trace in which none
    # of the programs ran reads 0; no trace, nothing
    bare = dict(run, queries=[{"id": query_id, "traced": True,
                               "record": {"phasesS": {}}}],
                trace={"queries": 1, "device_ops": [
                    ["jit_agg_fast %fusion.1 fusion f32[8]", 4.0]]})
    assert join_ms_per_query.read(bare) is None
    for reader in (join_device_ms_per_query, join_roofline,
                   sort_agg_device_ms_per_query):
        assert reader.read(bare) == 0.0
    for reader in (join_ms_per_query, join_device_ms_per_query,
                   join_roofline, sort_agg_device_ms_per_query):
        assert reader.read(dict(run, trace=None, queries=[])) is None
