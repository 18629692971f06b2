"""The cell `q1-sf5-defaultconf` through the harness's own `run_cell` on
the CPU backend at a tiny scale (no timing claimed): a sound run is
correct and shows the coalesce's counts, the float32 control is not
correct, and a fault planted in the mechanism the cell exists for — a
coalesce that never hands on its last, partial flush — is not correct.

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

from benchmarks import costs, costs_coalesce  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.tests.test_correct import (  # noqa: E402
    BENCH, CONTROL_SCALE, CPU_DEVICE, TINY_SCALE, reference_engine)

CELL = "q1-sf5-defaultconf"


def drive(engine_factory, seed=2 ** 31 + 27, scale=TINY_SCALE, **overrides):
    """`run_cell` on the cell's own configuration file, its scale cut and
    `overrides` laid over it."""
    cell, config, mix, limits = bench_run.resolve_cell(BENCH, CELL)
    config = dict(config, scale_factor=scale, **overrides)
    return bench_run.run_cell(
        cell, config, mix, limits, BENCH, seed, 0.3, 0, CPU_DEVICE,
        engine_factory, memory_reader=lambda: {"in_use": 1, "peak": 1})


def keeping_full_records():
    """The engine, each query's whole event record kept beside the slim."""
    from benchmarks import sut

    class Keeping(sut.Engine):
        records = []

        def query(self, text, annotate=None):
            answer, record = super().query(text, annotate)
            Keeping.records.append(self.session.last_event_record)
            return answer, record

    return Keeping


def metric(node, key, acc):
    if isinstance(node, dict):
        if key in (node.get("metrics") or {}):
            acc.append(node["metrics"][key]["value"])
        for v in node.values():
            metric(v, key, acc)
    elif isinstance(node, list):
        for v in node:
            metric(v, key, acc)
    return acc


def test_the_configuration_sets_no_session_conf():
    from spark_rapids_tpu.conf import BATCH_SIZE_BYTES
    _cell, config, _mix, _limits = bench_run.resolve_cell(BENCH, CELL)
    assert "session_conf" not in config
    assert BATCH_SIZE_BYTES.default == 1 << 30
    assert set(config["reduced"]) == {"scale_factor", "tables", "batches"}
    entry = {c["name"]: c for c in BENCH["configs"]}[config["name"]]
    assert entry["reduced"] == list(config["reduced"])


def test_sound_run_is_correct_and_coalesces_what_q1_reads():
    engine = keeping_full_records()
    result = drive(engine)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    for record in engine.records:
        assert metric(record["plan"], "coalescedColumns", []) == [7]
        assert metric(record["plan"], "dictUnions", []) == [0]
        assert metric(record["plan"], "concatBatches", []) == [16]
        assert record["phasesS"]["coalesceS"] > 0


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12])
def test_float32_control_is_not_correct(seed):
    result = drive(reference_engine(CELL, np.float32), seed=seed,
                   scale=CONTROL_SCALE)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["q1.exact_mismatches"]["value"] == 0


def test_a_coalesce_that_drops_its_last_partial_flush_is_not_correct(
        monkeypatch):
    """15 batches under a goal that two of them fill: seven flushes of
    two and a last one of one, which the planted fault never yields."""
    from benchmarks import sut
    from spark_rapids_tpu.execs.basic import TpuCoalesceExec
    real = TpuCoalesceExec.execute_masked

    def dropping(self):
        if self.columns is None:  # only the coalesce below the aggregate
            yield from real(self)
            return
        held = None
        for batch in real(self):
            if held is not None:
                yield held
            held = batch

    overrides = {"batches": {"lineitem": 15},
                 "session_conf": {"spark.rapids.sql.batchSizeBytes": "921600"}}
    sound = drive(sut.Engine, **overrides)
    assert sound["correct"] is True, sound["checks"]
    monkeypatch.setattr(TpuCoalesceExec, "execute_masked", dropping)
    result = drive(sut.Engine, **overrides)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["q1.exact_mismatches"]["value"] > 0


def test_copy_bytes_are_twice_what_the_statement_reads():
    from benchmarks import traffic
    from benchmarks.datagen import tpch
    _cell, config, mix, _limits = bench_run.resolve_cell(BENCH, CELL)
    tables = tpch.generate(dict(config, scale_factor=0.002), 5)
    ((query_id, params),) = traffic.distinct_statements(mix)
    text = traffic.statement(query_id, params)
    rows = tables["lineitem"]["num_rows"]
    # Q1 names 4 DOUBLE, 2 dictionary-coded strings and a date
    assert costs.scan_bytes(tables, text) == rows * (4 * 8 + 2 * 4 + 4)
    assert costs_coalesce.copy_bytes(tables, text) == 2 * rows * 44
    run = {"scan_bytes_per_query": {query_id: costs.scan_bytes(tables, text)}}
    assert costs_coalesce.copy_bytes_of(run, query_id) \
        == costs_coalesce.copy_bytes(tables, text)
    trace = {"device_ops": [["jit_coalesce %fusion.1 fusion f32[8]", 0.25],
                            ["jit_coalesce %copy.2 copy f32[8]", 0.5],
                            ["jit_concat %fusion.1 fusion f32[8]", 4.0]]}
    assert costs_coalesce.program_seconds(trace, "jit_coalesce") == 0.75
