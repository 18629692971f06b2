"""What `correct` has to refuse, driven through the harness's own
`run_cell` on the CPU backend at a tiny scale factor (no timing claimed):
the rest of a run with the look for a chip skipped.

- the control: the plain reference computed in float32 and put in the
  program's place must come out as not correct in every cell;
- the faults a cell can have, planted under the timed path: an answer
  altered where it is produced (a float cell by one part in a million, where the control reads, an
  integer cell by one, two rows swapped), half of the rows left out of
  the landed table, an answer that never comes;
- a sound run comes out correct.

    python3 -m pytest benchmarks/tests -q        (or benchmarks/selfcheck.py)
"""

import copy
import dataclasses
import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402

TINY_SCALE = 0.02
#: the float32 control's gap grows with the rows summed (numpy's pairwise
#: float32 sums): 1e-7 at SF0.02, 3.7e-7 to 5.1e-7 at SF1 on the seeds
#: below (PERF.md section 2 has the cell's own size). It is held to the
#: cell's limit, so it runs at the smallest size that reads well above it.
CONTROL_SCALE = 1.0
CPU_DEVICE = {"platform": "cpu", "kind": "cpu (test)", "count": 1}
BENCH = bench_run.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]


def drive(cell_name, engine_factory, seed=2 ** 31 + 7, seconds=0.3,
          scale=TINY_SCALE, trace=0):
    cell, config, mix, limits = bench_run.resolve_cell(BENCH, cell_name)
    config = dict(config, scale_factor=scale)
    return bench_run.run_cell(
        cell, config, mix, limits, BENCH, seed, seconds, trace, CPU_DEVICE,
        engine_factory, memory_reader=lambda: {"in_use": 1, "peak": 1})


def reference_engine(cell_name, float_dtype):
    """The plain reference in the program's place, in `float_dtype`."""
    from benchmarks import traffic
    mix = traffic.load_mix({w["name"]: w for w in BENCH["workloads"]}[
        cell_name]["traffic"])
    # the mixes of these cells send one query with fixed parameters
    ((query_id, params),) = traffic.distinct_statements(mix)
    reference = importlib.import_module(f"benchmarks.reference.{query_id}")

    class ReferenceAsEngine:
        def __init__(self, _config):
            self.tables = None

        def register(self, tables):
            self.tables = tables

        def land(self):
            return 0

        def query(self, text, annotate=None):
            answer = reference.run(self.tables, params, float_dtype)
            record = {"wallS": 0.0, "phasesS": {}, "dispatches": 0,
                      "compileMs": 0.0, "healthState": "HEALTHY"}
            return answer, record

        def close(self):
            self.tables = None

    return ReferenceAsEngine


def broken_engine(fault):
    """The engine with one fault planted under the timed path."""
    from benchmarks import sut

    class Broken(sut.Engine):
        calls = 0

        def register(self, tables):
            if fault == "half_the_rows":
                tables = copy.copy(tables)
                name = max(tables, key=lambda t: tables[t]["num_rows"])
                big = tables[name]
                half = big["num_rows"] // 2
                tables[name] = {"num_rows": half, "columns": {
                    c: dataclasses.replace(
                        col, values=col.values[:half],
                        lengths=None if col.lengths is None
                        else col.lengths[:half])
                    for c, col in big["columns"].items()}}
            super().register(tables)

        def query(self, text, annotate=None):
            self.calls += 1
            # warm-up makes two calls (a third only while something compiles)
            if fault == "never_comes" and self.calls >= 3 and self.calls % 2:
                raise RuntimeError("planted: the answer never comes")
            answer, record = super().query(text, annotate)
            floats = [c for c, v in answer.items()
                      if v and isinstance(v[0], float)]
            ints = [c for c, v in answer.items()
                    if v and isinstance(v[0], int)]
            if fault == "float_one_in_a_million":
                answer[floats[0]][0] *= 1.0 + 1e-6
            elif fault == "integer_off_by_one":
                answer[ints[-1]][0] += 1
            elif fault == "rows_swapped":
                for values in answer.values():
                    values[0], values[-1] = values[-1], values[0]
            return answer, record

    return Broken


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct(cell_name):
    from benchmarks import sut
    result = drive(cell_name, sut.Engine)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("cell_name", CELLS)
def test_float64_reference_in_place_is_correct(cell_name):
    result = drive(cell_name, reference_engine(cell_name, np.float64))
    assert result["correct"] is True, result["checks"]


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 13])
@pytest.mark.parametrize("cell_name", CELLS)
def test_float32_control_is_not_correct(cell_name, seed):
    result = drive(cell_name, reference_engine(cell_name, np.float32),
                   seed=seed, scale=CONTROL_SCALE)
    assert result["correct"] is False, result["checks"]
    assert not any(c["value"] for n, c in result["checks"].items()
                   if n.endswith("exact_mismatches")), \
        "the control fails by its floats, not by a changed key or count"


FAULTS = ["float_one_in_a_million", "integer_off_by_one", "rows_swapped",
          "half_the_rows", "never_comes"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("cell_name", CELLS)
def test_planted_fault_is_not_correct(cell_name, fault):
    result = drive(cell_name, broken_engine(fault))
    assert result["correct"] is False, (fault, result["checks"])
    if fault == "never_comes":
        assert result["failed"] >= 1


@pytest.mark.parametrize("cell_name", CELLS)
def test_traced_run_reports_every_layer_metric(cell_name, monkeypatch):
    """The CPU backend's trace holds no device plane, so the reduction
    reads the TPU trace recorded in data/; the rest is the traced run."""
    from benchmarks import sut, trace_reduce
    recorded = os.path.join(ROOT, "benchmarks", "data",
                            "small_trace.xplane.pb")
    reduce_dir = trace_reduce.reduce_dir
    monkeypatch.setattr(trace_reduce, "reduce_dir",
                        lambda _dir: reduce_dir(recorded))
    monkeypatch.setitem(CPU_DEVICE, "kind", "TPU v5 lite")
    result = drive(cell_name, sut.Engine, trace=1)
    assert result["correct"] is True, result["checks"]
    wanted = {m["name"] for m in BENCH["per_layer"]
              if cell_name in m.get("workloads", [cell_name])}
    assert set(result["metrics"]) == wanted
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]
    assert 0 < result["metrics"]["scan_roofline"]["value"] < 100
    assert len(result["breakdown"]["device_ops"]) <= 10
    assert list(result)[-1] == "checks"
