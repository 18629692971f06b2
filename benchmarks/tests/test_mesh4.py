"""The cell `q1-mesh4` through the harness's own `run_cell` on the CPU
backend's virtual devices at a tiny scale (no timing claimed): a sound
mesh run is correct and shows the sharded aggregate's counts; a fault
planted in the mechanism the cell exists for — one shard's partial
groups dropped before the merge — is not correct; each of the cell's
four readers gives a number on a hand-made run and nothing, or 0.0,
where its input is absent.

The cell's configuration asks for a 2x2 mesh, so the process needs at
least four devices. Run alone, this file asks XLA for eight virtual
ones before the backend starts (`ensure_cpu_test_mesh`, the setter the
mesh harnesses share). `selfcheck.py` starts the backend before it gets
here, so it needs them in its environment:

    python3 -m pytest benchmarks/tests -q
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        JAX_PLATFORMS=cpu python3 benchmarks/selfcheck.py
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from spark_rapids_tpu.parallel.mesh import ensure_cpu_test_mesh  # noqa: E402

DEVICES = ensure_cpu_test_mesh(8)

from benchmarks import costs_mesh  # noqa: E402
from benchmarks import run as bench_run  # noqa: E402
from benchmarks.layer_metrics import (  # noqa: E402
    collective_ms_per_query,
    mesh_agg_device_ms_per_query,
    mesh_agg_roofline,
    reland_ms_per_query,
)
from benchmarks.tests.test_correct import (  # noqa: E402
    BENCH, CPU_DEVICE, TINY_SCALE)
from benchmarks.tests.test_defaultconf import (  # noqa: E402
    keeping_full_records, metric)

CELL = "q1-mesh4"
#: every cached batch passes its coalesce and stays on its shards, as
#: the cell's 900 MB batches pass the configuration's 128 MiB goal
SMALL_GOAL = {"spark.rapids.sql.batchSizeBytes": "4096"}


def drive(engine_factory, seed=2 ** 31 + 31, batches=3):
    """`run_cell` on the cell's own configuration file, its scale cut and
    the coalesce goal cut with it."""
    assert DEVICES >= 4, (
        f"the 2x2 mesh needs four devices and the backend started with "
        f"{DEVICES}: set XLA_FLAGS=--xla_force_host_platform_device_count=8")
    cell, config, mix, limits = bench_run.resolve_cell(BENCH, CELL)
    config = dict(config, scale_factor=TINY_SCALE,
                  batches={"lineitem": batches},
                  session_conf=dict(config["session_conf"], **SMALL_GOAL))
    return bench_run.run_cell(
        cell, config, mix, limits, BENCH, seed, 0.3, 0, CPU_DEVICE,
        engine_factory, memory_reader=lambda: {"in_use": 1, "peak": 1})


def test_the_configuration_is_the_sharded_deployment():
    cell, config, _mix, limits = bench_run.resolve_cell(BENCH, CELL)
    assert cell["chips"] == 4 and list(config["chips"]) == ["4"]
    assert config["session_conf"]["spark.rapids.mesh.enabled"] == "true"
    assert config["session_conf"]["spark.rapids.mesh.shape"] == "2x2"
    assert len(config["tables"]["lineitem"]) == 16
    assert config["scale_factor"] == 10 and config["batches"] == {
        "lineitem": 8}
    entry = {c["name"]: c for c in BENCH["configs"]}[config["name"]]
    assert entry["reduced"] == list(config["reduced"])
    assert limits["max_rel_err"]["q1"] == 2e-7


def test_sound_mesh_run_is_correct_and_gathers_no_row():
    engine = keeping_full_records()
    result = drive(engine)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    for record in engine.records:
        assert metric(record["plan"], "meshAggBatches", []) == [3]
        assert metric(record["plan"], "meshAggShards", []) == [12]
        assert metric(record["plan"], "meshRelandRows", []) == []
        assert record["phasesS"]["relandS"] == 0.0
        assert record["hostSyncs"] == 1


def test_a_shard_whose_partial_groups_are_dropped_is_not_correct(
        monkeypatch):
    """The sharded program's output holds every shard's partial groups
    in shard order, each shard's in rising key order; the planted fault
    cuts the last shard's off before the merge sees them."""
    from benchmarks import sut
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    real = TpuHashAggregateExec._aggregate
    dropped = []

    def dropping(self, table, *a, shards=1, **k):
        out = real(self, table, *a, shards=shards, **k)
        if shards == 1:
            return out
        keys = list(zip(*list(out.to_host().to_pydict().values())[:2]))
        starts = [i for i in range(len(keys))
                  if i == 0 or keys[i] <= keys[i - 1]]
        dropped.append(len(keys) - starts[-1])
        return DeviceTable(out.names, out.columns, starts[-1], out.capacity)

    sound = drive(sut.Engine)
    assert sound["correct"] is True, sound["checks"]
    monkeypatch.setattr(TpuHashAggregateExec, "_aggregate", dropping)
    result = drive(sut.Engine)
    assert dropped and all(dropped)
    assert result["correct"] is False, result["checks"]
    # the groups are all still there: what is lost is a shard's rows
    assert result["checks"]["q1.exact_mismatches"]["value"] > 0


def hand_made_run(**over):
    """Two traced Q1 queries on two chips: `trace_reduce` has divided
    every operation's seconds by the chips already."""
    run = {
        "device": {"kind": "TPU v5 lite"},
        "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 800e9}},
        "scan_bytes_per_query": {"q1": 16e9},
        "queries": [
            {"id": "q1", "traced": True,
             "record": {"phasesS": {"relandS": 0.002}}},
            {"id": "q1", "traced": True,
             "record": {"phasesS": {"relandS": 0.004}}},
            {"id": "q1", "traced": False,
             "record": {"phasesS": {"relandS": 0.003}}}],
        "trace": {"chips": 2, "queries": 2, "busy_s": 1.0, "window_s": 2.0,
                  "device_ops": [
                      ["jit_agg_fast_mesh %fusion.1 fusion f32[8]", 0.03],
                      ["jit_agg_fast_mesh %all-gather.2 all-gather f32[64]",
                       0.006],
                      ["jit_agg_fast_mesh %all-reduce-start.3 "
                       "all-reduce-start f32[64]", 0.002],
                      ["jit_agg_fast_mesh %all-reduce-done.3 "
                       "all-reduce-done f32[64]", 0.002],
                      ["jit_agg_fast %fusion.1 fusion f32[8]", 4.0],
                      ["jit_reland_digest %reduce.4 reduce u32[]", 0.5]]},
    }
    run.update(over)
    return run


def test_each_reader_gives_a_number_on_a_hand_made_run():
    run = hand_made_run()
    assert reland_ms_per_query.read(run) == pytest.approx(3.0)
    # 0.03 + 0.006 + 0.002 + 0.002 s over two queries
    assert mesh_agg_device_ms_per_query.read(run) == pytest.approx(20.0)
    assert collective_ms_per_query.read(run) == pytest.approx(5.0)
    # 2 x 16 GB over 2 chips x 800 GB/s = 0.02 s, over 0.04 s
    assert costs_mesh.mesh_least_seconds(run, ["q1", "q1"]) \
        == pytest.approx(0.02)
    assert mesh_agg_roofline.read(run) == pytest.approx(50.0)
    assert costs_mesh.opcode_of(
        "jit_x %all-to-all.1 all-to-all f32[8]") == "all-to-all"
    assert costs_mesh.opcode_of("jit_x %weird") == ""


def test_each_reader_gives_nothing_or_zero_where_its_input_is_absent():
    untraced = hand_made_run(trace=None)
    assert mesh_agg_device_ms_per_query.read(untraced) is None
    assert mesh_agg_roofline.read(untraced) is None
    assert collective_ms_per_query.read(untraced) is None
    # a program that records no such phase (the parent of this cell)
    no_phase = hand_made_run(queries=[
        {"id": "q1", "traced": True, "record": {"phasesS": {}}}])
    assert reland_ms_per_query.read(no_phase) is None
    # a trace in which the sharded program and no collective ran
    one_chip = hand_made_run()
    one_chip["trace"] = dict(one_chip["trace"], chips=1, device_ops=[
        ["jit_agg_fast %fusion.1 fusion f32[8]", 4.0]])
    assert mesh_agg_device_ms_per_query.read(one_chip) == 0.0
    assert mesh_agg_roofline.read(one_chip) == 0.0
    assert collective_ms_per_query.read(one_chip) == 0.0
    # an older trace_reduce that names no chip count reads as one chip
    del one_chip["trace"]["chips"]
    assert costs_mesh.mesh_least_seconds(one_chip, ["q1"]) \
        == pytest.approx(0.02)
