"""The cell `q3-mesh4` through the harness's own `run_cell` on the CPU
backend's virtual devices at a tiny scale (no timing claimed): a sound
run is correct, runs the lineitem join as a shuffled hash join whose two
inputs were exchanged over the mesh, and gathers no row to one device; a
fault planted in the mechanism the cell exists for — one chip's part of
an exchanged lineitem batch dropped before the join — is not correct;
each of the cell's three readers gives a number on a hand-made run and
nothing, or 0.0, where its input is absent.

At the cell's scale the customer keys the first join measures (9.4 MB)
are under the shipped `broadcastSizeBytes` of 10 MiB and customer-orders
(19.9 MB) is over it (PERF.md section 4). At this test's scale both are
a few kilobytes, so the test cuts the threshold with the scale, between
the two, as the Q1 mesh test cuts the batch goal.

The cell's configuration asks for a 2x2 mesh, so the process needs at
least four devices. Run alone, this file asks XLA for eight virtual
ones before the backend starts (`ensure_cpu_test_mesh`):

    python3 -m pytest benchmarks/tests/test_q3_mesh4.py -q
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
    exchange_device_ms_per_query,
    exchange_ms_per_query,
    mesh_join_roofline,
)
from benchmarks.tests.test_correct import (  # noqa: E402
    BENCH, CPU_DEVICE, TINY_SCALE)
from benchmarks.tests.test_defaultconf import (  # noqa: E402
    keeping_full_records, metric)

CELL = "q3-mesh4"
#: at SF 0.02 the customer keys measure 36,864 bytes (2^12 rows x 9) and
#: customer-orders 77,824 (2^12 rows x 19): the threshold between them
SMALL_THRESHOLD = {"spark.rapids.sql.broadcastSizeBytes": "50000"}


def drive(engine_factory, seed=2 ** 31 + 42):
    """`run_cell` on the cell's own configuration file, its scale cut and
    the broadcast threshold cut with it."""
    assert DEVICES >= 4, (
        f"the 2x2 mesh needs four devices and the backend started with "
        f"{DEVICES}: set XLA_FLAGS=--xla_force_host_platform_device_count=8")
    cell, config, mix, limits = bench_run.resolve_cell(BENCH, CELL)
    config = dict(config, scale_factor=TINY_SCALE,
                  session_conf=dict(config["session_conf"],
                                    **SMALL_THRESHOLD))
    return bench_run.run_cell(
        cell, config, mix, limits, BENCH, seed, 0.3, 0, CPU_DEVICE,
        engine_factory, memory_reader=lambda: {"in_use": 1, "peak": 1})


def test_the_configuration_is_q3_tables_on_the_mesh():
    cell, config, mix, limits = bench_run.resolve_cell(BENCH, CELL)
    one_chip = bench_run.load_json(ROOT, "benchmarks", "configs",
                                   "tpch-q3-tables.json")
    assert cell["chips"] == 4 and list(config["chips"]) == ["4"]
    assert cell["traffic"] == "q3"
    for key in ("generator", "scale_factor", "tables", "types"):
        assert config[key] == one_chip[key], key
    assert config["scale_factor"] == 5
    assert config["batches"] == {"lineitem": 4, "orders": 1, "customer": 1}
    assert config["session_conf"] == {
        "spark.rapids.mesh.enabled": "true",
        "spark.rapids.mesh.shape": "2x2",
        "spark.rapids.sql.batchSizeBytes": "134217728"}
    assert config["guarantees"]["path"] == one_chip["guarantees"]["path"]
    # this cell's sums are a segment a row on each chip: the unblocked
    # split, never the block partials the one-chip text allows
    precision = config["guarantees"]["precision"]
    assert "unblocked three-stream split" in precision
    assert "never through f32 block partials" in precision
    assert {"mesh", "plan", "floats", "exact"} <= set(config["guarantees"])
    entry = {c["name"]: c for c in BENCH["configs"]}[config["name"]]
    assert entry["reduced"] == list(config["reduced"])
    assert limits["max_rel_err"]["q3"] > 0
    assert mix["queries"] == [{"id": "q3", "params": {
        "SEGMENT": "BUILDING", "DATE": "1995-03-15"}}]


def test_sound_run_is_correct_and_gathers_no_row():
    engine = keeping_full_records()
    result = drive(engine)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["checks"]["q3.exact_mismatches"]["value"] == 0
    for record in engine.records:
        assert record["meshRelandRows"] == 0
        # customer-orders' one batch and lineitem's four
        assert record["meshExchanges"] == 5
        assert record["meshExchangeRows"] > 0
        assert record["phasesS"]["exchangeS"] > 0
        assert metric(record["plan"], "joinOnChips", []) == [1]
        assert metric(record["plan"], "meshChipAggBatches", []) == [4]
        assert metric(record["plan"], "chipTopKBatches", []) == [1]
        assert metric(record["plan"], "sortJoinBatches", []) == []


def test_a_chip_whose_exchanged_rows_are_dropped_is_not_correct(
        monkeypatch):
    """The planted fault: the last chip's part of every exchanged
    lineitem batch is dropped before the join sees it."""
    import jax.numpy as jnp
    from benchmarks import sut
    from spark_rapids_tpu.columnar import DeviceTable
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.parallel.shards import chips_of
    real = TpuShuffleExchangeExec.exchanged
    dropped = []

    def dropping(self, batches):
        for out in real(self, batches):
            if "l_orderkey" in out.names:
                chips = chips_of(out)
                keep = jnp.arange(out.capacity) < (chips.n - 1) * chips.rows
                out = DeviceTable(out.names, out.columns, out.nrows_dev,
                                  out.capacity, live=out.live & keep,
                                  shard_spec=out.shard_spec)
                dropped.append(1)
            yield out

    sound = drive(sut.Engine)
    assert sound["correct"] is True, sound["checks"]
    monkeypatch.setattr(TpuShuffleExchangeExec, "exchanged", dropping)
    result = drive(sut.Engine)
    assert dropped
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["q3.exact_mismatches"]["value"] > 0


def hand_made_run(**over):
    """Two traced Q3 queries on four chips: `trace_reduce` has divided
    every operation's seconds by the chips already."""
    run = {
        "device": {"kind": "TPU v5 lite"},
        "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 800e9}},
        "scan_bytes_per_query": {"q3": 1.6e9},
        "queries": [
            {"id": "q3", "traced": True,
             "record": {"phasesS": {"exchangeS": 0.002}}},
            {"id": "q3", "traced": True,
             "record": {"phasesS": {"exchangeS": 0.006}}},
            {"id": "q3", "traced": False,
             "record": {"phasesS": {"exchangeS": 0.004}}}],
        "trace": {"chips": 4, "queries": 2, "busy_s": 1.0, "window_s": 2.0,
                  "device_ops": [
                      ["jit_mesh_exchange %all-to-all.1 all-to-all s64[8]",
                       0.003],
                      ["jit_mesh_exchange %fusion.2 fusion s32[8]", 0.001],
                      ["jit_join_chips_probe %fusion.2 fusion s32[8]",
                       0.010],
                      ["jit_join_chips_gather %fusion.9 fusion s32[8]",
                       0.006],
                      ["jit_agg_sorted %sort.1 sort s32[8]", 0.5]]},
    }
    run.update(over)
    return run


def test_each_reader_gives_a_number_on_a_hand_made_run():
    run = hand_made_run()
    assert exchange_ms_per_query.read(run) == pytest.approx(4.0)
    # 0.003 + 0.001 s over two queries
    assert exchange_device_ms_per_query.read(run) == pytest.approx(2.0)
    # 2 x 1.6 GB over 4 chips x 800 GB/s = 1 ms, over 0.020 s
    assert costs_mesh.mesh_least_seconds(run, ["q3", "q3"]) \
        == pytest.approx(0.001)
    assert mesh_join_roofline.read(run) == pytest.approx(5.0)


def test_each_reader_gives_nothing_or_zero_where_its_input_is_absent():
    untraced = hand_made_run(trace=None)
    assert exchange_device_ms_per_query.read(untraced) is None
    assert mesh_join_roofline.read(untraced) is None
    # a program that records no such phase (the parent of this cell)
    no_phase = hand_made_run(queries=[
        {"id": "q3", "traced": True, "record": {"phasesS": {}}}])
    assert exchange_ms_per_query.read(no_phase) is None
    # a trace in which neither the exchange nor a join program ran
    nothing = hand_made_run()
    nothing["trace"] = dict(nothing["trace"], device_ops=[
        ["jit_agg_sorted %sort.1 sort s32[8]", 0.5]])
    assert exchange_device_ms_per_query.read(nothing) == 0.0
    assert mesh_join_roofline.read(nothing) == 0.0
