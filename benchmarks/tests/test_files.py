"""The cell `q1-sf5-parquet` and the harness's `storage` arm, through the
harness's own `run_cell` on the CPU backend at a tiny scale (no timing
claimed): `q1-sf1-parquet` is the same deployment at a fifth of the
rows; a sound run of the file-backed cell is correct and every query
reads the files; the files read back with pyarrow alone are the
generator's table value for value and type for type, and each part file
and the directory are synced to the disk in set-up; the float32 control
is not correct; the faults the cell can have come out wrong (a part file
deleted after `register`, an engine that answers from a copy it kept);
a configuration WITHOUT `storage` makes the same calls in the same order
as before the arm (the guard that no accepted cell's path moved); the
file cells report the rate and the tail under the bounds every cell has,
the steady cells besides under their own; and every name `BENCHMARK.json` and `limits/` give names a cell
or a configuration that exists.

    python3 -m pytest benchmarks/tests -q        (or benchmarks/selfcheck.py)
"""

import glob
import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run as bench_run  # noqa: E402
from benchmarks import sut  # noqa: E402
from benchmarks.datagen import tpch  # noqa: E402
from benchmarks.tests.test_correct import (  # noqa: E402
    BENCH, CONTROL_SCALE, CPU_DEVICE, TINY_SCALE, reference_engine)

CELL = "q1-sf5-parquet"
SCAN_READERS = ("scan_ms_per_query", "scan_upload_ms_per_query",
                "scan_decode_wait_ms_per_query", "scan_rows_per_query")


def cell_config(scale=TINY_SCALE):
    cell, config, mix, limits = bench_run.resolve_cell(BENCH, CELL)
    return cell, dict(config, scale_factor=scale), mix, limits


def drive(engine_factory, seed=2 ** 31 + 33, scale=TINY_SCALE, seconds=0.3):
    cell, config, mix, limits = cell_config(scale)
    return bench_run.run_cell(
        cell, config, mix, limits, BENCH, seed, seconds, 0, CPU_DEVICE,
        engine_factory, memory_reader=lambda: {"in_use": 1, "peak": 1})


def read_scan_metrics(run):
    return {name: importlib.import_module(
        f"benchmarks.layer_metrics.{name}").read(run) for name in SCAN_READERS}


def keeping_records():
    """The engine, each query's slim record kept."""

    class Keeping(sut.Engine):
        records = []

        def query(self, text, annotate=None):
            answer, record = super().query(text, annotate)
            Keeping.records.append(record)
            return answer, record

    return Keeping


def test_the_configuration_is_the_deployment_the_entry_names():
    from spark_rapids_tpu.conf import BATCH_SIZE_BYTES, PARQUET_READER_TYPE
    from spark_rapids_tpu.io.filecache import FILECACHE_ENABLED
    cell, config, _mix, _limits = bench_run.resolve_cell(BENCH, CELL)
    assert "session_conf" not in config and "batches" not in config
    assert config["storage"] == {"lineitem": {
        "format": "parquet", "files": 8, "compression": "snappy",
        "row_groups_per_file": 2}}
    assert config["tables"]["lineitem"] == list(tpch.COLUMN_TYPES["lineitem"])
    assert config["scale_factor"] == 5
    assert cell["chips"] == 1 and list(config["chips"]) == ["1"]
    assert "read" in config["guarantees"]
    assert set(config["reduced"]) == {"scale_factor", "tables"}
    entry = {c["name"]: c for c in BENCH["configs"]}[config["name"]]
    assert entry["reduced"] == list(config["reduced"])
    assert len(entry["source"]) <= 200
    # the shipped settings the source names
    assert BATCH_SIZE_BYTES.default == 1 << 30
    assert PARQUET_READER_TYPE.default == "AUTO"
    assert FILECACHE_ENABLED.default is False


def test_the_sf1_file_cell_is_the_same_deployment_at_its_own_scale():
    """`q1-sf1-parquet` keeps the configuration it was accepted with: the
    SF5 file cell's deployment, guarantees and chip at a fifth of the
    rows, one row group a 29 MB file."""
    small, small_config, _mix, _limits = bench_run.resolve_cell(
        BENCH, "q1-sf1-parquet")
    large, large_config, _mix, _limits = bench_run.resolve_cell(BENCH, CELL)
    assert small["traffic"] == large["traffic"]
    assert small["chips"] == large["chips"] == 1
    assert small_config["scale_factor"] == 1
    assert small_config["storage"]["lineitem"] == dict(
        large_config["storage"]["lineitem"], row_groups_per_file=1)
    differ = {"name", "source", "scale_factor", "storage", "reduced",
              "assumed"}
    assert set(small_config) == set(large_config)
    for key in set(small_config) - differ:
        assert small_config[key] == large_config[key], key
    assert set(small_config["reduced"]) == set(large_config["reduced"])


def test_sound_run_is_correct_and_every_query_reads_the_files():
    engine = keeping_records()
    result = drive(engine)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert result["setup_split_s"]["landing_s"] < 0.1  # nothing is landed
    # the cell's rate and tail under the bounds every cell has, and no
    # steady cell's
    assert set(result["metrics"]) == {"rows_per_s", "query_p95_s", "setup_s"}
    _cell, config, _mix, _limits = cell_config()
    rows = tpch.generate(config, 2 ** 31 + 33)["lineitem"]["num_rows"]
    run = {"queries": [{"record": r} for r in engine.records]}
    read = read_scan_metrics(run)
    assert read["scan_rows_per_query"] == rows
    assert all(value > 0 for value in read.values()), read
    read = read_scan_metrics({"queries": [{"record": engine.records[-1]}]})
    assert read["scan_upload_ms_per_query"] \
        + read["scan_decode_wait_ms_per_query"] \
        == pytest.approx(read["scan_ms_per_query"])
    for record in engine.records:
        assert record["scan"]["scanBatches"] == 8
        assert record["transferS"] > 0
        assert not sut.off_device_path(record)


def test_records_without_a_file_scan_give_the_readers_nothing():
    run = {"queries": [{"record": {"wallS": 1.0}}, {"error": "no answer"}]}
    assert read_scan_metrics(run) == dict.fromkeys(SCAN_READERS)


def test_the_files_hold_the_generators_table_type_for_type(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq
    _cell, config, _mix, _limits = cell_config()
    table = tpch.generate(config, 2 ** 31 + 34)["lineitem"]
    spec = config["storage"]["lineitem"]
    paths = sut.write_parquet_parts(table, str(tmp_path), spec)
    assert paths == sorted(glob.glob(str(tmp_path / "*")))
    assert len(paths) == 8
    assert all(os.path.basename(p).startswith(f"part-{i:05d}-")
               and p.endswith(".snappy.parquet") for i, p in enumerate(paths))
    files = [pq.ParquetFile(p) for p in paths]
    for f in files:
        assert f.metadata.num_row_groups == 2
        assert f.metadata.row_group(0).column(0).compression == "SNAPPY"
        halves = [f.metadata.row_group(g).num_rows for g in (0, 1)]
        assert abs(halves[0] - halves[1]) <= 1
    sizes = [f.metadata.num_rows for f in files]
    assert sum(sizes) == table["num_rows"] and max(sizes) - min(sizes) <= 1
    back = pa.concat_tables([f.read() for f in files])  # in row order
    arrow_types = {"long": pa.int64(), "int": pa.int32(),
                   "double": pa.float64(), "date": pa.date32(),
                   "string": pa.string(), "text": pa.string()}
    physical = {"long": "INT64", "int": "INT32", "double": "DOUBLE",
                "date": "INT32", "string": "BYTE_ARRAY", "text": "BYTE_ARRAY"}
    assert back.column_names == list(table["columns"])
    for i, (name, col) in enumerate(table["columns"].items()):
        got = back.column(name)
        assert got.type == arrow_types[col.type], name
        assert files[0].schema.column(i).physical_type \
            == physical[col.type], name
        assert got.null_count == 0
        if col.type in ("string", "text"):
            assert got.to_pylist() == col.strings().tolist(), name
        elif col.type == "double":  # to the bit
            assert np.array_equal(
                got.to_numpy().view(np.int64),
                np.ascontiguousarray(col.values, np.float64).view(np.int64))
        elif col.type == "date":  # as days since 1970-01-01
            assert np.array_equal(
                got.cast(pa.int32()).to_numpy(), col.values), name
        else:
            assert np.array_equal(got.to_numpy(), col.values), name
    assert files[0].schema.column(10).logical_type.type == "DATE"
    assert files[0].schema.column(15).logical_type.type == "STRING"


def test_every_part_file_and_then_the_directory_is_synced(
        tmp_path, monkeypatch):
    """No dirty page of the files is left for the kernel to write back
    during the timed window."""
    synced = []
    real_fsync = os.fsync

    def counting_fsync(fd):
        synced.append(os.fstat(fd).st_ino)
        real_fsync(fd)

    monkeypatch.setattr(os, "fsync", counting_fsync)
    _cell, config, _mix, _limits = cell_config(scale=0.002)
    table = tpch.generate(config, 2 ** 31 + 35)["lineitem"]
    paths = sut.write_parquet_parts(
        table, str(tmp_path), config["storage"]["lineitem"])
    assert len(paths) == 8
    assert sorted(synced[:-1]) == sorted(os.stat(p).st_ino for p in paths)
    assert synced[-1] == os.stat(tmp_path).st_ino


def test_a_steady_cell_reports_its_rate_and_tail_under_both_bounds():
    """`rows_per_s.steady` and `query_p95_s.steady` are `rows_per_s` and
    `query_p95_s` to the bit, reported in the cells their `workloads`
    lists and nowhere else; every cell reports the unqualified three."""
    run = {"queries": [{"id": "q1", "answer": 1, "latency_s": s / 10}
                       for s in range(1, 21)],
           "rows_per_query": {"q1": 1000}, "window_s": 4.0,
           "setup": {"setup_s": 9.0}}
    steady = {m["name"]: set(m["workloads"]) for m in BENCH["end_to_end"]
              if "." in m["name"]}
    assert set(steady) == {"rows_per_s.steady", "query_p95_s.steady"}
    assert all("workloads" not in m for m in BENCH["end_to_end"]
               if "." not in m["name"])
    for cell in BENCH["workloads"]:
        metrics = bench_run.end_to_end_metrics(
            BENCH, dict(run, cell=cell))
        held = {n for n, cells in steady.items() if cell["name"] in cells}
        assert set(metrics) == {"rows_per_s", "query_p95_s", "setup_s"} | held
        assert metrics["rows_per_s"]["value"] == 20 * 1000 / 4.0
        for name in held:
            assert metrics[name] == metrics[name.split(".")[0]]
    assert CELL not in steady["rows_per_s.steady"]
    assert "q1-sf1-parquet" not in steady["rows_per_s.steady"]


def test_every_name_the_benchmark_gives_exists():
    """Each per-layer and end-to-end `workloads` list, each limits file
    and each configuration entry names a cell or a configuration that
    exists, so that no retirement leaves a name behind."""
    cells = {w["name"]: w for w in BENCH["workloads"]}
    configs = {c["name"]: c for c in BENCH["configs"]}
    for metric in BENCH["per_layer"] + BENCH["end_to_end"]:
        assert set(metric.get("workloads", ())) <= set(cells), metric["name"]
    limits = {os.path.basename(p)[:-len(".json")] for p in glob.glob(
        os.path.join(ROOT, "benchmarks", "limits", "*.json"))}
    assert limits == set(cells)
    assert {w["config"] for w in cells.values()} == set(configs)
    files = {os.path.relpath(p, ROOT) for p in glob.glob(
        os.path.join(ROOT, "benchmarks", "configs", "*.json"))}
    assert {c["file"] for c in configs.values()} == files
    for name, entry in configs.items():
        with open(os.path.join(ROOT, entry["file"])) as f:
            assert json.load(f)["name"] == name


@pytest.mark.parametrize("seed", [11, 2 ** 31 + 12, 2 ** 31 + 13])
def test_float32_control_is_not_correct(seed):
    result = drive(reference_engine(CELL, np.float32), seed=seed,
                   scale=CONTROL_SCALE)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["q1.exact_mismatches"]["value"] == 0


def one_part_gone(after_queries):
    """The engine, one of its part files deleted once it has answered
    `after_queries` queries (0: straight after `register`)."""

    class OnePartGone(sut.Engine):
        answered = 0

        def _delete(self):
            (directory,) = self._file_dirs
            parts = sorted(glob.glob(os.path.join(directory, "*")))
            if len(parts) == 8:
                os.remove(parts[3])

        def register(self, tables):
            super().register(tables)
            if after_queries == 0:
                self._delete()

        def query(self, text, annotate=None):
            if self.answered == after_queries:
                self._delete()
            self.answered += 1
            return super().query(text, annotate)

    return OnePartGone


def test_a_part_file_deleted_after_register_is_not_correct():
    """Gone before the first query, the run ends in warm-up with the
    engine's error and prints no result; gone after the window's first
    query (warm-up makes two calls), every later query fails and the run
    is not correct."""
    with pytest.raises(FileNotFoundError):
        drive(one_part_gone(0))
    result = drive(one_part_gone(3), seconds=2.5)
    assert result["correct"] is False, result["checks"]
    assert result["attempted"] >= 2
    assert result["failed"] == result["attempted"] - 1
    assert result["checks"]["answers_missing"]["value"] == result["failed"]


def test_an_answer_kept_from_the_first_query_counts_as_failed():
    """An engine that answers every later query from a copy it kept: the
    answers are right, but no row was pulled through the file scan, so
    each such query counts as failed."""

    class Keeps(sut.Engine):
        kept = None

        def query(self, text, annotate=None):
            if self.kept is None:
                self.kept = super().query(text, annotate)
                return self.kept
            answer, record = self.kept
            # what a kept result's record looks like: its plan ran no scan
            record = {k: v for k, v in record.items() if k != "scan"}
            return answer, self.mark_files_read(record)

    result = drive(Keeps)
    assert result["correct"] is True, result["checks"]  # the answers hold
    assert result["attempted"] >= 1
    assert result["failed"] == result["attempted"]

    class ScansNothing(sut.Engine):
        """`scanRows` 0 on every query after the first."""

        queries = 0

        def query(self, text, annotate=None):
            answer, record = super().query(text, annotate)
            self.queries += 1
            if self.queries > 1:
                record["scan"]["scanRows"] = 0
                self.mark_files_read(record)
            return answer, record

    result = drive(ScansNothing)
    assert result["failed"] == result["attempted"] >= 1


#: every call `register` and `land` make on the session, a configuration
#: without `storage`, as they stood before the arm (PR 32's tree)
GOLDEN_CALLS = [
    ("create_dataframe", "lineitem", 16, 3),
    ("create_or_replace_temp_view", "lineitem"),
    ("table", "lineitem"),
]


def test_a_configuration_without_storage_registers_and_lands_as_before(
        monkeypatch):
    calls = []

    class StubFrame:
        def __init__(self, name=None):
            self.plan = ("plan", name)

        def create_or_replace_temp_view(self, name):
            calls.append(("create_or_replace_temp_view", name))

    class StubSession:
        conf = object()
        last_event_record = None

        def create_dataframe(self, host, num_batches=None):
            calls.append(("create_dataframe", "lineitem", num_batches,
                          len(host.columns)))
            return StubFrame()

        def read_parquet(self, *paths, **options):
            calls.append(("read_parquet",) + paths)
            return StubFrame()

        def table(self, name):
            calls.append(("table", name))
            return StubFrame(name)

    class StubExec:
        def execute(self):
            return iter(())

    class StubExecutable:
        tpu_exec = StubExec()

    import spark_rapids_tpu.overrides.rules as rules
    import spark_rapids_tpu.session as session_module
    monkeypatch.setattr(session_module, "TpuSession",
                        lambda conf: StubSession())
    monkeypatch.setattr(rules, "apply_overrides",
                        lambda plan, conf: (StubExecutable(), None))
    _cell, config, _mix, _limits = bench_run.resolve_cell(BENCH, "q1-sf5")
    assert "storage" not in config
    config = dict(config, scale_factor=0.002, tables={
        "lineitem": ["l_quantity", "l_returnflag", "l_shipdate"]})
    tables = tpch.generate(config, 3)
    engine = sut.Engine(config)
    engine.register(tables)
    assert list(engine._host_tables) == ["lineitem"]
    assert engine._file_dirs == []
    assert engine.land() == 0  # the stub's exec yields no batch
    assert calls == GOLDEN_CALLS
