"""Column pruning reaches the file scan (overrides/pruning.py ``_visit``'s
leaf case + io/common.py ``FileScanNode.narrowed``): a query over files
decodes and uploads only the columns it reads, through a narrowed COPY of
the scan node a DataFrame or temp view shares. Held here: answers equal to
the unpruned scan's in every format, the reader asked for exactly the kept
names, the shared node left as it was, ``count(*)`` off the string columns,
Hive partition columns, the input_file_name() columns, pushdown filters,
the file cache, Delta and Iceberg, and the plans of the benchmark's Q1 and
Q3 over cached tables unchanged."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.orc as po
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.execs.basic import TpuCoalesceExec, TpuFileScanExec
from spark_rapids_tpu.io.common import FileScanNode
from spark_rapids_tpu.ops.expr import col, lit
from spark_rapids_tpu.overrides.pruning import prune_plan
from spark_rapids_tpu.overrides.rules import apply_overrides
from spark_rapids_tpu.session import TpuSession
from tests.avro_util import write_avro

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N = 300
#: the files' columns: two strings a query here never reads among them
SCHEMA = [("k", T.LONG), ("s", T.STRING), ("v", T.DOUBLE), ("i", T.INT),
          ("t", T.STRING), ("w", T.DOUBLE)]
NAMES = [n for n, _ in SCHEMA]


def _columns(seed):
    rng = np.random.default_rng(seed)
    return {
        "k": rng.integers(0, 5, N).astype(np.int64),
        "s": [f"s{j % 7}" for j in range(N)],
        "v": np.round(rng.random(N) * 100, 3),
        "i": rng.integers(0, 100, N).astype(np.int32),
        "t": [f"text-{seed}-{j}" for j in range(N)],
        "w": np.round(rng.random(N), 3),
    }


def _arrow(seed):
    c = _columns(seed)
    return pa.table({n: pa.array(c[n]) for n in NAMES})


def _host(seed):
    c = _columns(seed)
    cols = []
    for n, dt in SCHEMA:
        if isinstance(dt, T.StringType):
            data = np.empty(N, dtype=object)
            data[:] = c[n]
        else:
            data = np.asarray(c[n], dtype=dt.np_dtype)
        cols.append(HostColumn(dt, data))
    return HostTable(NAMES, cols)


AVRO_SCHEMA = {"type": "record", "name": "r", "fields": [
    {"name": "k", "type": "long"}, {"name": "s", "type": "string"},
    {"name": "v", "type": "double"}, {"name": "i", "type": "int"},
    {"name": "t", "type": "string"}, {"name": "w", "type": "double"}]}


def _write(fmt, directory, files=2):
    """``files`` files of SCHEMA under ``directory``; returns the reader:
    session -> DataFrame."""
    os.makedirs(directory, exist_ok=True)
    for f in range(files):
        path = os.path.join(directory, f"part-{f}.{fmt}")
        if fmt == "parquet":
            pq.write_table(_arrow(f), path, row_group_size=100)
        elif fmt == "orc":
            po.write_table(_arrow(f), path)
        elif fmt == "csv":
            from spark_rapids_tpu.io.csv import write_csv
            write_csv(_host(f), os.path.join(directory, f"d{f}"))
        elif fmt == "json":
            from spark_rapids_tpu.io.json import write_json
            write_json(_host(f), os.path.join(directory, f"d{f}"))
        elif fmt == "hive_text":
            from spark_rapids_tpu.io.hive_text import write_hive_text
            write_hive_text(_host(f), os.path.join(directory, f"d{f}"))
        elif fmt == "avro":
            c = _columns(f)
            rows = [{n: (c[n][j].item() if hasattr(c[n][j], "item")
                         else c[n][j]) for n in NAMES} for j in range(N)]
            write_avro(path, AVRO_SCHEMA, rows, rows_per_block=128)
    if fmt == "hive_text":
        return lambda s, **kw: s.read_hive_text(directory, schema=SCHEMA,
                                                **kw)
    return lambda s, **kw: getattr(s, f"read_{fmt}")(directory, **kw)


FORMATS = ["parquet", "orc", "csv", "json", "avro", "hive_text"]


@pytest.fixture(scope="module")
def unpruned():
    """The device engine with the pass off: scans read every column."""
    return TpuSession(
        {"spark.rapids.tpu.sql.columnPruning.enabled": "false"})


def _walk(e):
    """Every exec and node of an executed tree."""
    yield e
    for c in getattr(e, "children", ()):
        yield from _walk(c)
    for attr in ("source", "tpu_exec", "cpu_node", "scan_node"):
        nxt = getattr(e, attr, None)
        if nxt is not None:
            yield from _walk(nxt)


def _scan_execs(session):
    """The file scan execs of the session's last executed tree."""
    return [e for e in _walk(session._last_executable)
            if isinstance(e, TpuFileScanExec)]


def _scan_nodes(session):
    """Its file scan nodes, wherever they run: under a TpuFileScanExec, or
    as a host scan under HostToDevice (Avro has no device scan exec)."""
    return [e for e in _walk(session._last_executable)
            if isinstance(e, FileScanNode)]


def _subset_query(df):
    return df.filter(col("i") < lit(60)).group_by("k").agg(
        F.sum(col("v")).alias("sv"), F.count().alias("n"))


def _same(a, b):
    a, b = sorted(a), sorted(b)
    assert len(a) == len(b)
    for ra, rb in zip(a, b):
        for x, y in zip(ra, rb):
            if isinstance(x, float):
                assert x == pytest.approx(y, rel=1e-6)
            else:
                assert x == y


# -- every format ------------------------------------------------------------

@pytest.mark.parametrize("fmt", FORMATS)
def test_subset_query_gives_the_unpruned_answer(tmp_path, session,
                                                cpu_session, unpruned, fmt):
    read = _write(fmt, str(tmp_path / fmt))
    got = _subset_query(read(session)).collect()
    nodes = _scan_nodes(session)
    assert len(nodes) == 1 and nodes[0].columns == ["k", "v", "i"]
    assert (nodes[0].read_width(), nodes[0].full_width()) == (3, 6)
    scans = _scan_execs(session)
    if fmt != "avro":  # a host scan: no exec, no counters
        assert len(scans) == 1 and scans[0].scan_node is nodes[0]
        assert scans[0].metrics["scanColumnsRead"] == 3
        assert scans[0].metrics["scanColumnsPruned"] == 3
    _same(got, _subset_query(read(cpu_session)).collect())
    _same(got, _subset_query(read(unpruned)).collect())
    assert _scan_nodes(unpruned)[0].columns is None
    if fmt != "avro":
        full = _scan_execs(unpruned)
        assert full[0].metrics["scanColumnsRead"] == 6
        assert full[0].metrics["scanColumnsPruned"] == 0


@pytest.mark.parametrize("fmt", FORMATS)
def test_narrowed_node_decodes_only_the_kept_columns(tmp_path, session, fmt):
    """What the reader hands up, format by format: the host batch of a
    narrowed node holds the kept columns and nothing else (for the text
    and row formats, whose bytes are parsed whole, that is the conversion
    to host columns that is skipped)."""
    node = _write(fmt, str(tmp_path / fmt))(session).plan
    narrow = node.narrowed(["k", "w"])
    for batch in narrow.execute_cpu():
        assert list(batch.names) == ["k", "w"]
        assert [type(c.dtype) for c in batch.columns] == \
            [T.LongType, T.DoubleType]
    assert sum(b.num_rows for b in narrow.execute_cpu()) == 2 * N


@pytest.mark.parametrize("mode", ["PERFILE", "COALESCING", "MULTITHREADED"])
def test_parquet_reader_is_asked_for_exactly_the_kept_names(
        tmp_path, session, monkeypatch, mode):
    read = _write("parquet", str(tmp_path / "p"))
    asked = []
    real_read_table = pq.read_table
    real_read_row_group = pq.ParquetFile.read_row_group

    def spy_table(path, columns=None, **kw):
        asked.append(list(columns))
        return real_read_table(path, columns=columns, **kw)

    def spy_group(self, i, columns=None, **kw):
        asked.append(list(columns))
        return real_read_row_group(self, i, columns=columns, **kw)

    monkeypatch.setattr(pq, "read_table", spy_table)
    monkeypatch.setattr(pq.ParquetFile, "read_row_group", spy_group)
    _subset_query(read(session, reader_type=mode)).collect()
    assert asked and all(a == ["k", "v", "i"] for a in asked)


def test_orc_reader_is_asked_for_exactly_the_kept_names(
        tmp_path, session, monkeypatch):
    read = _write("orc", str(tmp_path / "o"))
    asked = []
    real = po.ORCFile.read

    def spy(self, columns=None, **kw):
        asked.append(list(columns))
        return real(self, columns=columns, **kw)

    monkeypatch.setattr(po.ORCFile, "read", spy)
    _subset_query(read(session)).collect()
    assert asked and all(a == ["k", "v", "i"] for a in asked)


# -- the shared node ---------------------------------------------------------

def test_two_queries_over_one_view_each_read_theirs(tmp_path, session):
    read = _write("parquet", str(tmp_path / "p"))
    df = read(session)
    node = df.plan
    df.create_or_replace_temp_view("scan_pruning_view")
    before = list(node.output_schema())
    a = session.sql("select sum(v) from scan_pruning_view").collect()
    assert _scan_execs(session)[0].scan_node.columns == ["v"]
    b = session.sql(
        "select s, count(*) from scan_pruning_view group by s").collect()
    assert _scan_execs(session)[0].scan_node.columns == ["s"]
    assert node.columns is None
    assert node.output_schema() == before and len(before) == 6
    assert a[0][0] == pytest.approx(
        sum(_columns(0)["v"]) + sum(_columns(1)["v"]))
    assert sorted(r[0] for r in b) == [f"s{j}" for j in range(7)]
    # and the whole table is still there for the query that reads it
    assert len(df.collect()[0]) == 6
    assert _scan_execs(session)[0].scan_node is node


def test_narrowed_copy_opens_no_file_to_plan(tmp_path, session, monkeypatch):
    node = _write("parquet", str(tmp_path / "p"))(session).plan
    node.output_schema()

    def no_io(*a, **kw):
        raise AssertionError("planning opened a file")

    monkeypatch.setattr(pq, "read_schema", no_io)
    monkeypatch.setattr(pq, "ParquetFile", no_io)
    monkeypatch.setattr(os, "walk", no_io)
    narrow = node.narrowed(["v", "t"])
    assert narrow.output_schema() == [("v", T.DOUBLE), ("t", T.STRING)]
    assert narrow.data_schema == narrow.output_schema()
    assert narrow.paths is node.paths and narrow.conf is node.conf
    assert narrow.full_width() == 6 and narrow.read_width() == 2
    assert narrow.describe() == \
        "ParquetScanNode[2 files, AUTO, 2 of 6 columns: v, t]"
    assert node.describe() == "ParquetScanNode[2 files, AUTO]"


def test_user_columns_narrow_within_themselves(tmp_path, session,
                                               cpu_session):
    read = _write("parquet", str(tmp_path / "p"))

    def q(s):
        return s.read_parquet(str(tmp_path / "p"),
                              columns=["k", "s", "v"]) \
            .group_by("k").agg(F.sum(col("v")).alias("sv"))
    _same(q(session).collect(), q(cpu_session).collect())
    scan = _scan_execs(session)[0]
    assert scan.scan_node.columns == ["k", "v"]
    assert scan.metrics["scanColumnsRead"] == 2
    assert scan.metrics["scanColumnsPruned"] == 4
    assert read is not None


@pytest.mark.parametrize("fmt", ["parquet", "orc", "csv", "json"])
def test_user_columns_out_of_file_order(tmp_path, session, cpu_session,
                                        fmt):
    """A batch is ordered as the node's output schema says, whatever order
    the reader hands columns up in: a narrowed copy keeps the node's."""
    read = _write(fmt, str(tmp_path / fmt), files=1)
    c = _columns(0)
    for s in (session, cpu_session):
        df = read(s, columns=["v", "s", "k"])
        assert df.columns == ["v", "s", "k"]
        assert sorted(df.collect()) == sorted(
            zip(c["v"].tolist(), c["s"], c["k"].tolist()))
    got = read(session, columns=["v", "s", "k"]).select(
        col("k"), col("v")).collect()
    assert sorted(got) == sorted(zip(c["k"].tolist(), c["v"].tolist()))
    assert _scan_nodes(session)[0].columns == ["v", "k"]


def test_explain_names_the_kept_columns(tmp_path, session):
    df = _subset_query(_write("parquet", str(tmp_path / "p"))(session))
    assert "ParquetScanNode[2 files, AUTO, 3 of 6 columns: k, v, i]" \
        in df.explain()


# -- count(*) ----------------------------------------------------------------

def _string_first(directory):
    os.makedirs(directory, exist_ok=True)
    pq.write_table(pa.table({
        "s": pa.array([f"s{j}" for j in range(50)]),
        "big": pa.array(np.arange(50, dtype=np.int64)),
        "small": pa.array(np.arange(50, dtype=np.int32)),
        "t": pa.array([f"t{j}" for j in range(50)])}),
        os.path.join(directory, "f.parquet"))


def test_count_star_decodes_no_string_column(tmp_path, session):
    _string_first(str(tmp_path / "c"))
    df = session.read_parquet(str(tmp_path / "c"))
    assert df.agg(F.count().alias("n")).collect() == [(50,)]
    scan = _scan_execs(session)[0]
    assert scan.scan_node.columns == ["small"]  # the narrowest fixed width
    assert scan.metrics["scanColumnsRead"] == 1
    assert scan.metrics["scanColumnsPruned"] == 3


def test_count_star_under_a_filter_decodes_no_string_column(tmp_path,
                                                            session):
    _string_first(str(tmp_path / "c"))
    df = session.read_parquet(str(tmp_path / "c"))
    got = df.filter(col("big") >= lit(40)).agg(F.count().alias("n"))
    assert got.collect() == [(10,)]
    assert _scan_execs(session)[0].scan_node.columns == ["big", "small"]


# -- Hive partition columns --------------------------------------------------

@pytest.fixture
def partitioned(tmp_path):
    """p=1/, p=2/ with 40 and 60 rows of (s, a, b)."""
    root = str(tmp_path / "hive")
    for p, n in ((1, 40), (2, 60)):
        d = os.path.join(root, f"p={p}")
        os.makedirs(d)
        pq.write_table(pa.table({
            "s": pa.array([f"s{j % 3}" for j in range(n)]),
            "a": pa.array(np.arange(n, dtype=np.int64)),
            "b": pa.array(np.arange(n, dtype=np.float64))}),
            os.path.join(d, "f.parquet"), row_group_size=25)
    return root


@pytest.mark.parametrize("mode", ["PERFILE", "COALESCING", "MULTITHREADED"])
@pytest.mark.parametrize("case", ["kept", "pruned", "alone"])
def test_hive_partition_column(partitioned, session, cpu_session, case, mode):
    def q(s):
        df = s.read_parquet(partitioned, reader_type=mode)
        if case == "kept":
            return df.group_by("p").agg(F.sum(col("a")).alias("sa"))
        if case == "pruned":
            return df.agg(F.sum(col("b")).alias("sb"))
        return df.group_by("p").agg(F.count().alias("n"))
    got = q(session).collect()
    _same(got, q(cpu_session).collect())
    scan = _scan_execs(session)[0]
    want = {"kept": ["a", "p"], "pruned": ["b"], "alone": ["p"]}[case]
    assert scan.scan_node.columns == want
    assert scan.metrics["scanColumnsRead"] == len(want)
    assert scan.metrics["scanColumnsPruned"] == 4 - len(want)
    if case == "alone":
        assert sorted(got) == [(1, 40), (2, 60)]


def test_count_star_over_partitions_reads_the_partition_column(
        partitioned, session):
    assert session.read_parquet(partitioned).agg(
        F.count().alias("n")).collect() == [(100,)]
    assert _scan_execs(session)[0].scan_node.columns == ["p"]


@pytest.mark.parametrize("fmt", ["orc", "csv", "json"])
def test_partition_column_alone_in_other_formats(tmp_path, session,
                                                 cpu_session, fmt):
    root = str(tmp_path / "hive")
    for p in (1, 2):
        _write(fmt, os.path.join(root, f"p={p}"), files=1)

    def q(s):
        return getattr(s, f"read_{fmt}")(root).group_by("p").agg(
            F.count().alias("n"))
    assert sorted(q(session).collect()) == [(1, N), (2, N)] == \
        sorted(q(cpu_session).collect())
    assert _scan_execs(session)[0].scan_node.columns == ["p"]


# -- input_file_name() -------------------------------------------------------

def test_input_file_name_beside_a_pruned_scan(tmp_path, session,
                                              cpu_session):
    read = _write("parquet", str(tmp_path / "p"), files=3)

    def q(s):
        return read(s).group_by(F.input_file_name().alias("f")).agg(
            F.sum(col("v")).alias("sv"))
    got = q(session).collect()
    _same(got, q(cpu_session).collect())
    assert len(got) == 3 and all(r[0].endswith(".parquet") for r in got)
    scan = _scan_execs(session)[0]
    assert scan.scan_node.columns == ["v"]
    assert scan.scan_node.provide_file_info
    assert scan.metrics["scanColumnsRead"] == 1
    assert scan.metrics["scanColumnsPruned"] == 5


def test_input_file_name_alone(tmp_path, session, cpu_session):
    read = _write("parquet", str(tmp_path / "p"), files=3)

    def q(s):
        return read(s).select(F.input_file_name().alias("f"))
    got = q(session).collect()
    assert sorted(got) == sorted(q(cpu_session).collect())
    assert len(got) == 3 * N and len(set(got)) == 3
    assert _scan_execs(session)[0].scan_node.columns == ["i"]


# -- pushdown filters --------------------------------------------------------

@pytest.mark.parametrize("mode", ["PERFILE", "COALESCING", "MULTITHREADED"])
def test_pushdown_filter_on_a_column_outside_the_kept_set(
        tmp_path, session, cpu_session, mode):
    _write("parquet", str(tmp_path / "p"))

    def q(s):
        return s.read_parquet(str(tmp_path / "p"), reader_type=mode,
                              filters=[("i", "<", 30)]) \
            .group_by("k").agg(F.sum(col("v")).alias("sv"),
                               F.count().alias("n"))
    got = q(session).collect()
    _same(got, q(cpu_session).collect())
    assert _scan_execs(session)[0].scan_node.columns == ["k", "v"]
    want = sum(int((np.asarray(_columns(f)["i"]) < 30).sum())
               for f in range(2))
    assert sum(r[2] for r in got) == want


def test_pushdown_filter_with_only_a_partition_column_read(
        partitioned, session, cpu_session):
    def q(s):
        return s.read_parquet(partitioned, filters=[("a", ">=", 30)]) \
            .group_by("p").agg(F.count().alias("n"))
    assert sorted(q(session).collect()) == [(1, 10), (2, 30)] == \
        sorted(q(cpu_session).collect())
    assert _scan_execs(session)[0].scan_node.columns == ["p"]


# -- the file cache ----------------------------------------------------------

@pytest.mark.parametrize("order", ["full_then_narrow", "narrow_then_full"])
def test_file_cache_keeps_narrowed_and_full_apart(tmp_path, order):
    from spark_rapids_tpu.io.filecache import FILE_CACHE
    _write("parquet", str(tmp_path / "p"))
    s = TpuSession({"spark.rapids.filecache.enabled": "true"})
    FILE_CACHE.clear()
    df = s.read_parquet(str(tmp_path / "p"))
    queries = [lambda: df.collect(),
               lambda: df.group_by("k").agg(
                   F.sum(col("w")).alias("sw")).collect()]
    if order == "narrow_then_full":
        queries.reverse()
    try:
        first = queries[0]()
        misses = FILE_CACHE.misses
        second = queries[1]()
        # another set of columns: nothing decoded for the first is served
        assert FILE_CACHE.misses == misses + 2
        hits = FILE_CACHE.hits
        again = queries[1]()
        assert FILE_CACHE.hits == hits + 2
        _same(second, again)
    finally:
        FILE_CACHE.clear()
    full, narrow = (first, second) if order == "full_then_narrow" \
        else (second, first)
    assert len(full) == 2 * N and len(full[0]) == 6
    sums = {}
    for r in full:
        sums[r[0]] = sums.get(r[0], 0.0) + r[5]
    _same(narrow, list(sums.items()))


# -- Delta and Iceberg -------------------------------------------------------

def test_delta_scan_is_narrowed(tmp_path, session, cpu_session):
    path = str(tmp_path / "delta")
    session.create_dataframe(_host(0)).write_delta(path)

    def q(s):
        return _subset_query(s.read_delta(path))
    _same(q(session).collect(), q(cpu_session).collect())
    scan = _scan_execs(session)[0]
    assert type(scan.scan_node).__name__ == "DeltaScanNode"
    assert scan.scan_node.columns == ["k", "v", "i"]
    assert scan.metrics["scanColumnsRead"] == 3
    assert scan.metrics["scanColumnsPruned"] == 3
    assert "3 of 6 columns: k, v, i" in scan.scan_node.describe()


def test_delta_partition_column_alone(tmp_path, session, cpu_session):
    path = str(tmp_path / "delta")
    session.create_dataframe(_host(0)).write_delta(path, partition_by=["k"])

    def q(s):
        return s.read_delta(path).group_by("k").agg(F.count().alias("n"))
    _same(q(session).collect(), q(cpu_session).collect())
    assert _scan_execs(session)[0].scan_node.columns == ["k"]


def test_iceberg_scan_is_narrowed_and_keeps_its_deletes(tmp_path, session,
                                                        cpu_session):
    from tests.iceberg_util import IcebergTableBuilder
    b = IcebergTableBuilder(str(tmp_path / "t"), _arrow(0).schema)
    f1 = b.add_data_file(_arrow(0))
    b.add_data_file(_arrow(1))
    b.add_position_deletes([(f1, 0), (f1, 1)])
    # by equality on "s" (field id 2)
    b.add_equality_deletes(pa.table({"s": pa.array(["s3"])}),
                           equality_ids=[2], sequence_number=2)
    b.commit()

    def q(s):
        return _subset_query(s.read_iceberg(str(tmp_path / "t")))
    _same(q(session).collect(), q(cpu_session).collect())
    scan = _scan_execs(session)[0]
    assert type(scan.scan_node).__name__ == "IcebergScanNode"
    # the equality delete's column is outside the kept set: still applied
    assert scan.scan_node.columns == ["k", "v", "i"]
    assert scan.metrics["scanColumnsPruned"] == 3
    total = session.read_iceberg(str(tmp_path / "t")).agg(
        F.count().alias("n")).collect()[0][0]
    keep = sum(1 for f in range(2) for j in range(N)
               if j % 7 != 3 and not (f == 0 and j < 2))
    assert total == keep


def test_iceberg_plans_without_reading_and_loads_its_deletes_once(
        tmp_path, session, monkeypatch):
    """Planning and explaining read no delete file; the first read loads
    them into the holder the shared node and every narrowed copy share."""
    from tests.iceberg_util import IcebergTableBuilder
    b = IcebergTableBuilder(str(tmp_path / "t"), _arrow(0).schema)
    f1 = b.add_data_file(_arrow(0))
    b.add_position_deletes([(f1, 0)])
    b.commit()
    df = session.read_iceberg(str(tmp_path / "t"))
    node = df.plan
    reads = []
    real = pq.read_table
    monkeypatch.setattr(
        pq, "read_table",
        lambda path, *a, **kw: reads.append(path) or real(path, *a, **kw))
    copy = node.narrowed(["k"])
    df.select(col("v")).explain()
    assert reads == [] and node._deletes == []
    assert copy._deletes is node._deletes
    assert df.select(col("k")).count() == N - 1
    assert df.select(col("v")).count() == N - 1
    deletes = [p for p in reads if "delete" in os.path.basename(p)]
    assert len(node._deletes) == 1 and len(deletes) == 1, reads


@pytest.mark.parametrize("mode", ["DROPMALFORMED", "FAILFAST"])
def test_csv_custom_floats_outside_permissive_opt_out(tmp_path, session,
                                                      cpu_session, mode):
    """Which rows DROPMALFORMED drops, and whether FAILFAST raises, depends
    on the float columns that are converted, so this scan is not narrowed:
    the same rows, or the same error, whatever the query reads."""
    path = str(tmp_path / "f.csv")
    with open(path, "w") as f:
        f.write("a,x\n1,1.5\n2,oops\n3,nan!\n")
    kw = dict(schema=[("a", T.LONG), ("x", T.DOUBLE)], mode=mode,
              nan_value="nan!")

    def q(s):
        return s.read_csv(path, **kw).agg(F.sum(col("a")).alias("sa"))
    if mode == "FAILFAST":
        for s in (session, cpu_session):
            with pytest.raises(ValueError, match="malformed float"):
                q(s).collect()
    else:
        assert q(session).collect() == q(cpu_session).collect() == [(4,)]
        assert _scan_execs(session)[0].scan_node.columns is None
    node = session.read_csv(path, **kw).plan
    assert node.narrowed(["a"]) is node


@pytest.mark.parametrize("select", ["b", "c", "a"])
def test_csv_ragged_rows_of_an_inferred_schema(tmp_path, session,
                                               cpu_session, unpruned, select):
    """PERMISSIVE null-fills a short row and cuts a long one by the FILE's
    column order, which the narrowed copy no longer has in ``data_schema``:
    it takes it from what the shared node discovered."""
    path = str(tmp_path / "f.csv")
    with open(path, "w") as f:
        f.write("a,b,c\n1,2,3\n4\n5,6\n7,8,9,10\n")

    def q(s):
        return s.read_csv(path).select(col(select)).collect()
    want = {"a": [1, 4, 5, 7], "b": [2, None, 6, 8],
            "c": [3, None, None, 9]}[select]
    key = lambda r: (r[0] is None, r[0])  # noqa: E731
    got = sorted(q(session), key=key)
    assert got == sorted(q(cpu_session), key=key) \
        == sorted(q(unpruned), key=key) \
        == sorted([(v,) for v in want], key=key)
    assert _scan_execs(session)[0].scan_node.columns == [select]
    assert _scan_execs(unpruned)[0].scan_node.columns is None


# -- the benchmark's plans ---------------------------------------------------

LINEITEM = [("l_orderkey", T.LONG), ("l_partkey", T.LONG),
            ("l_suppkey", T.LONG), ("l_linenumber", T.INT),
            ("l_quantity", T.DOUBLE), ("l_extendedprice", T.DOUBLE),
            ("l_discount", T.DOUBLE), ("l_tax", T.DOUBLE),
            ("l_returnflag", T.STRING), ("l_linestatus", T.STRING),
            ("l_shipdate", T.DATE), ("l_commitdate", T.DATE),
            ("l_receiptdate", T.DATE), ("l_shipinstruct", T.STRING),
            ("l_shipmode", T.STRING), ("l_comment", T.STRING)]
ORDERS = [("o_orderkey", T.LONG), ("o_custkey", T.LONG),
          ("o_orderstatus", T.STRING), ("o_totalprice", T.DOUBLE),
          ("o_orderdate", T.DATE), ("o_orderpriority", T.STRING),
          ("o_clerk", T.STRING), ("o_shippriority", T.INT),
          ("o_comment", T.STRING)]
CUSTOMER = [("c_custkey", T.LONG), ("c_name", T.STRING),
            ("c_address", T.STRING), ("c_nationkey", T.LONG),
            ("c_phone", T.STRING), ("c_acctbal", T.DOUBLE),
            ("c_mktsegment", T.STRING), ("c_comment", T.STRING)]
Q1_COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
              "l_returnflag", "l_linestatus", "l_shipdate"]


def _tpch_table(schema, n, seed=1):
    rng = np.random.default_rng(seed)
    cols = []
    for name, dt in schema:
        if isinstance(dt, T.StringType):
            vals = np.empty(n, dtype=object)
            vals[:] = ["BUILDING" if name == "c_mktsegment" else "ANR"[v]
                       for v in rng.integers(0, 3, n)]
            cols.append(HostColumn(dt, vals))
        elif isinstance(dt, T.DateType):
            cols.append(HostColumn(
                dt, rng.integers(8000, 10500, n).astype(np.int32)))
        elif isinstance(dt, T.DoubleType):
            cols.append(HostColumn(dt, rng.random(n)))
        else:
            cols.append(HostColumn(
                dt, rng.integers(0, 20, n).astype(dt.np_dtype)))
    return HostTable([n_ for n_, _ in schema], cols)


def _query_text(name, **params):
    with open(os.path.join(REPO, "benchmarks", "queries",
                           name + ".sql")) as f:
        text = f.read()
    for key, value in params.items():
        text = text.replace(f"[{key}]", str(value))
    return text


def _logical_tree(node):
    return [type(node).__name__, len(node.output_schema()),
            [_logical_tree(c) for c in node.children]]


def _exec_tree(e):
    kids = [_exec_tree(c) for c in getattr(e, "children", ())]
    for attr in ("source", "tpu_exec", "cpu_node"):
        nxt = getattr(e, attr, None)
        if nxt is not None:
            kids.append(_exec_tree(nxt))
    out = [type(e).__name__]
    if getattr(e, "columns", None) is not None:
        out.append(list(e.columns))
    return out + [kids]


#: the pruned plan and the converted tree of each statement over cached
#: tables, as PR 34's tree printed them (and as every cached cell of the
#: benchmark runs them): [class, width, children] and [class, (the
#: coalesce's columns,) children], as JSON
CACHED_PLANS = {
    "q1": (
        '["Sort", 10, [["Aggregate", 10, [["Project", 6, [["Filter", 7, '
        '[["Project", 7, [["LocalScan", 16, []]]]]]]]]]]]',
        '["DeviceToHost", [["TpuSortExec", [["TpuCoalesceExec", '
        '[["TpuHashAggregateExec", [["TpuCoalesceExec", [4, 5, 6, 7, 8, '
        '9, 10], [["TpuScanExec", []]]]]]]]]]]]',
    ),
    "q3": (
        '["TakeOrderedAndProject", 4, [["Project", 4, [["Aggregate", 4, '
        '[["Project", 5, [["Join", 6, [["Project", 3, [["Join", 5, '
        '[["Project", 1, [["Filter", 2, [["Project", 2, [["LocalScan", 8,'
        ' []]]]]]]], ["Filter", 4, [["Project", 4, [["LocalScan", 9, '
        '[]]]]]]]]]], ["Project", 3, [["Filter", 4, [["Project", 4, '
        '[["LocalScan", 16, []]]]]]]]]]]]]]]]]]',
        '["DeviceToHost", [["TpuTakeOrderedAndProjectExec", '
        '[["TpuProjectExec", [["TpuHashAggregateExec", '
        '[["TpuCoalesceExec", [1, 2, 3, 4, 5], [["TpuJoinExec", '
        '[["TpuBroadcastExchangeExec", [["TpuProjectExec", '
        '[["TpuJoinExec", [["TpuBroadcastExchangeExec", '
        '[["TpuProjectExec", [["TpuFilterExec", [["TpuProjectExec", '
        '[["TpuScanExec", []]]]]]]]]], ["TpuCoalesceExec", '
        '[["TpuFilterExec", [["TpuProjectExec", [["TpuScanExec", '
        '[]]]]]]]]]]]]]], ["TpuCoalesceExec", [["TpuProjectExec", '
        '[["TpuFilterExec", [["TpuProjectExec", [["TpuScanExec", '
        '[]]]]]]]]]]]]]]]]]]]]]]',
    ),
}
QUERY_PARAMS = {"q1": {"DELTA": 90},
                "q3": {"SEGMENT": "BUILDING", "DATE": "1995-03-15"}}


@pytest.fixture(scope="module")
def tpch_session():
    s = TpuSession()
    for name, schema, n in (("lineitem", LINEITEM, 400),
                            ("orders", ORDERS, 100),
                            ("customer", CUSTOMER, 30)):
        s.create_dataframe(_tpch_table(schema, n)) \
            .create_or_replace_temp_view(name)
    return s


@pytest.mark.parametrize("query", ["q1", "q3"])
def test_cached_plans_of_the_benchmark_are_what_they_were(tpch_session,
                                                          query):
    df = tpch_session.sql(_query_text(query, **QUERY_PARAMS[query]))
    logical, converted = CACHED_PLANS[query]
    assert _logical_tree(prune_plan(df.plan)) == json.loads(logical)
    executable, _ = apply_overrides(df.plan, tpch_session.conf)
    assert _exec_tree(executable) == json.loads(converted)


def test_q1_over_a_parquet_view_scans_seven_columns(tmp_path):
    from spark_rapids_tpu.io.parquet import write_parquet
    directory = str(tmp_path / "lineitem")
    write_parquet(_tpch_table(LINEITEM, 400), directory)
    s = TpuSession()
    s.read_parquet(directory).create_or_replace_temp_view("lineitem")
    text = _query_text("q1", **QUERY_PARAMS["q1"])
    df = s.sql(text)
    got = df.collect()

    scan = _scan_execs(s)[0]
    assert scan.scan_node.columns == Q1_COLUMNS
    assert scan.metrics["scanColumnsRead"] == 7
    assert scan.metrics["scanColumnsPruned"] == 9
    # the scan is already exact: the coalesce over it narrows nothing
    coalesce = [e for e in _walk(s._last_executable)
                if isinstance(e, TpuCoalesceExec)
                and isinstance(e.children[0], TpuFileScanExec)]
    assert len(coalesce) == 1 and coalesce[0].columns is None
    assert ("7 of 16 columns: " + ", ".join(Q1_COLUMNS)) in df.explain()

    cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
    cpu.read_parquet(directory).create_or_replace_temp_view("lineitem")
    _same(got, cpu.sql(text).collect())


def test_event_record_plan_tree_carries_the_counters(tmp_path):
    read = _write("parquet", str(tmp_path / "p"))
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true"})
    _subset_query(read(s)).collect()
    stack, found = [s.last_event_record["plan"]], []
    while stack:
        node = stack.pop()
        if node.get("op") == "TpuFileScanExec":
            found.append(node["metrics"])
        stack.extend(node.get("children") or ())
    assert len(found) == 1
    assert found[0]["scanColumnsRead"]["value"] == 3
    assert found[0]["scanColumnsPruned"]["value"] == 3
