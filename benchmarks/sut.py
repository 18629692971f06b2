"""The one file of the benchmark that touches the program under test.

Everything the harness takes from `spark_rapids_tpu` passes through here:
a session, the generator's tables as the engine's host tables or, for a
table the configuration's `storage` names, as Parquet files the engine
reads on every query, a query's collected result as plain Python values,
and the event record's counts.
"""

from __future__ import annotations

import concurrent.futures
import datetime
import os
import shutil
import sys
import tempfile
import time

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)

#: fields of the engine's event record that say the timed path was not the
#: device path: any of them set makes the query count as failed
NOT_THE_DEVICE_PATH = ("fallbacks", "demotions", "faultReplays",
                       "deviceReinits", "recovery", "oomRetries",
                       "splitRetries", "spillBytes", "unspills",
                       "filesNotRead")

#: the file scan exec's metrics that `slim_record` keeps, summed over the
#: plan's scan nodes, as `record["scan"]`
SCAN_EXEC = "TpuFileScanExec"
SCAN_METRICS = ("opTime", "scanUploadTime", "scanBatches", "scanRows")


class Engine:
    """A TpuSession with the event log on (the counts come from its
    records), its tables registered as temp views."""

    def __init__(self, config: dict):
        """`config` is the cell's configuration file: its `session_conf`
        are the deployment's Spark settings, its `batches` how many
        batches each table is cached as, its `storage` which tables are
        not cached at all but lie in files."""
        from spark_rapids_tpu.session import TpuSession
        self._event_dir = tempfile.mkdtemp(prefix="bench_events_")
        self.session = TpuSession({
            **config.get("session_conf", {}),
            "spark.rapids.sql.eventLog.enabled": "true",
            "spark.rapids.sql.eventLog.dir": self._event_dir})
        self._batches = config.get("batches", {})
        self._storage = config.get("storage", {})
        self._host_tables = {}
        self._file_dirs = []

    def register(self, tables: dict) -> None:
        """The generator's tables as temp views. The engine's host column
        takes strings as an object array of str: a dictionary column's
        rows point at its few strings, a text column's are cut from the
        pool one object a row (`Column.strings`)."""
        from spark_rapids_tpu import types as T
        from spark_rapids_tpu.columnar import HostColumn, HostTable
        kinds = {"long": (T.LONG, np.int64), "int": (T.INT, np.int32),
                 "double": (T.DOUBLE, np.float64),
                 "date": (T.DATE, np.int32)}
        for name, table in tables.items():
            if name in self._storage:
                self._register_files(name, table, self._storage[name])
                continue
            columns = []
            for col in table["columns"].values():
                if col.type in ("string", "text"):
                    columns.append(HostColumn(T.STRING, col.strings()))
                else:
                    dtype, np_dtype = kinds[col.type]
                    columns.append(HostColumn(
                        dtype, np.ascontiguousarray(col.values, np_dtype)))
            host = HostTable(list(table["columns"]), columns)
            self._host_tables[name] = host
            self.session.create_dataframe(
                host, num_batches=int(self._batches.get(name, 1))) \
                .create_or_replace_temp_view(name)

    def _register_files(self, name: str, table: dict, spec: dict) -> None:
        """The table as the files of a deployment that caches nothing: a
        fresh directory of Parquet part files (`write_parquet_parts`), and
        a temp view over `read_parquet` of it. Nothing of it is kept on
        the host, so `land()` uploads nothing of it."""
        if spec.get("format") != "parquet":
            raise ValueError(f"storage of {name!r}: this arm writes "
                             f"parquet; got {spec.get('format')!r}")
        t0 = time.perf_counter()
        directory = tempfile.mkdtemp(prefix="bench_files_")
        self._file_dirs.append(directory)
        written = write_parquet_parts(table, directory, spec)
        print(f"[bench sut] {name}: {table['num_rows']} rows as "
              f"{len(written)} parquet files, "
              f"{sum(map(os.path.getsize, written))} bytes, in "
              f"{time.perf_counter() - t0:.2f} s", file=sys.stderr, flush=True)
        self.session.read_parquet(directory) \
            .create_or_replace_temp_view(name)

    def land(self) -> int:
        """Upload every registered table, batch by batch as a query's
        scan does, and wait for the device; the engine keeps each batch's
        image on its host table, so later queries scan the device-resident
        copy. (`DataFrame.to_device_arrays` would do, but it concatenates
        the batches into a second copy of the table.) Returns the device
        bytes landed."""
        import jax
        from spark_rapids_tpu.overrides.rules import apply_overrides
        landed = 0
        for name in self._host_tables:
            executable, _ = apply_overrides(self.session.table(name).plan,
                                            self.session.conf)
            for batch in executable.tpu_exec.execute():
                jax.block_until_ready(
                    [(c.data, c.validity) for c in batch.columns])
                landed += batch.device_nbytes()
        return landed

    def query(self, text: str, annotate=None):
        """Plan and run one statement; returns (plain result, record).
        `annotate(name)` is a context manager the traced run passes in to
        mark plan and execute+fetch on the profiler's host timeline."""
        if annotate is None:
            df = self.session.sql(text)
            table = df.collect_table()
        else:
            with annotate("bench.plan"):
                df = self.session.sql(text)
            with annotate("bench.execute_fetch"):
                table = df.collect_table()
        return plain_result(table), self.mark_files_read(
            slim_record(self.session.last_event_record))

    def mark_files_read(self, record: dict) -> dict:
        """Every query reads the files: on an engine with a file-backed
        table, a record that shows no row pulled through a file scan (the
        answer came from something kept: a device image, a result cache)
        is marked `filesNotRead`, which counts the query as failed. It
        cannot see a cache UNDER the scan exec (io/filecache.py, shipped
        off): the configuration's `read` guarantee says that in words."""
        if self._file_dirs and not (record.get("scan") or {}).get("scanRows"):
            record["filesNotRead"] = 1
        return record

    def close(self) -> None:
        """Drop the session and the landed tables, so that the device
        memory is free before the reference runs."""
        from spark_rapids_tpu.columnar.table import evict_device_caches
        evict_device_caches()
        self._host_tables.clear()
        self.session = None
        shutil.rmtree(self._event_dir, ignore_errors=True)
        for directory in self._file_dirs:
            shutil.rmtree(directory, ignore_errors=True)
        self._file_dirs = []


def write_parquet_parts(table: dict, directory: str, spec: dict) -> list:
    """A generator's table as `spec["files"]` Parquet part files, the rows
    cut into contiguous parts in row order, written by pyarrow alone from
    the generator's arrays (the input of the system under test is not made
    by it). Types as spark-sql-perf's schema has them with
    `useDoubleForDecimal = true`: BIGINT as INT64, INT as INT32, DOUBLE,
    DATE as date32, STRING as UTF8 (a dictionary column from its codes, a
    text column from the pool's bytes: no Python object a row). No NULLs,
    no partition directories. Each file and then the directory is synced
    to the disk before it returns. Returns the paths written."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    files = int(spec["files"])
    groups = int(spec.get("row_groups_per_file", 1))
    codec = spec.get("compression", "snappy")
    rows = table["num_rows"]
    cuts = [rows * i // files for i in range(files + 1)]
    plain = {"long": pa.int64(), "int": pa.int32(), "double": pa.float64()}
    # a text column's pool as bytes (the grammar's text is ASCII: a
    # character is a byte; any other pool raises here)
    pools = {name: np.frombuffer(col.pool.encode("ascii"), np.uint8)
             for name, col in table["columns"].items() if col.type == "text"}

    def write_part(i: int) -> str:
        lo, hi = cuts[i], cuts[i + 1]
        arrays = []
        for name, col in table["columns"].items():
            values = col.values[lo:hi]
            if col.type == "string":
                arrays.append(pa.DictionaryArray.from_arrays(
                    pa.array(values, pa.int32()),
                    pa.array(col.dictionary.tolist(), pa.string()))
                    .cast(pa.string()))
            elif col.type == "text":
                arrays.append(_text_array(
                    pools[name], values, col.lengths[lo:hi]))
            elif col.type == "date":
                arrays.append(pa.array(values, pa.int32()).cast(pa.date32()))
            else:
                arrays.append(pa.array(values, plain[col.type]))
        path = os.path.join(
            directory, f"part-{i:05d}-bench.c000.{codec}.parquet")
        pq.write_table(
            pa.Table.from_arrays(arrays, names=list(table["columns"])), path,
            compression=codec, row_group_size=-(-max(hi - lo, 1) // groups))
        _fsync(path)
        return path

    # numpy's gather and pyarrow's writer release the GIL: a few parts at
    # once shorten set-up, which every run pays
    with concurrent.futures.ThreadPoolExecutor(max_workers=4) as executor:
        paths = list(executor.map(write_part, range(files)))
    _fsync(directory)
    return paths


def _fsync(path: str) -> None:
    """Write a file's (or a directory's entries') dirty pages back now, in
    set-up: left to the kernel's write-back, they could be written during
    the timed window. The pages stay cached, clean."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _text_array(pool: np.ndarray, starts: np.ndarray, lengths: np.ndarray):
    """Rows of a text column (a start and a length into the pool's bytes)
    as an Arrow string array, the bytes gathered by numpy."""
    import pyarrow as pa
    ends = np.cumsum(lengths, dtype=np.int64)
    if len(pool) >= 2 ** 31 or (len(ends) and ends[-1] >= 2 ** 31):
        raise ValueError("a part's text passes 2 GiB: cut it into more files")
    offsets = np.concatenate(([0], ends)).astype(np.int32)
    # byte j of row r is pool[starts[r] + j - offsets[r]]
    gather = np.repeat(starts.astype(np.int32) - offsets[:-1], lengths)
    gather += np.arange(offsets[-1], dtype=np.int32)
    return pa.StringArray.from_buffers(
        len(starts), pa.py_buffer(offsets), pa.py_buffer(pool[gather]))


def plain_result(table) -> dict:
    """A collected HostTable as {column: list of plain values}, dates as
    days since 1970-01-01 (what the references return)."""
    out = {}
    for name, values in table.to_pydict().items():
        out[name] = [(v - _EPOCH).days if isinstance(v, datetime.date) else v
                     for v in values]
    return out


def slim_record(rec) -> dict:
    """The counts the harness and the per-layer readers use."""
    if rec is None:
        raise RuntimeError("the engine kept no event record for the query")
    keep = ("wallS", "phasesS", "dispatches", "compileMs", "padWasteRows",
            "healthState", "executableCacheHit") + NOT_THE_DEVICE_PATH
    out = {k: rec.get(k) for k in keep}
    scan = scan_metrics(rec.get("plan"))
    if scan is not None:
        out["scan"] = scan
    transfer = ((rec.get("spans") or {}).get("byCategoryS") or {}) \
        .get("transfer")
    if transfer is not None:
        out["transferS"] = transfer
    return out


def scan_metrics(plan):
    """The file scan execs' metrics of a record's plan tree, summed over
    such nodes (an exec metric there is {"value", "kind", "level"});
    None where the plan has no file scan."""
    found = None
    stack = [plan] if plan else []
    while stack:
        node = stack.pop()
        if node.get("op") == SCAN_EXEC:
            found = found or dict.fromkeys(SCAN_METRICS, 0)
            for key in SCAN_METRICS:
                found[key] += (node["metrics"].get(key) or {}).get("value", 0)
        stack.extend(node.get("children") or ())
    return found


def off_device_path(rec: dict) -> list:
    """Names of the record's fields that show the query left the timed
    device path (empty for a clean query)."""
    bad = [k for k in NOT_THE_DEVICE_PATH if rec.get(k)]
    if rec.get("healthState") != "HEALTHY":
        bad.append("healthState")
    return bad
