"""The one file of the benchmark that touches the program under test.

Everything the harness takes from `spark_rapids_tpu` passes through here:
a session, the generator's tables as the engine's host tables, a query's
collected result as plain Python values, and the event record's counts.
"""

from __future__ import annotations

import datetime
import shutil
import tempfile

import numpy as np

_EPOCH = datetime.date(1970, 1, 1)

#: fields of the engine's event record that say the timed path was not the
#: device path: any of them set makes the query count as failed
NOT_THE_DEVICE_PATH = ("fallbacks", "demotions", "faultReplays",
                       "deviceReinits", "recovery", "oomRetries",
                       "splitRetries", "spillBytes", "unspills")


class Engine:
    """A TpuSession with the event log on (the counts come from its
    records), its tables registered as temp views."""

    def __init__(self, config: dict):
        """`config` is the cell's configuration file: its `session_conf`
        are the deployment's Spark settings, its `batches` how many
        batches each table is cached as."""
        from spark_rapids_tpu.session import TpuSession
        self._event_dir = tempfile.mkdtemp(prefix="bench_events_")
        self.session = TpuSession({
            **config.get("session_conf", {}),
            "spark.rapids.sql.eventLog.enabled": "true",
            "spark.rapids.sql.eventLog.dir": self._event_dir})
        self._batches = config.get("batches", {})
        self._host_tables = {}

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
        return plain_result(table), slim_record(self.session.last_event_record)

    def close(self) -> None:
        """Drop the session and the landed tables, so that the device
        memory is free before the reference runs."""
        from spark_rapids_tpu.columnar.table import evict_device_caches
        evict_device_caches()
        self._host_tables.clear()
        self.session = None
        shutil.rmtree(self._event_dir, ignore_errors=True)


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
    return {k: rec.get(k) for k in keep}


def off_device_path(rec: dict) -> list:
    """Names of the record's fields that show the query left the timed
    device path (empty for a clean query)."""
    bad = [k for k in NOT_THE_DEVICE_PATH if rec.get(k)]
    if rec.get("healthState") != "HEALTHY":
        bad.append("healthState")
    return bad
