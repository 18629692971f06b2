"""JSON scan + writer (reference: GpuJsonScan.scala /
GpuTextBasedPartitionReader — SURVEY.md §2.4).

Spark options honored: multiLine (whole-file JSON array/object parsed via
the stdlib and rebuilt as lines for arrow), primitivesAsString, and
mode = PERMISSIVE (malformed lines -> all-null row) | DROPMALFORMED |
FAILFAST, matching the reference's tagging-or-support contract instead of
silently ignoring options."""

from __future__ import annotations

import json as _json
from typing import List, Optional, Sequence

import pyarrow as pa
import pyarrow.json as pjson

from spark_rapids_tpu.columnar import HostTable
from spark_rapids_tpu.conf import RapidsConf, str_conf
from spark_rapids_tpu.io.arrow_convert import (
    arrow_schema_to_spark,
    decode_to_schema,
    spark_type_to_arrow,
)
from spark_rapids_tpu.io.common import FileScanNode, row_carrier_table
from spark_rapids_tpu.io.writer import write_partitioned
from spark_rapids_tpu.plan.nodes import Schema

JSON_READER_TYPE = str_conf(
    "spark.rapids.sql.format.json.reader.type", "AUTO",
    "PERFILE, COALESCING, MULTITHREADED or AUTO.")


class JsonScanNode(FileScanNode):
    format_name = "json"

    def __init__(self, paths, conf: RapidsConf, columns=None, reader_type=None,
                 schema: Optional[Schema] = None, multi_line: bool = False,
                 primitives_as_string: bool = False,
                 mode: str = "PERMISSIVE", **options):
        self.user_schema = schema
        self.multi_line = multi_line
        self.primitives_as_string = primitives_as_string
        self.mode = str(mode).upper()
        if self.mode not in ("PERMISSIVE", "DROPMALFORMED", "FAILFAST"):
            raise ValueError(f"unknown JSON mode {mode!r}")
        super().__init__(paths, conf, columns=columns, reader_type=reader_type,
                         **options)

    def _conf_reader_type(self) -> str:
        return self.conf.get_entry(JSON_READER_TYPE)

    def _cache_key_extra(self) -> tuple:
        return (tuple(self.user_schema or ()), self.multi_line,
                self.primitives_as_string, self.mode)

    def _parse_opts(self):
        if self.primitives_as_string and self.user_schema is None:
            return None  # schema inference happens post-stringify
        if not self.user_schema:
            return None
        from spark_rapids_tpu import types as T
        schema = []
        for n, dt in self.user_schema:
            nested = isinstance(dt, (T.ArrayType, T.StructType, T.MapType))
            at = (pa.string() if self.primitives_as_string and not nested
                  else spark_type_to_arrow(dt))
            schema.append((n, at))
        return pjson.ParseOptions(explicit_schema=pa.schema(schema))

    def _normalized_lines(self, path: str) -> bytes:
        """Apply multiLine + mode to produce clean JSON-lines bytes."""
        with open(path, "rb") as f:
            raw = f.read()
        if self.multi_line:
            try:
                doc = _json.loads(raw)
            except _json.JSONDecodeError:
                if self.mode == "FAILFAST":
                    raise
                # PERMISSIVE: one all-null row; DROPMALFORMED: empty
                return b"{}" if self.mode == "PERMISSIVE" else b""
            rows = doc if isinstance(doc, list) else [doc]
            return ("\n".join(_json.dumps(r) for r in rows)).encode()
        if self.mode == "FAILFAST":
            for ln in raw.splitlines():
                if ln.strip():
                    _json.loads(ln)  # raises on malformed
            return raw

        def _reject_const(_):
            raise _json.JSONDecodeError("non-standard constant", "", 0)

        out = []
        for ln in raw.splitlines():
            s = ln.strip()
            if not s:
                continue
            try:
                # parse_constant: Python json accepts NaN/Infinity that
                # Arrow rejects — treat them as malformed consistently
                _json.loads(s, parse_constant=_reject_const)
                out.append(ln)
            except _json.JSONDecodeError:
                if self.mode == "PERMISSIVE":
                    out.append(b"{}")  # all-null row (Spark permissive)
                # DROPMALFORMED: skip
        return b"\n".join(out)

    def _read_arrow(self, path: str) -> pa.Table:
        import io as _io
        if not self.multi_line:
            # fast path: stream straight through arrow; the per-line
            # salvage pass only runs if arrow rejects the file
            try:
                return pjson.read_json(path,
                                       parse_options=self._parse_opts())
            except pa.ArrowInvalid:
                if self.mode == "FAILFAST":
                    raise
        data = self._normalized_lines(path)
        if not data.strip():
            # every row dropped (DROPMALFORMED): an empty typed table
            if self.user_schema:
                return pa.table({n: pa.array([], spark_type_to_arrow(dt))
                                 for n, dt in self.user_schema})
            return pa.table({})
        return pjson.read_json(_io.BytesIO(data),
                               parse_options=self._parse_opts())

    def file_schema(self, path: str) -> Schema:
        if self.user_schema:
            return list(self.user_schema)
        schema = arrow_schema_to_spark(self._read_arrow(path).schema)
        if self.primitives_as_string:
            # Spark stringifies only PRIMITIVE leaves; nested stay as-is
            from spark_rapids_tpu import types as T
            schema = [(n, T.STRING if not isinstance(
                dt, (T.ArrayType, T.StructType, T.MapType)) else dt)
                for n, dt in schema]
        return schema

    def read_file(self, path: str) -> HostTable:
        tbl = self._read_arrow(path)
        if not self.data_schema:
            # only partition columns are read: the rows still count
            return row_carrier_table(tbl.num_rows)
        if self.primitives_as_string and self.user_schema is None:
            cols = []
            for i in range(tbl.num_columns):
                c = tbl.column(i)
                if pa.types.is_nested(c.type):
                    cols.append(c)  # Spark leaves nested types intact
                else:
                    cols.append(c.cast(pa.string()))
            tbl = pa.table(dict(zip(tbl.column_names, cols)))
        return decode_to_schema(tbl, self.data_schema)


def write_json(table: HostTable, path: str,
               partition_by: Optional[Sequence[str]] = None,
               committer=None) -> List[str]:
    """JSON-lines writer (Arrow has no JSON writer; rows serialize via the
    host columns directly)."""
    def _write_one(tbl: HostTable, file_path: str):
        cols = [c.to_pylist() for c in tbl.columns]
        with open(file_path, "w") as f:
            for i in range(tbl.num_rows):
                row = {n: cols[j][i] for j, n in enumerate(tbl.names)
                       if cols[j][i] is not None}
                f.write(_json.dumps(row, default=str) + "\n")
    return write_partitioned(table, path, _write_one, "json", partition_by,
                             committer=committer)
