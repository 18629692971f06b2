"""CSV scan + writer (reference: GpuCSVScan.scala over
GpuTextBasedPartitionReader — SURVEY.md §2.4: CPU line splitting + parse).

The reference splits lines on CPU and parses on device; for the TPU build
the Arrow CSV parser is the host decode and the parsed columns upload as
one batch. The SPARK OPTIONS MATRIX is honored (GpuCSVScan's tagging
checks; options Arrow cannot express are emulated or rejected loudly,
never silently ignored):

  sep/delimiter, quote, escape, header, comment (line pre-filter),
  nullValue/emptyValue, nanValue/positiveInf/negativeInf (custom float
  spellings parse via string + host convert), dateFormat/timestampFormat
  (Spark pattern -> strptime translation for the common tokens),
  ignoreLeadingWhiteSpace/ignoreTrailingWhiteSpace,
  mode = PERMISSIVE | DROPMALFORMED | FAILFAST.
"""

from __future__ import annotations

import io as _io
from typing import List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.csv as pcsv

from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.conf import RapidsConf, str_conf
from spark_rapids_tpu import types as T
from spark_rapids_tpu.io.arrow_convert import (
    arrow_schema_to_spark,
    decode_to_schema,
    host_table_to_arrow,
    spark_type_to_arrow,
)
from spark_rapids_tpu.io.common import FileScanNode, row_carrier_table
from spark_rapids_tpu.io.writer import write_partitioned
from spark_rapids_tpu.plan.nodes import Schema

CSV_READER_TYPE = str_conf(
    "spark.rapids.sql.format.csv.reader.type", "AUTO",
    "PERFILE, COALESCING, MULTITHREADED or AUTO.")

import re as _re

#: Spark datetime pattern tokens -> strptime (the common subset the
#: reference's tagging accepts; any other LETTER RUN raises loudly — runs
#: are matched exactly, so e.g. MMMM cannot half-translate)
_PATTERN_TOKENS = {
    "yyyy": "%Y", "yy": "%y", "MM": "%m", "dd": "%d",
    "HH": "%H", "mm": "%M", "ss": "%S", "SSSSSS": "%f",
    "SSS": "%f", "a": "%p",
}

def spark_pattern_to_strptime(pattern: str) -> str:
    out = []
    for piece in _re.split(r"([A-Za-z]+)", pattern):
        if piece and piece[0].isalpha():
            rep = _PATTERN_TOKENS.get(piece)
            if rep is None:
                raise ValueError(
                    f"datetime pattern {pattern!r}: token {piece!r} is "
                    "outside the supported subset "
                    f"({' '.join(_PATTERN_TOKENS)})")
            out.append(rep)
        else:
            out.append(piece)
    return "".join(out)


class CsvScanNode(FileScanNode):
    format_name = "csv"

    def __init__(self, paths, conf: RapidsConf, columns=None, reader_type=None,
                 schema: Optional[Schema] = None, header: bool = True,
                 delimiter: str = ",", sep: Optional[str] = None,
                 quote: str = '"', escape: Optional[str] = None,
                 comment: Optional[str] = None,
                 null_value: str = "", empty_value: Optional[str] = None,
                 nan_value: str = "NaN",
                 positive_inf: str = "Inf", negative_inf: str = "-Inf",
                 timestamp_format: Optional[str] = None,
                 ignore_leading_whitespace: bool = False,
                 ignore_trailing_whitespace: bool = False,
                 mode: str = "PERMISSIVE", **options):
        self.user_schema = schema
        self.header = header
        self.delimiter = sep if sep is not None else delimiter
        self.quote = quote
        self.escape = escape
        self.comment = comment
        self.null_value = null_value
        self.empty_value = empty_value
        self.nan_value = nan_value
        self.positive_inf = positive_inf
        self.negative_inf = negative_inf
        self.timestamp_format = timestamp_format
        self.ignore_leading_ws = ignore_leading_whitespace
        self.ignore_trailing_ws = ignore_trailing_whitespace
        self.mode = str(mode).upper()
        if self.mode not in ("PERMISSIVE", "DROPMALFORMED", "FAILFAST"):
            raise ValueError(f"unknown CSV mode {mode!r}")
        if len(self.delimiter) != 1:
            raise ValueError("CSV sep must be a single character")
        super().__init__(paths, conf, columns=columns, reader_type=reader_type,
                         **options)

    def _conf_reader_type(self) -> str:
        return self.conf.get_entry(CSV_READER_TYPE)

    def _newlines_in_values(self) -> bool:
        return False  # Spark CSV multiLine=false semantics

    def _cache_key_extra(self) -> tuple:
        return (tuple(self.user_schema or ()), self.header, self.delimiter,
                self.quote, self.escape, self.comment, self.null_value,
                self.empty_value, self.nan_value, self.positive_inf,
                self.negative_inf, self.timestamp_format,
                self.ignore_leading_ws, self.ignore_trailing_ws, self.mode)

    # -- option plumbing ----------------------------------------------------
    @property
    def _custom_floats(self) -> bool:
        return (self.nan_value != "NaN" or self.positive_inf != "Inf"
                or self.negative_inf != "-Inf")

    def _read_opts(self):
        read_opts = pcsv.ReadOptions()
        if not self.header:
            if not self.user_schema:
                raise ValueError("headerless CSV requires an explicit schema")
            read_opts = pcsv.ReadOptions(
                column_names=[n for n, _ in self.user_schema])
        parse_opts = pcsv.ParseOptions(
            delimiter=self.delimiter,
            quote_char=self.quote if self.quote else False,
            escape_char=self.escape if self.escape else False,
            double_quote=self.escape is None,
            # False for Spark CSV (multiLine=false: newlines always end
            # records, and the comment pre-filter relies on it — see
            # _load_bytes); hive text overrides when escape.delim is set
            newlines_in_values=self._newlines_in_values(),
        )
        salvage = []
        if self.mode == "DROPMALFORMED":
            parse_opts.invalid_row_handler = lambda row: "skip"
        elif self.mode == "PERMISSIVE":
            # Spark PERMISSIVE null-fills ragged rows: capture the row text
            # and rebuild it with nulls appended after the arrow pass
            def _capture(row, _s=salvage):
                if row.text is not None:
                    _s.append(row.text)
                return "skip"
            parse_opts.invalid_row_handler = _capture

        null_values = [self.null_value]
        if self.empty_value is not None:
            null_values.append(self.empty_value)
        types = {}
        timestamp_parsers = None
        if self.user_schema:
            for n, dt in self.user_schema:
                if isinstance(dt, (T.FloatType, T.DoubleType)) \
                        and self._custom_floats:
                    types[n] = pa.string()  # host converts spellings below
                elif isinstance(dt, T.TimestampType):
                    # parse naive (no zone column in CSV); values are
                    # UTC-epoch micros like Spark's session-UTC convention
                    types[n] = pa.timestamp("us")
                else:
                    types[n] = spark_type_to_arrow(dt)
        if self.timestamp_format:
            timestamp_parsers = [
                spark_pattern_to_strptime(self.timestamp_format)]
        convert = pcsv.ConvertOptions(
            column_types=types or None,
            null_values=null_values,
            strings_can_be_null=True,
            quoted_strings_can_be_null=False,
            timestamp_parsers=timestamp_parsers or None,
        )
        return read_opts, parse_opts, convert, salvage

    def file_schema(self, path: str) -> Schema:
        if self.user_schema:
            return list(self.user_schema)
        tbl, _ = self._read_arrow(path)
        return arrow_schema_to_spark(tbl.schema)

    def _load_bytes(self, path: str) -> bytes:
        # comment filtering is LINE-based; quoted fields spanning newlines
        # are already unsupported by the parser config (newlines_in_values
        # stays False), so a dropped continuation line fails parsing loudly
        # rather than corrupting rows
        with open(path, "rb") as f:
            data = f.read()
        cb = self.comment.encode()
        lines = [ln for ln in data.split(b"\n")
                 if not ln.lstrip().startswith(cb)]
        return b"\n".join(lines)

    def _read_arrow(self, path: str):
        read_opts, parse_opts, convert, salvage = self._read_opts()
        # stream straight from the file unless the comment pre-filter
        # requires materializing the text
        source = (_io.BytesIO(self._load_bytes(path)) if self.comment
                  else path)
        tbl = pcsv.read_csv(source,
                            read_options=read_opts,
                            parse_options=parse_opts,
                            convert_options=convert)
        return tbl, salvage

    def narrowed(self, names):
        if self.mode != "PERMISSIVE" and self._custom_floats:
            # which rows DROPMALFORMED drops, and whether FAILFAST raises,
            # depends on the float columns that are converted: every
            # query converts the same ones
            return self
        return super().narrowed(names)

    def read_file(self, path: str) -> HostTable:
        tbl, salvage = self._read_arrow(path)
        if not self.data_schema:
            # only partition columns are read: the rows still count
            return row_carrier_table(tbl.num_rows + len(salvage))
        host = decode_to_schema(tbl, self._pre_float_schema())
        host = self._post_process(host)
        if salvage:
            host = self._append_null_filled(host, salvage)
        return host

    def _append_null_filled(self, host: HostTable, rows) -> HostTable:
        """PERMISSIVE ragged rows: parse what fields exist (naive split —
        these rows already failed structured parsing) against the FILE's
        physical column order, then project into the (possibly pruned or
        reordered) output columns; appended at the end (row order within a
        file is not part of the engine's contract)."""
        # physical file order = the full user/file schema, NOT host.names
        # and not data_schema, which a narrowed copy cuts to what it keeps
        file_schema = list(self.user_schema) if self.user_schema else \
            list(self._discovered[0])
        file_pos = {n: j for j, (n, _) in enumerate(file_schema)}
        schema = [(n, c.dtype) for n, c in zip(host.names, host.columns)]
        extra = []
        for text in rows:
            parts = text.split(self.delimiter)
            row = []
            for n, dt in schema:
                j = file_pos.get(n)
                raw = (parts[j].strip()
                       if j is not None and j < len(parts) else None)
                if raw in (None, self.null_value):
                    row.append(None)
                    continue
                try:
                    from spark_rapids_tpu.ops.cast import parse_string_cast
                    v = (raw if isinstance(dt, T.StringType)
                         else parse_string_cast(raw, dt))
                except Exception:
                    v = None
                row.append(v)
            extra.append(row)
        cols = []
        for j, (n, dt) in enumerate(schema):
            vals = [r[j] for r in extra]
            cols.append(HostColumn.from_pylist(vals, dt))
        return HostTable(host.names, [
            HostColumn(c.dtype,
                       np.concatenate([c.data, e.data]),
                       np.concatenate([c.validity, e.validity]))
            for c, e in zip(host.columns, cols)])

    def _pre_float_schema(self) -> Schema:
        """Schema for the arrow decode: custom-float columns arrive as
        STRING and convert in _post_process."""
        if not (self.user_schema and self._custom_floats):
            return self.data_schema
        fcols = {n for n, dt in self.user_schema
                 if isinstance(dt, (T.FloatType, T.DoubleType))}
        return [(n, T.STRING if n in fcols else dt)
                for n, dt in self.data_schema]

    def _post_process(self, host: HostTable) -> HostTable:
        cols = list(host.columns)
        names = list(host.names)
        target = dict(self.data_schema)
        drop_mask = None  # DROPMALFORMED: rows with unparseable floats
        for i, (n, c) in enumerate(zip(names, cols)):
            if isinstance(c.dtype, T.StringType) and (
                    self.ignore_leading_ws or self.ignore_trailing_ws):
                data = c.data.copy()
                for j in range(len(data)):
                    if c.validity[j] and data[j] is not None:
                        if self.ignore_leading_ws:
                            data[j] = data[j].lstrip()
                        if self.ignore_trailing_ws:
                            data[j] = data[j].rstrip()
                c = HostColumn(T.STRING, data, c.validity.copy())
            want = target.get(n)
            if isinstance(c.dtype, T.StringType) and isinstance(
                    want, (T.FloatType, T.DoubleType)) and self._custom_floats:
                c, bad = self._convert_custom_floats(c, want)
                if drop_mask is None:
                    drop_mask = bad
                else:
                    drop_mask = drop_mask | bad
            cols[i] = c
        if self.mode == "DROPMALFORMED" and drop_mask is not None \
                and drop_mask.any():
            keep = ~drop_mask
            cols = [HostColumn(c.dtype, c.data[keep], c.validity[keep])
                    for c in cols]
        return HostTable(names, cols)

    def _convert_custom_floats(self, c: HostColumn, dt):
        specials = {self.nan_value: np.nan, self.positive_inf: np.inf,
                    self.negative_inf: -np.inf}
        out = np.zeros(len(c), dtype=dt.np_dtype)
        validity = np.zeros(len(c), dtype=np.bool_)
        malformed = np.zeros(len(c), dtype=np.bool_)
        for i in range(len(c)):
            if not c.validity[i] or c.data[i] is None:
                continue
            s = c.data[i].strip()
            if s in specials:
                out[i] = specials[s]
                validity[i] = True
            else:
                try:
                    out[i] = float(s)
                    validity[i] = True
                except ValueError:
                    if self.mode == "FAILFAST":
                        raise ValueError(
                            f"malformed float {s!r} (FAILFAST mode)")
                    malformed[i] = True
        return HostColumn(dt, out, validity), malformed


def write_csv(table: HostTable, path: str,
              partition_by: Optional[Sequence[str]] = None,
              header: bool = True, committer=None) -> List[str]:
    def _write_one(tbl: HostTable, file_path: str):
        opts = pcsv.WriteOptions(include_header=header)
        pcsv.write_csv(host_table_to_arrow(tbl), file_path, opts)

    return write_partitioned(table, path, _write_one, "csv", partition_by,
                             committer=committer)
