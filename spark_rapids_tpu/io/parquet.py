"""Parquet scan + writer.

Reference: GpuParquetScan.scala (2,897 LoC; three reader modes, footer parse
on CPU, predicate pushdown), GpuParquetFileFormat.scala writer — SURVEY.md
§2.4. Here the footer parse / row-group pruning is pyarrow metadata; the
COALESCING mode stitches at row-group granularity like
MultiFileParquetPartitionReader (GpuParquetScan.scala:1867)."""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import pyarrow.parquet as pq

from spark_rapids_tpu.columnar import HostTable
from spark_rapids_tpu.conf import PARQUET_READER_TYPE, RapidsConf
from spark_rapids_tpu.io.arrow_convert import arrow_schema_to_spark, decode_to_schema
from spark_rapids_tpu.io.common import FileScanNode, row_carrier_table
from spark_rapids_tpu.io.writer import write_partitioned
from spark_rapids_tpu.plan.nodes import Schema


class ParquetScanNode(FileScanNode):
    format_name = "parquet"

    def __init__(self, paths, conf: RapidsConf, columns=None, reader_type=None,
                 filters=None, **options):
        #: pyarrow-style predicate pushdown filters, e.g. [("x", ">", 3)]
        self.filters = filters

        super().__init__(paths, conf, columns=columns, reader_type=reader_type,
                         **options)

    def _conf_reader_type(self) -> str:
        return self.conf.get_entry(PARQUET_READER_TYPE)

    def _cache_key_extra(self) -> tuple:
        return (repr(self.filters),)

    def file_schema(self, path: str) -> Schema:
        return arrow_schema_to_spark(pq.read_schema(path))

    def _count_rows(self, path: str) -> int:
        """The rows a scan that reads no data column still yields: the
        footer's count, or with pushdown filters the rows that pass them
        (their own columns are read for that)."""
        if self.filters is None:
            return pq.ParquetFile(path).metadata.num_rows
        names = sorted({term[0] for term in _flat_filters(self.filters)})
        return pq.read_table(path, columns=names,
                             filters=self.filters).num_rows

    def read_file(self, path: str) -> HostTable:
        if not self.data_schema:
            return row_carrier_table(self._count_rows(path))
        t = pq.read_table(path, columns=self._file_columns(),
                          filters=self.filters)
        return decode_to_schema(t, self.data_schema)

    def _coalescing_chunks(self, paths=None) -> Iterator[HostTable]:
        """Row-group-granular chunks for the stitcher (one device upload per
        stitched group). With pushdown filters the row-group fast path is
        bypassed so filtering stays identical across reader modes."""
        if self.filters is not None:
            yield from self._perfile(paths)
            return
        cols = self._file_columns()
        for path in (self.paths if paths is None else paths):
            f = pq.ParquetFile(path)
            for rg in range(f.metadata.num_row_groups):
                if not self.data_schema:
                    chunk = row_carrier_table(
                        f.metadata.row_group(rg).num_rows)
                else:
                    chunk = decode_to_schema(
                        f.read_row_group(rg, columns=cols), self.data_schema)
                yield self._with_partition_columns(chunk, path)


def _flat_filters(filters) -> list:
    """The (column, op, value) terms of a pyarrow filter in either of its
    forms: one conjunction, or a list of them."""
    terms = []
    for f in filters:
        if isinstance(f, (list, tuple)) and f and \
                isinstance(f[0], (list, tuple)):
            terms.extend(f)
        else:
            terms.append(f)
    return terms


def write_parquet(table: HostTable, path: str,
                  partition_by: Optional[Sequence[str]] = None,
                  compression: str = "snappy", row_group_rows: int = 1 << 20,
                  committer=None) -> List[str]:
    """Write a HostTable as parquet file(s); returns written paths.

    With ``partition_by``, writes Hive-style key=value directories via the
    dynamic-partitioning writer (GpuFileFormatDataWriter analog). All
    output stages through the transactional committer (io/committer.py);
    pass ``committer`` to run under a caller-owned WriteJob."""
    def _write_one(tbl: HostTable, file_path: str):
        from spark_rapids_tpu.io.arrow_convert import host_table_to_arrow
        pq.write_table(host_table_to_arrow(tbl), file_path,
                       compression=compression, row_group_size=row_group_rows)

    return write_partitioned(table, path, _write_one, "parquet",
                             partition_by, committer=committer)
