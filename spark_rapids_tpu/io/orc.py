"""ORC scan + writer (reference: GpuOrcScan.scala, GpuOrcFileFormat.scala —
SURVEY.md §2.4; same three reader modes as parquet, stripe-granular
coalescing)."""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import pyarrow as pa
import pyarrow.orc as po

from spark_rapids_tpu.columnar import HostTable
from spark_rapids_tpu.conf import str_conf
from spark_rapids_tpu.io.arrow_convert import arrow_schema_to_spark, decode_to_schema
from spark_rapids_tpu.io.common import FileScanNode, row_carrier_table
from spark_rapids_tpu.io.writer import write_partitioned
from spark_rapids_tpu.plan.nodes import Schema

ORC_READER_TYPE = str_conf(
    "spark.rapids.sql.format.orc.reader.type", "AUTO",
    "PERFILE, COALESCING, MULTITHREADED or AUTO (reference: GpuOrcScan "
    "reader modes).")


class OrcScanNode(FileScanNode):
    format_name = "orc"

    def _conf_reader_type(self) -> str:
        return self.conf.get_entry(ORC_READER_TYPE)

    def file_schema(self, path: str) -> Schema:
        return arrow_schema_to_spark(po.ORCFile(path).schema)

    def read_file(self, path: str) -> HostTable:
        if not self.data_schema:
            return row_carrier_table(po.ORCFile(path).nrows)
        t = po.ORCFile(path).read(columns=self._file_columns())
        return decode_to_schema(t, self.data_schema)

    def _coalescing_chunks(self, paths=None) -> Iterator[HostTable]:
        """Stripe-granular chunks (MultiFileOrcPartitionReader analog)."""
        cols = self._file_columns()
        for path in (self.paths if paths is None else paths):
            if not self.data_schema:
                # nothing of the file's contents is read: one chunk a file
                yield self._read_with_partitions(path)
                continue
            f = po.ORCFile(path)
            for s in range(f.nstripes):
                batch = f.read_stripe(s, columns=cols)
                yield self._with_partition_columns(
                    decode_to_schema(pa.Table.from_batches([batch]),
                                     self.data_schema),
                    path)


def write_orc(table: HostTable, path: str,
              partition_by: Optional[Sequence[str]] = None,
              compression: str = "zstd", committer=None) -> List[str]:
    def _write_one(tbl: HostTable, file_path: str):
        from spark_rapids_tpu.io.arrow_convert import host_table_to_arrow
        po.write_table(host_table_to_arrow(tbl), file_path,
                       compression=compression)
    return write_partitioned(table, path, _write_one, "orc", partition_by,
                             committer=committer)
