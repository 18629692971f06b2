"""Iceberg snapshot scan with delete-file application.

Reference: ``GpuIcebergReader.java`` (applies the delete filter then hands
batches to the engine), ``GpuDeleteFilter.java`` (positional + equality
deletes), ``GpuMultiFileBatchReader.java`` (reader-mode integration).
Positional deletes are parquet files of (file_path, pos); equality
deletes are parquet files whose rows name deleted keys over the columns
given by ``equality_ids``, applied to data files with a SMALLER sequence
number (v2 sequence-number semantics)."""

from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Set, Tuple

import numpy as np

from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.iceberg.metadata import (
    EQUALITY_DELETES,
    POSITION_DELETES,
    IcebergSnapshot,
    IcebergTableMetadata,
    load_snapshot,
    load_table_metadata,
)
from spark_rapids_tpu.io.common import FileScanNode
from spark_rapids_tpu.plan.nodes import Schema


class IcebergScanNode(FileScanNode):
    format_name = "iceberg"

    def __init__(self, table_path: str, conf: RapidsConf,
                 snapshot_id: Optional[int] = None,
                 columns: Optional[Sequence[str]] = None, **options):
        self.table_path = table_path
        self.meta: IcebergTableMetadata = load_table_metadata(table_path)
        self.snap: IcebergSnapshot = load_snapshot(table_path, self.meta,
                                                   snapshot_id)
        self._seq_by_path = {d.file_path: d.sequence_number
                             for d in self.snap.data_files}
        #: the delete files as loaded, [(positional, equality)]: filled by
        #: the first read, and one list for this node and every narrowed
        #: copy of it, so planning reads no delete file and no copy reads
        #: them again
        self._deletes: List[Tuple[
            Dict[str, np.ndarray],
            List[Tuple[int, List[str], Set[tuple]]]]] = []
        paths = [d.file_path for d in self.snap.data_files]
        self._empty = not paths
        super().__init__(paths or ["<empty>"], conf, columns=columns,
                         **options)

    def output_schema(self) -> Schema:
        full = list(self.meta.schema)
        if self.columns is not None:
            by_name = dict(full)
            for c in self.columns:
                if c not in by_name:
                    raise ColumnarProcessingError(
                        f"column {c!r} not in {[n for n, _ in full]}")
            full = [(c, by_name[c]) for c in self.columns]
        return full

    def file_schema(self, path: str) -> Schema:
        return list(self.meta.schema)

    def _resolve_schemas(self):
        if self._schema is not None:
            return
        self._discovered = (list(self.meta.schema), [])
        self._schema = self.output_schema()
        self._data_schema = self._schema
        self._partition_schema = []

    def _cache_key_extra(self) -> tuple:
        return (self.snap.snapshot_id,)

    # -- delete files --------------------------------------------------------
    def _load_deletes(self):
        """(positional deletes by data file, equality deletes)."""
        if self._deletes:
            return self._deletes[0]
        import pyarrow.parquet as pq
        pos: Dict[str, List[np.ndarray]] = {}
        eqs: List[Tuple[int, List[str], Set[tuple]]] = []
        for d in self.snap.delete_files:
            t = pq.read_table(d.file_path)
            if d.content == POSITION_DELETES:
                paths = t.column("file_path").to_pylist()
                positions = np.asarray(t.column("pos").to_pylist(),
                                       dtype=np.int64)
                for p in set(paths):
                    mask = np.array([x == p for x in paths])
                    pos.setdefault(self._norm(p), []).append(
                        positions[mask])
            elif d.content == EQUALITY_DELETES:
                cols = [self.meta.field_ids[i] for i in d.equality_ids]
                if not cols:
                    cols = t.column_names
                keys = set()
                data = [t.column(c).to_pylist() for c in cols]
                for row in zip(*data):
                    keys.add(row)
                eqs.append((d.sequence_number, cols, keys))
        self._deletes.append((
            {p: np.unique(np.concatenate(v)) for p, v in pos.items()}, eqs))
        return self._deletes[0]

    def _norm(self, p: str) -> str:
        if p.startswith("file://"):
            p = p[len("file://"):]
        return os.path.normpath(p)

    def read_file(self, path: str) -> HostTable:
        import pyarrow.parquet as pq

        from spark_rapids_tpu.io.arrow_convert import decode_to_schema
        self._resolve_schemas()
        pos_deletes, eq_deletes = self._load_deletes()
        # equality deletes may need columns beyond the projection
        eq_cols = {c for seq, cols, _k in eq_deletes for c in cols
                   if seq > self._seq_by_path.get(path, 0)}
        proj = [n for n, _ in self._data_schema]
        read_cols = list(dict.fromkeys(proj + sorted(eq_cols)))
        t = pq.read_table(path, columns=read_cols)
        all_schema = dict(self.meta.schema)
        table = decode_to_schema(t, [(n, all_schema[n]) for n in read_cols])

        keep = np.ones(table.num_rows, dtype=bool)
        dv = pos_deletes.get(self._norm(path))
        if dv is not None:
            keep[dv[dv < table.num_rows]] = False
        my_seq = self._seq_by_path.get(path, 0)
        for seq, cols, keys in eq_deletes:
            if seq <= my_seq:
                continue  # deletes only apply to OLDER data
            idx = [list(table.names).index(c) for c in cols]
            for r in range(table.num_rows):
                if keep[r] and tuple(table.columns[i].data[r]
                                     for i in idx) in keys:
                    keep[r] = False
        cols_out = []
        names_out = []
        for n in proj:
            i = list(table.names).index(n)
            c = table.columns[i]
            cols_out.append(HostColumn(c.dtype, c.data[keep],
                                       c.validity[keep]))
            names_out.append(n)
        return HostTable(names_out, cols_out)

    def execute_cpu(self, dynamic_prunes=None, metrics=None):
        if self._empty:
            from spark_rapids_tpu.plan.nodes import _empty_table
            yield _empty_table(self.output_schema())
            return
        yield from super().execute_cpu(dynamic_prunes=dynamic_prunes,
                                       metrics=metrics)

    def estimate_bytes(self):
        try:
            return sum(os.path.getsize(d.file_path)
                       for d in self.snap.data_files)
        except OSError:
            return None

    def describe(self):
        return (f"IcebergScan[snap={self.snap.snapshot_id}, "
                f"{len(self.snap.data_files)} data files, "
                f"{len(self.snap.delete_files)} delete files"
                f"{self._describe_columns()}]")
