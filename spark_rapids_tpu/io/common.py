"""Shared file-scan machinery: the three reader modes.

Reference architecture (SURVEY.md §2.4, GpuMultiFileReader.scala):
  PERFILE        — decode one file at a time, one batch per file.
  COALESCING     — stitch many small files/row-groups into one large buffer
                   and do a single decode+upload (MultiFileCoalescingPartition-
                   ReaderBase analog). Best for many small files on fast storage.
  MULTITHREADED  — a thread pool prefetches and decodes a bounded window of
                   files ahead of the consumer so host decode overlaps device
                   compute (MultiFileCloudPartitionReaderBase analog).
  AUTO           — MULTITHREADED when more than one file, else PERFILE.

The TPU engine decodes on host via Arrow and uploads decoded columns; the
modes govern prefetch/stitching exactly as in the reference. Hive-style
``key=value`` directory components are recovered as partition columns
(GpuFileSourceScanExec partition-value reconstruction analog).
"""

from __future__ import annotations

import concurrent.futures as cf
import copy
import glob as _glob
import os
from typing import Iterable, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.conf import (
    MULTITHREADED_READ_NUM_THREADS,
    RapidsConf,
    READER_COALESCE_TARGET_BYTES,
)
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.plan.nodes import PlanNode, Schema


class ReaderMode:
    PERFILE = "PERFILE"
    COALESCING = "COALESCING"
    MULTITHREADED = "MULTITHREADED"
    AUTO = "AUTO"


def expand_paths(paths: Sequence[str]) -> List[str]:
    """Expand globs and directories into a sorted file list.

    Hidden entries — ``_``/``.``-prefixed files AND directories — are
    excluded on every listing branch (Spark's InMemoryFileIndex
    contract). Pruning directories matters for correctness, not just
    hygiene: the transactional writer stages in-flight output under
    ``_temporary/<job>/<attempt>/``, and those staged ``part-*`` files
    must never be visible to a scan. Explicitly named single files are
    honored as given (the caller asked for that exact path)."""
    out: List[str] = []
    for p in paths:
        if any(ch in p for ch in "*?["):
            # reject hidden components anywhere a WILDCARD could have
            # matched them (a glob crossing _temporary/ must not
            # surface staged files) while honoring hidden components
            # the caller spelled out in the static prefix
            comps = p.split(os.sep)
            first_wild = next(i for i, seg in enumerate(comps)
                              if any(ch in seg for ch in "*?["))
            for m in sorted(_glob.glob(p)):
                tail = m.rstrip(os.sep).split(os.sep)[first_wild:]
                if not any(c.startswith(("_", ".")) for c in tail if c):
                    out.append(m)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(d for d in dirs
                                 if not d.startswith(("_", ".")))
                for f in sorted(files):
                    if not f.startswith(("_", ".")):
                        out.append(os.path.join(root, f))
        else:
            out.append(p)
    if not out:
        raise ColumnarProcessingError(f"no input files for {list(paths)}")
    return out


HIVE_DEFAULT_PARTITION = "__HIVE_DEFAULT_PARTITION__"


def _unescape_partition_value(s: str) -> Optional[str]:
    if s == HIVE_DEFAULT_PARTITION:
        return None
    out, i = [], 0
    while i < len(s):
        if s[i] == "%" and i + 3 <= len(s):
            try:
                out.append(chr(int(s[i + 1:i + 3], 16)))
                i += 3
                continue
            except ValueError:
                pass
        out.append(s[i])
        i += 1
    return "".join(out)


def partition_spec_of(path: str) -> List[Tuple[str, Optional[str]]]:
    """Extract ordered (key, value) pairs from Hive-style path components."""
    spec = []
    for comp in os.path.dirname(path).split(os.sep):
        if "=" in comp and not comp.startswith("."):
            k, _, v = comp.partition("=")
            spec.append((k, _unescape_partition_value(v)))
    return spec


def _infer_partition_type(values: Iterable[Optional[str]]) -> T.DataType:
    """Spark-style partition value type inference: long -> double -> string."""
    saw_any = False
    all_long = all_double = True
    for v in values:
        if v is None:
            continue
        saw_any = True
        try:
            int(v)
        except ValueError:
            all_long = False
            try:
                float(v)
            except ValueError:
                all_double = False
    if not saw_any:
        return T.STRING
    if all_long:
        return T.LONG
    if all_double:
        return T.DOUBLE
    return T.STRING


def coalesce_batches(batches: Iterable[HostTable], target_bytes: int
                     ) -> Iterator[HostTable]:
    """Accumulate host batches until the byte target, then concat — the one
    shared stitching loop behind every COALESCING reader."""
    pending: List[HostTable] = []
    pending_bytes = 0
    for t in batches:
        pending.append(t)
        pending_bytes += t.nbytes()
        if pending_bytes >= target_bytes:
            yield HostTable.concat(pending)
            pending, pending_bytes = [], 0
    if pending:
        yield HostTable.concat(pending)


class FileScanNode(PlanNode):
    """Base scan node. Subclasses implement ``read_file`` (one file decoded
    to the host columns of ``data_schema``) and ``file_schema``; COALESCING
    may be refined per-format (parquet splits at row-group granularity).

    ``columns`` restricts the node's output, and with it what the reader
    decodes and what ``TpuFileScanExec`` uploads. A node is shared by every
    query over its DataFrame or temp view, so a query never sets it:
    column pruning (overrides/pruning.py ``_visit``) asks for
    ``narrowed(names)``, a copy restricted to the columns the plan reads
    that keeps the files listed and the schema discovered by this node."""

    format_name = "file"

    def __init__(self, paths: Sequence[str], conf: RapidsConf,
                 columns: Optional[Sequence[str]] = None,
                 reader_type: Optional[str] = None, **options):
        self.paths = expand_paths(paths)
        self.conf = conf
        self.columns = list(columns) if columns else None
        self.options = options
        self.reader_type = (reader_type or self._conf_reader_type()).upper()
        self._schema: Optional[Schema] = None
        self._data_schema: Optional[Schema] = None
        self._partition_schema: Optional[Schema] = None
        #: (data schema, partition schema) of the files before ``columns``
        #: narrows them: found once, carried by every narrowed copy
        self._discovered: Optional[Tuple[Schema, Schema]] = None

    def narrowed(self, names: Sequence[str]) -> "FileScanNode":
        """A copy of this node whose output is ``names`` (columns of this
        node's output, in its order): the reader decodes only those. The
        copy shares the file list, the conf, the options and the
        subclass's own state, and resolves its schemas from what this node
        discovered, so it lists no directory and opens no file to plan."""
        self._resolve_schemas()
        out = copy.copy(self)
        out.columns = list(names)
        out._schema = out._data_schema = out._partition_schema = None
        return out

    def cheapest_column(self) -> int:
        """Ordinal of the output column that costs least to produce, for
        a plan that reads none (``count(*)``) and needs one to carry its
        rows: a partition column, whose values come from the path, else
        the narrowest fixed-width data column, else column 0."""
        self._resolve_schemas()
        parts = {n for n, _ in self._partition_schema or ()}
        best, best_width = 0, None
        for i, (name, dt) in enumerate(self._schema):
            if name in parts:
                return i
            np_dtype = getattr(dt, "np_dtype", None)
            if np_dtype is None or np_dtype == object \
                    or isinstance(dt, T.DecimalType):
                continue  # decoded a row at a time through Python objects
            width = np_dtype.itemsize
            if best_width is None or width < best_width:
                best, best_width = i, width
        return best

    def read_width(self) -> int:
        """Columns this node reads: its output without the hidden
        provenance columns."""
        self._resolve_schemas()
        return len(self._schema)

    def full_width(self) -> int:
        """Columns the files (and their partition directories) hold, before
        any narrowing."""
        self._resolve_schemas()
        data_schema, part_schema = self._discovered
        return len(data_schema) + len(part_schema)

    def _effective_paths(self, dynamic_prunes) -> list:
        """File list after dynamic partition pruning
        (GpuFileSourceScanExec partitionFilters with
        DynamicPruningExpression). ``dynamic_prunes`` is a list of
        (partition column name, provider) where provider() -> set of
        allowed values; it is EXECUTION-scoped state owned by the calling
        exec (execs/basic.TpuFileScanExec), never by this shared plan
        node — a prune must not leak into other queries over the same
        scan."""
        paths = list(self.paths)
        if not dynamic_prunes:
            return paths
        self._resolve_schemas()
        part_types = dict(self._partition_schema or [])
        for part_col, provider in dynamic_prunes:
            dt = part_types.get(part_col)
            if dt is None:
                continue
            allowed = provider()
            kept = []
            for p in paths:
                spec = dict(partition_spec_of(p))
                raw = spec.get(part_col)
                if raw is None:
                    kept.append(p)  # null partition: keep (null-safe)
                    continue
                if isinstance(dt, T.StringType):
                    val = raw
                elif isinstance(dt, T.DoubleType):
                    val = float(raw)
                else:
                    val = int(raw)
                if val in allowed:
                    kept.append(p)
            paths = kept
        return paths

    # -- subclass surface ---------------------------------------------------
    def _conf_reader_type(self) -> str:
        return ReaderMode.AUTO

    def file_schema(self, path: str) -> Schema:
        raise NotImplementedError

    def read_file(self, path: str) -> HostTable:
        """Decode one file to its data columns (partition columns appended
        by the driver loop)."""
        raise NotImplementedError

    def _file_columns(self) -> Optional[List[str]]:
        """The names a columnar reader asks the file for: None for all of
        them, else the kept data columns (none when ``data_schema`` is
        empty: the rows are then counted, not read)."""
        if self.columns is None:
            return None
        return [n for n, _ in self.data_schema]

    # -- schema -------------------------------------------------------------
    def _discover_schemas(self) -> Tuple[Schema, Schema]:
        """(data schema, partition schema) of the files as they lie: opens
        the first file and walks every path's directories."""
        data_schema = self.file_schema(self.paths[0])
        data_names = {n for n, _ in data_schema}
        # partition columns from Hive-style dirs, in first-seen key order
        part_values: dict = {}
        for p in self.paths:
            for k, v in partition_spec_of(p):
                if k not in data_names:
                    part_values.setdefault(k, []).append(v)
        part_schema = [(k, _infer_partition_type(vs))
                       for k, vs in part_values.items()]
        return data_schema, part_schema

    def _resolve_schemas(self):
        if self._schema is not None:
            return
        if self._discovered is None:
            self._discovered = self._discover_schemas()
        data_schema, part_schema = self._discovered
        full = data_schema + part_schema
        if self.columns is not None:
            by_name = dict(full)
            for c in self.columns:
                if c not in by_name:
                    raise ColumnarProcessingError(
                        f"column {c!r} not in {[n for n, _ in full]}")
            full = [(c, by_name[c]) for c in self.columns]
            data_schema = [(n, dt) for n, dt in data_schema
                           if n in set(self.columns)]
            part_schema = [(n, dt) for n, dt in part_schema
                           if n in set(self.columns)]
        self._schema = full
        self._data_schema = data_schema
        self._partition_schema = part_schema

    #: set by overrides/input_file.py when the plan references
    #: input_file_name()/input_file_block_*: every batch gains hidden
    #: per-row provenance columns (reference: GpuInputFileName family +
    #: InputFileBlockRule keeping the exprs in the scan's stage)
    provide_file_info: bool = False

    def enable_file_info(self) -> None:
        self.provide_file_info = True

    def _attach_file_info(self, table: HostTable, path: str) -> HostTable:
        if not self.provide_file_info:
            return table
        from spark_rapids_tpu.ops.inputfile import (
            FILE_LENGTH_COL,
            FILE_NAME_COL,
            FILE_START_COL,
        )
        if FILE_NAME_COL in table.names:
            return table  # chunk already stamped
        n = table.num_rows
        name = np.empty(n, dtype=object)
        name[:] = path
        try:
            size = os.path.getsize(path)
            start = 0
        except OSError:
            # unreadable between decode and stamping: coherent Spark
            # no-info pair, not a 0/-1 mix
            size = start = -1
        cols = list(table.columns) + [
            HostColumn(T.STRING, name),
            HostColumn(T.LONG, np.full(n, start, dtype=np.int64)),
            HostColumn(T.LONG, np.full(n, size, dtype=np.int64))]
        return HostTable(
            list(table.names) + [FILE_NAME_COL, FILE_START_COL,
                                 FILE_LENGTH_COL], cols)

    def output_schema(self) -> Schema:
        self._resolve_schemas()
        if self.provide_file_info:
            from spark_rapids_tpu.ops.inputfile import (
                FILE_LENGTH_COL,
                FILE_NAME_COL,
                FILE_START_COL,
            )
            return list(self._schema) + [
                (FILE_NAME_COL, T.STRING), (FILE_START_COL, T.LONG),
                (FILE_LENGTH_COL, T.LONG)]
        return self._schema

    @property
    def data_schema(self) -> Schema:
        """Schema of columns read from file contents (post-pruning)."""
        self._resolve_schemas()
        return self._data_schema

    def _with_partition_columns(self, table: HostTable, path: str) -> HostTable:
        """Append recovered partition-value columns (and, when enabled,
        the input-file provenance columns) and order to the output
        schema."""
        self._resolve_schemas()
        spec = dict(partition_spec_of(path)) if self._partition_schema \
            else {}
        n = table.num_rows
        names = list(table.names)
        cols = list(table.columns)
        for name, dt in self._partition_schema:
            raw = spec.get(name)
            if raw is None:
                validity = np.zeros(n, dtype=np.bool_)
                if isinstance(dt, T.StringType):
                    data = np.full(n, None, dtype=object)
                else:
                    data = np.zeros(n, dtype=dt.np_dtype)
            else:
                validity = np.ones(n, dtype=np.bool_)
                if isinstance(dt, T.StringType):
                    data = np.full(n, raw, dtype=object)
                elif isinstance(dt, T.DoubleType):
                    data = np.full(n, float(raw), dtype=np.float64)
                else:
                    data = np.full(n, int(raw), dtype=np.int64)
            names.append(name)
            cols.append(HostColumn(dt, data, validity))
        out_names = [n for n, _ in self._schema]
        if self._partition_schema or names != out_names:
            # partition columns appended, a row carrier to drop, or a
            # reader that hands columns up in the file's order
            by_name = dict(zip(names, cols))
            table = HostTable(out_names, [by_name[n] for n in out_names])
        return self._attach_file_info(table, path)

    # -- PlanNode -----------------------------------------------------------
    def execute_cpu(self, dynamic_prunes=None,
                    metrics: Optional[dict] = None) -> Iterator[HostTable]:
        paths = self._effective_paths(dynamic_prunes)
        if metrics is not None and dynamic_prunes:
            metrics["dppPrunedFiles"] = len(self.paths) - len(paths)
            metrics["dppScannedFiles"] = len(paths)
        if not paths:
            from spark_rapids_tpu.plan.nodes import _empty_table
            yield _empty_table(self.output_schema())
            return
        # multi-host cluster routing (runtime/cluster.py): with an
        # active cluster, source files partition BY HOST and each
        # executor process scans only its subset, shipping the decoded
        # shards back over the driver/executor wire — batch-per-file in
        # path order, byte-identical to the local PERFILE walk below.
        # Inactive/unroutable scans fall through to the local modes.
        from spark_rapids_tpu.runtime.cluster import CLUSTER
        routed = CLUSTER.scan_route(self, paths)
        if routed is not None:
            yield from routed
            return
        mode = self.reader_type
        if mode == ReaderMode.AUTO:
            mode = (ReaderMode.MULTITHREADED if len(paths) > 1
                    else ReaderMode.PERFILE)
        if mode == ReaderMode.PERFILE:
            it = self._perfile(paths)
        elif mode == ReaderMode.COALESCING:
            it = coalesce_batches(
                self._coalescing_chunks(paths),
                self.conf.get_entry(READER_COALESCE_TARGET_BYTES))
        elif mode == ReaderMode.MULTITHREADED:
            it = self._multithreaded(paths)
        else:
            raise ColumnarProcessingError(f"unknown reader type {mode}")
        yield from it

    def _cache_key_extra(self) -> tuple:
        """Subclasses add every decode-affecting option here (named kwargs
        consumed before **options never reach self.options)."""
        return ()

    def _cache_key(self) -> tuple:
        return (type(self).__name__, tuple(self.columns or ()),
                tuple(sorted((k, str(v)) for k, v in self.options.items())),
                self._cache_key_extra())

    def _read_decoded(self, path: str) -> HostTable:
        from spark_rapids_tpu.io.filecache import (
            FILE_CACHE,
            FILECACHE_ENABLED,
            FILECACHE_MAX_BYTES,
        )
        from spark_rapids_tpu.runtime.faults import fault_point
        fault_point("io.read.file")
        if not self.conf.get_entry(FILECACHE_ENABLED):
            return self.read_file(path)
        return FILE_CACHE.get_or_decode(
            path, self._cache_key(), lambda: self.read_file(path),
            self.conf.get_entry(FILECACHE_MAX_BYTES))

    def _read_with_partitions(self, path: str) -> HostTable:
        return self._with_partition_columns(self._read_decoded(path), path)

    def _perfile(self, paths=None) -> Iterator[HostTable]:
        for p in (self.paths if paths is None else paths):
            yield self._read_with_partitions(p)

    def _coalescing_chunks(self, paths=None) -> Iterator[HostTable]:
        """Chunk stream feeding the COALESCING stitcher. Default: whole
        files; formats with sub-file granularity (parquet row groups, ORC
        stripes) override."""
        return self._perfile(paths)

    def _multithreaded(self, paths=None) -> Iterator[HostTable]:
        """Ordered prefetch with a bounded in-flight window: at most
        ~2x pool-size files are decoded ahead of the consumer, so host
        memory stays bounded and early iterator abandonment (limits) does
        not decode the whole dataset."""
        if paths is None:
            paths = self.paths
        nthreads = max(1, self.conf.get_entry(MULTITHREADED_READ_NUM_THREADS))
        window = min(len(paths), nthreads * 2)
        with cf.ThreadPoolExecutor(max_workers=min(nthreads, len(paths))) as pool:
            futures = {}
            next_submit = 0
            for i in range(len(paths)):
                while next_submit < len(paths) and next_submit < i + window:
                    futures[next_submit] = pool.submit(
                        self._read_with_partitions, paths[next_submit])
                    next_submit += 1
                yield futures.pop(i).result()

    def _describe_columns(self) -> str:
        """", 7 of 16 columns: a, b, ..." when the node reads fewer columns
        than the files hold, else ""."""
        if self.columns is None:
            return ""
        return (f", {len(self.columns)} of {self.full_width()} columns: "
                + ", ".join(self.columns))

    def describe(self):
        return (f"{type(self).__name__}[{len(self.paths)} files, "
                f"{self.reader_type}{self._describe_columns()}]")


def row_carrier_table(n: int) -> HostTable:
    """Placeholder 1-column table carrying only a row count — used when a
    projection touches no data columns (e.g. only Hive partition columns):
    the count still comes from the file, and the carrier column is dropped
    when _with_partition_columns re-selects the output schema."""
    return HostTable(["__rows__"], [
        HostColumn(T.LONG, np.zeros(n, dtype=np.int64))])
