"""CPU physical plan nodes with Spark-exact execution.

Reference analog: the Spark physical operators that GpuOverrides walks
(ProjectExec, FilterExec, HashAggregateExec, SortExec, *Join*Exec,
ShuffleExchangeExec ... — SURVEY.md §2.3 / Appendix B). Here they double as
the fallback implementations."""

from __future__ import annotations

import os

from dataclasses import dataclass
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.ops import aggregates as agg
from spark_rapids_tpu.ops.expr import (
    Alias,
    Expression,
    bind,
    evaluate_cpu,
    output_name,
)

Schema = List[Tuple[str, T.DataType]]


class PlanNode:
    children: Tuple["PlanNode", ...] = ()

    def output_schema(self) -> Schema:
        raise NotImplementedError

    def execute_cpu(self) -> Iterator[HostTable]:
        raise NotImplementedError

    def estimate_bytes(self) -> Optional[int]:
        """Rough output-size upper bound for physical planning (broadcast
        vs shuffle — the stats the reference reads from Spark's logical
        plan). None = unknown. Row-preserving/shrinking unary nodes
        propagate their child's estimate."""
        return None

    @property
    def name(self) -> str:
        return type(self).__name__

    def collect_cpu(self) -> HostTable:
        batches = list(self.execute_cpu())
        if not batches:
            return _empty_table(self.output_schema())
        return HostTable.concat(batches)

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + self.describe() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def describe(self) -> str:
        return self.name


def _empty_table(schema: Schema) -> HostTable:
    cols = []
    for _, dt in schema:
        if isinstance(dt, T.StringType):
            cols.append(HostColumn(dt, np.array([], dtype=object), np.array([], dtype=np.bool_)))
        else:
            cols.append(HostColumn(dt, np.array([], dtype=dt.np_dtype), np.array([], dtype=np.bool_)))
    return HostTable([n for n, _ in schema], cols)


class LocalScan(PlanNode):
    """In-memory scan over pre-built host batches (test/demo source; file
    scans live in io/)."""

    def __init__(self, batches: Sequence[HostTable]):
        if not batches:
            raise ColumnarProcessingError("LocalScan needs at least one batch")
        self.batches = list(batches)

    def output_schema(self):
        return self.batches[0].schema()

    def execute_cpu(self):
        yield from self.batches

    def describe(self):
        return f"LocalScan[{len(self.batches)} batches]"

    def estimate_bytes(self):
        return sum(b.nbytes() for b in self.batches)


class RangeNode(PlanNode):
    """spark.range analog (reference: GpuRangeExec)."""

    def __init__(self, start: int, end: int, step: int = 1, batch_rows: int = 1 << 20,
                 name: str = "id"):
        self.start, self.end, self.step = start, end, step
        self.batch_rows = batch_rows
        self.col_name = name

    def output_schema(self):
        return [(self.col_name, T.LONG)]

    def execute_cpu(self):
        total = max(0, -(-(self.end - self.start) // self.step))
        pos = 0
        while pos < total:
            cnt = min(self.batch_rows, total - pos)
            vals = self.start + (pos + np.arange(cnt, dtype=np.int64)) * self.step
            yield HostTable([self.col_name], [HostColumn(T.LONG, vals)])
            pos += cnt

    def describe(self):
        return f"Range({self.start}, {self.end}, {self.step})"


class Project(PlanNode):
    def __init__(self, child: PlanNode, exprs: Sequence[Expression]):
        from spark_rapids_tpu.ops.collections import Explode

        def _no_generators(e, top=False):
            if isinstance(e, Explode) and not top:
                raise ColumnarProcessingError(
                    "generators (explode/posexplode) are only valid as "
                    "top-level select expressions (Spark rule); use "
                    "df.select(..., F.explode(col))")
            for c in e.children:
                _no_generators(c)

        for e in exprs:
            # Alias(Explode) and bare Explode at top level are rewritten to
            # Generate by DataFrame.select BEFORE Project sees them; any
            # generator reaching here is misplaced
            _no_generators(e)
        self.children = (child,)
        schema = child.output_schema()
        self.exprs = [bind(e, schema) for e in exprs]
        self.names = [output_name(e, f"col{i}") for i, e in enumerate(exprs)]

    @property
    def child(self):
        return self.children[0]

    def output_schema(self):
        return [(n, e.data_type) for n, e in zip(self.names, self.exprs)]

    def execute_cpu(self):
        for batch in self.child.execute_cpu():
            yield evaluate_cpu(self.exprs, batch, self.names)

    def describe(self):
        return f"Project{self.names}"

    def estimate_bytes(self):
        # projections can WIDEN rows (duplicated/derived columns); scale the
        # child estimate by the column-count ratio so the broadcast
        # threshold check stays an upper-bound-ish heuristic
        est = self.children[0].estimate_bytes()
        if est is None:
            return None
        n_in = max(len(self.children[0].output_schema()), 1)
        return int(est * max(len(self.names), 1) / n_in) \
            if len(self.names) > n_in else est


class Filter(PlanNode):
    def __init__(self, child: PlanNode, condition: Expression):
        self.children = (child,)
        self.condition = bind(condition, child.output_schema())

    @property
    def child(self):
        return self.children[0]

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        for batch in self.children[0].execute_cpu():
            pred = self.condition.eval_cpu(batch)
            keep = pred.validity & pred.data.astype(np.bool_)
            idx = np.nonzero(keep)[0]
            cols = []
            for c in batch.columns:
                cols.append(HostColumn(c.dtype, c.data[idx], c.validity[idx]))
            yield HostTable(batch.names, cols)

    def describe(self):
        return f"Filter[{self.condition!r}]"

    def estimate_bytes(self):
        return self.children[0].estimate_bytes()


class Aggregate(PlanNode):
    """Hash aggregate (group-by or global)."""

    def __init__(self, child: PlanNode, grouping: Sequence[Expression],
                 aggregates: Sequence[Expression]):
        self.children = (child,)
        schema = child.output_schema()
        self.grouping = [bind(g, schema) for g in grouping]
        self.agg_specs: List[Tuple[str, agg.AggregateFunction]] = []
        for i, a in enumerate(aggregates):
            name = output_name(a, f"agg{i}")
            fn = a.children[0] if isinstance(a, Alias) else a
            if not isinstance(fn, agg.AggregateFunction):
                raise ColumnarProcessingError(f"not an aggregate: {a!r}")
            bound = bind(fn, schema)
            self.agg_specs.append((name, bound))
        self.grouping_names = [output_name(g, f"k{i}") for i, g in enumerate(self.grouping)]

    @property
    def child(self):
        return self.children[0]

    def output_schema(self):
        out = [(n, g.data_type) for n, g in zip(self.grouping_names, self.grouping)]
        out += [(n, fn.data_type) for n, fn in self.agg_specs]
        return out

    def execute_cpu(self):
        from spark_rapids_tpu.plan.cpu_agg import aggregate_cpu
        table = self.children[0].collect_cpu()
        yield aggregate_cpu(table, self.grouping, self.agg_specs)

    def describe(self):
        return f"Aggregate[keys={self.grouping_names}, aggs={[n for n, _ in self.agg_specs]}]"


@dataclass
class SortOrder:
    expr: Expression
    ascending: bool = True
    nulls_first: Optional[bool] = None  # Spark default: asc->first, desc->last

    def resolved_nulls_first(self) -> bool:
        return self.ascending if self.nulls_first is None else self.nulls_first


def _stable_sort_indices(cols: List[HostColumn], orders: List[SortOrder], n: int) -> np.ndarray:
    """Multi-key stable sort: apply keys least-significant first; each key is
    reduced to a dense integer rank (works for strings too, and makes
    descending order stable), with nulls ranked before/after all values per
    the order's nulls_first."""
    idx = np.arange(n)
    for col, order in reversed(list(zip(cols, orders))):
        if isinstance(col.dtype, T.StringType):
            vals = np.where(col.validity, col.data, "")
        else:
            vals = col.data
        sub_vals = vals[idx]
        sub_valid = col.validity[idx]
        uniq = np.unique(sub_vals)
        rank = np.searchsorted(uniq, sub_vals).astype(np.int64)
        if not order.ascending:
            rank = len(uniq) - 1 - rank
        null_rank = -1 if order.resolved_nulls_first() else len(uniq)
        rank = np.where(sub_valid, rank, null_rank)
        idx = idx[np.argsort(rank, kind="stable")]
    return idx


class Sort(PlanNode):
    def __init__(self, child: PlanNode, orders: Sequence[SortOrder], global_sort: bool = True):
        self.children = (child,)
        schema = child.output_schema()
        self.orders = [SortOrder(bind(o.expr, schema), o.ascending, o.nulls_first) for o in orders]
        self.global_sort = global_sort

    @property
    def child(self):
        return self.children[0]

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        table = self.children[0].collect_cpu()
        n = table.num_rows
        key_cols = [o.expr.eval_cpu(table) for o in self.orders]
        idx = _stable_sort_indices(key_cols, self.orders, n)
        cols = [HostColumn(c.dtype, c.data[idx], c.validity[idx]) for c in table.columns]
        yield HostTable(table.names, cols)

    def describe(self):
        return f"Sort[{len(self.orders)} keys]"

    def estimate_bytes(self):
        return self.children[0].estimate_bytes()


class Limit(PlanNode):
    def __init__(self, child: PlanNode, limit: int):
        self.children = (child,)
        self.limit = limit

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        remaining = self.limit
        for batch in self.children[0].execute_cpu():
            if remaining <= 0:
                return
            if batch.num_rows <= remaining:
                remaining -= batch.num_rows
                yield batch
            else:
                yield batch.slice(0, remaining)
                return

    def describe(self):
        return f"Limit[{self.limit}]"

    def estimate_bytes(self):
        return self.children[0].estimate_bytes()


class Union(PlanNode):
    def __init__(self, children: Sequence[PlanNode]):
        self.children = tuple(children)
        s0 = self.children[0].output_schema()
        for c in self.children[1:]:
            if [dt for _, dt in c.output_schema()] != [dt for _, dt in s0]:
                raise ColumnarProcessingError("UNION schema mismatch")

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        for c in self.children:
            yield from c.execute_cpu()


class Expand(PlanNode):
    """Rollup/cube support: replicate each input row through N projections
    (reference: GpuExpandExec)."""

    def __init__(self, child: PlanNode, projections: Sequence[Sequence[Expression]],
                 names: Sequence[str]):
        self.children = (child,)
        schema = child.output_schema()
        self.projections = [[bind(e, schema) for e in proj] for proj in projections]
        self.names = list(names)

    def output_schema(self):
        return [(n, e.data_type) for n, e in zip(self.names, self.projections[0])]

    def execute_cpu(self):
        for batch in self.children[0].execute_cpu():
            for proj in self.projections:
                yield evaluate_cpu(proj, batch, self.names)


class WindowNode(PlanNode):
    """Appends window-function columns (reference: GpuWindowExec appends
    window expressions to the child's output)."""

    def __init__(self, child: PlanNode, window_cols: Sequence[Tuple[str, "object"]]):
        self.children = (child,)
        schema = child.output_schema()
        self.window_cols = [(name, w.bind(schema)) for name, w in window_cols]

    def output_schema(self):
        return (self.children[0].output_schema()
                + [(n, w.data_type) for n, w in self.window_cols])

    def execute_cpu(self):
        from spark_rapids_tpu.ops.window import eval_window_cpu
        table = self.children[0].collect_cpu()
        cols = list(table.columns)
        names = list(table.names)
        for name, w in self.window_cols:
            cols.append(eval_window_cpu(table, w))
            names.append(name)
        yield HostTable(names, cols)

    def describe(self):
        return f"Window[{[n for n, _ in self.window_cols]}]"


class Join(PlanNode):
    """Equi-join (hash join analog). Types: inner, left, right, full, leftsemi,
    leftanti, cross."""

    def __init__(self, left: PlanNode, right: PlanNode, join_type: str,
                 left_keys: Sequence[Expression], right_keys: Sequence[Expression],
                 condition: Optional[Expression] = None):
        self.children = (left, right)
        self.join_type = join_type
        ls, rs = left.output_schema(), right.output_schema()
        self.left_keys = [bind(k, ls) for k in left_keys]
        self.right_keys = [bind(k, rs) for k in right_keys]
        self.condition = bind(condition, ls + rs) if condition is not None else None

    def output_schema(self):
        ls = self.children[0].output_schema()
        rs = self.children[1].output_schema()
        if self.join_type in ("leftsemi", "leftanti"):
            return ls
        return ls + rs

    def execute_cpu(self):
        from spark_rapids_tpu.plan.cpu_join import join_cpu
        left = self.children[0].collect_cpu()
        right = self.children[1].collect_cpu()
        yield join_cpu(left, right, self.join_type, self.left_keys,
                       self.right_keys, self.condition)

    def describe(self):
        return f"Join[{self.join_type}]"

    def estimate_bytes(self):
        """A keyed join's rows are taken to be no more than its larger
        side's (every foreign-key join), each with both sides' columns:
        the two estimates added. A semi/anti join keeps left rows. A
        keyless join is a product: unknown."""
        left = self.children[0].estimate_bytes()
        if self.join_type in ("leftsemi", "leftanti"):
            return left
        right = self.children[1].estimate_bytes()
        if not self.left_keys or left is None or right is None:
            return None
        return left + right


class Generate(PlanNode):
    """Generator node (explode/posexplode [outer]) — reference:
    GpuGenerateExec.scala. Output = child columns + [pos] + element column;
    non-outer drops rows with null/empty arrays, outer emits one null row."""

    def __init__(self, child: PlanNode, gen_child: Expression,
                 pos: bool, outer: bool, out_names: Sequence[str],
                 required: Optional[Sequence[str]] = None):
        self.children = (child,)
        schema = child.output_schema()
        self.gen_child = bind(gen_child, schema)
        if not isinstance(self.gen_child.data_type, T.ArrayType):
            raise ColumnarProcessingError(
                f"explode input must be an array, got "
                f"{self.gen_child.data_type.simple_string()}")
        self.pos = pos
        self.outer = outer
        self.out_names = list(out_names)
        # requiredChildOutput pruning (Spark Generate): only child columns
        # consumers actually reference pass through
        names = [n for n, _ in schema]
        self.required = [n for n in names
                         if required is None or n in set(required)]

    def output_schema(self):
        child_schema = dict(self.children[0].output_schema())
        out = [(n, child_schema[n]) for n in self.required]
        i = 0
        if self.pos:
            out.append((self.out_names[i], T.INT))
            i += 1
        out.append((self.out_names[i], self.gen_child.data_type.element_type))
        return out

    def execute_cpu(self):
        for full in self.children[0].execute_cpu():
            arr = self.gen_child.eval_cpu(full)
            keep = [full.names.index(n) for n in self.required]
            batch = HostTable([full.names[i] for i in keep],
                              [full.columns[i] for i in keep])
            e_dt = self.gen_child.data_type.element_type
            rows_idx, poss, vals, vvalid, pvalid = [], [], [], [], []
            # iterate the FULL batch: the pruned pass-through table may
            # have zero columns (explode with nothing else selected),
            # which would read as zero rows
            for i in range(full.num_rows):
                if arr.validity[i] and len(arr.data[i]):
                    for k, v in enumerate(arr.data[i]):
                        rows_idx.append(i)
                        poss.append(k)
                        vals.append(v if v is not None else 0)
                        vvalid.append(v is not None)
                        pvalid.append(True)
                elif self.outer:
                    rows_idx.append(i)
                    poss.append(0)
                    vals.append(0)
                    vvalid.append(False)
                    pvalid.append(False)  # pos null ONLY on outer null rows
            idx = np.asarray(rows_idx, dtype=np.int64)
            cols = [HostColumn(c.dtype, c.data[idx], c.validity[idx])
                    for c in batch.columns]
            names = list(batch.names)
            i = 0
            if self.pos:
                pv = np.asarray(poss, dtype=np.int32)
                cols.append(HostColumn(
                    T.INT, pv, np.asarray(pvalid, dtype=np.bool_)))
                names.append(self.out_names[i])
                i += 1
            cols.append(HostColumn(
                e_dt, np.asarray(vals, dtype=e_dt.np_dtype),
                np.asarray(vvalid, dtype=np.bool_)))
            names.append(self.out_names[i])
            yield HostTable(names, cols)

    def describe(self):
        kind = ("posexplode" if self.pos else "explode") + \
            ("_outer" if self.outer else "")
        return f"Generate[{kind}({self.gen_child!r})]"


class Sample(PlanNode):
    """Bernoulli sample without replacement (reference: GpuSampleExec /
    Spark SampleExec). Deterministic per (seed, row position)."""

    def __init__(self, child: PlanNode, fraction: float, seed: int = 0):
        self.children = (child,)
        self.fraction = float(fraction)
        self.seed = int(seed)

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        rng = np.random.default_rng(self.seed)
        for batch in self.children[0].execute_cpu():
            keep = rng.random(batch.num_rows) < self.fraction
            idx = np.nonzero(keep)[0]
            yield HostTable(batch.names,
                            [HostColumn(c.dtype, c.data[idx], c.validity[idx])
                             for c in batch.columns])

    def describe(self):
        return f"Sample[fraction={self.fraction}, seed={self.seed}]"


class TakeOrderedAndProject(PlanNode):
    """ORDER BY ... LIMIT n (+ optional projection) — reference:
    GpuTakeOrderedAndProjectExec: per-batch top-k, then merge."""

    def __init__(self, child: PlanNode, orders: Sequence["SortOrder"],
                 limit: int, project: Optional[Sequence[Expression]] = None):
        self.children = (child,)
        schema = child.output_schema()
        self.orders = [SortOrder(bind(o.expr, schema), o.ascending,
                                 o.nulls_first) for o in orders]
        self.limit = int(limit)
        self.project = ([bind(e, schema) for e in project]
                        if project is not None else None)
        self.project_names = ([output_name(e, f"col{i}")
                               for i, e in enumerate(project)]
                              if project is not None else None)

    def output_schema(self):
        if self.project is None:
            return self.children[0].output_schema()
        return [(n, e.data_type)
                for n, e in zip(self.project_names, self.project)]

    def execute_cpu(self):
        table = self.children[0].collect_cpu()
        cols = [o.expr.eval_cpu(table) for o in self.orders]
        perm = _stable_sort_indices(cols, self.orders, table.num_rows)
        take = perm[:self.limit]
        out = HostTable(table.names,
                        [HostColumn(c.dtype, c.data[take], c.validity[take])
                         for c in table.columns])
        if self.project is None:
            yield out
        else:
            yield evaluate_cpu(self.project, out, self.project_names)

    def describe(self):
        return f"TakeOrderedAndProject[limit={self.limit}]"


class WindowGroupLimit(PlanNode):
    """Pre-window group-limit (reference: GpuWindowGroupLimitExec, Spark
    3.5's WindowGroupLimit): when a rank()/row_number()/dense_rank()
    column is filtered to <= k right above the window, at most k(+ties)
    rows per partition need to ENTER the window at all. This node is a
    pure optimization — the exact filter stays above — so the CPU path
    is a passthrough and the device exec prunes."""

    def __init__(self, child: PlanNode, partition_exprs, orders,
                 rank_kind: str, limit: int):
        self.children = (child,)
        self.partition_exprs = list(partition_exprs)
        self.orders = list(orders)
        self.rank_kind = rank_kind  # rownumber | rank | denserank
        self.limit = int(limit)

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        yield from self.children[0].execute_cpu()

    def describe(self):
        return f"WindowGroupLimit[{self.rank_kind} <= {self.limit}]"


class CollectLimit(PlanNode):
    """LIMIT without ordering (reference: GpuCollectLimitExec)."""

    def __init__(self, child: PlanNode, limit: int):
        self.children = (child,)
        self.limit = int(limit)

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        remaining = self.limit
        for batch in self.children[0].execute_cpu():
            if remaining <= 0:
                return
            take = min(batch.num_rows, remaining)
            yield batch.slice(0, take)
            remaining -= take

    def describe(self):
        return f"CollectLimit[{self.limit}]"


class CachedRelation(PlanNode):
    """df.cache(): lazily materializes the child ONCE (through the full
    engine when a session is attached) and serves the result from memory;
    re-uploads hit the scan device cache, so repeated queries stay device-
    resident (reference: InMemoryTableScanExec + GpuInMemoryTableScan)."""

    def __init__(self, child: PlanNode, session=None):
        self.children = (child,)
        self._session = session
        self._table: Optional[HostTable] = None

    def materialize(self) -> HostTable:
        if self._table is None:
            if self._session is not None:
                self._table = self._session.execute(self.children[0])
            else:
                self._table = self.children[0].collect_cpu()
        return self._table

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        yield self.materialize()

    def estimate_bytes(self):
        if self._table is not None:
            return self._table.nbytes()
        return self.children[0].estimate_bytes()

    def describe(self):
        state = "materialized" if self._table is not None else "lazy"
        return f"CachedRelation[{state}]"


class WriteFiles(PlanNode):
    """Data-writing command (reference: GpuDataWritingCommandExec +
    GpuFileFormatDataWriter): runs the child (on device when convertible —
    this node itself stays host-side like the reference's write encode),
    writes files under the TRANSACTIONAL commit protocol
    (io/committer.py: stage into _temporary/<job>/<attempt>/, atomic
    per-file promotion at task commit, a _SUCCESS MANIFEST at job
    commit, full rollback on abort), and returns one stats row
    (numFiles, numRows, numBytes).

    The job id is fixed at plan time, so re-executing the SAME node —
    the query service's worker-loss/device-loss replay resubmits the
    handle's original plan — is idempotent: a rerun that finds its own
    job id in the destination manifest returns the recorded stats
    instead of writing twice; a rerun after a mid-write crash
    re-stages and re-promotes the same deterministic filenames."""

    def __init__(self, child: PlanNode, fmt: str, path: str,
                 partition_by: Optional[Sequence[str]] = None,
                 options: Optional[dict] = None):
        import uuid as _uuid
        self.children = (child,)
        self.fmt = fmt
        self.path = path
        self.partition_by = list(partition_by) if partition_by else None
        self.options = dict(options or {})
        #: idempotency key: stable across replays of this plan node
        self.job_id = _uuid.uuid4().hex[:16]
        self._attempt = 0

    def output_schema(self):
        return [("numFiles", T.LONG), ("numRows", T.LONG),
                ("numBytes", T.LONG)]

    def _writer(self):
        from spark_rapids_tpu import io as _io_pkg
        return {
            "parquet": _io_pkg.write_parquet,
            "orc": _io_pkg.write_orc,
            "csv": _io_pkg.write_csv,
            "json": _io_pkg.write_json,
            "hive_text": _io_pkg.write_hive_text,
        }[self.fmt]

    def _stats_row(self, num_files: int, num_rows: int, num_bytes: int):
        return HostTable(
            ["numFiles", "numRows", "numBytes"],
            [HostColumn(T.LONG, np.asarray([num_files], dtype=np.int64)),
             HostColumn(T.LONG, np.asarray([num_rows], dtype=np.int64)),
             HostColumn(T.LONG, np.asarray([num_bytes], dtype=np.int64))])

    def execute_cpu(self):
        from spark_rapids_tpu.io.committer import WriteJob, read_manifest

        # exactly-once replay: this job already committed (the service
        # requeued a write whose worker died AFTER job commit) — serve
        # the manifest's stats, do not double-write
        manifest = read_manifest(self.path)
        if manifest is not None and manifest.get("jobId") == self.job_id:
            yield self._stats_row(manifest["numFiles"],
                                  manifest["numRows"],
                                  manifest["numBytes"])
            return

        table = self.children[0].collect_cpu()
        job = WriteJob(self.path, job_id=self.job_id,
                       attempt=self._attempt)
        self._attempt += 1
        try:
            self._writer()(table, self.path,
                           partition_by=self.partition_by,
                           committer=job, **self.options)
            final_files = job.commit_task()
            manifest = job.commit_job(num_rows=table.num_rows)
        except BaseException:
            # any failure — injected fault, device loss mid-drain of a
            # downstream re-read, a full disk — rolls the job back:
            # promoted files deleted, staging swept
            job.abort()
            raise
        yield self._stats_row(len(final_files), table.num_rows,
                              manifest["numBytes"])

    def describe(self):
        part = f", partitionBy={self.partition_by}" if self.partition_by else ""
        return f"WriteFiles[{self.fmt} -> {self.path}{part}]"


class Exchange(PlanNode):
    """Shuffle exchange placeholder: single-process CPU path is pass-through;
    the TPU path repartitions batches (parallel/exchange.py)."""

    def __init__(self, child: PlanNode, partitioning: str, num_partitions: int,
                 keys: Sequence[Expression] = ()):
        self.children = (child,)
        self.partitioning = partitioning
        self.num_partitions = num_partitions
        schema = child.output_schema()
        self.keys = [bind(k, schema) for k in keys]

    def output_schema(self):
        return self.children[0].output_schema()

    def execute_cpu(self):
        yield from self.children[0].execute_cpu()

    def describe(self):
        return f"Exchange[{self.partitioning}, n={self.num_partitions}]"
