"""DataFrame builder API over the plan layer.

The reference integrates into Spark SQL transparently; standalone, this
PySpark-flavored DataFrame API is the user surface that builds CPU plans
which the overrides engine then rewrites onto the TPU (session.py)."""

from __future__ import annotations

from typing import Optional, Sequence

from spark_rapids_tpu.columnar import HostTable
from spark_rapids_tpu.ops.expr import (
    Alias,
    AttributeReference,
    Expression,
    col,
    lit,
    output_name,
)
from spark_rapids_tpu.plan import nodes as P


class DataFrame:
    def __init__(self, plan: P.PlanNode, session=None):
        self.plan = plan
        self.session = session

    # -- transformations ----------------------------------------------------
    def _wrap(self, plan: P.PlanNode) -> "DataFrame":
        return DataFrame(plan, self.session)

    def select(self, *exprs) -> "DataFrame":
        from spark_rapids_tpu.ops.collections import Explode
        exprs = [col(e) if isinstance(e, str) else e for e in exprs]

        # Spark rule: a generator (explode/posexplode) in the select list
        # plans as Generate(child) + Project; at most one generator
        gens = [(i, e) for i, e in enumerate(exprs)
                if isinstance(e, Explode)
                or (isinstance(e, Alias) and isinstance(e.children[0], Explode))]
        if gens:
            if len(gens) > 1:
                raise ValueError("only one generator per select (Spark rule)")
            i, e = gens[0]
            gen = e.children[0] if isinstance(e, Alias) else e
            if gen.pos:
                names = ["pos", output_name(e, "col")]
            else:
                names = [output_name(e, "col")]

            # requiredChildOutput: only columns the surrounding select
            # references pass through the Generate
            refs = set()

            def _walk_refs(x):
                if isinstance(x, AttributeReference):
                    refs.add(x.col_name)
                for ch in x.children:
                    _walk_refs(ch)

            for j, other in enumerate(exprs):
                if j != i:
                    _walk_refs(other)
            g = P.Generate(self.plan, gen.children[0], gen.pos, gen.outer,
                           names, required=sorted(refs))
            out = [col(n) if isinstance(n, str) else n
                   for n in ([*exprs[:i]]
                             + [col(n2) for n2 in names]
                             + [*exprs[i + 1:]])]
            return DataFrame(g, self.session)._wrap(P.Project(g, out))

        # scalar pandas UDFs in the select list plan as ArrowEvalPython +
        # Project (the reference splits PythonUDF out of projects the same
        # way — GpuArrowEvalPythonExec)
        from spark_rapids_tpu.plan.pandas_udf import (
            PandasUDFExpr,
            extract_scalar_udfs,
        )
        def _contains_udf(e):
            return isinstance(e, PandasUDFExpr) or any(
                _contains_udf(c) for c in e.children)

        if any(_contains_udf(e) for e in exprs):
            names = [output_name(e, f"col{i}") for i, e in enumerate(exprs)]
            plan, rewritten = extract_scalar_udfs(self.plan, exprs, names)
            return self._wrap(P.Project(plan, rewritten))
        return self._wrap(P.Project(self.plan, exprs))

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """fn(iterator of pandas DataFrames) -> iterator of pandas
        DataFrames (Spark mapInPandas; GpuMapInPandasExec analog)."""
        from spark_rapids_tpu.plan.pandas_udf import MapInPandas
        return self._wrap(MapInPandas(self.plan, fn, schema))

    mapInPandas = map_in_pandas

    def map_in_arrow(self, fn, schema) -> "DataFrame":
        """fn(iterator of pyarrow RecordBatches) -> iterator of pyarrow
        RecordBatches (Spark mapInArrow; GpuMapInArrowExec analog)."""
        from spark_rapids_tpu.plan.pandas_udf import MapInArrow
        return self._wrap(MapInArrow(self.plan, fn, schema))

    mapInArrow = map_in_arrow

    def with_column(self, name: str, expr: Expression) -> "DataFrame":
        existing = [col(n) for n, _ in self.plan.output_schema() if n != name]
        return self.select(*existing, expr.alias(name))

    def filter(self, condition: Expression) -> "DataFrame":
        return self._wrap(P.Filter(self.plan, condition))

    where = filter

    def group_by(self, *keys) -> "GroupedData":
        keys = [col(k) if isinstance(k, str) else k for k in keys]
        return GroupedData(self, keys)

    groupBy = group_by

    def agg(self, *aggs) -> "DataFrame":
        return GroupedData(self, []).agg(*aggs)

    def sort(self, *orders, ascending: bool = True) -> "DataFrame":
        sos = []
        for o in orders:
            if isinstance(o, str):
                o = col(o)
            if isinstance(o, P.SortOrder):
                sos.append(o)
            else:
                sos.append(P.SortOrder(o, ascending))
        return self._wrap(P.Sort(self.plan, sos))

    order_by = sort
    orderBy = sort

    def limit(self, n: int) -> "DataFrame":
        if isinstance(self.plan, P.Sort):
            # ORDER BY + LIMIT plans as TakeOrderedAndProject (per-batch
            # top-k, no full sorted materialization — Spark's planner rule)
            return self._wrap(P.TakeOrderedAndProject(
                self.plan.children[0], self.plan.orders, n))
        # LIMIT without ordering = CollectLimit (Spark's planner shape)
        return self._wrap(P.CollectLimit(self.plan, n))

    def sample(self, fraction: float, seed: int = 0) -> "DataFrame":
        return self._wrap(P.Sample(self.plan, fraction, seed))

    def cache(self) -> "DataFrame":
        return self._wrap(P.CachedRelation(self.plan, self.session))

    def union(self, other: "DataFrame") -> "DataFrame":
        return self._wrap(P.Union([self.plan, other.plan]))

    def join(self, other: "DataFrame", on=None, how: str = "inner") -> "DataFrame":
        if on is None:
            return self._wrap(P.Join(self.plan, other.plan, "cross", [], []))
        if isinstance(on, Expression):
            # arbitrary condition over both sides -> nested-loop join
            return self._wrap(P.Join(self.plan, other.plan, how, [], [],
                                     condition=on))
        if isinstance(on, str):
            on = [on]
        if isinstance(on, (list, tuple)) and on and isinstance(on[0], str):
            lk = [col(k) for k in on]
            rk = [col(k) for k in on]
            return self._wrap(P.Join(self.plan, other.plan, how, lk, rk))
        raise ValueError(
            "join `on` must be a column name, list of names, or a condition "
            "Expression")

    def stack(self, n: int, *exprs, names=None) -> "DataFrame":
        """stack(n, e1..ek): n output rows per input row with k/n columns
        (reference: GpuGenerateExec Stack). TPU rewrite: a UNION of n
        projections — fully static shapes, no generator kernel (row order
        across generated rows is unspecified, as in Spark)."""
        exprs = [col(e) if isinstance(e, str) else e for e in exprs]
        if n <= 0 or len(exprs) % n != 0:
            raise ValueError("stack(n, ...) needs a multiple of n exprs")
        width = len(exprs) // n
        if names is None:
            names = [f"col{i}" for i in range(width)]
        parts = []
        for r in range(n):
            row = [exprs[r * width + j].alias(names[j])
                   for j in range(width)]
            parts.append(self.select(*row).plan)
        return self._wrap(parts[0] if len(parts) == 1 else P.Union(parts))

    def replicate_rows(self, n_expr) -> "DataFrame":
        """replicate_rows(n): each row repeated n times (reference:
        GpuReplicateRows). TPU rewrite: explode(sequence(1, n)) and drop
        the sequence column — rides the existing Generate machinery."""
        from spark_rapids_tpu.functions import sequence
        n_expr = col(n_expr) if isinstance(n_expr, str) else n_expr
        from spark_rapids_tpu.ops.collections import Explode
        keep = [c for c, _ in self.plan.output_schema()]
        # rows with n <= 0 are DROPPED (GpuReplicateRows semantics);
        # filtering first also pins the sequence direction to ascending
        filtered = self.filter(n_expr > lit(0))
        seq = sequence(lit(1), n_expr, lit(1))
        exploded = filtered.select(*keep, Explode(seq).alias("__rep"))
        return exploded.select(*keep)

    def with_windows(self, **named_exprs) -> "DataFrame":
        """Append window-function columns:
        df.with_windows(rn=F.row_number().over(W.partition_by("k").order_by("v")))

        GROUPED_AGG pandas UDFs applied .over(spec) plan separately as a
        WindowInPandas node (GpuWindowInPandasExec analog)."""
        from spark_rapids_tpu.plan.pandas_udf import (
            WindowedPandasUDF,
            WindowInPandas,
        )
        builtin = [(n, e) for n, e in named_exprs.items()
                   if not isinstance(e, WindowedPandasUDF)]
        pandas_udfs = []
        for n, e in named_exprs.items():
            if isinstance(e, WindowedPandasUDF):
                args = []
                for a in e.udf.children:
                    if not isinstance(a, AttributeReference):
                        raise ValueError(
                            "window pandas UDF args must be plain columns")
                    args.append(a.col_name)
                for k in (list(e.spec.partition_exprs)
                          + [o.expr for o in e.spec.orders]):
                    if not isinstance(k, AttributeReference):
                        raise ValueError(
                            "window pandas UDF partition/order keys must "
                            f"be plain columns, got {k}")
                pandas_udfs.append((n, e.udf.fn, e.udf.data_type, args,
                                    e.spec))
        out = self
        if builtin:
            out = out._wrap(P.WindowNode(out.plan, builtin))
        if pandas_udfs:
            out = out._wrap(WindowInPandas(out.plan, pandas_udfs))
        return out

    def repartition(self, num_partitions: int, *keys) -> "DataFrame":
        keys = [col(k) if isinstance(k, str) else k for k in keys]
        mode = "hash" if keys else "roundrobin"
        return self._wrap(P.Exchange(self.plan, mode, num_partitions, keys))

    def create_or_replace_temp_view(self, name: str) -> None:
        """Register this DataFrame's plan as a temp view resolvable from
        session.sql() / session.table() (requires a session)."""
        if self.session is None:
            raise ValueError(
                "create_or_replace_temp_view requires a session-attached "
                "DataFrame")
        self.session.catalog.create_or_replace_temp_view(name, self)

    createOrReplaceTempView = create_or_replace_temp_view

    # -- actions ------------------------------------------------------------
    @property
    def schema(self):
        return self.plan.output_schema()

    @property
    def columns(self):
        return [n for n, _ in self.plan.output_schema()]

    def collect_table(self) -> HostTable:
        if self.session is not None:
            # SQL-origin DataFrames carry their text and the seconds
            # sql() took to lower it; hand both to the session so the
            # query event log records them
            sql_text = getattr(self, "sql_text", None)
            if sql_text is not None:
                self.session.next_query_sql = sql_text
                self.session.next_query_parse_s = getattr(
                    self, "parse_s", None)
            return self.session.execute(self.plan)
        return self.plan.collect_cpu()

    def collect(self):
        t = self.collect_table()
        cols = [c.to_pylist() for c in t.columns]
        return [tuple(c[i] for c in cols) for i in range(t.num_rows)]

    def to_pandas(self):
        return self.collect_table().to_pandas()

    def to_device_arrays(self):
        """Zero-copy device export (ColumnarRdd analog): {name: jax
        arrays} + row count, no host round trip. See to_device_arrays()."""
        return to_device_arrays(self)

    def to_pydict(self):
        return self.collect_table().to_pydict()

    def count(self) -> int:
        return self.collect_table().num_rows

    def explain(self) -> str:
        if self.session is not None:
            out = self.session.explain(self.plan)
        else:
            out = self.plan.tree_string()
        # SQL-origin plans (session.sql) carry their text so the explain
        # output ties fallback reasons back to the query
        sql_text = getattr(self, "sql_text", None)
        if sql_text:
            one_line = " ".join(sql_text.split())
            return f"-- SQL: {one_line}\n{out}"
        return out

    # -- writers (reference: GpuDataWritingCommandExec + format writers) ----
    def _write(self, fmt: str, path: str, partition_by, options):
        """Plan a WriteFiles command: the CHILD runs through the overrides
        engine (device when convertible), the write commits atomically
        (staging dir + rename + _SUCCESS), and the stats row returns."""
        node = P.WriteFiles(self.plan, fmt, path, partition_by, options)
        if self.session is not None:
            return self.session.execute(node)
        return node.collect_cpu()

    def write_parquet(self, path: str, partition_by=None, **options):
        return self._write("parquet", path, partition_by, options)

    def write_orc(self, path: str, partition_by=None, **options):
        return self._write("orc", path, partition_by, options)

    def write_csv(self, path: str, partition_by=None, **options):
        return self._write("csv", path, partition_by, options)

    def write_json(self, path: str, partition_by=None, **options):
        return self._write("json", path, partition_by, options)

    def write_hive_text(self, path: str, partition_by=None, **options):
        return self._write("hive_text", path, partition_by, options)

    def write_delta(self, path: str, mode: str = "error",
                    partition_by=None, merge_schema: bool = False) -> int:
        """Write as a Delta table; returns the committed version
        (reference: delta-lake module write path). ``merge_schema``
        allows adding columns (Spark mergeSchema)."""
        from spark_rapids_tpu.delta import write_delta
        return write_delta(self.plan, self.session, path, mode=mode,
                           partition_by=partition_by,
                           merge_schema=merge_schema)


class GroupedData:
    def __init__(self, df: DataFrame, keys: Sequence[Expression]):
        self.df = df
        self.keys = keys

    def _key_names(self, what: str):
        names = []
        for k in self.keys:
            if not isinstance(k, AttributeReference):
                raise ValueError(
                    f"{what} requires plain column-name grouping keys")
            names.append(k.col_name)
        return names

    def pivot(self, pivot_col: str, values) -> "PivotedData":
        """df.group_by(k).pivot(c, [v1, v2]).agg(...) — the reference's
        GpuPivotFirst surface. The TPU rewrite turns each (pivot value,
        aggregate) pair into a conditionally-masked aggregate
        (agg(when(c == v, x))) — the same rewrite Spark applies before
        PivotFirst, with no new device kernel."""
        return PivotedData(self, pivot_col, list(values))

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu.plan.pandas_udf import (
            AggregateInPandas,
            PandasUDFExpr,
        )

        def _udf_of(e):
            inner = e.children[0] if isinstance(e, Alias) else e
            return inner if isinstance(inner, PandasUDFExpr) else None

        udfs = [_udf_of(e) for e in aggs]
        if any(u is not None for u in udfs):
            if not all(u is not None and u.kind == "grouped_agg"
                       for u in udfs):
                raise ValueError(
                    "pandas grouped-agg UDFs cannot mix with built-in "
                    "aggregates in one agg() (Spark restriction)")
            keys = self._key_names("agg with pandas UDFs")
            entries = []
            for e, u in zip(aggs, udfs):
                out = output_name(e, u.udf_name)
                args = []
                for a in u.children:
                    if not isinstance(a, AttributeReference):
                        raise ValueError(
                            "pandas grouped-agg UDF args must be plain "
                            "columns")
                    args.append(a.col_name)
                entries.append((out, u.fn, u.data_type, args))
            return self.df._wrap(
                AggregateInPandas(self.df.plan, keys, entries))
        return self.df._wrap(P.Aggregate(self.df.plan, self.keys, list(aggs)))

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """fn(pandas DataFrame of one group) -> pandas DataFrame
        (Spark applyInPandas; GpuFlatMapGroupsInPandasExec analog)."""
        from spark_rapids_tpu.plan.pandas_udf import FlatMapGroupsInPandas
        keys = self._key_names("apply_in_pandas")
        return self.df._wrap(
            FlatMapGroupsInPandas(self.df.plan, keys, fn, schema))

    applyInPandas = apply_in_pandas

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """df1.group_by(k).cogroup(df2.group_by(k)) — Spark cogroup
        (GpuFlatMapCoGroupsInPandasExec analog)."""
        return CoGroupedData(self, other)


class CoGroupedData:
    """Pair of grouped DataFrames awaiting apply_in_pandas (pyspark's
    PandasCogroupedOps)."""

    def __init__(self, left: GroupedData, right: GroupedData):
        self.left = left
        self.right = right

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """fn(left pandas DataFrame, right pandas DataFrame of one
        cogrouped key) -> pandas DataFrame."""
        from spark_rapids_tpu.plan.pandas_udf import FlatMapCoGroupsInPandas
        lk = self.left._key_names("cogroup")
        rk = self.right._key_names("cogroup")
        return self.left.df._wrap(FlatMapCoGroupsInPandas(
            self.left.df.plan, self.right.df.plan, lk, rk, fn, schema))

    applyInPandas = apply_in_pandas


def from_pydict(data, dtypes=None, session=None, num_batches: int = 1) -> DataFrame:
    table = HostTable.from_pydict(data, dtypes)
    return from_host_table(table, session, num_batches)


def from_pandas(df, session=None, num_batches: int = 1) -> DataFrame:
    return from_host_table(HostTable.from_pandas(df), session, num_batches)


def from_host_table(table: HostTable, session=None, num_batches: int = 1) -> DataFrame:
    if num_batches <= 1 or table.num_rows == 0:
        batches = [table]
    else:
        per = -(-table.num_rows // num_batches)
        batches = [table.slice(i * per, min(per, table.num_rows - i * per))
                   for i in range(num_batches) if i * per < table.num_rows]
    return DataFrame(P.LocalScan(batches), session)


def range_df(start: int, end: Optional[int] = None, step: int = 1, session=None) -> DataFrame:
    if end is None:
        start, end = 0, start
    return DataFrame(P.RangeNode(start, end, step), session)


def to_device_arrays(df: "DataFrame"):
    """ColumnarRdd analog (reference: sql-plugin-api ColumnarRdd.scala:54
    — zero-copy GPU-table export for ML/XGBoost): execute the plan on
    device and hand back the raw jax arrays WITHOUT a host round trip:
    {name: (data, validity)} per column, plus the live row count. String
    columns export as (codes, validity, dictionary)."""
    from spark_rapids_tpu.overrides.rules import apply_overrides
    from spark_rapids_tpu.execs.base import DeviceToHost
    from spark_rapids_tpu.runtime.retry import retry_block
    if df.session is None:
        # session-less DataFrame: CPU plan, one upload at the end
        # (retry_block: a device-budget squeeze spills and replays)
        from spark_rapids_tpu.columnar import DeviceTable, HostTable
        host = HostTable.concat(list(df.plan.execute_cpu()))
        t = retry_block(lambda: DeviceTable.from_host(host))
        out = {}
        for name, c in zip(t.names, t.columns):
            out[name] = ((c.data, c.validity, c.dictionary)
                         if c.dictionary is not None
                         else (c.data, c.validity))
        return out, t.num_rows
    executable, _ = apply_overrides(df.plan, df.session.conf)
    if isinstance(executable, DeviceToHost):
        exec_dev = executable.tpu_exec
        batches = list(exec_dev.execute())
    else:
        # fully-fallen-back plan: upload the host result once
        from spark_rapids_tpu.columnar import DeviceTable, HostTable
        host = HostTable.concat(list(executable.execute_cpu()))
        batches = [retry_block(lambda: DeviceTable.from_host(host))]
    if len(batches) != 1:
        from spark_rapids_tpu.columnar.table import concat_device
        batches = [concat_device(batches)]
    t = batches[0]
    out = {}
    for name, c in zip(t.names, t.columns):
        if c.dictionary is not None:
            out[name] = (c.data, c.validity, c.dictionary)
        else:
            out[name] = (c.data, c.validity)
    return out, t.num_rows


class PivotedData:
    """group_by(...).pivot(col, values) — expands to masked aggregates."""

    def __init__(self, grouped: GroupedData, pivot_col: str, values):
        self.grouped = grouped
        self.pivot_col = pivot_col
        self.values = values

    def agg(self, *aggs) -> DataFrame:
        from spark_rapids_tpu.ops import aggregates as _agg
        from spark_rapids_tpu.ops.conditional import CaseWhen
        from spark_rapids_tpu.ops.expr import col as _col, lit as _lit
        from spark_rapids_tpu.ops.expr import Alias, output_name

        out = []
        for pv in self.values:
            for i, a in enumerate(aggs):
                name = output_name(a, f"agg{i}")
                fn = a.children[0] if isinstance(a, Alias) else a
                if not isinstance(fn, _agg.AggregateFunction):
                    raise ValueError(f"pivot agg must be an aggregate: {a!r}")
                if fn.child is None:  # count(*): count matching rows
                    masked = _agg.Count(CaseWhen(
                        _col(self.pivot_col) == _lit(pv), _lit(1)))
                else:
                    # with_children preserves extra ctor params
                    # (Percentile.percentage etc.)
                    masked = fn.with_children([CaseWhen(
                        _col(self.pivot_col) == _lit(pv), fn.child)])
                label = (f"{pv}" if len(aggs) == 1 else f"{pv}_{name}")
                out.append(Alias(masked, label))
        return self.grouped.agg(*out)
