"""Pandas/Arrow Python UDF plan nodes.

Reference (SURVEY.md §2.3 ``execution/python/``, 3,075 LoC):
``GpuArrowEvalPythonExec.scala`` (scalar pandas UDFs: device batch → Arrow
IPC → external Python worker → Arrow → device),
``GpuMapInPandasExec``/``GpuFlatMapGroupsInPandasExec``/
``GpuAggregateInPandasExec``, gated by ``PythonWorkerSemaphore``.

TPU mapping: the engine is already in-process Python, so the "worker" is
the user's function; the REAL boundary the reference models — device
columnar → Arrow host data → pandas and back — is preserved exactly
(execs/python_exec.py routes device batches through pyarrow), and
concurrent UDF evaluation is gated by the PythonWorkerSemaphore analog.
These nodes carry the plan shape + the CPU oracle path."""

from __future__ import annotations

from typing import Callable, Iterator, List, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostTable
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.ops.expr import Expression
from spark_rapids_tpu.plan.nodes import PlanNode, Schema


_DDL_TYPES = {
    "boolean": T.BOOLEAN, "byte": T.BYTE, "short": T.SHORT,
    "int": T.INT, "integer": T.INT, "long": T.LONG, "bigint": T.LONG,
    "float": T.FLOAT, "double": T.DOUBLE, "string": T.STRING,
    "date": T.DATE, "timestamp": T.TIMESTAMP,
}


def _normalize_schema(schema) -> Schema:
    """Accept [(name, DataType)] or a 'name type, name type' DDL string."""
    if isinstance(schema, str):
        out = []
        for part in schema.split(","):
            name, _, tname = part.strip().partition(" ")
            tname = tname.strip().lower()
            if tname not in _DDL_TYPES:
                raise ColumnarProcessingError(
                    f"unknown type {tname!r} in schema string (supported: "
                    f"{sorted(_DDL_TYPES)})")
            out.append((name, _DDL_TYPES[tname]))
        return out
    return list(schema)


def _pandas_to_host(pdf, schema: Schema) -> HostTable:
    """pandas → HostTable coerced to the declared result schema (the
    reference's Arrow-read side enforces the UDF's declared return type)."""
    import pyarrow as pa

    from spark_rapids_tpu.io.arrow_convert import (
        decode_to_schema,
        spark_type_to_arrow,
    )
    fields = [pa.field(n, spark_type_to_arrow(dt)) for n, dt in schema]
    try:
        at = pa.Table.from_pandas(pdf, schema=pa.schema(fields),
                                  preserve_index=False)
    except (pa.ArrowInvalid, pa.ArrowTypeError, KeyError) as e:
        raise ColumnarProcessingError(
            f"pandas UDF result does not match declared schema "
            f"{[(n, dt.simple_string()) for n, dt in schema]}: {e}")
    return decode_to_schema(at, schema)


class MapInPandas(PlanNode):
    """df.map_in_pandas(fn, schema): fn(iterator of pandas DataFrames) ->
    iterator of pandas DataFrames (Spark mapInPandas contract)."""

    def __init__(self, child: PlanNode, fn: Callable, schema):
        self.children = (child,)
        self.fn = fn
        self.schema = _normalize_schema(schema)

    def output_schema(self) -> Schema:
        return self.schema

    def execute_cpu(self) -> Iterator[HostTable]:
        def pdfs():
            for batch in self.children[0].execute_cpu():
                yield batch.to_pandas()
        for out in self.fn(pdfs()):
            yield _pandas_to_host(out, self.schema)

    def describe(self):
        return f"MapInPandas[{getattr(self.fn, '__name__', 'fn')}]"


class FlatMapGroupsInPandas(PlanNode):
    """df.group_by(keys).apply_in_pandas(fn, schema): fn(pandas DataFrame
    of one group) -> pandas DataFrame."""

    def __init__(self, child: PlanNode, keys: Sequence[str], fn: Callable,
                 schema):
        self.children = (child,)
        self.keys = list(keys)
        self.fn = fn
        self.schema = _normalize_schema(schema)
        child_names = {n for n, _ in child.output_schema()}
        for k in self.keys:
            if k not in child_names:
                raise ColumnarProcessingError(
                    f"grouping column {k!r} not in {sorted(child_names)}")

    def output_schema(self) -> Schema:
        return self.schema

    def _groups(self):
        batches = list(self.children[0].execute_cpu())
        if not batches:
            return
        pdf = HostTable.concat(batches).to_pandas()
        if len(pdf) == 0:
            return
        for _key, group in pdf.groupby(self.keys, dropna=False, sort=True):
            yield group.reset_index(drop=True)

    def execute_cpu(self) -> Iterator[HostTable]:
        for group in self._groups():
            out = self.fn(group)
            if len(out):
                yield _pandas_to_host(out, self.schema)

    def describe(self):
        return f"FlatMapGroupsInPandas[keys={self.keys}]"


class AggregateInPandas(PlanNode):
    """df.group_by(keys).agg(pandas grouped-agg UDFs): each UDF is
    fn(*pandas Series of the group) -> scalar."""

    def __init__(self, child: PlanNode, keys: Sequence[str],
                 aggs: Sequence[Tuple[str, Callable, T.DataType,
                                      Sequence[str]]]):
        self.children = (child,)
        self.keys = list(keys)
        self.aggs = list(aggs)  # (out_name, fn, return_type, arg_col_names)

    def output_schema(self) -> Schema:
        child_schema = dict(self.children[0].output_schema())
        return ([(k, child_schema[k]) for k in self.keys]
                + [(name, rt) for name, _fn, rt, _args in self.aggs])

    def execute_cpu(self) -> Iterator[HostTable]:
        import pandas as pd
        batches = list(self.children[0].execute_cpu())
        pdf = (HostTable.concat(batches).to_pandas() if batches
               else pd.DataFrame())
        rows = []
        if len(pdf):
            for key, group in pdf.groupby(self.keys, dropna=False,
                                          sort=True):
                if not isinstance(key, tuple):
                    key = (key,)
                row = dict(zip(self.keys, key))
                for name, fn, _rt, args in self.aggs:
                    row[name] = fn(*[group[a] for a in args])
                rows.append(row)
        out = pd.DataFrame(rows, columns=[n for n, _ in
                                          self.output_schema()])
        yield _pandas_to_host(out, self.output_schema())

    def describe(self):
        return f"AggregateInPandas[keys={self.keys}]"


class MapInArrow(PlanNode):
    """df.map_in_arrow(fn, schema): fn(iterator of pyarrow RecordBatches)
    -> iterator of pyarrow RecordBatches (Spark mapInArrow contract;
    reference: GpuMapInArrowExec in execution/python/)."""

    def __init__(self, child: PlanNode, fn: Callable, schema):
        self.children = (child,)
        self.fn = fn
        self.schema = _normalize_schema(schema)

    def output_schema(self) -> Schema:
        return self.schema

    def execute_cpu(self) -> Iterator[HostTable]:
        from spark_rapids_tpu.io.arrow_convert import host_table_to_arrow

        def rbs():
            for batch in self.children[0].execute_cpu():
                for rb in host_table_to_arrow(batch).to_batches():
                    yield rb
        for out in self.fn(rbs()):
            host = arrow_batch_to_host(out, self.schema)
            if host.num_rows:
                yield host

    def describe(self):
        return f"MapInArrow[{getattr(self.fn, '__name__', 'fn')}]"


def arrow_batch_to_host(rb, schema: Schema) -> HostTable:
    """pyarrow RecordBatch/Table → HostTable coerced to the declared
    schema (the Arrow-read side of the MapInArrow boundary)."""
    import pyarrow as pa

    from spark_rapids_tpu.io.arrow_convert import (
        decode_to_schema,
        spark_type_to_arrow,
    )
    if isinstance(rb, pa.RecordBatch):
        rb = pa.Table.from_batches([rb])
    fields = [pa.field(n, spark_type_to_arrow(dt)) for n, dt in schema]
    try:
        rb = rb.select([n for n, _ in schema]).cast(pa.schema(fields))
    except (pa.ArrowInvalid, pa.ArrowTypeError, KeyError) as e:
        raise ColumnarProcessingError(
            f"mapInArrow result does not match declared schema "
            f"{[(n, dt.simple_string()) for n, dt in schema]}: {e}")
    return decode_to_schema(rb, schema)


def _drain_to_pandas(child: PlanNode):
    """Drain a plan node's CPU path into ONE pandas frame; an empty
    result keeps the child's column names."""
    import pandas as pd
    batches = list(child.execute_cpu())
    if not batches:
        return pd.DataFrame(columns=[n for n, _ in child.output_schema()])
    return HostTable.concat(batches).to_pandas()


def align_cogroups(left_pdf, right_pdf, left_keys, right_keys):
    """Full outer alignment of two grouped frames by key (Spark cogroup
    semantics: the UDF sees every key present on either side, with an
    empty frame for the absent side)."""
    import pandas as pd

    def _norm(k):
        # NaN != NaN would keep null-key groups from matching across
        # sides; normalize to None so nulls cogroup (Spark semantics)
        k = k if isinstance(k, tuple) else (k,)
        return tuple(None if pd.isna(v) else v for v in k)

    lgroups = ({_norm(k): g.reset_index(drop=True)
                for k, g in left_pdf.groupby(left_keys, dropna=False,
                                             sort=True)}
               if len(left_pdf) else {})
    rgroups = ({_norm(k): g.reset_index(drop=True)
                for k, g in right_pdf.groupby(right_keys, dropna=False,
                                              sort=True)}
               if len(right_pdf) else {})
    lempty = left_pdf.iloc[0:0]
    rempty = right_pdf.iloc[0:0]
    for key in sorted(set(lgroups) | set(rgroups), key=repr):
        yield lgroups.get(key, lempty), rgroups.get(key, rempty)


class FlatMapCoGroupsInPandas(PlanNode):
    """df1.group_by(k).cogroup(df2.group_by(k)).apply_in_pandas(fn,
    schema): fn(left pandas DataFrame, right pandas DataFrame of one
    cogrouped key) -> pandas DataFrame. Reference:
    execution/python/GpuFlatMapCoGroupsInPandasExec.scala."""

    def __init__(self, left: PlanNode, right: PlanNode,
                 left_keys: Sequence[str], right_keys: Sequence[str],
                 fn: Callable, schema):
        if len(left_keys) != len(right_keys):
            raise ColumnarProcessingError(
                "cogroup key lists must have the same arity "
                f"({list(left_keys)} vs {list(right_keys)})")
        self.children = (left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.fn = fn
        self.schema = _normalize_schema(schema)

    def output_schema(self) -> Schema:
        return self.schema

    def execute_cpu(self) -> Iterator[HostTable]:
        left_pdf = _drain_to_pandas(self.children[0])
        right_pdf = _drain_to_pandas(self.children[1])
        for lg, rg in align_cogroups(left_pdf, right_pdf,
                                     self.left_keys, self.right_keys):
            out = self.fn(lg, rg)
            if len(out):
                yield _pandas_to_host(out, self.schema)

    def describe(self):
        return f"FlatMapCoGroupsInPandas[keys={self.left_keys}]"


class WindowInPandas(PlanNode):
    """Window-function pandas UDFs: child columns pass through, each UDF
    column appends fn evaluated over the row's window frame (reference:
    execution/python/GpuWindowInPandasExec.scala). ``udfs`` entries are
    (out_name, fn, return_type, arg col names, WindowSpec)."""

    def __init__(self, child: PlanNode, udfs):
        self.children = (child,)
        self.udfs = list(udfs)
        child_names = {n for n, _ in child.output_schema()}
        for name, _fn, _rt, args, spec in self.udfs:
            keys = list(args) + [getattr(e, "col_name", None)
                                 for e in spec.partition_exprs] \
                + [getattr(o.expr, "col_name", None) for o in spec.orders]
            for k in keys:
                if k not in child_names:
                    raise ColumnarProcessingError(
                        f"window pandas UDF {name}: column {k!r} not in "
                        f"{sorted(child_names)}")

    def output_schema(self) -> Schema:
        return (list(self.children[0].output_schema())
                + [(name, rt) for name, _f, rt, _a, _s in self.udfs])

    def execute_cpu(self) -> Iterator[HostTable]:
        import pandas as pd
        pdf = _drain_to_pandas(self.children[0])
        out_schema = self.output_schema()
        if len(pdf) == 0:
            yield _pandas_to_host(
                pd.DataFrame(columns=[n for n, _ in out_schema]),
                out_schema)
            return
        for name, fn, rt, args, spec in self.udfs:
            pdf[name] = eval_window_udf(pdf, fn, args, spec)
        yield _pandas_to_host(pdf, out_schema)

    def describe(self):
        return f"WindowInPandas[{[n for n, *_ in self.udfs]}]"


def _window_col_name(e) -> str:
    name = getattr(e, "col_name", None)
    if name is None:
        raise ColumnarProcessingError(
            "window pandas UDF partition/order keys must be plain "
            f"columns, got expression {e}")
    return name


def eval_window_udf(pdf, fn, arg_names, spec):
    """Evaluate one window pandas UDF over every partition of ``pdf``.

    Whole-partition (unbounded) frames call fn ONCE per partition
    (series in, scalar or aligned series out); the default ORDER BY
    frame (RANGE UNBOUNDED PRECEDING..CURRENT ROW) is a running
    aggregate whose frame ends at the last PEER of each row; bounded
    rows frames slice per row — the same frame classes the reference
    implements in GpuWindowInPandasExec."""
    import numpy as np
    import pandas as pd

    part_cols = [_window_col_name(e) for e in spec.partition_exprs]
    kind, lo, hi = spec.resolved_frame()
    running_range = kind == "range" and lo is None and hi == 0
    if kind == "range" and lo is None and hi is None:
        kind = "rows"  # RANGE fully unbounded == whole partition
        lo = hi = None
    elif kind == "range" and not running_range:
        raise ColumnarProcessingError(
            "window pandas UDFs support unbounded, running (default "
            "ORDER BY), or rows-based frames (Spark restriction)")

    out = pd.Series(index=pdf.index, dtype=object)
    groups = (pdf.groupby(part_cols, dropna=False, sort=False).groups.items()
              if part_cols else [((), pdf.index)])
    for _key, idx in groups:
        g = pdf.loc[idx]
        if len(g) == 0:
            continue
        by = [_window_col_name(o.expr) for o in spec.orders]
        if by:
            asc = [o.ascending for o in spec.orders]
            g = g.sort_values(by=by, ascending=asc, kind="stable")
        arg_series = [g[a] for a in arg_names]
        n = len(g)
        if kind == "rows" and (lo is not None or hi is not None):
            vals = []
            for i in range(n):
                a = 0 if lo is None else max(0, min(n, i + lo))
                b = n if hi is None else max(0, min(n, i + hi + 1))
                vals.append(fn(*[s.iloc[a:max(a, b)] for s in arg_series]))
            res = pd.Series(vals, index=g.index)
        elif running_range and by:
            # frame ends at the last peer (rows tied on ALL order keys
            # share one result — Spark RANGE CURRENT ROW semantics)
            keys = g[by]
            shifted = keys.shift()
            # nulls are peers of each other (Spark null ordering)
            new_grp = np.array((keys.ne(shifted)
                                & ~(keys.isna() & shifted.isna())).any(
                                    axis=1))
            new_grp[0] = True
            grp_ids = np.cumsum(new_grp) - 1
            ends = np.zeros(grp_ids[-1] + 1, dtype=np.int64)
            np.maximum.at(ends, grp_ids, np.arange(n) + 1)
            vals = [fn(*[s.iloc[0:e] for s in arg_series])
                    for e in ends]
            res = pd.Series([vals[gi] for gi in grp_ids], index=g.index)
        else:
            r = fn(*arg_series)
            res = (pd.Series(r, index=g.index) if np.ndim(r) else
                   pd.Series([r] * n, index=g.index))
        out.loc[res.index] = res
    return out


class ArrowEvalPython(PlanNode):
    """Scalar pandas UDFs appended as extra columns: each UDF is
    fn(*pandas Series) -> pandas Series aligned with the input
    (GpuArrowEvalPythonExec: child columns pass through, UDF results
    append)."""

    def __init__(self, child: PlanNode,
                 udfs: Sequence[Tuple[str, Callable, T.DataType,
                                      Sequence[Expression]]]):
        from spark_rapids_tpu.ops.expr import bind
        self.children = (child,)
        schema = child.output_schema()
        self.udfs = [(name, fn, rt, [bind(a, schema) for a in args])
                     for name, fn, rt, args in udfs]

    def output_schema(self) -> Schema:
        return (list(self.children[0].output_schema())
                + [(name, rt) for name, _f, rt, _a in self.udfs])

    def execute_cpu(self) -> Iterator[HostTable]:
        import pandas as pd
        for batch in self.children[0].execute_cpu():
            extra_schema = []
            frames = {}
            for name, fn, rt, args in self.udfs:
                arg_series = [pd.Series(a.eval_cpu(batch).to_pylist())
                              for a in args]
                result = fn(*arg_series)
                if len(result) != batch.num_rows:
                    raise ColumnarProcessingError(
                        f"scalar pandas UDF {name} returned {len(result)} "
                        f"rows for a {batch.num_rows}-row batch")
                frames[name] = result
                extra_schema.append((name, rt))
            extra = _pandas_to_host(pd.DataFrame(frames), extra_schema)
            yield HostTable(list(batch.names) + list(extra.names),
                            list(batch.columns) + list(extra.columns))

    def describe(self):
        return f"ArrowEvalPython[{[n for n, *_ in self.udfs]}]"


class PandasUDFExpr(Expression):
    """Marker expression produced by functions.pandas_udf(...); extracted
    by the DataFrame layer into ArrowEvalPython / AggregateInPandas nodes
    (the reference's GpuOverrides splits PythonUDF out of projects the
    same way). Never evaluated directly."""

    def __init__(self, fn: Callable, return_type: T.DataType,
                 children: Sequence[Expression], kind: str,
                 udf_name: str = ""):
        self.fn = fn
        self._return_type = return_type
        self.children = tuple(children)
        self.kind = kind  # "scalar" | "grouped_agg"
        self.udf_name = udf_name or getattr(fn, "__name__", "pandas_udf")

    @property
    def data_type(self) -> T.DataType:
        return self._return_type

    @property
    def name(self) -> str:
        return self.udf_name

    def with_children(self, children):
        return PandasUDFExpr(self.fn, self._return_type, children,
                             self.kind, self.udf_name)

    def key(self):
        return ("PandasUDF", id(self.fn),
                tuple(c.key() for c in self.children))

    def eval_cpu(self, table):
        raise ColumnarProcessingError(
            f"pandas UDF {self.udf_name} must appear as a top-level select/"
            "agg expression (optionally aliased), not nested inside other "
            "expressions")

    def over(self, spec) -> "WindowedPandasUDF":
        """Spark semantics: a GROUPED_AGG pandas UDF applied .over(window)
        becomes a window pandas UDF (GpuWindowInPandasExec)."""
        if self.kind != "grouped_agg":
            raise ColumnarProcessingError(
                "only grouped_agg pandas UDFs can be used over a window "
                "(Spark restriction)")
        return WindowedPandasUDF(self, spec)

    device_supported = False


class WindowedPandasUDF:
    """Marker produced by PandasUDFExpr.over(spec); consumed by
    DataFrame.with_windows, which plans a WindowInPandas node."""

    def __init__(self, udf: PandasUDFExpr, spec):
        self.udf = udf
        self.spec = spec


def pandas_udf(return_type, function_type: str = "scalar"):
    """Decorator/factory: F.pandas_udf(T.DOUBLE)(fn) or
    @F.pandas_udf("double"). Scalar UDFs take/return pandas Series per
    batch; grouped_agg UDFs take Series per group and return a scalar."""
    rt = _normalize_schema(f"x {return_type}")[0][1] \
        if isinstance(return_type, str) else return_type
    if function_type not in ("scalar", "grouped_agg"):
        raise ColumnarProcessingError(
            f"unknown pandas UDF function_type {function_type!r}")

    def wrap(fn):
        def call(*args):
            from spark_rapids_tpu.ops.expr import col
            exprs = [col(a) if isinstance(a, str) else a for a in args]
            return PandasUDFExpr(fn, rt, exprs, function_type)
        call.__name__ = getattr(fn, "__name__", "pandas_udf")
        call._is_pandas_udf = True
        call._function_type = function_type
        return call
    return wrap


def _strip_alias(e: Expression):
    from spark_rapids_tpu.ops.expr import Alias
    if isinstance(e, Alias):
        return e.children[0], e
    return e, None


def extract_scalar_udfs(plan: PlanNode, exprs: List[Expression],
                        names: List[str]):
    """DataFrame.select hook: if top-level scalar pandas UDFs appear,
    plan ArrowEvalPython(child) + Project; returns (plan, rewritten
    exprs) — the rewrite replaces each UDF with a column reference to the
    appended result column."""
    from spark_rapids_tpu.ops.expr import col
    udfs = []
    rewritten = []
    for e, out_name in zip(exprs, names):
        inner, _alias = _strip_alias(e)
        if isinstance(inner, PandasUDFExpr):
            if inner.kind != "scalar":
                raise ColumnarProcessingError(
                    f"grouped_agg pandas UDF {inner.udf_name} is only "
                    "valid in group_by(...).agg(...)")
            slot = f"__pandas_udf_{len(udfs)}__{out_name}"
            udfs.append((slot, inner.fn, inner.data_type,
                         list(inner.children)))
            rewritten.append(col(slot).alias(out_name))
        else:
            _reject_nested_udf(e)
            rewritten.append(e)
    if not udfs:
        return plan, exprs
    return ArrowEvalPython(plan, udfs), rewritten


def _reject_nested_udf(e: Expression):
    if isinstance(e, PandasUDFExpr):
        raise ColumnarProcessingError(
            f"pandas UDF {e.udf_name} must be a top-level select "
            "expression (optionally aliased)")
    for c in e.children:
        _reject_nested_udf(c)
