"""Expression base classes and the device compilation machinery."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Iterator, List, NamedTuple, Optional, Sequence, Tuple

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceColumn, DeviceTable, HostColumn, HostTable, bucket_for
from spark_rapids_tpu.errors import ColumnarProcessingError, UnsupportedOnTpu


class DevVal(NamedTuple):
    """A traced intermediate: data array + validity array (bool)."""

    data: jax.Array
    validity: jax.Array


@dataclass
class NodePrep:
    """Host-side per-batch preparation result for one expression node."""

    out_dict: Optional[np.ndarray] = None  # dictionary if output is STRING
    dict_sorted: bool = True
    aux_slots: Tuple[int, ...] = ()
    extra: dict = field(default_factory=dict)
    #: (min, max) bound on valid values of an integer-family output
    #: (DeviceColumn.domain carried through prep; per-batch data, NOT part
    #: of the trace key — consumers must feed the bounds in as device
    #: operands, never bake them into the trace)
    out_domain: Optional[Tuple[int, int]] = None


class PrepCtx:
    """Accumulates auxiliary device inputs during the host prep pass."""

    def __init__(self, table: DeviceTable):
        self.table = table
        self.aux_arrays: List[np.ndarray] = []
        self.aux_intern: List[bool] = []

    def add_aux(self, arr: np.ndarray, intern: bool = True) -> int:
        """Register a host array as a device input, padded (on the leading
        dim) to a bucket so that compiled programs are shared across batches
        with different dictionary sizes."""
        n = len(arr)
        cap = bucket_for(max(n, 1))
        if cap != n:
            padded = np.zeros((cap,) + arr.shape[1:], dtype=arr.dtype)
            padded[:n] = arr
            arr = padded
        self.aux_arrays.append(arr)
        self.aux_intern.append(intern)
        return len(self.aux_arrays) - 1


class EvalCtx:
    """Traced-side context handed to eval_dev. ``live`` carries a masked
    batch's liveness (DeviceTable.live); row-position semantics stay
    slot-based either way."""

    def __init__(self, cols: Sequence[DevVal], aux: Sequence[jax.Array],
                 nrows: jax.Array, capacity: int, live=None,
                 ansi: bool = False):
        self.cols = tuple(cols)
        self.aux = tuple(aux)
        self.nrows = nrows
        self.capacity = capacity
        self.live = live
        #: ANSI mode: expressions append (label, device bool flag) pairs
        #: for violations in LIVE rows; the hosting kernel returns them
        self.ansi = ansi
        self.ansi_errors: List[tuple] = []
        #: branch-selection mask: inside a CASE WHEN / IF branch only the
        #: selected rows may raise (Spark evaluates branches lazily; the
        #: engine evaluates eagerly and guards the error check instead)
        self.ansi_guard = None
        self._prep_iter: Optional[Iterator[NodePrep]] = None

    def ansi_check(self, label: str, bad) -> None:
        """Record an ANSI violation flag (True anywhere = error). Callers
        pass ``bad`` already masked to valid, live rows."""
        if self.ansi_guard is not None:
            bad = bad & self.ansi_guard
        self.ansi_errors.append(
            (label, jnp.any(bad & self.row_mask())))

    def guarded(self, mask):
        """Context manager scoping ansi_check to ``mask``-selected rows
        (composes with an enclosing guard for nested conditionals)."""
        import contextlib

        @contextlib.contextmanager
        def cm():
            prev = self.ansi_guard
            self.ansi_guard = mask if prev is None else (prev & mask)
            try:
                yield
            finally:
                self.ansi_guard = prev
        return cm()

    def next_prep(self) -> NodePrep:
        return next(self._prep_iter)  # type: ignore[arg-type]

    def row_mask(self) -> jax.Array:
        if self.live is not None:
            return self.live
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.nrows


class Expression:
    """Base expression. Subclasses set ``children`` and implement the three
    evaluation paths. Expressions are immutable; ``with_children`` rebuilds."""

    children: Tuple["Expression", ...] = ()

    #: True for expressions whose value depends on a row's physical slot
    #: (monotonically_increasing_id, rand): masked batches must compact
    #: before evaluating them so slot numbering matches the prefix form
    position_dependent = False

    # --- static properties -------------------------------------------------
    @property
    def data_type(self) -> T.DataType:
        raise NotImplementedError(type(self).__name__)

    @property
    def nullable(self) -> bool:
        return any(c.nullable for c in self.children) if self.children else True

    @property
    def name(self) -> str:
        return type(self).__name__

    def with_children(self, children: Sequence["Expression"]) -> "Expression":
        raise NotImplementedError(type(self).__name__)

    def key(self) -> tuple:
        """Structural key for the compile cache. Must capture everything
        that changes the traced computation (not per-batch data)."""
        return (self.name, tuple(c.key() for c in self.children))

    def __repr__(self):
        args = ", ".join(repr(c) for c in self.children)
        return f"{self.name}({args})"

    # --- binding -----------------------------------------------------------
    def bind(self, schema: Sequence[Tuple[str, T.DataType]]) -> "Expression":
        bound = [c.bind(schema) for c in self.children]
        return self.resolve(bound)

    def resolve(self, bound_children: Sequence["Expression"]) -> "Expression":
        """Hook for type coercion: may insert casts or rewrite. Default:
        rebuild with bound children."""
        return self.with_children(bound_children)

    # --- CPU path (Spark-exact oracle) ------------------------------------
    def eval_cpu(self, table: HostTable) -> HostColumn:
        raise NotImplementedError(f"{self.name}.eval_cpu")

    # --- device path -------------------------------------------------------
    def prep(self, pctx: PrepCtx, child_preps: Sequence[NodePrep]) -> NodePrep:
        return NodePrep()

    def eval_dev(self, ctx: EvalCtx, child_vals: Sequence[DevVal],
                 prep: NodePrep) -> DevVal:
        raise UnsupportedOnTpu(f"{self.name} has no device implementation")

    #: False for expressions that only have a CPU path; the overrides layer
    #: uses this to tag fallbacks.
    device_supported: bool = True

    # --- operator sugar for the DataFrame API ------------------------------
    def _bin(self, opcls, other, reflect=False):
        other = other if isinstance(other, Expression) else Literal.of(other)
        return opcls(other, self) if reflect else opcls(self, other)

    def __add__(self, o):
        from spark_rapids_tpu.ops.arithmetic import Add
        return self._bin(Add, o)

    def __radd__(self, o):
        from spark_rapids_tpu.ops.arithmetic import Add
        return self._bin(Add, o, True)

    def __sub__(self, o):
        from spark_rapids_tpu.ops.arithmetic import Subtract
        return self._bin(Subtract, o)

    def __rsub__(self, o):
        from spark_rapids_tpu.ops.arithmetic import Subtract
        return self._bin(Subtract, o, True)

    def __mul__(self, o):
        from spark_rapids_tpu.ops.arithmetic import Multiply
        return self._bin(Multiply, o)

    def __rmul__(self, o):
        from spark_rapids_tpu.ops.arithmetic import Multiply
        return self._bin(Multiply, o, True)

    def __truediv__(self, o):
        from spark_rapids_tpu.ops.arithmetic import Divide
        return self._bin(Divide, o)

    def __mod__(self, o):
        from spark_rapids_tpu.ops.arithmetic import Remainder
        return self._bin(Remainder, o)

    def __neg__(self):
        from spark_rapids_tpu.ops.arithmetic import UnaryMinus
        return UnaryMinus(self)

    def __eq__(self, o):  # type: ignore[override]
        from spark_rapids_tpu.ops.predicates import EqualTo
        return self._bin(EqualTo, o)

    def __ne__(self, o):  # type: ignore[override]
        from spark_rapids_tpu.ops.predicates import EqualTo, Not
        return Not(self._bin(EqualTo, o))

    def __lt__(self, o):
        from spark_rapids_tpu.ops.predicates import LessThan
        return self._bin(LessThan, o)

    def __le__(self, o):
        from spark_rapids_tpu.ops.predicates import LessThanOrEqual
        return self._bin(LessThanOrEqual, o)

    def __gt__(self, o):
        from spark_rapids_tpu.ops.predicates import GreaterThan
        return self._bin(GreaterThan, o)

    def __ge__(self, o):
        from spark_rapids_tpu.ops.predicates import GreaterThanOrEqual
        return self._bin(GreaterThanOrEqual, o)

    def __and__(self, o):
        from spark_rapids_tpu.ops.predicates import And
        return self._bin(And, o)

    def __or__(self, o):
        from spark_rapids_tpu.ops.predicates import Or
        return self._bin(Or, o)

    def __invert__(self):
        from spark_rapids_tpu.ops.predicates import Not
        return Not(self)

    def __hash__(self):
        return hash(self.key())

    def alias(self, name: str) -> "Alias":
        return Alias(self, name)

    def cast(self, dtype) -> "Expression":
        from spark_rapids_tpu.ops.cast import Cast
        if isinstance(dtype, str):
            dtype = T.parse_type(dtype)
        return Cast(self, dtype)

    def isnull(self):
        from spark_rapids_tpu.ops.predicates import IsNull
        return IsNull(self)

    def isnotnull(self):
        from spark_rapids_tpu.ops.predicates import IsNotNull
        return IsNotNull(self)


class AttributeReference(Expression):
    """Unresolved column-by-name (pre-binding)."""

    def __init__(self, col_name: str):
        self.col_name = col_name

    @property
    def name(self):
        return f"'{self.col_name}"

    @property
    def data_type(self):
        raise ColumnarProcessingError(f"unresolved attribute {self.col_name}")

    def key(self):
        return ("attr", self.col_name)

    def bind(self, schema):
        for i, (n, dt) in enumerate(schema):
            if n == self.col_name:
                return BoundReference(i, dt, name_hint=self.col_name)
        raise ColumnarProcessingError(
            f"column {self.col_name!r} not in {[n for n, _ in schema]}")

    def __repr__(self):
        return f"col({self.col_name!r})"


class BoundReference(Expression):
    """Input column by ordinal (post-binding)."""

    def __init__(self, ordinal: int, dtype: T.DataType, nullable_: bool = True,
                 name_hint: str = ""):
        self.ordinal = ordinal
        self._dtype = dtype
        self._nullable = nullable_
        self.name_hint = name_hint

    @property
    def data_type(self):
        return self._dtype

    @property
    def nullable(self):
        return self._nullable

    def key(self):
        return ("ref", self.ordinal, str(self._dtype))

    def with_children(self, children):
        return self

    def eval_cpu(self, table: HostTable) -> HostColumn:
        return table.columns[self.ordinal]

    def prep(self, pctx: PrepCtx, child_preps) -> NodePrep:
        c = pctx.table.columns[self.ordinal]
        # lambda-scope evaluation binds SimpleNamespace pseudo-columns
        # (ops/nested.py), hence getattr
        return NodePrep(out_dict=c.dictionary, dict_sorted=c.dict_sorted,
                        out_domain=getattr(c, "domain", None))

    def eval_dev(self, ctx: EvalCtx, child_vals, prep) -> DevVal:
        return ctx.cols[self.ordinal]

    def __repr__(self):
        return f"#{self.ordinal}:{self._dtype}"


class Literal(Expression):
    def __init__(self, value, dtype: Optional[T.DataType] = None):
        self._dtype = dtype if dtype is not None else T.python_to_spark_type(value)
        # temporal literals normalize to the INTERNAL representation
        # (days / UTC micros) at construction so both eval paths fill
        # plain ints
        import datetime as _dt
        if isinstance(value, _dt.datetime):
            epoch = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)
            v = value if value.tzinfo is not None else \
                value.replace(tzinfo=_dt.timezone.utc)
            value = (v - epoch) // _dt.timedelta(microseconds=1)
        elif isinstance(value, _dt.date):
            value = (value - _dt.date(1970, 1, 1)).days
        self.value = value

    @staticmethod
    def of(value, dtype: Optional[T.DataType] = None) -> "Literal":
        return Literal(value, dtype)

    @property
    def data_type(self):
        return self._dtype

    @property
    def nullable(self):
        return self.value is None

    def key(self):
        # literal VALUE is part of the traced constant, so it is in the key;
        # string literals trace as code 0 over a 1-entry dict, so only
        # null-ness matters for them.
        if isinstance(self._dtype, T.StringType):
            return ("lit", "str", self.value is None)
        return ("lit", str(self._dtype), self.value)

    def with_children(self, children):
        return self

    def eval_cpu(self, table: HostTable) -> HostColumn:
        n = table.num_rows
        validity = np.full(n, self.value is not None, dtype=np.bool_)
        if isinstance(self._dtype, T.StringType):
            data = np.full(n, self.value, dtype=object)
        else:
            fill = self.value if self.value is not None else 0
            data = np.full(n, fill, dtype=self._dtype.np_dtype)
        return HostColumn(self._dtype, data, validity)

    def prep(self, pctx: PrepCtx, child_preps) -> NodePrep:
        if isinstance(self._dtype, T.StringType) and self.value is not None:
            return NodePrep(out_dict=np.array([self.value], dtype=object))
        return NodePrep()

    def eval_dev(self, ctx: EvalCtx, child_vals, prep) -> DevVal:
        cap = ctx.capacity
        if isinstance(self._dtype, T.StringType):
            data = jnp.zeros(cap, dtype=jnp.int32)
        elif self.value is None and T.is_dec128(self._dtype):
            # a typed NULL in the two-limb layout of its column kind (the
            # streaming decimal sum's overflow arm: If(.., NULL, sum))
            data = jnp.zeros((cap, 2), dtype=jnp.int64)
        else:
            fill = self.value if self.value is not None else 0
            data = jnp.full(cap, fill, dtype=self._dtype.np_dtype)
        validity = jnp.full(cap, self.value is not None, dtype=jnp.bool_)
        return DevVal(data, validity)

    def __repr__(self):
        return f"lit({self.value!r})"


class Alias(Expression):
    def __init__(self, child: Expression, out_name: str):
        self.children = (child,)
        self.out_name = out_name

    @property
    def data_type(self):
        return self.children[0].data_type

    @property
    def nullable(self):
        return self.children[0].nullable

    def key(self):
        return ("alias", self.children[0].key())

    def with_children(self, children):
        return Alias(children[0], self.out_name)

    def eval_cpu(self, table):
        return self.children[0].eval_cpu(table)

    def prep(self, pctx, child_preps):
        return child_preps[0]

    def eval_dev(self, ctx, child_vals, prep):
        return child_vals[0]

    def __repr__(self):
        return f"{self.children[0]!r} AS {self.out_name}"


def col(name: str) -> AttributeReference:
    return AttributeReference(name)


def lit(value, dtype: Optional[T.DataType] = None) -> Literal:
    return Literal(value, dtype)


def output_name(expr: Expression, default: str) -> str:
    if isinstance(expr, Alias):
        return expr.out_name
    if isinstance(expr, AttributeReference):
        return expr.col_name
    if isinstance(expr, BoundReference) and expr.name_hint:
        return expr.name_hint
    return default


def bind(expr: Expression, schema: Sequence[Tuple[str, T.DataType]]) -> Expression:
    return expr.bind(schema)


# ---------------------------------------------------------------------------
# Evaluation drivers
# ---------------------------------------------------------------------------

def evaluate_cpu(exprs: Sequence[Expression], table: HostTable,
                 names: Optional[Sequence[str]] = None) -> HostTable:
    """Project on the CPU path."""
    out_names = list(names) if names else [
        output_name(e, f"col{i}") for i, e in enumerate(exprs)]
    return HostTable(out_names, [e.eval_cpu(table) for e in exprs])


def _walk_prep(expr: Expression, pctx: PrepCtx, out: List[NodePrep]) -> NodePrep:
    child_preps = [_walk_prep(c, pctx, out) for c in expr.children]
    p = expr.prep(pctx, child_preps)
    out.append(p)
    return p


def _walk_eval(expr: Expression, ctx: EvalCtx) -> DevVal:
    walk = getattr(expr, "eval_walk", None)
    if walk is not None:
        # conditionals control their own child evaluation (branch guards);
        # they must consume preps in the standard post-order
        return walk(ctx)
    child_vals = [_walk_eval(c, ctx) for c in expr.children]
    p = ctx.next_prep()
    return expr.eval_dev(ctx, child_vals, p)


def _prep_trace_key(preps: List[NodePrep]) -> tuple:
    """Everything in a NodePrep that eval_dev may consume at TRACE time.

    Contract for eval_dev implementations: per-batch data (dictionary
    contents, literal codes, remap tables, hashes...) must flow through aux
    arrays; only aux slot assignment and items recorded in ``extra`` may
    shape the trace. This is what makes the jit cache sound across batches."""
    return tuple(
        (p.aux_slots, p.out_dict is not None, p.dict_sorted,
         tuple(sorted(p.extra.items())))
        for p in preps
    )


class CompiledProject:
    """A fused, jitted projection of one or more expression trees over a
    device table. Reused across batches via ProjectCache; within one
    CompiledProject, jitted traces are cached per (capacity, prep structure)
    and jax.jit's signature cache handles aux shapes/dtypes."""

    def __init__(self, exprs: Sequence[Expression]):
        self.exprs = tuple(exprs)
        self._traces = {}

    def _get_traced(self, capacity: int, all_preps: List[List[NodePrep]],
                    has_mask: bool, ansi: bool):
        tkey = (capacity, has_mask, ansi,
                tuple(_prep_trace_key(p) for p in all_preps))
        got = self._traces.get(tkey)
        if got is None:
            exprs = self.exprs
            labels: List[str] = []  # filled at trace time, stable per key

            def traced(cols, aux, nrows, live):
                outs = []
                errs = []
                for e, preps in zip(exprs, all_preps):
                    ctx = EvalCtx(cols, aux, nrows, capacity, live=live,
                                  ansi=ansi)
                    ctx._prep_iter = iter(preps)
                    outs.append(_walk_eval(e, ctx))
                    errs.extend(ctx.ansi_errors)
                labels.clear()
                labels.extend(lbl for lbl, _ in errs)
                return outs, tuple(f for _, f in errs)

            got = (tpu_jit(traced, name="project"), labels)
            self._traces[tkey] = got
        return got

    def __call__(self, table: DeviceTable) -> List[DeviceColumn]:
        from spark_rapids_tpu.dispatch import ANSI_MODE, prep_aux
        pctx = PrepCtx(table)
        all_preps: List[List[NodePrep]] = []
        for e in self.exprs:
            preps: List[NodePrep] = []
            _walk_prep(e, pctx, preps)
            all_preps.append(preps)
        col_arrays = tuple(DevVal(c.data, c.validity) for c in table.columns)
        aux_arrays = prep_aux(pctx)

        fn, labels = self._get_traced(table.capacity, all_preps,
                                      table.live is not None,
                                      ANSI_MODE.get())
        out_vals, err_flags = fn(col_arrays, aux_arrays, table.nrows_dev,
                                 table.live)
        deliver_ansi_flags(labels, err_flags)

        out_cols = []
        for e, preps, dv in zip(self.exprs, all_preps, out_vals):
            root_prep = preps[-1]
            out_cols.append(DeviceColumn(
                e.data_type, dv.data, dv.validity,
                dictionary=root_prep.out_dict, dict_sorted=root_prep.dict_sorted,
                domain=root_prep.out_domain))
        return out_cols


def deliver_ansi_flags(labels, err_flags) -> None:
    """Route a kernel's ANSI violation flags: through the speculation
    context (rides the collect's packed fetch — zero extra round trips)
    when one is active, else one immediate device check."""
    if not err_flags:
        return
    from spark_rapids_tpu.runtime import speculation as spec
    ctx = spec.current()
    if ctx is not None:
        for lbl, f in zip(labels, err_flags):
            ctx.add_flag("ansi:" + lbl, f)
        return
    from spark_rapids_tpu.dispatch import host_fetch
    vals = host_fetch(jnp.stack(list(err_flags)))
    spec.check_flag_values(["ansi:" + l for l in labels], vals)


class ProjectCache:
    """Compile cache keyed by (expr keys, schema key). The jitted function
    inside CompiledProject further caches per (bucket, aux shapes) thanks to
    jax.jit's own signature cache."""

    def __init__(self):
        self._cache = {}

    def get(self, exprs: Sequence[Expression], table: DeviceTable) -> CompiledProject:
        key = (tuple(e.key() for e in exprs), table.schema_key()[0])
        cp = self._cache.get(key)
        if cp is None:
            cp = CompiledProject(exprs)
            self._cache[key] = cp
        return cp


_GLOBAL_PROJECT_CACHE = ProjectCache()

#: process-wide cache of jitted exec kernels keyed by STRUCTURE (expression
#: keys + schema + capacity + prep trace keys). Exec instances are per-query,
#: but two queries with the same shape must share one trace/compile — without
#: this every query re-traces and re-fetches from the compile cache (the
#: XLA analog of cuDF's precompiled kernels, SURVEY.md §7).
_GLOBAL_KERNEL_CACHE: dict = {}


def cached_kernel(key: tuple, build):
    """Return the jitted kernel for ``key``, building it on first use.
    ``build`` returns the ``tpu_jit`` program (named at its own site) and
    must close only over values captured by the key."""
    fn = _GLOBAL_KERNEL_CACHE.get(key)
    if fn is None:
        fn = build()
        _GLOBAL_KERNEL_CACHE[key] = fn
    return fn


def shared_traces(key: tuple) -> dict:
    """Process-wide trace dict for an exec kernel, keyed by STRUCTURE
    (operator kind + bound expression keys + input schema). Exec instances
    are per-query; two queries with the same structure must share traces so
    a warm process never re-traces/re-compiles (VERDICT r1: per-instance jit
    caches made every fresh DataFrame recompile the whole pipeline)."""
    return _GLOBAL_KERNEL_CACHE.setdefault(key, {})


def clear_kernel_caches() -> int:
    """Drop every structurally-keyed kernel trace and compiled project
    (device-loss recovery, runtime/health.py): cached jitted callables
    hold executables and interned constants on the dead backend, so a
    reinitialized device must trace fresh. Returns entries dropped."""
    n = len(_GLOBAL_KERNEL_CACHE) + len(_GLOBAL_PROJECT_CACHE._cache)
    _GLOBAL_KERNEL_CACHE.clear()
    _GLOBAL_PROJECT_CACHE._cache.clear()
    return n


def compile_project(exprs: Sequence[Expression], table: DeviceTable):
    """Evaluate bound expressions over a device table, returning device
    columns. Compilation is cached globally."""
    return _GLOBAL_PROJECT_CACHE.get(exprs, table)(table)


def has_position_dependent(expr: "Expression") -> bool:
    """Does any node in the tree depend on physical row position? Used to
    force compaction before evaluating over a masked batch."""
    if getattr(expr, "position_dependent", False):
        return True
    return any(has_position_dependent(c) for c in expr.children)
