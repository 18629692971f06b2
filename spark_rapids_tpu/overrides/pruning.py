"""Column pruning (reference: Spark's ColumnPruning logical rule, which the
reference plugin inherits for free by overriding PHYSICAL plans:
GpuOverrides.scala consumes plans that are already pruned, down to the
``readDataSchema`` of their file scans. This engine builds its own logical
plans, so it needs the rule itself).

``prune_plan(root)`` returns an equivalent plan in which every node's input
carries only the columns referenced above it (plus the node's own keys and
conditions), and pushes what is left INTO the leaf where the leaf can act
on it:

- a file scan (io/common.py ``FileScanNode``) is replaced by
  ``node.narrowed(names)``, a copy that reads only those columns: the
  reader decodes, and ``TpuFileScanExec`` stages and uploads, nothing
  else. The node of a DataFrame or temp view is shared by every query
  over it and is never changed. A plan that reads no column at all
  (``count(*)``) keeps the scan's cheapest one, see ``_default_column``;
- any other leaf (a cached table's ``LocalScan``, a range) is kept whole
  under a Project of the kept columns, which the consumers that peel
  their input chain (execs/fuse.py) turn into ``narrow_to_references``.

On TPU the payoff is direct. A column that survives to a join is a 1M-row
gather (and, on the sort path, a scatter) of emulated-64-bit halves,
~10-30 ms per column per operator at 1M rows (PERF.md). A column that
survives to a file scan is decoded on the host, string rows through Python
objects, and uploaded: TPC-H Q1 reads 7 of lineitem's 16.

The pass rewrites BOUND expressions (BoundReference ordinals), preserving
output names exactly: the root's schema is unchanged.
"""

from __future__ import annotations

from typing import FrozenSet, List, Sequence

from spark_rapids_tpu.io.common import FileScanNode
from spark_rapids_tpu.ops.expr import Alias, BoundReference, Expression
from spark_rapids_tpu.ops.inputfile import FILE_INFO_COLS
from spark_rapids_tpu.plan import nodes as P


def _collect_refs(e: Expression, acc: set) -> None:
    if isinstance(e, BoundReference):
        acc.add(e.ordinal)
    for c in e.children:
        _collect_refs(c, acc)


def _remap(e: Expression, mapping: dict) -> Expression:
    if isinstance(e, BoundReference):
        return BoundReference(mapping[e.ordinal], e.data_type, e.nullable,
                              name_hint=e.name_hint)
    if not e.children:
        return e
    return e.with_children([_remap(c, mapping) for c in e.children])


def _keep_project(node: P.PlanNode, keep: List[int]) -> P.PlanNode:
    """Wrap ``node`` in a Project keeping columns ``keep`` (ordinal order),
    preserving names."""
    schema = node.output_schema()
    exprs = [Alias(BoundReference(i, schema[i][1], name_hint=schema[i][0]),
                   schema[i][0]) for i in keep]
    return P.Project(node, exprs)


#: nodes whose output schema is their child's, column for column
_SCHEMA_TRANSPARENT = (P.Filter, P.Sort, P.Limit, P.CollectLimit)


def _default_column(node: P.PlanNode) -> int:
    """The one column ``node`` keeps when nothing above reads any (row
    counts need one to carry them). Ordinal 0, except over a file scan,
    where it is the cheapest to produce: a partition column (its values
    come from the path, no byte of the file is decoded) or else the
    narrowest fixed-width data column. ``count(*)`` over files must not
    decode a string column."""
    while isinstance(node, _SCHEMA_TRANSPARENT):
        node = node.children[0]
    if isinstance(node, FileScanNode):
        return node.cheapest_column()
    return 0


def _kept(node: P.PlanNode, required) -> List[int]:
    """The ordinals ``_visit(node, required)`` outputs, in order."""
    nall = len(node.output_schema())
    kept = sorted(frozenset(i for i in required if i < nall))
    if not kept and nall:
        kept = [_default_column(node)]
    return kept


def _visit(node: P.PlanNode, required: FrozenSet[int]):
    """Rewrite ``node`` so its output is exactly
    ``[schema[i] for i in _kept(node, required)]``: ``sorted(required)``,
    or the node's default column when nothing is required. Returns the new
    node; the caller remaps its ordinals via ``_kept(...).index(old)``."""
    schema = node.output_schema()
    nall = len(schema)
    kept = _kept(node, required)
    required = frozenset(kept)

    if isinstance(node, P.Project):
        exprs = [node.exprs[i] for i in kept]
        names = [node.names[i] for i in kept]
        creq: set = set()
        for e in exprs:
            _collect_refs(e, creq)
        child = _visit(node.children[0], frozenset(creq))
        cmap = {o: i for i, o in enumerate(_kept(node.children[0], creq))}
        new = P.Project(child, [Alias(_remap_strip(e, cmap), n)
                                for e, n in zip(exprs, names)])
        return new

    if isinstance(node, P.Filter):
        creq: set = set(kept)
        _collect_refs(node.condition, creq)
        child = _visit(node.children[0], frozenset(creq))
        ckept = _kept(node.children[0], creq)
        cmap = {o: i for i, o in enumerate(ckept)}
        new = P.Filter(child, _remap(node.condition, cmap))
        if ckept != kept:
            new = _keep_project(new, [cmap[o] for o in kept])
        return new

    if isinstance(node, P.Join):
        nl = len(node.children[0].output_schema())
        semi = node.join_type in ("leftsemi", "leftanti")
        lreq: set = set(o for o in kept if o < nl)
        rreq: set = set(o - nl for o in kept if o >= nl)
        for k in node.left_keys:
            _collect_refs(k, lreq)
        for k in node.right_keys:
            _collect_refs(k, rreq)
        if node.condition is not None:
            cond_refs: set = set()
            _collect_refs(node.condition, cond_refs)
            lreq |= {o for o in cond_refs if o < nl}
            rreq |= {o - nl for o in cond_refs if o >= nl}
        left = _visit(node.children[0], frozenset(lreq))
        right = _visit(node.children[1], frozenset(rreq))
        lkept = _kept(node.children[0], lreq)
        rkept = _kept(node.children[1], rreq)
        lmap = {o: i for i, o in enumerate(lkept)}
        rmap = {o: i for i, o in enumerate(rkept)}
        jmap = dict(lmap)
        for o, i in rmap.items():
            jmap[o + nl] = len(lkept) + i
        cond = (_remap(node.condition, jmap)
                if node.condition is not None else None)
        new = P.Join(left, right, node.join_type,
                     [_remap(k, lmap) for k in node.left_keys],
                     [_remap(k, rmap) for k in node.right_keys], cond)
        out_idx = [jmap[o] for o in kept]
        out_all = list(range(len(lkept) + (0 if semi else len(rkept))))
        if out_idx != out_all:
            new = _keep_project(new, out_idx)
        return new

    if isinstance(node, P.Aggregate):
        creq: set = set()
        for g in node.grouping:
            _collect_refs(g, creq)
        for _, fn in node.agg_specs:
            _collect_refs(fn, creq)
        child = _visit(node.children[0], frozenset(creq))
        ckept = _kept(node.children[0], creq)
        cmap = {o: i for i, o in enumerate(ckept)}
        new = P.Aggregate.__new__(P.Aggregate)
        new.children = (child,)
        new.grouping = [_remap(g, cmap) for g in node.grouping]
        new.agg_specs = [(n, _remap(fn, cmap)) for n, fn in node.agg_specs]
        new.grouping_names = list(node.grouping_names)
        if kept != list(range(nall)):
            new = _keep_project(new, kept)
        return new

    if isinstance(node, (P.Sort, P.TakeOrderedAndProject)):
        is_topk = isinstance(node, P.TakeOrderedAndProject)
        creq: set = set()
        for o in node.orders:
            _collect_refs(o.expr, creq)
        if is_topk and node.project is not None:
            proj = [node.project[i] for i in kept]
            names = [node.project_names[i] for i in kept]
            for e in proj:
                _collect_refs(e, creq)
        else:
            creq |= set(kept)
        child = _visit(node.children[0], frozenset(creq))
        ckept = _kept(node.children[0], creq)
        cmap = {o: i for i, o in enumerate(ckept)}
        orders = [P.SortOrder(_remap(o.expr, cmap), o.ascending,
                              o.nulls_first) for o in node.orders]
        if is_topk:
            new = P.TakeOrderedAndProject.__new__(P.TakeOrderedAndProject)
            new.children = (child,)
            new.orders = orders
            new.limit = node.limit
            if node.project is not None:
                new.project = [_remap_strip(e, cmap) for e in proj]
                new.project_names = names
                return new
            new.project = None
            new.project_names = None
            if ckept != kept:
                new = _keep_project(new, [cmap[o] for o in kept])
            return new
        new = P.Sort.__new__(P.Sort)
        new.children = (child,)
        new.orders = orders
        new.global_sort = node.global_sort
        if ckept != kept:
            new = _keep_project(new, [cmap[o] for o in kept])
        return new

    if isinstance(node, (P.Limit, P.CollectLimit)):
        child = _visit(node.children[0], required)
        new = type(node)(child, node.limit)
        return new

    if isinstance(node, P.Union):
        kids = [_visit(c, required) for c in node.children]
        # each child now outputs exactly sorted(required) — schemas align
        return P.Union(kids)

    if kept == list(range(nall)):
        return node
    if isinstance(node, FileScanNode):
        return _narrow_scan(node, kept)
    # conservative default: keep the node whole, prune nothing below it
    return _keep_project(node, kept)


def _narrow_scan(node: FileScanNode, kept: List[int]) -> P.PlanNode:
    """A file scan that outputs exactly the ordinals ``kept`` of ``node``:
    a narrowed copy, never the shared node changed. The hidden provenance
    columns of overrides/input_file.py follow the scan's own, all or none:
    the copy goes on appending them, and a Project drops those nothing
    reads."""
    names = [n for n, _ in node.output_schema()]
    nbase = len(names)
    if tuple(names[-len(FILE_INFO_COLS):]) == FILE_INFO_COLS:
        nbase -= len(FILE_INFO_COLS)
    base = [o for o in kept if o < nbase] or [node.cheapest_column()]
    new = node if len(base) == nbase \
        else node.narrowed([names[o] for o in base])
    if new is node:
        # nothing of the scan's own to drop, or a reader that opts out
        return _keep_project(node, kept)
    out = base + list(range(nbase, len(names)))
    if out == kept:
        return new
    return _keep_project(new, [out.index(o) for o in kept])


def _remap_strip(e: Expression, cmap: dict) -> Expression:
    """Remap refs; tolerate an outer Alias (rebuild preserves out_name)."""
    if isinstance(e, Alias):
        return Alias(_remap(e.children[0], cmap), e.out_name)
    return _remap(e, cmap)


def prune_plan(root: P.PlanNode) -> P.PlanNode:
    """Apply column pruning below the root; the root's schema is unchanged
    (names, order, types)."""
    try:
        n = len(root.output_schema())
        return _visit(root, frozenset(range(n)))
    except Exception:
        # pruning is an optimization — never fail a query over it
        return root


def narrow_to_references(width: int, exprs: Sequence[Expression],
                         preds: Sequence[Expression]):
    """What a consumer that peeled its input chain (execs/fuse.py) reads
    of the base exec's ``width`` columns: returns (kept ordinals in
    schema order, ``exprs`` and ``preds`` rebound to positions in that
    list), or (None, exprs, preds) when every column is read. A consumer
    that reads none (``count(*)`` alone) keeps column 0: a batch needs
    one to carry its rows."""
    refs: set = set()
    for e in list(exprs) + list(preds):
        _collect_refs(e, refs)
    kept = sorted(o for o in refs if o < width) or [0]
    if len(kept) >= width:
        return None, list(exprs), list(preds)
    mapping = {o: i for i, o in enumerate(kept)}
    return (kept, [_remap(e, mapping) for e in exprs],
            [_remap(p, mapping) for p in preds])
