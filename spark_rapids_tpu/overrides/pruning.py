"""Column pruning (reference: Spark's ColumnPruning logical rule, which the
reference plugin inherits for free by overriding PHYSICAL plans —
GpuOverrides.scala consumes already-pruned plans. This engine builds its own
logical plans, so it needs the rule itself).

On TPU the payoff is direct: every column that survives to a join is a
1M-row gather (and, on the sort path, a scatter) of emulated-64-bit halves
— measured ~10-30ms per column per operator at 1M rows (PERF.md). A q3-
style plan carries 4 dead columns through two joins; pruning removes every
gather for them.

``prune_plan(root)`` returns an equivalent plan in which each Join input
carries only the columns referenced above it (plus its own keys/condition).
The pass rewrites BOUND expressions (BoundReference ordinals), preserving
output names exactly — the root's schema is unchanged.
"""

from __future__ import annotations

from typing import FrozenSet, List, Sequence

from spark_rapids_tpu.ops.expr import Alias, BoundReference, Expression
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


def _visit(node: P.PlanNode, required: FrozenSet[int]):
    """Rewrite ``node`` so its output is exactly
    ``[schema[i] for i in sorted(required)]``. Returns the new node; the
    caller remaps its ordinals via ``sorted(required).index(old)``."""
    schema = node.output_schema()
    nall = len(schema)
    required = frozenset(i for i in required if i < nall)
    if not required and nall:
        required = frozenset([0])  # keep one column (row counts need one)
    kept = sorted(required)
    mapping = {o: i for i, o in enumerate(kept)}

    if isinstance(node, P.Project):
        exprs = [node.exprs[i] for i in kept]
        names = [node.names[i] for i in kept]
        creq: set = set()
        for e in exprs:
            _collect_refs(e, creq)
        child = _visit(node.children[0], frozenset(creq))
        cmap = {o: i for i, o in enumerate(sorted(
            o for o in creq if o < len(node.children[0].output_schema())))}
        new = P.Project(child, [Alias(_remap_strip(e, cmap), n)
                                for e, n in zip(exprs, names)])
        return new

    if isinstance(node, P.Filter):
        creq: set = set(kept)
        _collect_refs(node.condition, creq)
        child = _visit(node.children[0], frozenset(creq))
        ckept = sorted(frozenset(i for i in creq if i < nall) or {0})
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
        lkept = sorted(frozenset(
            o for o in lreq if o < nl) or {0})
        rkept = sorted(frozenset(
            o for o in rreq
            if o < len(node.children[1].output_schema())) or {0})
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
        ckept = sorted(frozenset(
            o for o in creq
            if o < len(node.children[0].output_schema())) or {0})
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
        ckept = sorted(frozenset(
            o for o in creq
            if o < len(node.children[0].output_schema())) or {0})
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

    # conservative default: keep the node whole, prune nothing below it
    if kept == list(range(nall)):
        return node
    return _keep_project(node, kept)


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
