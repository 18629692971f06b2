"""Oracle assertions (reference: integration_tests asserts.py —
assert_gpu_and_cpu_are_equal_collect / assert_gpu_fallback_collect,
SURVEY.md §4). Every test builds a DataFrame pipeline, runs it through the
TPU overrides engine AND the pure-CPU path, and compares results."""

from __future__ import annotations

import math

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.overrides import wrap_plan
from spark_rapids_tpu.overrides.rules import _EXEC_RULES


def _canon_row(row, approx):
    out = []
    for v in row:
        if v is None:
            out.append(("n",))
        elif isinstance(v, float):
            if math.isnan(v):
                out.append(("nan",))
            elif approx:
                out.append(("f", round(v, 9) if abs(v) < 1e15 else v))
            else:
                out.append(("f", v))
        else:
            out.append((type(v).__name__, v))
    return tuple(out)


def _sort_key(row):
    return tuple((x is None, str(type(x)), str(x)) for x in row)


def assert_tpu_and_cpu_are_equal(build_df, session, cpu_session,
                                 ignore_order: bool = True,
                                 approximate_float: bool = False):
    """build_df: fn(session) -> DataFrame. Runs on both paths, asserts
    equality (bit-for-bit unless approximate_float)."""
    tpu_df = build_df(session)
    cpu_df = build_df(cpu_session)

    tpu_rows = tpu_df.collect()
    cpu_rows = cpu_df.collect()

    assert len(tpu_rows) == len(cpu_rows), \
        f"row count: tpu={len(tpu_rows)} cpu={len(cpu_rows)}"
    if ignore_order:
        tpu_rows = sorted(tpu_rows, key=_sort_key)
        cpu_rows = sorted(cpu_rows, key=_sort_key)
    for i, (t, c) in enumerate(zip(tpu_rows, cpu_rows)):
        tc = _canon_row(t, approximate_float)
        cc = _canon_row(c, approximate_float)
        if approximate_float:
            assert len(t) == len(c), f"row {i} arity"
            for j, (tv, cv) in enumerate(zip(t, c)):
                if isinstance(tv, float) and isinstance(cv, float) \
                        and not (math.isnan(tv) or math.isnan(cv)):
                    assert tv == cv or abs(tv - cv) <= 1e-6 * max(1.0, abs(cv)), \
                        f"row {i} col {j}: tpu={tv!r} cpu={cv!r}"
                else:
                    assert _canon_row([tv], False) == _canon_row([cv], False), \
                        f"row {i} col {j}: tpu={tv!r} cpu={cv!r}"
        else:
            assert tc == cc, f"row {i}: tpu={t!r} cpu={c!r}"


def assert_runs_on_tpu(build_df, session):
    """Asserts the WHOLE plan converts (no fallback) — the plan-capture
    analog of the reference's fallback assertions."""
    df = build_df(session)
    meta = wrap_plan(df.plan, session.conf)

    def walk(m):
        assert m.can_run_on_tpu, \
            f"{m.node.describe()} fell back: {m.reasons}\n{meta.explain(only_fallback=False)}"
        for c in m.children:
            walk(c)

    walk(meta)


def assert_falls_back(build_df, session, node_name: str):
    df = build_df(session)
    meta = wrap_plan(df.plan, session.conf)
    found = []

    def walk(m):
        if m.node.name == node_name:
            found.append(m)
        for c in m.children:
            walk(c)

    walk(meta)
    assert found, f"no node {node_name} in plan"
    assert any(not m.can_run_on_tpu for m in found), \
        f"{node_name} unexpectedly supported on TPU"


def plan_metric_total(session, key):
    """Sum of an exec metric over the plan tree of the session's last
    event record (the event log must be on)."""
    def walk(node):
        m = node.get("metrics", {}).get(key)
        return (m["value"] if m else 0) + sum(
            walk(c) for c in node.get("children", ()))
    return walk(session.last_event_record["plan"])
