"""Cost-based optimizer (reference: CostBasedOptimizer.scala — SURVEY.md
§2.2).

The reference's CBO estimates each operator's GPU cost vs CPU cost from
row counts and conf-tunable per-op factors, and reverts plan SECTIONS to
CPU when the accelerator isn't worth the transfer+dispatch overhead (small
inputs are the classic case). Same shape here: a device query pays a
fixed dispatch/sync overhead plus per-row work that is far cheaper than
CPU per-row work. The constants below are guesses from an earlier
backend; PERF.md lists re-measuring them as an open question.

Model (all conf-tunable):
  device_cost(plan) = execOverhead * n_execs + gpuRowCost * sum(rows)
  cpu_cost(plan)    = cpuRowCost * sum(rows)
When ``cpu_cost < device_cost`` for the WHOLE eligible plan, every node is
tagged with a CBO reason so conversion falls back — mirroring the
reference's "avoid transitions that don't pay for themselves" behavior.
Nodes without row estimates (no stats) leave the plan untouched, like the
reference treating unknown stats as not-optimizable.
"""

from __future__ import annotations

from typing import Optional

from spark_rapids_tpu.conf import bool_conf, float_conf

OPTIMIZER_ENABLED = bool_conf(
    "spark.rapids.sql.optimizer.enabled", False,
    "Cost-based optimizer: estimate device vs CPU cost from row counts and "
    "fall back plan sections that don't pay for the transfer/dispatch "
    "overhead (CostBasedOptimizer analog; off by default like the "
    "reference).")

OPTIMIZER_EXEC_OVERHEAD = float_conf(
    "spark.rapids.sql.optimizer.gpu.execOverhead", 0.05,
    "Estimated fixed cost (arbitrary units ~seconds) per device operator "
    "dispatch — the per-sync (device round trip) latency class.")

OPTIMIZER_GPU_ROW_COST = float_conf(
    "spark.rapids.sql.optimizer.gpu.rowCost", 2e-9,
    "Estimated device cost per input row.")

OPTIMIZER_CPU_ROW_COST = float_conf(
    "spark.rapids.sql.optimizer.cpu.rowCost", 3e-7,
    "Estimated CPU cost per input row.")


def estimate_rows(node) -> Optional[int]:
    """Row-count estimate (the stats Spark's CBO reads from the logical
    plan). Leaf scans know; row-preserving unaries propagate; unknown
    stays None."""
    from spark_rapids_tpu.plan import nodes as P

    if isinstance(node, P.LocalScan):
        return sum(b.num_rows for b in node.batches)
    if isinstance(node, P.CachedRelation):
        if node._table is not None:
            return node._table.num_rows
        return estimate_rows(node.children[0])
    row_preserving = [P.Project, P.Filter, P.Sort, P.Sample]
    if hasattr(P, "WindowNode"):
        row_preserving.append(P.WindowNode)
    if isinstance(node, tuple(row_preserving)):
        return estimate_rows(node.children[0])
    if isinstance(node, (P.Limit, P.CollectLimit)):
        child = estimate_rows(node.children[0])
        return min(child, node.limit) if child is not None else node.limit
    if isinstance(node, P.TakeOrderedAndProject):
        return node.limit
    if isinstance(node, P.Exchange):
        return estimate_rows(node.children[0])
    return None


def apply_cbo(meta, conf) -> None:
    """Tag the whole plan for CPU when the device estimate loses."""
    if not conf.get_entry(OPTIMIZER_ENABLED):
        return
    if not meta.can_run_on_tpu:
        return  # already (partially) falling back; don't double-decide

    total_rows = 0
    n_execs = 0
    stack = [meta]
    while stack:
        m = stack.pop()
        n_execs += 1
        r = estimate_rows(m.node)
        if r is None:
            return  # unknown stats: leave the plan alone (reference rule)
        total_rows += r
        stack.extend(m.children)

    overhead = conf.get_entry(OPTIMIZER_EXEC_OVERHEAD)
    gpu_row = conf.get_entry(OPTIMIZER_GPU_ROW_COST)
    cpu_row = conf.get_entry(OPTIMIZER_CPU_ROW_COST)
    device_cost = overhead * n_execs + gpu_row * total_rows
    cpu_cost = cpu_row * total_rows
    if cpu_cost < device_cost:
        reason = (f"CBO: est. CPU cost {cpu_cost:.4g} < device cost "
                  f"{device_cost:.4g} ({total_rows} rows, {n_execs} ops)")
        stack = [meta]
        while stack:
            m = stack.pop()
            m.reasons.append(reason)
            stack.extend(m.children)
