"""Observability subsystem.

Three layers over the same execution machinery (reference: GpuMetric +
NvtxWithMetrics + profiler.scala + the spark-rapids-tools event-log
analyzer — SURVEY.md §5):

* :mod:`spark_rapids_tpu.obs.metrics` — the unified MetricRegistry:
  typed metric specs (timing/count/bytes at ESSENTIAL/MODERATE/DEBUG
  levels), the per-operator :class:`MetricSet` every exec carries, and
  process-wide scopes for the subsystems that are not operators
  (spill, recovery, shuffle).
* :mod:`spark_rapids_tpu.obs.spans` — a thread-aware host-side span
  tracer (enter/exit wall times with query/op attribution) exportable
  as Chrome trace-event JSON, plus the per-query exec-boundary
  instrumentation that feeds both spans and the ESSENTIAL
  opTime/numOutputRows metrics.
* :mod:`spark_rapids_tpu.obs.events` — the per-query structured event
  log (JSONL) that `python -m spark_rapids_tpu.tools` analyzes
  offline.
* :mod:`spark_rapids_tpu.obs.telemetry` — the BETWEEN-queries layer:
  a passive background telemetry ring (per-scope metric deltas +
  topology at a conf-driven interval) and the flight recorder that
  dumps bounded incident bundles on every ladder action and
  quarantine strike (`tools incident` renders them).
"""

from spark_rapids_tpu.obs.metrics import (  # noqa: F401
    MetricSet,
    metric_scope,
    register_metric,
    set_metrics_level,
)
