"""Exec base + host<->device transitions (reference: GpuExec.scala,
GpuRowToColumnarExec / GpuColumnarToRowExec — SURVEY.md §2.2/§2.3)."""

from __future__ import annotations

import contextvars
import time
from typing import Iterator, List, Optional, Sequence, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceTable, HostTable
from spark_rapids_tpu.obs.metrics import (
    METRIC_LEVELS,  # noqa: F401  (re-export: historical import site)
    MetricSet,
    set_metrics_level,  # noqa: F401  (re-export: the session's setter)
)
from spark_rapids_tpu.plan.nodes import PlanNode, Schema

#: spark.rapids.tpu.maskedBatches.enabled, set per-query by the session
#: (execs have no conf handle — same pattern as retry.MAX_RETRIES_VAR)
MASKED_ENABLED = contextvars.ContextVar("rapids_masked_batches",
                                        default=True)


class TpuExec:
    """Base of device operators. ``execute`` yields DeviceTable batches.

    Two output protocols (columnar/table.py DeviceTable.live):
    ``execute()`` always yields PREFIX tables (live rows at [0, nrows));
    ``execute_masked()`` may yield MASKED tables (liveness as a device
    bool mask), letting mask-aware consumers skip the per-column
    compaction scatter. The default implementations tie them together so
    an exec only ever implements one of the two: mask-oblivious execs
    implement ``execute`` (and ``execute_masked`` forwards to it); mask-
    producing execs implement ``execute_masked`` (and ``execute`` compacts
    each batch)."""

    children: Tuple[object, ...] = ()  # TpuExec or HostToDevice

    #: set by mask-producing execs that implement execute_masked directly
    produces_masked = False

    def __init__(self):
        self.metrics = MetricSet()

    def output_schema(self) -> Schema:
        raise NotImplementedError

    def execute(self) -> Iterator[DeviceTable]:
        if not self.produces_masked:
            raise NotImplementedError
        for b in self.execute_masked():
            yield b.compacted()

    def execute_masked(self) -> Iterator[DeviceTable]:
        return self.execute()

    @property
    def name(self):
        return type(self).__name__

    def describe(self) -> str:
        return self.name

    def tree_string(self, indent: int = 0) -> str:
        s = "  " * indent + "* " + self.describe() + "\n"
        for c in self.children:
            s += c.tree_string(indent + 1)
        return s

    def add_metric(self, key: str, value, level: Optional[str] = None):
        """Record into the unified registry (obs/metrics.py). ``level``
        None resolves from the metric's registered spec (undeclared
        names default to MODERATE — the historical behavior)."""
        self.metrics.add(key, value, level)


class HostToDevice(TpuExec):
    """Transition: wraps a CPU PlanNode, uploading its host batches
    (GpuRowToColumnarExec analog; columnar host->HBM copy)."""

    def __init__(self, cpu_node: PlanNode):
        super().__init__()
        self.cpu_node = cpu_node

    def output_schema(self):
        return self.cpu_node.output_schema()

    def execute(self):
        from spark_rapids_tpu.obs.spans import span
        from spark_rapids_tpu.runtime.memory import scan_chunks
        from spark_rapids_tpu.runtime.retry import retry_block
        for batch in self.cpu_node.execute_cpu():
            # transitions are device landings like scans: batches over
            # their budget share land as bounded partitions, and a
            # budget squeeze (arbiter RetryOOM) spills and replays
            # instead of failing the query at the upload
            for ch in scan_chunks(batch):
                t0 = time.perf_counter()
                with span("HostToDevice", "transfer"):
                    dt = retry_block(
                        lambda c=ch: DeviceTable.from_host(c))
                self.add_metric("h2dTime", time.perf_counter() - t0)
                self.add_metric("h2dBatches", 1)
                yield dt

    def describe(self):
        return f"HostToDevice[{self.cpu_node.describe()}]"

    def tree_string(self, indent: int = 0):
        s = "  " * indent + "* " + "HostToDevice\n"
        return s + self.cpu_node.tree_string(indent + 1)


class DeviceToHost:
    """Transition: device exec -> host batches (GpuColumnarToRowExec analog).

    When the session arms ``_async_fetch`` (root transition only,
    ``spark.rapids.sql.asyncResultFetch``), batches yield as
    :class:`~spark_rapids_tpu.columnar.table.PendingHostTable` — the
    packed d2h kernel is ENQUEUED here (still under the device
    semaphore) and the session completes the round trip after releasing
    it, so the fetch latency stops blocking the next admitted query.
    Mid-plan transitions feeding CPU fallback nodes never arm it."""

    def __init__(self, tpu_exec: TpuExec):
        self.tpu_exec = tpu_exec
        self.metrics = MetricSet()
        #: set per query by the session on the ROOT transition
        self._async_fetch = False

    def output_schema(self):
        return self.tpu_exec.output_schema()

    def add_metric(self, key: str, value, level: Optional[str] = None):
        """Same level-honoring path as TpuExec.add_metric, so
        spark.rapids.sql.metrics.level applies to transitions too."""
        self.metrics.add(key, value, level)

    def execute_cpu(self) -> Iterator[HostTable]:
        from spark_rapids_tpu.columnar.table import PendingHostTable
        from spark_rapids_tpu.obs.spans import span
        for dt in self.tpu_exec.execute():
            t0 = time.perf_counter()
            with span("DeviceToHost", "transfer"):
                out = dt.to_host_pending() if self._async_fetch \
                    else dt.to_host()
            # incremental so an early-terminating consumer (limit) still
            # leaves accurate numbers; measures ONLY the d2h conversion
            # (under async fetch: only the ENQUEUE — the fetch itself is
            # recorded as resultFetchTime by the session's resolver)
            self.add_metric("d2hTime", time.perf_counter() - t0)
            self.add_metric("numOutputBatches", 1)
            if isinstance(out, PendingHostTable):
                self.add_metric("asyncFetchBatches", 1)
            else:
                self.add_metric("numOutputRows", out.num_rows)
            yield out

    def describe(self):
        return "DeviceToHost"

    def tree_string(self, indent: int = 0):
        return "  " * indent + "DeviceToHost\n" + self.tpu_exec.tree_string(indent + 1)


class InputAdapter(PlanNode):
    """CPU plan node that sources batches from an arbitrary executable
    (used when a CPU fallback node sits above converted children)."""

    def __init__(self, source, schema: Schema):
        self.source = source
        self._schema = schema

    def output_schema(self):
        return self._schema

    def execute_cpu(self):
        return self.source.execute_cpu()

    def describe(self):
        return "InputAdapter"

    def tree_string(self, indent: int = 0):
        return "  " * indent + "InputAdapter\n" + self.source.tree_string(indent + 1)
