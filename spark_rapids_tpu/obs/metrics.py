"""Unified metric registry (reference: GpuMetric, GpuExec.scala:52-342).

One process-wide table of TYPED metric specs — a name maps to a kind
(``timing`` seconds / ``count`` / ``bytes``) and a collection level
(ESSENTIAL < MODERATE < DEBUG) — plus the :class:`MetricSet` container
every metric producer holds. The set keeps the historical ``dict`` shape
(execs exposed ``self.metrics`` as a plain dict since the seed; tests,
``session.last_metrics`` and the lore pickler all index it), so it IS a
dict: ``add()`` is the level-honoring write path, raw ``[]`` writes stay
possible for bookkeeping values (``dispatches``) that bypass levels.

The active level comes from ``spark.rapids.sql.metrics.level`` and is
set per query by the session; subsystems that are not operators (spill
catalog, recovery counters, shuffle manager) record into named
process-wide scopes fetched via :func:`metric_scope`, so the event log
and crash reports read one registry instead of N ad-hoc counters.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, Optional
from spark_rapids_tpu.lockorder import ordered_lock

#: collection levels, ordered (reference: GpuMetric ESSENTIAL/MODERATE/
#: DEBUG). The session sets the active level from
#: spark.rapids.sql.metrics.level; MetricSet.add drops records above it.
METRIC_LEVELS = {"ESSENTIAL": 0, "MODERATE": 1, "DEBUG": 2}

METRIC_KINDS = ("timing", "count", "bytes")

_ACTIVE_LEVEL = [METRIC_LEVELS["MODERATE"]]


def set_metrics_level(name: str) -> None:
    _ACTIVE_LEVEL[0] = METRIC_LEVELS.get(
        str(name).upper(), METRIC_LEVELS["MODERATE"])


def active_level() -> int:
    return _ACTIVE_LEVEL[0]


@dataclass(frozen=True)
class MetricSpec:
    name: str
    kind: str          # timing | count | bytes
    level: str         # ESSENTIAL | MODERATE | DEBUG
    doc: str = ""


_SPECS: Dict[str, MetricSpec] = {}
_SPEC_LOCK = ordered_lock("obs.metrics.spec")


def register_metric(name: str, kind: str = "count",
                    level: str = "MODERATE", doc: str = "") -> MetricSpec:
    """Declare a typed metric. Re-registering an identical spec is a
    no-op; a CONFLICTING re-registration raises — two subsystems must
    not disagree about what a metric name means."""
    if kind not in METRIC_KINDS:
        raise ValueError(f"unknown metric kind {kind!r} for {name!r} "
                         f"(known: {', '.join(METRIC_KINDS)})")
    if level not in METRIC_LEVELS:
        raise ValueError(f"unknown metric level {level!r} for {name!r} "
                         f"(known: {', '.join(METRIC_LEVELS)})")
    spec = MetricSpec(name, kind, level, doc)
    with _SPEC_LOCK:
        old = _SPECS.get(name)
        if old is not None:
            if (old.kind, old.level) != (kind, level):
                raise ValueError(
                    f"metric {name!r} re-registered as "
                    f"({kind}, {level}) but is already "
                    f"({old.kind}, {old.level})")
            return old
        _SPECS[name] = spec
    return spec


def spec_for(name: str) -> MetricSpec:
    """Spec for a metric name; undeclared names get an inferred spec
    (``*Time`` -> timing, ``*Bytes*`` -> bytes, else count) at MODERATE
    — the historical default of ``add_metric``."""
    spec = _SPECS.get(name)
    if spec is not None:
        return spec
    if name.endswith("Time") or name.endswith("TimeS"):
        kind = "timing"
    elif "Bytes" in name or name.endswith("bytes"):
        kind = "bytes"
    else:
        kind = "count"
    return MetricSpec(name, kind, "MODERATE")


def registered_specs() -> Dict[str, MetricSpec]:
    with _SPEC_LOCK:
        return dict(_SPECS)


class MetricSet(dict):
    """A producer's metrics: a plain dict (name -> value) whose ``add``
    honors the level machinery. Raw ``[]`` assignment bypasses levels —
    reserved for bookkeeping the session always records (dispatches,
    replay counts)."""

    def add(self, key: str, value, level: Optional[str] = None) -> None:
        lvl = level if level is not None else spec_for(key).level
        if METRIC_LEVELS.get(lvl, 1) > _ACTIVE_LEVEL[0]:
            return
        self[key] = self.get(key, 0) + value

    def typed(self) -> Dict[str, dict]:
        """{name: {value, kind, level}} — the event-log rendering."""
        return {k: {"value": v, "kind": spec_for(k).kind,
                    "level": spec_for(k).level}
                for k, v in sorted(self.items())}


# ---------------------------------------------------------------------------
# Process-wide subsystem scopes
# ---------------------------------------------------------------------------


class LockedMetricSet(MetricSet):
    """A MetricSet whose ``add`` is atomic. Process-wide scopes are
    written from many threads at once (shuffle pool workers, concurrent
    query-service workers); the plain read-modify-write ``add`` would
    lose increments under that interleaving. Per-EXEC metric sets stay
    unlocked — an exec instance is drained by one thread."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self._lock = ordered_lock("obs.metrics.scope")

    def add(self, key: str, value, level: Optional[str] = None) -> None:
        with self._lock:
            super().add(key, value, level)


_SCOPES: Dict[str, LockedMetricSet] = {}
_SCOPE_LOCK = ordered_lock("obs.metrics.scopes")


def metric_scope(name: str) -> LockedMetricSet:
    """The named process-wide MetricSet for a non-operator subsystem
    (``spill``, ``recovery``, ``shuffle``, ``semaphore``, ``service``).
    Created on first use; the event log snapshots/diffs these per
    query. Thread-safe: ``add`` is atomic."""
    with _SCOPE_LOCK:
        s = _SCOPES.get(name)
        if s is None:
            s = _SCOPES[name] = LockedMetricSet()
        return s


def scopes_snapshot() -> Dict[str, Dict[str, object]]:
    with _SCOPE_LOCK:
        return {name: dict(s) for name, s in _SCOPES.items()}


# ---------------------------------------------------------------------------
# Core metric specs. ESSENTIAL is the set every exec must emit
# (RA-ESSENTIAL-METRICS audits this after a golden-corpus run); the
# subsystem scopes declare theirs where they record them.
# ---------------------------------------------------------------------------

#: the per-operator metrics the exec-boundary instrumentation
#: (obs.spans.install_observation) guarantees on every executed exec
ESSENTIAL_EXEC_METRICS = ("opTime", "numOutputRows", "numOutputBatches")

register_metric("opTime", "timing", "ESSENTIAL",
                "wall time spent inside this operator's execute "
                "boundary (includes children; self time = opTime minus "
                "the children's)")
register_metric("numOutputRows", "count", "ESSENTIAL",
                "rows this operator produced")
register_metric("numOutputBatches", "count", "ESSENTIAL",
                "batches this operator produced")
register_metric("d2hTime", "timing", "ESSENTIAL",
                "device->host conversion time at the DeviceToHost "
                "transition (under async result fetch: the kernel "
                "ENQUEUE only — the fetch is resultFetchTime)")
register_metric("resultFetchTime", "timing", "ESSENTIAL",
                "async d2h completion time for the root transition's "
                "packed result buffers, paid AFTER the device "
                "semaphore released")
register_metric("asyncFetchBatches", "count", "MODERATE",
                "result batches whose download was enqueued under the "
                "semaphore and completed asynchronously after release")
register_metric("h2dTime", "timing", "ESSENTIAL",
                "host->device upload time at the HostToDevice "
                "transition")
register_metric("h2dBatches", "count", "MODERATE",
                "batches uploaded at the HostToDevice transition")
register_metric("scanUploadTime", "timing", "MODERATE",
                "host->device upload time at file scans")
register_metric("scanColumnsRead", "count", "ESSENTIAL",
                "columns a file scan decodes and uploads (its own and "
                "its partition columns; set once an execution)")
register_metric("scanColumnsPruned", "count", "ESSENTIAL",
                "columns of the files that column pruning kept a file "
                "scan from reading")
register_metric("scanStringsPreEncoded", "count", "ESSENTIAL",
                "string columns of a file scan's landed batches whose "
                "dictionary encoding came with the decoded batch (the "
                "upload walked no Python object a row)")
register_metric("scanF64SplitOnDevice", "count", "ESSENTIAL",
                "DOUBLE columns of a file scan's landed batches handed "
                "over as their 64-bit words and split into the f32 pair "
                "on the device (0 on the CPU backend)")
register_metric("shuffleWriteTime", "timing", "MODERATE",
                "shuffle partition split + write time")
register_metric("shuffleReadTime", "timing", "MODERATE",
                "shuffle partition read + upload time")
register_metric("shuffleBytesWritten", "bytes", "ESSENTIAL",
                "serialized bytes this exchange wrote")
register_metric("shuffleBytesRead", "bytes", "ESSENTIAL",
                "serialized bytes this exchange read")
register_metric("spillTime", "timing", "MODERATE",
                "time spent demoting buffers between tiers")
register_metric("spillDeviceCount", "count", "ESSENTIAL",
                "device->host spill demotions")
register_metric("spillDiskCount", "count", "ESSENTIAL",
                "host->disk spill demotions")
register_metric("spillDeviceBytes", "bytes", "ESSENTIAL",
                "device bytes freed by spilling")
register_metric("spillDiskBytes", "bytes", "ESSENTIAL",
                "host bytes demoted to disk")
register_metric("serializeTime", "timing", "MODERATE",
                "shuffle batch pack/compress wall time (recorded from "
                "the writing thread)")
