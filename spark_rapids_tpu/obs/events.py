"""Per-query structured event log (JSONL).

Reference: the Spark event log that spark-rapids-tools' qualification /
profiling analyzers consume — the machine-readable record every perf PR
diffs instead of hand-timing (PERF.md's essay form). One JSON object per
completed query, written by ``TpuSession.execute`` when
``spark.rapids.sql.eventLog.enabled`` is set:

* the executed plan tree with per-operator typed metrics and lore ids;
* fallback reasons (overrides tagging) and circuit-breaker demotions;
* AQE runtime conversions, spill / retry / fault-recovery counter
  deltas, per-exchange shuffle bytes;
* query wall / phase times and the span summary (category totals,
  attribution of wall time to named spans).

``python -m spark_rapids_tpu.tools`` analyzes these offline; the record
schema is versioned and pinned by a golden test so drift breaks a test,
not the tools.
"""

from __future__ import annotations

import json
import os
import threading
import uuid
from collections import deque
from typing import Dict, List, Optional

from spark_rapids_tpu.conf import bool_conf, str_conf
from spark_rapids_tpu.lockorder import ordered_lock

EVENT_LOG_ENABLED = bool_conf(
    "spark.rapids.sql.eventLog.enabled", False,
    "Write one structured JSONL record per executed query (plan tree "
    "with per-op metrics, fallback/demotion reasons, recovery counters, "
    "span attribution) under spark.rapids.sql.eventLog.dir — the input "
    "to `python -m spark_rapids_tpu.tools`.", commonly_used=True)

EVENT_LOG_DIR = str_conf(
    "spark.rapids.sql.eventLog.dir", "/tmp/rapids_tpu_eventlog",
    "Directory for query event logs (one events-<session>.jsonl per "
    "session).")

#: bump on ANY record shape change and update the golden test — the
#: offline tools key off this.
#: v2 (query service PR): + tenant, pool, queueWaitS, cacheHit fields
#: (null/false for queries executed outside the service).
#: v3 (serving-latency PR): + compileMs (wall spent on new XLA traces:
#: trace + lowering + backend compile; 0.0 on fully warm queries),
#: executableCacheHit (the query checked out a cached converted
#: executable — false outside the cache paths / when disabled), and
#: padWasteRows (dead tail rows uploaded to pad batches to their
#: capacity buckets; 0 when every batch landed exactly on a bucket).
#: Result-cache-served replays carry compileMs=0.0,
#: executableCacheHit=false, padWasteRows=0 (nothing executed).
#: v4 (survivability PR): + healthState (process health at record
#: time: HEALTHY / DEGRADED / CPU_ONLY — runtime/health.py),
#: quarantined (the query's template carries poison strikes; false
#: outside the service), workerRestarts (service workers respawned
#: during this query's wall) and deviceReinits (backend
#: reinitializations after device loss during this query's wall) —
#: the last two are per-record DELTAS of the ``health`` scope, 0 on a
#: quiet process. Result-cache serves carry 0/0 and the serve-time
#: healthState.
#: v5 (transactional-write PR): + filesWritten (data files committed
#: into place by the transactional output committer during this
#: query's wall), bytesWritten (their bytes), and commitRetries
#: (Delta optimistic commits rebased and retried after losing the
#: version race) — per-record DELTAS of the ``write`` scope, all 0
#: for read-only queries and result-cache serves.
#: v6 (mesh-native execution PR): + meshShape (the active device-mesh
#: topology — '8' / '2x4' — null when mesh-native execution is off),
#: iciBytes (payload bytes this query moved through ICI all-to-all
#: collectives; per-record DELTA of the ``mesh`` scope, 0 off-mesh)
#: and shardSkew (max over the query's ICI exchanges of per-shard
#: map-output max/median bytes — the AQE skew signal measured from
#: REAL shard distributions; 0.0 when no collective exchange ran).
#: Result-cache serves carry the serve-time meshShape and 0/0.0.
#: v7 (mesh fault-domain PR): + meshDegradations (degradation-ladder
#: demotions — single-device re-lands and mesh shrinks — during this
#: query's wall; per-record DELTA of the ``health`` scope),
#: shardRetries (local re-gathers paid at mesh gather boundaries after
#: failed row-count/checksum validations) and gatherChecksFailed
#: (validations that TRIPPED — corrupted shards caught instead of
#: served) — the latter two per-record DELTAS of the ``mesh`` scope.
#: All 0 on a healthy mesh (and off-mesh); result-cache serves carry
#: 0/0/0 (nothing gathered).
#: v8 (multi-host fault-domain PR): + hostTopology (the active cluster
#: host topology at record time — '2' at full strength, '1/2' with a
#: host lost/excluded, '0/2' under the single-process latch; null when
#: cluster execution is off), hostsLost (executor hosts declared lost
#: during this query's wall — missed-beat sweep, dead dispatch socket,
#: or the host ladder's re-land rung), hostRelands (scans that
#: re-assigned a lost host's source files onto survivors) and
#: dcnExchanges (shuffle collectives whose mesh spanned more than one
#: cluster host group — the all-to-all crossed the DCN axis) — the
#: last three per-record DELTAS of the new ``cluster`` scope
#: (runtime/cluster.py). All 0/null off-cluster; result-cache serves
#: carry the serve-time hostTopology and 0/0/0.
#: v9 (flight-recorder PR): + hostScans — per-executor-host scan
#: attribution merged from cluster scan replies ({host: {scans, files,
#: bytes, wallS, execWallS, crcRetries}}: dispatch round trips, TPAK
#: frames landed and their bytes, driver-side round-trip wall,
#: executor-reported scan wall, CRC-caught re-lands). {} off-cluster,
#: for local-fallback scans, and for result-cache serves (nothing
#: dispatched).
#: v10 (out-of-core PR): + oomRetries (spill-and-replay retries the
#: OOM retry framework performed during this query's wall),
#: splitRetries (split-and-retry escalations — an input halved by rows
#: and both halves replayed), spillBytes (device bytes freed by spill
#: demotions) and unspills (spilled batches re-landed on device) —
#: per-record DELTAS of the new ``memory`` scope (runtime/memory.py);
#: plus budgetPeak (the memory arbiter's PEAK accounted device bytes
#: at record time — absolute, process-wide, not a delta). All deltas 0
#: on an unbudgeted quiet process and for result-cache serves.
#: v11 (streaming PR): + microBatches (streaming micro-batches whose
#: execution rode this query's wall), mvRefreshes (materialized-view
#: refreshes taken), mvIncrementalRefreshes (refreshes satisfied from
#: the CDF delta instead of a full recompute), mvFullRecomputes
#: (refreshes that fell back to recomputing the whole plan),
#: sinkCommits (transactional micro-batch sink commits) and
#: sinkReplays (micro-batches skipped at the sink because their txn
#: watermark was already committed — the exactly-once dedupe firing) —
#: per-record DELTAS of the new ``streaming`` scope (streaming/), all
#: 0 for non-streaming queries and result-cache serves; plus mvEpoch
#: (the maintained table's Delta version when this query was served
#: FROM a materialized view; null for every other query).
#: v12 (tracing PR): + hostSyncs (blocking device->host fetches of the
#: query: ``dispatch.host_fetch`` calls plus root-result
#: ``PendingHostTable.resolve``s; 0 for result-cache serves), and
#: phasesS gains the host seconds taken where the work happens —
#: parseS (``sql()`` lowering, absent for DataFrame-built queries),
#: dispatchS (inside ``tpu_jit`` calls: enqueue, compile, a full
#: queue), syncWaitS (blocked in ``host_fetch``), fetchWaitS /
#: fetchUnpackS (the root result's blocking d2h and its host unpack)
#: and semaphoreWaitS (the wait for a device slot). planS / executeS /
#: collectS are unchanged; the new ones lie inside executeS + collectS
#: (parseS before the wall) and overlap nothing but them.
#: v13 (coalesce PR): phasesS gains coalesceS — host seconds inside the
#: coalesce exec's multi-batch flushes (range ``srt.coalesce.flush``:
#: dictionary checks, the ``jit_coalesce`` dispatch, which dispatchS
#: counts too); 0.0 for a query whose coalesces only passed batches on.
#: v14 (mesh aggregate PR): phasesS gains relandS — host seconds inside
#: mesh re-lands (range ``srt.mesh.reland``: the gather's enqueue, its
#: two digest programs and the fetch that compares them, which
#: dispatchS and syncWaitS count too); 0.0 for a query that gathered no
#: sharded batch to one device (no mesh, or every consumer ran on the
#: resident shards).
#: v15 (join PR): phasesS gains joinS — host seconds inside the join
#: execs' work (ranges ``srt.join.build``: making the build side ready,
#: its coalesce and, for the direct-address body, the reads of its key
#: range and uniqueness; ``srt.join.batch``: each probe batch's join and
#: the read of the output counts), which hold dispatches and host
#: fetches that dispatchS and syncWaitS count too, and for the build
#: range the build child's own execution; 0.0 for a query without a
#: join. The join exec's plan-tree metrics say which body ran
#: (``directJoinBatches`` / ``sortJoinBatches``), on what
#: (``buildRows``, ``probeBatches``, ``joinOutputRows``), which side
#: was built (``buildSideSwapped``) and, for a direct inner join, how
#: many probe batches the device found clustered and looked up by
#: windows of the table (``clusteredProbeBatches``).
#: v16 (tracing PR): phasesS gains gcS — host seconds this query's
#: thread spent in Python's collector from the envelope's start to the
#: record (plan, execute, collect and the observation's row-count fetch;
#: a collection on another thread is that thread's), by the process-wide
#: ``gc.callbacks`` hook of obs/spans.py, which also opens each
#: collection as the range ``srt.gc.gen<N>``. It overlaps whichever
#: phase the collection interrupted.
#: v17 (mesh join PR): phasesS gains exchangeS — host seconds inside the
#: ICI exchanges (range ``srt.mesh.exchange``: the key projection, the
#: ``mesh_exchange`` enqueue of each input batch, which dispatchS counts
#: too, and the one read of the live counts after an exchange's last
#: batch); 0.0 for a query without one. The record gains meshExchanges
#: (input batches those exchanges moved), meshExchangeRows and
#: meshExchangeBytes (the live rows and bytes they sent, from that read;
#: iciBytes stays the padded blocks) and meshRelandRows (row slots gathered from the chips to one
#: device at wide-kernel boundaries, execs/mesh.py): sums of the plan
#: tree's exec metrics, all 0 off the mesh.
EVENT_SCHEMA_VERSION = 17


def plan_tree(executable) -> dict:
    """The executed tree as nested dicts: operator name, lore id,
    describe() and TYPED metrics per node (children include transition/
    adapter links, matching lore's tree walk)."""
    from spark_rapids_tpu.obs.metrics import MetricSet

    def node(e) -> dict:
        m = getattr(e, "metrics", None)
        if isinstance(m, MetricSet):
            metrics = m.typed()
        elif m:
            metrics = {k: {"value": v, "kind": "count",
                           "level": "MODERATE"}
                       for k, v in sorted(m.items())}
        else:
            metrics = {}
        d = {
            "op": type(e).__name__,
            "describe": e.describe() if hasattr(e, "describe")
            else type(e).__name__,
            "loreId": getattr(e, "_lore_id", None),
            "metrics": metrics,
            "children": [],
        }
        for c in getattr(e, "children", ()):
            d["children"].append(node(c))
        for attr in ("source", "tpu_exec", "cpu_node", "scan_node"):
            nxt = getattr(e, attr, None)
            if nxt is not None:
                d["children"].append(node(nxt))
        return d

    return node(executable)


def collect_fallbacks(meta) -> List[dict]:
    """Flatten the overrides meta tree into [{op, reasons}] for every
    node tagged with fallback reasons."""
    out: List[dict] = []

    def walk(m):
        if m is None:
            return
        reasons = list(getattr(m, "reasons", ()) or ())
        if reasons:
            out.append({"op": type(getattr(m, "node", m)).__name__,
                        "reasons": reasons})
        for c in getattr(m, "children", ()) or ():
            walk(c)

    walk(meta)
    return out


def _walk_exec_tree(executable):
    from spark_rapids_tpu.lore import _iter_tree
    return _iter_tree(executable)


def collect_exchanges(executable) -> List[dict]:
    """Per-exchange shuffle summary from the executed tree's metrics —
    bytes, times, skew and AQE coalescing per exchange node."""
    keys = ("shuffleBytesWritten", "shuffleBytesRead", "shuffleWriteTime",
            "shuffleReadTime", "mapOutputBytesMax", "mapOutputBytesMedian",
            "skewedPartitions", "aqeCoalescedPartitions",
            "recomputedMapOutputs", "iciExchangeTime", "iciPartitions",
            "iciBytes", "hostShuffleFallbacks",
            "localSplitParts", "localSplitTime")
    out = []
    for e in _walk_exec_tree(executable):
        m = getattr(e, "metrics", None)
        if not m or not any(k in m for k in keys):
            continue
        entry = {"op": type(e).__name__,
                 "loreId": getattr(e, "_lore_id", None)}
        entry.update({k: m[k] for k in keys if k in m})
        out.append(entry)
    return out


def collect_metric_sums(executable, keys) -> Dict[str, int]:
    """Each of ``keys`` summed over the executed tree's exec metrics (0
    where no exec has it)."""
    totals = dict.fromkeys(keys, 0)
    for e in _walk_exec_tree(executable):
        m = getattr(e, "metrics", None)
        if m:
            for k in keys:
                totals[k] += int(m.get(k, 0))
    return totals


def collect_aqe(executable) -> Dict[str, int]:
    """AQE runtime re-plan summary (measured broadcast conversions,
    coalesced partitions) aggregated over the tree."""
    totals = {"broadcastConversions": 0, "coalescedPartitions": 0}
    for e in _walk_exec_tree(executable):
        m = getattr(e, "metrics", None)
        if not m:
            continue
        totals["broadcastConversions"] += int(m.get("aqeBroadcastConverted",
                                                    0))
        totals["coalescedPartitions"] += int(m.get("aqeCoalescedPartitions",
                                                   0))
    return totals


def build_query_record(*, query_index: int, wall_s: float,
                       phases: Dict[str, float], executable, meta,
                       sql_text: Optional[str], query_tag: Optional[str],
                       dispatches: int, recovery_delta: Dict[str, int],
                       scope_deltas: Dict[str, dict],
                       fault_fires: Dict[str, int],
                       demotions: Dict[str, str],
                       spans_summary: Optional[dict],
                       fault_replays: int,
                       service: Optional[dict] = None,
                       compile_ms: float = 0.0,
                       executable_cache_hit: bool = False,
                       pad_waste_rows: int = 0,
                       health_state: str = "HEALTHY",
                       device_reinits: int = 0,
                       worker_restarts: int = 0,
                       files_written: int = 0,
                       bytes_written: int = 0,
                       commit_retries: int = 0,
                       mesh_shape: Optional[str] = None,
                       ici_bytes: int = 0,
                       mesh_degradations: int = 0,
                       shard_retries: int = 0,
                       gather_checks_failed: int = 0,
                       host_topology: Optional[str] = None,
                       hosts_lost: int = 0,
                       host_relands: int = 0,
                       dcn_exchanges: int = 0,
                       host_scans: Optional[Dict[str, dict]] = None,
                       oom_retries: int = 0,
                       split_retries: int = 0,
                       spill_bytes: int = 0,
                       unspills: int = 0,
                       budget_peak: int = 0,
                       micro_batches: int = 0,
                       mv_refreshes: int = 0,
                       mv_incremental_refreshes: int = 0,
                       mv_full_recomputes: int = 0,
                       sink_commits: int = 0,
                       sink_replays: int = 0,
                       mv_epoch: Optional[int] = None,
                       host_syncs: int = 0) -> dict:
    """Assemble one event-log record. Every field is JSON-native; the
    golden schema test normalizes timings and pins the shape.
    ``service`` is the query-service envelope (tenant, pool, queueWaitS,
    cacheHit) — None for queries executed outside the service, which
    still record the fields as null/false so the schema is stable."""
    service = service or {}
    exchanges = collect_exchanges(executable)
    mesh_counts = collect_metric_sums(
        executable, ("meshExchanges", "meshExchangeRows",
                     "meshExchangeBytes", "meshRelandRows"))
    # per-shard skew of this query's ICI exchanges (measured from the
    # collective's live counts, not file sizes): max over exchanges of
    # max/median per-shard map-output bytes
    shard_skew = 0.0
    for e in exchanges:
        if "iciBytes" in e and e.get("mapOutputBytesMedian"):
            shard_skew = max(shard_skew, e["mapOutputBytesMax"]
                             / max(e["mapOutputBytesMedian"], 1))
    return {
        "schema": EVENT_SCHEMA_VERSION,
        "event": "queryCompleted",
        "queryIndex": query_index,
        "queryTag": query_tag,
        "sqlText": sql_text,
        "tenant": service.get("tenant"),
        "pool": service.get("pool"),
        "queueWaitS": service.get("queueWaitS"),
        "cacheHit": bool(service.get("cacheHit", False)),
        "wallS": round(wall_s, 6),
        "phasesS": {k: round(v, 6) for k, v in sorted(phases.items())},
        "dispatches": dispatches,
        "hostSyncs": int(host_syncs),
        "compileMs": round(float(compile_ms), 3),
        "executableCacheHit": bool(executable_cache_hit),
        "padWasteRows": int(pad_waste_rows),
        "healthState": str(health_state),
        "quarantined": bool(service.get("quarantined", False)),
        "deviceReinits": int(device_reinits),
        "workerRestarts": int(worker_restarts),
        "filesWritten": int(files_written),
        "bytesWritten": int(bytes_written),
        "commitRetries": int(commit_retries),
        "meshShape": mesh_shape,
        "iciBytes": int(ici_bytes),
        "meshExchanges": mesh_counts["meshExchanges"],
        "meshExchangeRows": mesh_counts["meshExchangeRows"],
        "meshExchangeBytes": mesh_counts["meshExchangeBytes"],
        "meshRelandRows": mesh_counts["meshRelandRows"],
        "shardSkew": round(float(shard_skew), 4),
        "meshDegradations": int(mesh_degradations),
        "shardRetries": int(shard_retries),
        "gatherChecksFailed": int(gather_checks_failed),
        "hostTopology": host_topology,
        "hostsLost": int(hosts_lost),
        "hostRelands": int(host_relands),
        "dcnExchanges": int(dcn_exchanges),
        "hostScans": {h: dict(v)
                      for h, v in sorted((host_scans or {}).items())},
        "oomRetries": int(oom_retries),
        "splitRetries": int(split_retries),
        "spillBytes": int(spill_bytes),
        "unspills": int(unspills),
        "budgetPeak": int(budget_peak),
        "microBatches": int(micro_batches),
        "mvRefreshes": int(mv_refreshes),
        "mvIncrementalRefreshes": int(mv_incremental_refreshes),
        "mvFullRecomputes": int(mv_full_recomputes),
        "sinkCommits": int(sink_commits),
        "sinkReplays": int(sink_replays),
        "mvEpoch": mv_epoch if mv_epoch is None else int(mv_epoch),
        "faultReplays": fault_replays,
        "plan": plan_tree(executable),
        "fallbacks": collect_fallbacks(meta),
        "demotions": dict(demotions),
        "aqe": collect_aqe(executable),
        "exchanges": exchanges,
        "recovery": dict(recovery_delta),
        "scopes": scope_deltas,
        "faultFires": dict(fault_fires),
        "spans": spans_summary,
    }


class QueryEventWriter:
    """Appends one JSON line per query to a per-session file under the
    configured directory. Lazy: the file is created at the first
    record, so enabling the conf on an idle session writes nothing."""

    def __init__(self, directory: str):
        self.directory = directory
        self.path = os.path.join(
            directory, f"events-{uuid.uuid4().hex[:12]}.jsonl")
        self._lock = ordered_lock("obs.events.writer")
        self.records_written = 0

    def write(self, record: dict) -> str:
        line = json.dumps(record, sort_keys=True)
        with self._lock:
            os.makedirs(self.directory, exist_ok=True)
            with open(self.path, "a") as f:
                f.write(line + "\n")
            self.records_written += 1
        return self.path


# ---------------------------------------------------------------------------
# Recent-record ring (the flight recorder's "what was the engine doing
# just before the incident" context — obs/telemetry.py embeds it in
# every incident bundle)
# ---------------------------------------------------------------------------

#: slimmed summaries of the most recent event records, process-wide
#: (full records carry whole plan trees — the bundle only needs the
#: headline facts)
_RECENT_KEEP = 32
_RECENT_LOCK = ordered_lock("obs.events.recent")
_RECENT = deque(maxlen=_RECENT_KEEP)
_RECENT_FIELDS = ("queryIndex", "queryTag", "wallS", "healthState",
                  "hostTopology", "meshShape", "dispatches",
                  "faultReplays", "hostsLost", "hostRelands",
                  "meshDegradations", "deviceReinits", "cacheHit")


def note_recent_record(record: dict) -> None:
    """Remember a slim summary of one written event record (called by
    the session's event-log append path)."""
    slim = {k: record.get(k) for k in _RECENT_FIELDS}
    slim["demotions"] = sorted(record.get("demotions") or {})
    slim["faultFires"] = dict(record.get("faultFires") or {})
    with _RECENT_LOCK:
        _RECENT.append(slim)


def recent_records(n: int = _RECENT_KEEP) -> List[dict]:
    if n <= 0:
        return []  # [-0:] would return ALL
    with _RECENT_LOCK:
        return list(_RECENT)[-int(n):]


def scope_delta(before: Dict[str, dict],
                after: Dict[str, dict]) -> Dict[str, dict]:
    """Per-scope numeric deltas between two scopes_snapshot() calls —
    only keys that moved, so idle subsystems stay out of the record."""
    out: Dict[str, dict] = {}
    for scope, vals in after.items():
        prev = before.get(scope, {})
        moved = {}
        for k, v in vals.items():
            d = v - prev.get(k, 0)
            if d:
                moved[k] = round(d, 6) if isinstance(d, float) else d
        if moved:
            out[scope] = moved
    return out
