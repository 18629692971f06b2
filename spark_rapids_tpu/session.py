"""TpuSession — the user entry point (reference analog: SQLPlugin +
RapidsDriverPlugin/RapidsExecutorPlugin lifecycle, Plugin.scala — SURVEY.md
§2.1/§3.1). Owns the conf, the device runtime, and plan execution through
the overrides engine."""

from __future__ import annotations

import threading
from typing import Dict, Optional

from spark_rapids_tpu.columnar import HostTable
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.overrides import apply_overrides, explain_plan
from spark_rapids_tpu.plan import DataFrame, from_host_table
from spark_rapids_tpu.plan import nodes as P


def _mem_budget_peak() -> int:
    """The memory arbiter's peak accounted device bytes (event-log
    schema v10 budgetPeak field; lazy import: the session module must
    stay importable standalone)."""
    from spark_rapids_tpu.runtime.memory import MEMORY
    return int(MEMORY.peak_bytes())


class _TLQueryState:
    """Per-(session, thread) in-flight query state. A session may run
    queries CONCURRENTLY from query-service worker threads; everything a
    single execute() writes while running (depth, phases, the executed
    tree, the next-query attribution fields harnesses set) must be
    thread-local or two in-flight queries corrupt each other's
    envelope. ``last_*`` reads fall back to the session-wide mirror so
    serial callers on another thread still see the most recent query."""

    __slots__ = ("exec_depth", "next_tag", "next_sql", "next_parse_s",
                 "next_service",
                 "next_mv_epoch", "stream_deltas", "meta", "phases",
                 "executable",
                 "dispatches", "host_syncs", "fault_replays", "event_record",
                 "event_path", "exec_cache_token", "exec_cache_hit",
                 "compile_ms", "pad_waste")

    def __init__(self):
        self.exec_depth = 0
        self.next_tag = None
        self.next_sql = None
        self.next_parse_s = None
        self.next_service = None
        self.next_mv_epoch = None
        self.stream_deltas = None
        self.meta = None
        self.phases = None
        self.executable = None
        self.dispatches = None
        self.host_syncs = None
        self.fault_replays = None
        self.event_record = None
        self.event_path = None
        self.exec_cache_token = None
        self.exec_cache_hit = None
        self.compile_ms = None
        self.pad_waste = None


def _tl_mirrored(tls_field: str, doc: str):
    """Property: read this thread's value, else the session-wide mirror
    of the last completed query; writes update both."""

    def _get(self):
        v = getattr(self._q, tls_field)
        return v if v is not None else self._mirror.get(tls_field)

    def _set(self, value):
        setattr(self._q, tls_field, value)
        self._mirror[tls_field] = value

    return property(_get, _set, doc=doc)


def _tl_only(tls_field: str, doc: str):
    def _get(self):
        return getattr(self._q, tls_field)

    def _set(self, value):
        setattr(self._q, tls_field, value)

    return property(_get, _set, doc=doc)


class TpuSession:
    # -- per-thread query state (concurrent executes; see _TLQueryState) --
    next_query_tag = _tl_only(
        "next_tag", "query tag the NEXT execute() on this thread records")
    next_query_sql = _tl_only(
        "next_sql", "SQL text the NEXT execute() on this thread records")
    next_query_parse_s = _tl_only(
        "next_parse_s", "seconds sql() took to lower the statement the "
        "NEXT execute() on this thread runs (its record's phasesS.parseS)")
    next_query_service = _tl_only(
        "next_service", "service envelope (tenant/pool/queue-wait/"
        "cache-hit) the NEXT execute() on this thread records")
    next_query_mv_epoch = _tl_only(
        "next_mv_epoch", "materialized-view epoch (the maintained "
        "table's Delta version) the NEXT execute() on this thread "
        "records as mvEpoch — set by MV serve paths, null otherwise")

    def stage_stream_delta(self, key: str, n: int = 1) -> None:
        """Attribute streaming work (microBatches/mvRefreshes/.../
        sinkReplays) to the NEXT execute() on this thread: the streaming
        subsystem's bookkeeping runs BETWEEN query envelopes (after one
        execute returns, before the next starts), so the process-wide
        scope deltas alone would never land inside a record's window.
        Drained (and zeroed) by the next record built on this thread."""
        q = self._q
        d = q.stream_deltas or {}
        d[key] = d.get(key, 0) + n
        q.stream_deltas = d
    _exec_depth = _tl_only(
        "exec_depth", "nested-execute depth on this thread")
    _last_meta = _tl_only("meta", "overrides meta of this thread's query")
    _last_phases = _tl_only("phases", "phase times of this thread's query")
    _last_executable = _tl_mirrored(
        "executable", "executed tree of the last query (thread, then "
        "session-wide)")
    last_dispatches = _tl_mirrored(
        "dispatches", "device dispatches of the last query")
    last_fault_replays = _tl_mirrored(
        "fault_replays", "circuit-breaker replays of the last query")
    last_event_record = _tl_mirrored(
        "event_record", "event-log record of the last query")
    last_event_path = _tl_mirrored(
        "event_path", "event-log path of the last query")
    last_executable_cache_hit = _tl_mirrored(
        "exec_cache_hit", "did the last query check out a cached "
        "converted executable (plan/executable_cache.py)?")
    last_compile_ms = _tl_mirrored(
        "compile_ms", "milliseconds the last query spent on new XLA "
        "traces (trace + lowering + backend compile)")
    last_pad_waste_rows = _tl_mirrored(
        "pad_waste", "dead tail rows the last query uploaded to pad "
        "batches up to their capacity buckets")

    def __init__(self, conf: Optional[Dict] = None):
        self.conf = RapidsConf(conf)
        self._runtime = None
        self._profiler = None
        self._catalog = None
        # observability state (obs/): per-session query sequence, the
        # lazy event-log writer, and the caller-settable attribution
        # fields the next execute() consumes (harnesses tag queries so
        # the offline tools can match runs per query). In-flight query
        # state is per-thread (_TLQueryState); _mirror keeps the
        # last-completed-query view for readers on other threads.
        self._tls = threading.local()
        self._mirror: Dict[str, object] = {}
        self._obs_lock = threading.Lock()
        self._obs_query_seq = 0
        self._event_writer = None
        self._placement = None

    @property
    def _q(self) -> _TLQueryState:
        q = getattr(self._tls, "q", None)
        if q is None:
            q = self._tls.q = _TLQueryState()
        return q

    # -- SQL front end -------------------------------------------------------
    @property
    def catalog(self):
        """Session catalog: temp views, registered file-format tables
        (sources SPI) and SQL-callable functions."""
        if self._catalog is None:
            from spark_rapids_tpu.sql.catalog import SessionCatalog
            self._catalog = SessionCatalog(self)
        return self._catalog

    def sql(self, text: str) -> DataFrame:
        """Run one SQL statement (SELECT / CREATE TEMP VIEW / DROP VIEW)
        through parser -> analyzer -> the existing plan layer; the
        resulting DataFrame flows through overrides/AQE exactly like a
        DSL-built one."""
        import time as _time

        from spark_rapids_tpu.obs.spans import span
        from spark_rapids_tpu.sql import lower_statement
        t0 = _time.perf_counter()
        with span("parse", "phase"):
            df = lower_statement(self, text)
        df.sql_text = text
        df.parse_s = _time.perf_counter() - t0
        return df

    def table(self, name: str) -> DataFrame:
        """DataFrame over a temp view or registered table."""
        return self.catalog.table(name)

    @property
    def placement(self):
        """The placement half of the session split
        (runtime/placement.py): mesh realization, device-residency
        gating, the speculative drain and async-fetch resolution. This
        class keeps the DRIVER half — SQL/catalog, planning,
        overrides/AQE, verification, caches, observability."""
        if self._placement is None:
            from spark_rapids_tpu.runtime.placement import PlacementLayer
            self._placement = PlacementLayer(self)
        return self._placement

    @property
    def profiler(self):
        if self._profiler is None:
            from spark_rapids_tpu.runtime.profiler import TpuProfiler
            self._profiler = TpuProfiler(self.conf)
        return self._profiler

    # -- lifecycle ----------------------------------------------------------
    @property
    def runtime(self):
        if self._runtime is None:
            from spark_rapids_tpu.runtime.device_manager import TpuDeviceManager
            self._runtime = TpuDeviceManager(self.conf)
            self._runtime.initialize()
        return self._runtime

    def set_conf(self, key: str, value) -> "TpuSession":
        self.conf = self.conf.set(key, value)
        return self

    # -- data sources -------------------------------------------------------
    def create_dataframe(self, data, dtypes=None, num_batches: int = 1) -> DataFrame:
        if isinstance(data, HostTable):
            return from_host_table(data, self, num_batches)
        if isinstance(data, dict):
            return from_host_table(HostTable.from_pydict(data, dtypes), self, num_batches)
        import pandas as pd
        if isinstance(data, pd.DataFrame):
            return from_host_table(HostTable.from_pandas(data), self, num_batches)
        raise TypeError(f"cannot create DataFrame from {type(data)}")

    def range(self, start: int, end: Optional[int] = None, step: int = 1) -> DataFrame:
        if end is None:
            start, end = 0, start
        return DataFrame(P.RangeNode(start, end, step), self)

    def read_parquet(self, *paths, **options) -> DataFrame:
        from spark_rapids_tpu.io.parquet import ParquetScanNode
        return DataFrame(ParquetScanNode(list(paths), self.conf, **options), self)

    def read_csv(self, *paths, **options) -> DataFrame:
        from spark_rapids_tpu.io.csv import CsvScanNode
        return DataFrame(CsvScanNode(list(paths), self.conf, **options), self)

    def read_json(self, *paths, **options) -> DataFrame:
        from spark_rapids_tpu.io.json import JsonScanNode
        return DataFrame(JsonScanNode(list(paths), self.conf, **options), self)

    def read_orc(self, *paths, **options) -> DataFrame:
        from spark_rapids_tpu.io.orc import OrcScanNode
        return DataFrame(OrcScanNode(list(paths), self.conf, **options), self)

    # connectors resolve through the provider SPI (sources.py —
    # ExternalSource.scala analog), never by direct import here
    @property
    def read(self):
        """session.read.format("delta").load(path) — reader surface
        routed through the external-source provider SPI."""
        from spark_rapids_tpu.sources import DataFrameReader
        return DataFrameReader(self)

    def read_format(self, fmt: str, *paths, **options) -> DataFrame:
        from spark_rapids_tpu.sources import create_scan
        return DataFrame(create_scan(fmt, list(paths), self.conf,
                                     **options), self)

    def read_delta(self, path, version_as_of=None, **options) -> DataFrame:
        return self.read_format("delta", path,
                                version_as_of=version_as_of, **options)

    def delta_table(self, path) -> "object":
        from spark_rapids_tpu.errors import ColumnarProcessingError
        from spark_rapids_tpu.sources import provider_for
        p = provider_for("delta")
        if p is None:
            raise ColumnarProcessingError(
                "delta source provider is not available")
        return p.create_table_api(self, path)

    def read_iceberg(self, path, snapshot_id=None, **options) -> DataFrame:
        return self.read_format("iceberg", path, snapshot_id=snapshot_id,
                                **options)

    def read_avro(self, *paths, **options) -> DataFrame:
        return self.read_format("avro", *paths, **options)

    def read_hive_text(self, *paths, schema=None, **options) -> DataFrame:
        return self.read_format("hive-text", *paths, schema=schema,
                                **options)

    # -- execution ----------------------------------------------------------
    def execute(self, plan: P.PlanNode) -> HostTable:
        """Run one query: recovery-wrapped execution plus the per-query
        observability envelope — when the event log or host tracing is
        enabled, spans collect for the duration and a structured record
        (obs/events.py) is written on success. Nested executes
        (cached-relation / broadcast materialization inside an outer
        query) ride the outer envelope. Safe to call concurrently from
        multiple threads (the query service's worker pool): in-flight
        state is thread-local and the span tracer scopes each query to
        its executing thread."""
        from spark_rapids_tpu.obs import events as E
        from spark_rapids_tpu.obs.spans import (
            TRACE_ENABLED,
            TRACER,
            install_gc_hook,
            span,
        )

        q = self._q
        tags = {"query_tag": q.next_tag, "sql_text": q.next_sql,
                "parse_s": q.next_parse_s, "service": q.next_service,
                "mv_epoch": q.next_mv_epoch,
                "stream_deltas": q.stream_deltas or {}}
        q.next_tag = q.next_sql = q.next_parse_s = q.next_service = None
        q.next_mv_epoch = q.stream_deltas = None

        if q.exec_depth:
            # nested query: no separate envelope, no index
            q.exec_depth += 1
            try:
                return self._execute_with_recovery(plan)
            finally:
                q.exec_depth -= 1

        # fresh per-host scan attribution for this top-level query
        # (thread-local, like the dispatch counters — nested executes
        # accumulate into the outer query's table)
        from spark_rapids_tpu.runtime.cluster import reset_host_scan_stats
        reset_host_scan_stats()

        ev_enabled = bool(self.conf.get_entry(E.EVENT_LOG_ENABLED))
        tr_enabled = bool(self.conf.get_entry(TRACE_ENABLED))
        # this thread's view while THIS query is in flight: no record
        # yet (readers fall back to the session-wide mirror of the last
        # completed query)
        q.event_record = None
        q.event_path = None
        with self._obs_lock:
            qidx = self._obs_query_seq
            self._obs_query_seq += 1
        if ev_enabled or tr_enabled:
            install_gc_hook()
            TRACER.begin_query(qidx)
        else:
            # no envelope for THIS query, but another session's
            # observed query may be live on a worker thread: block the
            # tracer's helper-thread adoption so this query's spans
            # can't pollute that query's record
            TRACER.begin_unobserved_query(qidx)
        try:
            # srt.query: the whole of the query on this thread, the
            # observation tail included
            with span("query", "query"):
                return self._execute_enveloped(plan, qidx, ev_enabled,
                                               tr_enabled, tags)
        finally:
            TRACER.leave_query()

    def _execute_enveloped(self, plan: P.PlanNode, qidx: int,
                           ev_enabled: bool, tr_enabled: bool,
                           tags: dict) -> HostTable:
        """One top-level query inside its ``srt.query`` range: the
        recovery-wrapped run, then (event log or tracing on) the
        observation tail, ``srt.phase.observe``, that resolves deferred
        row counts, builds the record and writes it."""
        import threading as _threading
        import time as _time

        from spark_rapids_tpu.obs import events as E
        from spark_rapids_tpu.obs.spans import (
            TRACE_DIR,
            TRACER,
            gc_seconds,
            span,
        )

        q = self._q
        obs_active = ev_enabled or tr_enabled
        gc_before = gc_seconds()
        if obs_active:
            # the observation's first half: the counters the record
            # reports as deltas, read before the run
            with span("observe", "phase"):
                from spark_rapids_tpu.obs.metrics import scopes_snapshot
                from spark_rapids_tpu.runtime.faults import FAULTS, RECOVERY
                from spark_rapids_tpu.runtime.health import HEALTH
                before_scopes = scopes_snapshot()
                before_recovery = RECOVERY.snapshot()
                before_fires = FAULTS.counters()
                before_health = HEALTH.snapshot()
        q.exec_depth = 1
        t0 = _time.perf_counter()
        try:
            result = self._execute_with_recovery(plan)
        except BaseException:
            # a failed run may have left the checked-out tree partially
            # drained — drop the entry, never hand it to another query
            self._release_exec_cache(drop=True)
            raise
        finally:
            q.exec_depth = 0
            # success OR failure: a WriteFiles plan that failed
            # mid-drain may still have changed on-disk contents, so
            # cached results over its paths are stale either way
            self._invalidate_result_cache_on_write(plan)
        if not obs_active:
            self._release_exec_cache()
            return result
        wall_s = _time.perf_counter() - t0
        spans = TRACER.end_query()
        with span("observe", "phase"):
            from spark_rapids_tpu.obs.spans import (
                finalize_observation,
                summarize_spans,
                write_chrome_trace,
            )
            from spark_rapids_tpu.parallel.mesh import MESH
            from spark_rapids_tpu.runtime.cluster import (
                CLUSTER,
                host_scan_stats,
            )
            from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER
            stream_deltas = tags["stream_deltas"]
            phases = dict(q.phases or {})
            if tags["parse_s"] is not None:
                phases["parseS"] = tags["parse_s"]
            executable = q.executable
            if executable is not None:
                finalize_observation(executable)
            after_recovery = RECOVERY.snapshot()
            after_fires = FAULTS.counters()
            after_health = HEALTH.snapshot()
            after_scopes = scopes_snapshot()
            # worker restarts ride the process-wide ``health`` scope (the
            # service's watchdog respawns workers while queries run), so
            # the per-record delta attributes restarts to the wall they
            # happened under (0 on a quiet process)
            worker_restarts = int(
                after_scopes.get("health", {}).get("workersRespawned", 0)
                - before_scopes.get("health", {}).get("workersRespawned", 0))

            # transactional-write accounting: per-record deltas of the
            # ``write`` scope (io/committer.py) — the committer/Delta
            # transaction counters are process-wide, so the delta
            # attributes files/bytes/retries to the query whose wall they
            # happened under (all 0 for read-only queries)
            def _wdelta(key: str, scope: str = "write") -> int:
                return int(after_scopes.get(scope, {}).get(key, 0)
                           - before_scopes.get(scope, {}).get(key, 0))

            phases["gcS"] = gc_seconds() - gc_before
            record = E.build_query_record(
                query_index=qidx,
                wall_s=wall_s,
                phases=phases,
                executable=executable,
                meta=q.meta,
                sql_text=tags["sql_text"],
                query_tag=tags["query_tag"],
                dispatches=int(q.dispatches or 0),
                host_syncs=int(q.host_syncs or 0),
                recovery_delta={k: v - before_recovery.get(k, 0)
                                for k, v in after_recovery.items()
                                if v - before_recovery.get(k, 0)},
                scope_deltas=E.scope_delta(before_scopes, after_scopes),
                fault_fires={k: v - before_fires.get(k, 0)
                             for k, v in after_fires.items()
                             if v - before_fires.get(k, 0)},
                demotions=CIRCUIT_BREAKER.demoted_ops(),
                spans_summary=summarize_spans(
                    spans, _threading.get_ident(), wall_s),
                fault_replays=int(q.fault_replays or 0),
                service=tags["service"],
                compile_ms=float(q.compile_ms or 0.0),
                executable_cache_hit=bool(q.exec_cache_hit),
                pad_waste_rows=int(q.pad_waste or 0),
                health_state=HEALTH.state(),
                device_reinits=int(after_health["deviceReinits"]
                                   - before_health["deviceReinits"]),
                worker_restarts=worker_restarts,
                files_written=_wdelta("filesWritten"),
                bytes_written=_wdelta("bytesWritten"),
                commit_retries=_wdelta("commitRetries"),
                mesh_shape=MESH.shape_str(),
                ici_bytes=_wdelta("iciBytes", "mesh"),
                mesh_degradations=_wdelta("meshDegradations", "health"),
                shard_retries=_wdelta("shardRetries", "mesh"),
                gather_checks_failed=_wdelta("gatherChecksFailed", "mesh"),
                host_topology=CLUSTER.topology_str(),
                hosts_lost=_wdelta("hostsLost", "cluster"),
                host_relands=_wdelta("hostRelands", "cluster"),
                dcn_exchanges=_wdelta("dcnExchanges", "cluster"),
                host_scans=host_scan_stats(),
                oom_retries=_wdelta("oomRetries", "memory"),
                split_retries=_wdelta("splitRetries", "memory"),
                spill_bytes=_wdelta("spillBytes", "memory"),
                unspills=_wdelta("unspills", "memory"),
                budget_peak=_mem_budget_peak(),
                # streaming attribution: scope deltas (work done INSIDE
                # this window) plus the deltas the streaming subsystem
                # staged on this thread between envelopes
                micro_batches=_wdelta("microBatches", "streaming")
                + stream_deltas.get("microBatches", 0),
                mv_refreshes=_wdelta("mvRefreshes", "streaming")
                + stream_deltas.get("mvRefreshes", 0),
                mv_incremental_refreshes=_wdelta(
                    "mvIncrementalRefreshes", "streaming")
                + stream_deltas.get("mvIncrementalRefreshes", 0),
                mv_full_recomputes=_wdelta("mvFullRecomputes", "streaming")
                + stream_deltas.get("mvFullRecomputes", 0),
                sink_commits=_wdelta("sinkCommits", "streaming")
                + stream_deltas.get("sinkCommits", 0),
                sink_replays=_wdelta("sinkReplays", "streaming")
                + stream_deltas.get("sinkReplays", 0),
                mv_epoch=tags["mv_epoch"],
            )
            self.last_event_record = record
            # the record has read the tree's metrics — the cached executable
            # may now be handed to the next query (which resets them)
            self._release_exec_cache()
            # emission is best-effort: an unwritable log dir or full disk
            # must not fail a query that already computed its result
            try:
                if ev_enabled:
                    self._write_event_record(record)
                if tr_enabled:
                    import os
                    trace_dir = str(self.conf.get_entry(TRACE_DIR))
                    os.makedirs(trace_dir, exist_ok=True)
                    write_chrome_trace(
                        os.path.join(trace_dir, f"query_{qidx}.trace.json"),
                        spans, query_id=qidx)
            except OSError as exc:
                print(f"spark_rapids_tpu: event/trace emission failed "
                      f"(query {qidx}): {exc}")
        return result

    def _write_event_record(self, record: dict) -> str:
        """THE event-log append path — lazily creates the per-session
        writer under the obs lock. Used by execute() and by the query
        service's cache-hit record emission, so writer setup can never
        diverge between executed and served queries. Raises OSError on
        emission failure; callers treat it as best-effort."""
        from spark_rapids_tpu.obs import events as E
        with self._obs_lock:
            if self._event_writer is None:
                self._event_writer = E.QueryEventWriter(
                    str(self.conf.get_entry(E.EVENT_LOG_DIR)))
        # the flight recorder's "recent events" context rides the same
        # funnel (slim summary, bounded ring — obs/events.py)
        E.note_recent_record(record)
        from spark_rapids_tpu.obs.spans import span
        with span("write", "eventlog"):
            path = self._event_writer.write(record)
        self.last_event_path = path
        return path

    def _release_exec_cache(self, drop: bool = False) -> None:
        """Return this thread's checked-out executable-cache entry (if
        any). Called once the query's envelope is fully done with the
        tree — after the event record on observed queries — or with
        ``drop`` when the run failed and the tree's state is suspect."""
        tok = self._q.exec_cache_token
        self._q.exec_cache_token = None
        if tok is not None:
            tok.release(drop=drop)

    def _invalidate_result_cache_on_write(self, plan: P.PlanNode) -> None:
        """A completed write (WriteFiles / Delta / Iceberg commands ride
        plans or commit through delta.log, which bumps the epoch itself)
        invalidates every cached service result — contents under the
        written paths changed."""
        stack = [plan]
        while stack:
            node = stack.pop()
            if isinstance(node, P.WriteFiles):
                from spark_rapids_tpu.service.result_cache import (
                    bump_invalidation_epoch,
                )
                bump_invalidation_epoch("WriteFiles")
                return
            stack.extend(getattr(node, "children", ()))

    def _configure_runtime(self) -> None:
        """The runtime follows this session's conf, once a query, before
        its first attempt plans (range ``srt.phase.configure``)."""
        from spark_rapids_tpu.conf import TEST_FAULTS
        from spark_rapids_tpu.runtime import faults as F
        F.FAULTS.arm(str(self.conf.get_entry(TEST_FAULTS) or ""))
        # a host that relies on JAX auto-detection (no JAX_PLATFORMS)
        # has no cache decision from import time: take it here, where
        # the backend is about to be used anyway (a flag read once the
        # cache is on; two cached lookups on the CPU backend)
        import spark_rapids_tpu as _pkg
        _pkg.ensure_compile_cache()
        # telemetry sampler + flight-recorder defaults follow this
        # session's conf (cheap no-op when unchanged, the arm contract)
        from spark_rapids_tpu.obs.telemetry import TELEMETRY
        TELEMETRY.configure(self.conf)
        # the device memory arbiter's hard budget follows it too
        from spark_rapids_tpu.runtime import memory as _memory
        _memory.MEMORY.configure(self.conf)
        # runtime lock witness (construction-time election — locks
        # built after this point are wrapped iff the conf arms it)
        from spark_rapids_tpu import lockorder as _lockorder
        _lockorder.configure(self.conf)

    def _execute_with_recovery(self, plan: P.PlanNode) -> HostTable:
        """Plan, verify, and drain a query — wrapped in TWO distinct
        recovery layers:

        * a non-OOM KERNEL failure (KernelCrashError) replays the query
          through the runtime circuit breaker, and once the same
          operator fails spark.rapids.sql.runtimeFallback.maxFailures
          times it is demoted to the CPU fallback path for the session
          (the replay re-plans, so the demotion takes effect
          immediately);
        * a FATAL device error (is_fatal_device_error — the device or
          its PJRT client is gone, not one operator) captures a crash report
          and hands recovery to the health monitor (runtime/health.py):
          backend reinit, device-referencing caches invalidated, and
          after deviceLoss.maxReinits consecutive losses the CPU-only
          latch. The query surfaces a typed RETRYABLE DeviceLostError —
          the query service requeues it against the recovered backend.

        OOMs never come through here — the retry framework owns those."""
        from spark_rapids_tpu.conf import (
            RUNTIME_FALLBACK_ENABLED,
            RUNTIME_FALLBACK_MAX_FAILURES,
        )
        from spark_rapids_tpu.errors import DeviceLostError, KernelCrashError
        from spark_rapids_tpu.runtime import faults as F
        from spark_rapids_tpu.runtime.crash_handler import (
            handle_fatal,
            is_fatal_device_error,
        )

        from spark_rapids_tpu.obs.spans import span
        with span("configure", "phase"):
            self._configure_runtime()
        rf_enabled = bool(self.conf.get_entry(RUNTIME_FALLBACK_ENABLED))
        max_failures = int(self.conf.get_entry(RUNTIME_FALLBACK_MAX_FAILURES))
        # enough budget to demote every op in a pathological plan without
        # ever replaying unboundedly on an unattributable crash
        max_replays = 4 * max_failures + 4
        replays = 0
        # mesh degradation ladder (runtime/health.py): PARTIAL device
        # losses replay internally — enough budget to walk every rung
        # (retry -> single-device -> every shrink -> every reinit ->
        # the CPU-only latch) without replaying unboundedly
        from contextlib import nullcontext

        from spark_rapids_tpu.errors import (
            HostLostError,
            MeshDeviceLostError,
        )
        from spark_rapids_tpu.parallel import mesh as _mesh
        from spark_rapids_tpu.runtime import cluster as _cluster
        from spark_rapids_tpu.runtime.health import DEVICE_LOSS_MAX_REINITS
        max_mesh_replays = (
            int(self.conf.get_entry(_mesh.MESH_DEGRADE_MAX_SHRINKS))
            + int(self.conf.get_entry(DEVICE_LOSS_MAX_REINITS)) + 6)
        mesh_replays = 0
        # host degradation ladder (runtime/health.py on_host_loss):
        # enough budget to walk every rung (retry -> reland -> every
        # shrink -> the single-process latch) plus escalation slack
        max_host_replays = (
            int(self.conf.get_entry(_cluster.CLUSTER_MAX_HOST_LOSSES))
            + int(self.conf.get_entry(DEVICE_LOSS_MAX_REINITS)) + 6)
        host_replays = 0
        # memory degradation ladder (runtime/health.py
        # on_memory_pressure): FatalDeviceOOMs that escaped the retry
        # framework replay internally — enough budget to walk every
        # rung (full-spill retry -> chunked re-execution -> one CPU
        # demotion per plan operator) without replaying unboundedly
        max_mem_replays = 4 * max_failures + 4
        mem_replays = 0
        suppress_reason = None
        suppress_cluster = None
        force_chunk = None
        while True:
            was_suppressed = suppress_reason is not None
            was_csuppressed = suppress_cluster is not None
            attempt_ctx = (_mesh.suppressed_mesh(suppress_reason)
                           if was_suppressed else nullcontext())
            cluster_ctx = (_cluster.suppressed_cluster(suppress_cluster)
                           if was_csuppressed else nullcontext())
            from spark_rapids_tpu.runtime import memory as _memory
            chunk_ctx = (_memory.forced_chunking(force_chunk)
                         if force_chunk is not None else nullcontext())
            suppress_reason = None
            suppress_cluster = None
            force_chunk = None
            try:
                with attempt_ctx, cluster_ctx, chunk_ctx:
                    result = self._execute_attempt(plan)
                # the run is accounted: its replays, and the health
                # ladders told it succeeded
                with span("account", "phase"):
                    self.last_fault_replays = replays
                    if replays and hasattr(self._last_executable, "metrics"):
                        self._last_executable.metrics[
                            "runtimeFaultReplays"] = replays
                    from spark_rapids_tpu.runtime.health import HEALTH
                    # the MESH ladder only resets on a mesh-NATIVE success:
                    # a suppressed (single-device) convergence proves
                    # nothing about the mesh's health — and the HOST ladder
                    # likewise only on a cluster-NATIVE success
                    HEALTH.note_success(
                        mesh_native=not was_suppressed and _mesh.MESH.enabled,
                        cluster_native=(not was_csuppressed
                                        and _cluster.CLUSTER.active()))
                return result
            except Exception as exc:
                from spark_rapids_tpu.errors import FatalDeviceOOM
                from spark_rapids_tpu.runtime.retry import is_device_oom
                if (is_device_oom(exc)
                        and not isinstance(exc, FatalDeviceOOM)
                        and not getattr(exc, "_mem_handled", False)):
                    # a RETRYABLE OOM that escaped every retry wrapper
                    # (a landing site without its own retry_block):
                    # the memory ladder is strictly better than
                    # failing the query — normalize and fall through
                    # to the FatalDeviceOOM branch below
                    wrapped = FatalDeviceOOM(
                        f"unhandled retryable OOM escaped to the "
                        f"session ({type(exc).__name__}: {exc})")
                    wrapped.__cause__ = exc
                    if getattr(exc, "fault_op", None) is not None:
                        wrapped.fault_op = exc.fault_op
                    exc = wrapped
                if isinstance(exc, FatalDeviceOOM) and \
                        not getattr(exc, "_mem_handled", False):
                    # the retry framework is out of moves (spill
                    # replays AND split-and-retry both exhausted): the
                    # MEMORY degradation ladder owns the attempt —
                    # full-spill retry, then chunked re-execution,
                    # then per-op CPU demotion, each action recording
                    # a flight-recorder incident bundle
                    from spark_rapids_tpu.runtime.health import HEALTH
                    action = HEALTH.on_memory_pressure(exc, self.conf)
                    if action == "abort" or mem_replays >= max_mem_replays:
                        exc._mem_handled = True
                        raise
                    if self._q.exec_depth == 1:
                        self._release_exec_cache(drop=True)
                    mem_replays += 1
                    F.RECOVERY.bump("query_replays")
                    if action == "chunk":
                        # replay with scans forced onto chunks half
                        # the normal budget share — bounded partitions
                        # stream where one batch could not fit
                        force_chunk = max(
                            1, _memory.MEMORY.scan_chunk_bytes() // 2)
                    # "retry" replays same-shape after the full spill;
                    # "cpu_demote" re-plans with the attributed op
                    # demoted to the CPU path (circuit breaker)
                    continue
                if isinstance(exc, HostLostError) and \
                        not getattr(exc, "_health_handled", False):
                    # a whole executor HOST died (the local backend is
                    # fine): the HOST degradation ladder owns recovery
                    # — classified before the whole-backend is_fatal
                    # branch (HostLostError IS a DeviceLostError)
                    from spark_rapids_tpu.runtime.health import HEALTH
                    action = HEALTH.on_host_loss(exc, self.conf)
                    self._strike_fault_template(
                        plan, exc, action, domain="host",
                        benign=("retry",))
                    if host_replays >= max_host_replays:
                        exc._health_handled = True
                        raise
                    if self._q.exec_depth == 1:
                        self._release_exec_cache(drop=True)
                    host_replays += 1
                    F.RECOVERY.bump("query_replays")
                    if action in ("single_process", "DEGRADED",
                                  "CPU_ONLY"):
                        # pin the replay to local scans even if a host
                        # rejoins (clearing the latch) mid-attempt —
                        # the attempt must be deterministic. The
                        # escalated actions replay too (the mesh
                        # branch's contract): the re-plan sees the
                        # reinitialized backend or the CPU-only latch
                        # and serves the query without the cluster.
                        suppress_cluster = HEALTH.host_demotion_note()
                    # "retry"/"reland"/"shrink" replay plain: the
                    # re-plan's scans see the re-routed topology
                    continue
                if isinstance(exc, MeshDeviceLostError) and \
                        not getattr(exc, "_health_handled", False):
                    # PARTIAL loss (one mesh device dead, backend
                    # alive): the degradation ladder owns recovery —
                    # classified DISTINCTLY from the whole-backend
                    # is_fatal branch below
                    from spark_rapids_tpu.runtime.health import HEALTH
                    action = HEALTH.on_mesh_device_loss(exc, self.conf)
                    self._strike_fault_template(plan, exc, action,
                                                domain="mesh")
                    if mesh_replays >= max_mesh_replays:
                        exc._health_handled = True
                        raise
                    if self._q.exec_depth == 1:
                        self._release_exec_cache(drop=True)
                    mesh_replays += 1
                    F.RECOVERY.bump("query_replays")
                    if action == "single_device":
                        suppress_reason = HEALTH.mesh_demotion_note()
                    # "retry"/"shrink"/"DEGRADED"/"CPU_ONLY" all replay
                    # plain: the re-plan sees the shrunken mesh, the
                    # reinitialized backend, or the CPU-only latch
                    continue
                if is_fatal_device_error(exc):
                    # a nested execute already ran recovery for this
                    # exception — the outer envelope just propagates it
                    if getattr(exc, "_health_handled", False):
                        raise
                    ex = getattr(self, "_last_executable", None)
                    handle_fatal(exc, self.conf,
                                 plan_description=ex.tree_string()
                                 if ex is not None else "")
                    # the in-flight tree references the dead device —
                    # drop it before recovery clears the cache (TOP
                    # LEVEL only: depth >= 2 holds no token)
                    if self._q.exec_depth == 1:
                        self._release_exec_cache(drop=True)
                    from spark_rapids_tpu.runtime.health import HEALTH
                    HEALTH.on_device_loss(exc, self.conf)
                    if isinstance(exc, DeviceLostError):
                        exc._health_handled = True
                        raise
                    lost = DeviceLostError(
                        f"device lost during execution "
                        f"({type(exc).__name__}: {exc}); backend "
                        f"recovered — retry the query")
                    lost._health_handled = True
                    if getattr(exc, "fault_op", None) is not None:
                        lost.fault_op = exc.fault_op
                    raise lost from exc
                demotable = isinstance(exc, KernelCrashError)
                if not rf_enabled or not demotable or replays >= max_replays:
                    raise
                op = getattr(exc, "fault_op", None)
                if op is not None:
                    F.CIRCUIT_BREAKER.record_failure(op, exc, max_failures)
                # the crashed attempt's cached/filled executable is
                # suspect AND a recorded demotion must re-plan — drop
                # the entry so the replay converts fresh. TOP LEVEL
                # only: a nested execute's recovery (depth >= 2) holds
                # no token of its own and must not release the OUTER
                # query's mid-run
                if self._q.exec_depth == 1:
                    self._release_exec_cache(drop=True)
                replays += 1
                F.RECOVERY.bump("query_replays")

    def _strike_fault_template(self, plan: P.PlanNode, exc: BaseException,
                               action: str, domain: str = "mesh",
                               benign=("retry",)) -> None:
        """A template that repeatedly kills mesh or cluster execution
        is a poison suspect like any worker/device killer: every
        ladder action past the plain retry records a quarantine strike
        (the service then refuses the template at admission once it
        crosses spark.rapids.service.quarantine.maxStrikes).
        Best-effort — strike accounting must never mask recovery."""
        if action in benign:
            return
        try:
            from spark_rapids_tpu.plan.fingerprint import (
                template_fingerprint,
            )
            from spark_rapids_tpu.runtime.health import (
                QUARANTINE,
                QUARANTINE_MAX_STRIKES,
            )
            first = (str(exc).splitlines()[0] if str(exc)
                     else type(exc).__name__)
            QUARANTINE.strike(
                template_fingerprint(plan, self.conf),
                f"{domain} execution killed ({action}): "
                f"{type(exc).__name__}: {first}",
                int(self.conf.get_entry(QUARANTINE_MAX_STRIKES)))
        except Exception:
            pass

    def _plan(self, plan: P.PlanNode):
        """Physical planning of one attempt (``srt.phase.plan``, the
        record's ``planS``): placement, the executable cache, overrides,
        plan verification and the per-query boundaries. Returns the
        converted tree, its overrides meta and the cache token."""
        from spark_rapids_tpu.overrides.input_file import \
            rewrite_input_file_exprs
        plan = rewrite_input_file_exprs(plan)

        # placement first: the mesh runtime must reflect THIS query's
        # spark.rapids.mesh.* conf before the fingerprint folds the
        # mesh identity token and the executable cache stamps its
        # generation (a reconfiguration invalidates cached trees)
        self.placement.prepare()

        # plan -> executable cache (plan/executable_cache.py): a
        # repeated template checks out its already-converted (and
        # already-verified: planVerify.mode folds into the fingerprint)
        # tree — no overrides run, no verification, and every kernel
        # already traced. Top-level queries only; a replayed attempt
        # dropped its entry in _execute_with_recovery and plans fresh
        # so circuit-breaker demotions take effect.
        q = self._q
        from spark_rapids_tpu.conf import (
            EXECUTABLE_CACHE_ENABLED,
            EXECUTABLE_CACHE_MAX_PLANS,
            EXECUTABLE_CACHE_MAX_VARIANTS,
        )
        tok = None
        if q.exec_depth == 1 and \
                bool(self.conf.get_entry(EXECUTABLE_CACHE_ENABLED)):
            from spark_rapids_tpu.plan.executable_cache import EXEC_CACHE
            EXEC_CACHE.configure(
                int(self.conf.get_entry(EXECUTABLE_CACHE_MAX_PLANS)),
                int(self.conf.get_entry(EXECUTABLE_CACHE_MAX_VARIANTS)))
            tok = EXEC_CACHE.checkout(plan, self.conf)
            q.exec_cache_token = tok
        if q.exec_depth == 1:
            # top level only: a nested execute (cached-relation /
            # broadcast materialization) must not clobber the OUTER
            # query's hit flag
            self.last_executable_cache_hit = bool(
                tok is not None and tok.hit)

        if tok is not None and tok.hit:
            executable, meta = tok.executable, tok.meta
        else:
            executable, meta = apply_overrides(plan, self.conf)
        self._last_meta = meta
        if meta is not None and self.conf.explain_mode in ("NOT_ON_GPU",
                                                           "ALL"):
            print(meta.explain(
                only_fallback=self.conf.explain_mode == "NOT_ON_GPU"))

        if tok is None or not tok.hit:
            # static plan verification (lint/plan_verifier): prove the
            # converted tree's cross-layer invariants BEFORE execution
            # (Catalyst validatePlan / assert-on-fallback analog)
            from spark_rapids_tpu.conf import PLAN_VERIFY_MODE
            verify_mode = str(self.conf.get_entry(PLAN_VERIFY_MODE)).lower()
            if verify_mode not in ("off", "warn", "error"):
                from spark_rapids_tpu.errors import ColumnarProcessingError
                raise ColumnarProcessingError(
                    f"spark.rapids.sql.planVerify.mode must be off, warn or "
                    f"error, got {verify_mode!r}")
            if verify_mode in ("warn", "error") and meta is not None:
                from spark_rapids_tpu.lint.plan_verifier import \
                    verify_converted
                diags = verify_converted(executable, meta, self.conf)
                if diags:
                    from spark_rapids_tpu.errors import PlanVerificationError
                    if verify_mode == "error":
                        raise PlanVerificationError(diags)
                    for d in diags:
                        print(f"planVerify: {d}")

        from spark_rapids_tpu.conf import METRICS_LEVEL
        from spark_rapids_tpu.execs.base import set_metrics_level
        set_metrics_level(self.conf.get_entry(METRICS_LEVEL))

        # rand(seed)/monotonically_increasing_id reproduce per query
        from spark_rapids_tpu.ops.misc import reset_nondeterministic_streams
        reset_nondeterministic_streams()

        # LORE: number every operator; arm input dumping for tagged ids
        # — FRESH trees only. A cached tree keeps the ids and _TeeChild
        # dumpers it was filled with (lore conf folds into the
        # executable fingerprint, so they match this query's conf);
        # re-numbering would shift ids across inserted dumper nodes and
        # install_dumpers is not idempotent (wrappers would stack)
        if tok is None or not tok.hit:
            from spark_rapids_tpu import lore
            lore.assign_lore_ids(executable)
            lore.install_dumpers(executable, self.conf)
        # fault boundaries: the exec.execute injection point + op
        # attribution for non-OOM device failures (circuit breaker input)
        from spark_rapids_tpu.runtime.faults import install_fault_boundaries
        install_fault_boundaries(executable)
        # observation boundaries OVER the fault guards: per-pull spans +
        # the ESSENTIAL opTime/numOutputRows/numOutputBatches metrics on
        # every device exec (obs/spans.py)
        from spark_rapids_tpu.obs.spans import install_observation
        install_observation(executable)
        # cancellation boundaries OUTERMOST (third wrapper in the
        # install_fault_boundaries family): the boundary resolves the
        # ACTIVE cancel scope per pull (contextvar), so it is installed
        # unconditionally — a cached executable filled by a scopeless
        # query still honors cancel()/deadlines when the query service
        # reuses it (service/query.py)
        from spark_rapids_tpu.service.query import install_cancellation
        install_cancellation(executable)
        self._last_executable = executable
        return executable, meta, tok

    def _execute_attempt(self, plan: P.PlanNode) -> HostTable:
        import time as _time

        from spark_rapids_tpu.conf import (
            RETRY_OOM_MAX_RETRIES,
            TEST_INJECT_RETRY_OOM,
        )
        from spark_rapids_tpu.obs.spans import span
        from spark_rapids_tpu.runtime import RMM_TPU
        from spark_rapids_tpu.runtime.retry import MAX_RETRIES_VAR

        q = self._q
        t_phase = _time.perf_counter()
        with span("plan", "phase"):
            executable, meta, tok = self._plan(plan)
        phases = {"planS": _time.perf_counter() - t_phase}

        # the planned tree armed for its run: injected OOMs, the root's
        # async fetch, the retry budget, the per-query counters reset
        with span("arm", "phase"):
            inject = str(self.conf.get_entry(TEST_INJECT_RETRY_OOM) or "")
            if inject:
                kind, _, num = inject.partition(":")
                count = int(num) if num else 1
                if kind.strip().lower() == "retry":
                    RMM_TPU.force_retry_oom(count)
                elif kind.strip().lower() == "split":
                    RMM_TPU.force_split_and_retry_oom(count)

            # async result fetch: arm the ROOT transition only — mid-plan
            # DeviceToHost nodes feed CPU fallback operators that expect
            # plain host batches. Re-set either way so a cached executable
            # never carries a previous query's flag.
            from spark_rapids_tpu.conf import ASYNC_RESULT_FETCH
            from spark_rapids_tpu.execs.base import DeviceToHost as _D2H
            if isinstance(executable, _D2H):
                executable._async_fetch = bool(
                    self.conf.get_entry(ASYNC_RESULT_FETCH))

            token = MAX_RETRIES_VAR.set(
                self.conf.get_entry(RETRY_OOM_MAX_RETRIES))
            from spark_rapids_tpu.dispatch import (
                dispatch_count,
                reset_compile_stats,
                reset_dispatch_count,
                reset_query_phases,
            )
            reset_dispatch_count()
            if q.exec_depth == 1:
                # top level only: a NESTED execute resetting mid-drain
                # would zero the outer query's trace/pad-waste accounting
                # and its dispatch/sync/fetch seconds
                reset_compile_stats()
                reset_query_phases()
        t_phase = _time.perf_counter()
        try:
            with span("execute", "phase"), self.profiler.profile_query():
                # placement owns the drain: device-residency gating
                # (semaphore), speculation, async-fetch resolution
                batches = self.placement.drain(executable)
            # per-query device dispatch count (VERDICT r3: observable)
            self.last_dispatches = dispatch_count()
            if hasattr(executable, "metrics"):
                executable.metrics["dispatches"] = self.last_dispatches
        finally:
            MAX_RETRIES_VAR.reset(token)
            phases["executeS"] = _time.perf_counter() - t_phase
            self._last_phases = phases
        t_phase = _time.perf_counter()
        try:
            with span("collect", "phase"):
                if not batches:
                    from spark_rapids_tpu.plan.nodes import _empty_table
                    out = _empty_table(plan.output_schema())
                else:
                    out = HostTable.concat(batches)
        finally:
            phases["collectS"] = _time.perf_counter() - t_phase
            # compile accounting AFTER collect: the packed d2h kernels
            # jit during it, and their traces belong to this query
            # (top level only — a nested execute rides the outer's
            # counters, mirroring the reset above); the seconds the
            # dispatch layer took where the work happened join the
            # phases here too
            if q.exec_depth == 1:
                with span("account", "phase"):
                    from spark_rapids_tpu.dispatch import (
                        compile_stats,
                        flush_trace_cache_hits,
                        host_fetch_count,
                        phase_seconds,
                    )
                    traces, compile_s, pad = compile_stats()
                    self.last_compile_ms = round(compile_s * 1000.0, 3)
                    self.last_pad_waste_rows = pad
                    flush_trace_cache_hits()
                    phases.update(phase_seconds())
                    q.host_syncs = host_fetch_count()
        # a fully successful run fills its executable-cache slot (the
        # entry stays checked out until the query envelope releases it)
        if tok is not None and not tok.hit:
            tok.fill(executable, meta)
        return out

    def execute_cpu_only(self, plan: P.PlanNode) -> HostTable:
        """Run fully on the CPU path (the oracle)."""
        return plan.collect_cpu()

    def last_metrics(self) -> str:
        """Per-operator metrics of the most recent execute(), rendered as a
        tree with lore ids (reference: GpuExec metrics + LORE ids shown in
        the Spark UI / explain output)."""
        ex = getattr(self, "_last_executable", None)
        if ex is None:
            return "(no query executed yet)"
        # resolve deferred device row counts (one batched fetch) so
        # numOutputRows is complete in the rendered tree
        from spark_rapids_tpu.obs.spans import finalize_observation
        finalize_observation(ex)
        lines = []

        def walk(e, indent):
            lid = getattr(e, "_lore_id", "?")
            desc = e.describe() if hasattr(e, "describe") else type(e).__name__
            m = getattr(e, "metrics", None)
            mtxt = ""
            if m:
                parts = [f"{k}={v:.4f}" if isinstance(v, float) else f"{k}={v}"
                         for k, v in sorted(m.items())]
                mtxt = "  [" + ", ".join(parts) + "]"
            lines.append("  " * indent + f"[loreId={lid}] {desc}{mtxt}")
            for c in getattr(e, "children", ()):
                walk(c, indent + 1)
            for attr in ("source", "tpu_exec", "cpu_node", "scan_node"):
                nxt = getattr(e, attr, None)
                if nxt is not None:
                    walk(nxt, indent + 1)

        walk(ex, 0)
        return "\n".join(lines)

    def explain(self, plan: P.PlanNode) -> str:
        return explain_plan(plan, self.conf)
