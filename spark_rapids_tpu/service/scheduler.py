"""QueryService: worker pool + admission control + weighted fair queueing.

Reference: the reference plugin leans on Spark's scheduler — FAIR
scheduler pools (``spark.scheduler.pool``) queue jobs per tenant, the
driver bounds concurrent tasks, and ``GpuSemaphore`` bounds how many of
those touch the device at once. This engine owns its sessions, so this
module provides that stack natively:

* **Admission**: bounded per-pool queue depth; a full queue raises the
  typed :class:`~spark_rapids_tpu.errors.QueryRejectedError` carrying a
  ``retry_after_ms`` backpressure hint. Before a worker takes a query,
  admission consults the spill catalog's device-resident bytes
  (``spark.rapids.service.admission.maxDeviceBytes``): over the high
  water mark, queued queries HOLD until a running query finishes —
  unless nothing is running (forward progress beats the gate).
* **Scheduling**: two-level weighted fair queueing. Pools come from
  ``spark.rapids.service.pools`` (``name[:weight=W]`` entries); tenants
  weight via ``spark.rapids.service.tenantWeights``. Each completed
  query charges its wall time / weight to its pool and tenant virtual
  clocks; the next admitted query comes from the least-charged pool,
  then the least-charged tenant within it. A newly active tenant joins
  at the pool's current minimum clock so it can neither starve veterans
  nor be starved by them.
* **Execution**: ``maxConcurrentQueries`` daemon workers share ONE
  TpuSession — `TpuSession.execute` is concurrency-safe (thread-local
  envelopes, worker-scoped span attribution) and the TpuSemaphore
  finally sees real concurrent acquirers. Results optionally come from
  / fill the plan-fingerprint result cache (result_cache.py).
* **Lifecycle**: deadlines (``defaultTimeoutMs`` or per-submit) expire
  queued queries at the sweep and running ones cooperatively at exec
  boundaries; ``QueryHandle.cancel()`` likewise.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu.conf import (
    RapidsConf,
    bool_conf,
    float_conf,
    int_conf,
    str_conf,
)
from spark_rapids_tpu.errors import (
    ColumnarProcessingError,
    DeviceLostError,
    QueryCancelledError,
    QueryQuarantinedError,
    QueryRejectedError,
    QueryTimeoutError,
    WorkerLostError,
)
from spark_rapids_tpu.runtime.faults import fault_point
from spark_rapids_tpu.runtime.health import (
    HEALTH,
    QUARANTINE,
    QUARANTINE_MAX_STRIKES,
)
from spark_rapids_tpu.service.query import (
    QueryHandle,
    QueryState,
    cancel_scope,
)
from spark_rapids_tpu.service.result_cache import (
    ResultCache,
    epoch_snapshot,
    fingerprint,
    invalidation_epoch,  # noqa: F401  (stable import surface for tests)
    plan_table_ids,
)
from spark_rapids_tpu.service.watchdog import WorkerWatchdog, _Worker
from spark_rapids_tpu.lockorder import ordered_condition, ordered_lock


def _mesh_shape():
    """The active mesh topology for serve-time event records (None when
    mesh-native execution is off)."""
    from spark_rapids_tpu.parallel.mesh import MESH
    return MESH.shape_str()


def _host_topology():
    """The active cluster host topology for serve-time event records
    (None when cluster execution is off)."""
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    return CLUSTER.topology_str()


def _mem_budget_peak() -> int:
    """The memory arbiter's peak accounted device bytes for serve-time
    event records (schema v10 budgetPeak)."""
    from spark_rapids_tpu.runtime.memory import MEMORY
    return int(MEMORY.peak_bytes())


SERVICE_POOLS = str_conf(
    "spark.rapids.service.pools", "default",
    "Named scheduling pools: semicolon-separated 'name[:weight=W]' "
    "entries (weight defaults to 1.0). Submissions name a pool; the "
    "scheduler shares workers across pools by weighted fair queueing "
    "on measured query wall time (FAIR scheduler pools analog).",
    commonly_used=True)

SERVICE_MAX_CONCURRENT = int_conf(
    "spark.rapids.service.maxConcurrentQueries", 4,
    "Worker threads executing admitted queries concurrently against "
    "the shared session. Device residency within them is still gated "
    "by spark.rapids.sql.concurrentGpuTasks (TpuSemaphore).",
    commonly_used=True)

SERVICE_QUEUE_DEPTH = int_conf(
    "spark.rapids.service.queueDepth", 64,
    "Max queued (not yet running) queries per pool; submission beyond "
    "it raises QueryRejectedError with a retry_after_ms backpressure "
    "hint instead of queueing unboundedly.")

SERVICE_DEFAULT_TIMEOUT_MS = int_conf(
    "spark.rapids.service.defaultTimeoutMs", 0,
    "Default per-query deadline from submission, milliseconds; expiry "
    "times the query out while queued or cooperatively between batches "
    "while running. 0 = no deadline; submit(timeout_ms=...) overrides "
    "per query.")

SERVICE_TENANT_WEIGHTS = str_conf(
    "spark.rapids.service.tenantWeights", "",
    "Per-tenant fair-share weights inside a pool: comma-separated "
    "'tenant=W' entries; unlisted tenants weigh 1.0. A tenant with "
    "weight 2 receives twice the service of a weight-1 tenant under "
    "contention.")

SERVICE_ADMISSION_MAX_DEVICE_BYTES = int_conf(
    "spark.rapids.service.admission.maxDeviceBytes", 0,
    "Memory-pressure admission gate: while the spill catalog reports "
    "more device-resident spillable bytes than this, queued queries "
    "hold instead of dispatching (a query is always released when "
    "nothing is running, so the gate cannot deadlock). 0 disables.")

SERVICE_RESULT_CACHE_ENABLED = bool_conf(
    "spark.rapids.service.resultCache.enabled", True,
    "Serve repeated queries from the plan-fingerprint result cache "
    "(service/result_cache.py): structurally identical plans under "
    "result-identical conf return the cached HostTable without "
    "executing. Invalidated by catalog mutations and table writes.")

SERVICE_RESULT_CACHE_MAX_BYTES = int_conf(
    "spark.rapids.service.resultCache.maxBytes", 256 << 20,
    "LRU byte bound on cached result tables (HostTable.nbytes sum); "
    "results larger than this never cache.")

SERVICE_INTROSPECT_ENABLED = bool_conf(
    "spark.rapids.service.introspect.enabled", False,
    "Serve the service's live surface (health/stats/SLOs/query table/"
    "telemetry tail) as JSON on a loopback-only HTTP endpoint "
    "(service/introspect.py) polled by `python -m spark_rapids_tpu."
    "tools top`. The bound port is QueryService.introspect_port.",
    commonly_used=True)

SERVICE_INTROSPECT_PORT = int_conf(
    "spark.rapids.service.introspect.port", 0,
    "Port for the loopback introspection endpoint; 0 (default) binds "
    "an ephemeral port, reported as QueryService.introspect_port.")

SERVICE_DEGRADE_ON_HOST_LOSS = bool_conf(
    "spark.rapids.service.degrade.onHostLoss", True,
    "Driver/service unification: while the cluster runtime serves "
    "below its declared host strength (lost or excluded hosts, or the "
    "single-process latch), the service reports DEGRADED and sheds "
    "its lowest-weight pool under load, exactly as it does for its "
    "own worker losses. Off restores the pre-fleet behavior where "
    "the service was blind to host topology.")

SERVICE_DEGRADE_MEMORY_FRACTION = float_conf(
    "spark.rapids.service.degrade.memoryOccupancyFraction", 0.0,
    "While the memory arbiter's live occupancy exceeds this fraction "
    "of its device budget, the service reports DEGRADED and sheds its "
    "lowest-weight pool under load — backpressure from the memory "
    "fault domain into admission control. 0 (default) disables.")


def parse_pools(spec: str) -> "OrderedDict[str, float]":
    """'name[:weight=W];...' -> {name: weight}. Raises on duplicates,
    empty names, or non-positive weights (a typo'd pool spec must fail
    service construction, not silently rebalance)."""
    pools: "OrderedDict[str, float]" = OrderedDict()
    for entry in (e.strip() for e in str(spec).split(";")):
        if not entry:
            continue
        name, _, rest = entry.partition(":")
        name = name.strip()
        weight = 1.0
        if rest:
            key, _, val = rest.partition("=")
            if key.strip() != "weight" or not val:
                raise ColumnarProcessingError(
                    f"bad pool spec entry {entry!r} (want "
                    "'name[:weight=W]')")
            try:
                weight = float(val)
            except ValueError:
                raise ColumnarProcessingError(
                    f"pool {name!r} weight {val!r} is not a number "
                    "(spark.rapids.service.pools)")
        if not name:
            raise ColumnarProcessingError(
                f"bad pool spec entry {entry!r}: empty pool name")
        if name in pools:
            raise ColumnarProcessingError(
                f"duplicate pool {name!r} in spark.rapids.service.pools")
        if weight <= 0:
            raise ColumnarProcessingError(
                f"pool {name!r} weight must be positive, got {weight}")
        pools[name] = weight
    if not pools:
        raise ColumnarProcessingError(
            "spark.rapids.service.pools defines no pools")
    return pools


def parse_tenant_weights(spec: str) -> Dict[str, float]:
    """'tenant=W,tenant=W' -> {tenant: weight}; unlisted tenants 1.0."""
    out: Dict[str, float] = {}
    for entry in (e.strip() for e in str(spec).split(",")):
        if not entry:
            continue
        name, sep, val = entry.partition("=")
        if not sep or not name.strip():
            raise ColumnarProcessingError(
                f"bad tenant weight entry {entry!r} (want 'tenant=W')")
        try:
            w = float(val)
        except ValueError:
            raise ColumnarProcessingError(
                f"tenant {name.strip()!r} weight {val!r} is not a "
                "number (spark.rapids.service.tenantWeights)")
        if w <= 0:
            raise ColumnarProcessingError(
                f"tenant {name.strip()!r} weight must be positive, got {w}")
        out[name.strip()] = w
    return out


def _default_memory_probe() -> int:
    """Admission's device-occupancy read: the memory arbiter's LIVE
    ledger (every accounted landing and kernel intermediate, not only
    spill-catalog-registered buffers) — the max with the catalog's own
    view covers any spillable registered before its table was ever
    accounted. The forward-progress escape (admit when nothing runs)
    lives in the gate, unchanged."""
    from spark_rapids_tpu.runtime.memory import MEMORY
    from spark_rapids_tpu.runtime.spill import BufferCatalog
    return max(BufferCatalog.get().device_bytes(), MEMORY.occupancy())


class QueryService:
    """Concurrent multi-tenant front end over one TpuSession.

    >>> svc = QueryService({"spark.rapids.service.maxConcurrentQueries": 4})
    >>> h = svc.submit(df, tenant="alice")
    >>> table = h.result(timeout=60)

    Accepts DataFrames, raw PlanNodes, or SQL text (lowered through the
    shared session's catalog at submit time, so parse/analysis errors
    surface to the submitter immediately)."""

    #: how long an idle worker sleeps between deadline sweeps
    _SWEEP_INTERVAL_S = 0.05

    def __init__(self, conf=None, session=None,
                 max_concurrent: Optional[int] = None,
                 queue_depth: Optional[int] = None):
        if session is None:
            from spark_rapids_tpu.session import TpuSession
            session = TpuSession(conf)
        elif conf is not None:
            raise ColumnarProcessingError(
                "pass conf or a session, not both (the service reads "
                "its knobs from the session's conf)")
        self.session = session
        self.conf: RapidsConf = session.conf
        # arm the runtime lock witness FIRST (construction-time
        # election): every lock this __init__ builds — the scheduler
        # condition, the streams lock, the result cache's — is wrapped
        # iff the conf arms it
        from spark_rapids_tpu import lockorder
        lockorder.configure(self.conf)
        self.pools = parse_pools(self.conf.get_entry(SERVICE_POOLS))
        self.tenant_weights = parse_tenant_weights(
            self.conf.get_entry(SERVICE_TENANT_WEIGHTS))
        self.max_concurrent = max(1, int(
            max_concurrent if max_concurrent is not None
            else self.conf.get_entry(SERVICE_MAX_CONCURRENT)))
        self.queue_depth = max(1, int(
            queue_depth if queue_depth is not None
            else self.conf.get_entry(SERVICE_QUEUE_DEPTH)))
        self.default_timeout_ms = int(
            self.conf.get_entry(SERVICE_DEFAULT_TIMEOUT_MS))
        self.admission_max_device_bytes = int(
            self.conf.get_entry(SERVICE_ADMISSION_MAX_DEVICE_BYTES))
        # fleet-degrade knobs — read BEFORE workers spawn (workers
        # consult _health_state_locked from their first pick)
        self._degrade_on_host_loss = bool(
            self.conf.get_entry(SERVICE_DEGRADE_ON_HOST_LOSS))
        self._degrade_memory_fraction = float(
            self.conf.get_entry(SERVICE_DEGRADE_MEMORY_FRACTION))
        # exclusive mesh occupancy: a multi-device computation's
        # collective rendezvous requires every device to reach ITS
        # launch, but each device executes launches in arrival order —
        # two concurrent mesh queries can interleave arrival per-device
        # and deadlock both rendezvous. When this service drives a
        # mesh topology, workers serialize the device-launch window
        # (admission, queues, watchdog and SLO machinery stay fully
        # concurrent); single-chip services skip the gate entirely.
        from spark_rapids_tpu.parallel.mesh import MESH_ENABLED
        self._mesh_gate = None
        if bool(self.conf.get_entry(MESH_ENABLED)):
            self._mesh_gate = ordered_lock("service.mesh_gate")
        self.result_cache: Optional[ResultCache] = None
        if bool(self.conf.get_entry(SERVICE_RESULT_CACHE_ENABLED)):
            self.result_cache = ResultCache(
                int(self.conf.get_entry(SERVICE_RESULT_CACHE_MAX_BYTES)))
        #: injectable for tests; production consults the spill catalog
        self._memory_probe = _default_memory_probe
        #: recurring tenants (streaming/query.py StreamingQuery
        #: registers itself for its lifetime): name -> stream object
        #: exposing describe() — surfaced by streams()/stats()//top so
        #: long-lived micro-batch streams are visible next to one-shot
        #: queries
        self._streams_lock = ordered_lock("service.scheduler.streams")
        self._streams: Dict[str, object] = {}
        self._mvs = None

        self._cond = ordered_condition("service.scheduler.cond")
        #: (pool, tenant) -> FIFO of queued handles
        self._queues: Dict[Tuple[str, str], deque] = {}
        #: per-pool queued-handle count (admission bound)
        self._queued_per_pool: Dict[str, int] = {p: 0 for p in self.pools}
        #: WFQ virtual clocks: seconds of service / weight
        self._tenant_clock: Dict[Tuple[str, str], float] = {}
        self._pool_clock: Dict[str, float] = {p: 0.0 for p in self.pools}
        self._running = 0
        self._held_for_memory = 0
        self._memory_gate_was_open = True
        self._shutdown = False
        self._recent_run_s: deque = deque(maxlen=32)
        self.counters = {"submitted": 0, "finished": 0, "failed": 0,
                         "cancelled": 0, "timed_out": 0, "rejected": 0,
                         "requeued": 0, "quarantineRejected": 0,
                         "hardTimeouts": 0}
        # survivability state (runtime/health.py, service/watchdog.py):
        # worker lifecycle counters, the DEGRADED latch (cleared by
        # _DEGRADE_CLEAR_SUCCESSES completed queries — event-count
        # based, so tests and chaos runs are wall-clock free), and the
        # quarantine strike budget. ALL mutated under _cond.
        from spark_rapids_tpu.obs.metrics import metric_scope
        self._health_metrics = metric_scope("health")
        self._workers_lost = 0
        self._workers_respawned = 0
        self._degraded_pending = 0
        self.quarantine_max_strikes = int(
            self.conf.get_entry(QUARANTINE_MAX_STRIKES))
        #: the pool DEGRADED mode sheds first (lowest weight; name
        #: breaks ties) — None with a single pool (nothing to shed to)
        self._shed_pool = (min(self.pools,
                               key=lambda p: (self.pools[p], p))
                           if len(self.pools) > 1 else None)

        # arm the chaos registry NOW: the service-level fault points
        # (service.worker_crash) fire in the scheduler BEFORE the first
        # session.execute would have armed it from the same conf
        # (re-arming an identical spec later is a no-op by contract)
        from spark_rapids_tpu.conf import TEST_FAULTS
        from spark_rapids_tpu.runtime.faults import FAULTS
        FAULTS.arm(str(self.conf.get_entry(TEST_FAULTS) or ""))

        self._worker_seq = 0
        self._workers: List[_Worker] = []
        with self._cond:
            for _ in range(self.max_concurrent):
                self._spawn_worker_locked()
        # dedicated deadline sweeper: idle workers sweep too, but when
        # EVERY worker is busy a queued query's deadline must still
        # expire on time (the backpressure signal is useless late)
        self._sweeper = threading.Thread(target=self._sweeper_loop,
                                         name="rapids-svc-sweeper",
                                         daemon=True)
        self._sweeper.start()
        # the watchdog: hard wall limits on RUNNING queries + the
        # dead-worker liveness backstop (service/watchdog.py)
        self._watchdog = WorkerWatchdog(self)

        # rolling SLO window: (pool, tenant) -> deque of
        # (latency_s, run_s) for recently FINISHED handles — the
        # introspection endpoint's p50/p95 source. Mutated under _cond.
        self._finished_lat: Dict[Tuple[str, str], deque] = {}

        # observability plumbing (obs/telemetry.py): the sampler +
        # flight-recorder defaults follow this service's conf, and the
        # recorder embeds this service's live query table in incident
        # bundles (weak registration — shutdown just drops out)
        from spark_rapids_tpu.obs.telemetry import (
            TELEMETRY,
            register_service,
        )
        TELEMETRY.configure(self.conf)
        register_service(self)
        # the device memory arbiter's budget follows this service's
        # conf too (admission consults its live occupancy)
        from spark_rapids_tpu.runtime.memory import MEMORY
        MEMORY.configure(self.conf)
        # the service runs AS the cluster driver: constructing it
        # configures the host-cluster runtime from the same conf, so
        # admission control, quarantine, the /slo surface, and the
        # three degradation ladders all see ONE topology — and the
        # DEGRADED/shedding decision below consults live host strength
        # and arbiter occupancy from that shared view
        from spark_rapids_tpu.runtime.cluster import CLUSTER
        CLUSTER.configure(self.conf)

        # live introspection endpoint (service/introspect.py):
        # loopback-only HTTP JSON, polled by `tools top`
        self.introspect = None
        self.introspect_port: Optional[int] = None
        if bool(self.conf.get_entry(SERVICE_INTROSPECT_ENABLED)):
            from spark_rapids_tpu.service.introspect import (
                IntrospectionServer,
            )
            self.introspect = IntrospectionServer(
                self, int(self.conf.get_entry(SERVICE_INTROSPECT_PORT)))
            self.introspect_port = self.introspect.port

    # -- submission ----------------------------------------------------------
    def submit(self, query, *, tenant: str = "default",
               pool: Optional[str] = None,
               timeout_ms: Optional[int] = None,
               tag: Optional[str] = None) -> QueryHandle:
        """Admit one query. ``query`` is a DataFrame, a PlanNode, or SQL
        text. Raises QueryRejectedError when the pool queue is full (or
        when DEGRADED mode is shedding this pool's load) and
        QueryQuarantinedError when the query's template is
        quarantined."""
        pool = pool if pool is not None else next(iter(self.pools))
        if pool not in self.pools:
            raise ColumnarProcessingError(
                f"unknown scheduling pool {pool!r} "
                f"(configured: {', '.join(self.pools)})")
        plan, sql_text = self._resolve(query)
        if timeout_ms is None:
            timeout_ms = self.default_timeout_ms
        deadline = (time.monotonic() + timeout_ms / 1000.0
                    if timeout_ms and timeout_ms > 0 else None)
        handle = QueryHandle(tenant=tenant, pool=pool, tag=tag,
                             sql_text=sql_text, plan=plan,
                             deadline=deadline)
        handle._service = self
        # poison-query quarantine (runtime/health.py): templates that
        # killed workers/the device quarantine.maxStrikes times are
        # refused outright, with the strike history attached. The
        # template fingerprint walk runs OUTSIDE the scheduler lock,
        # and ONLY when something is actually quarantined — the clean
        # process pays one snapshot call per submit
        if QUARANTINE.snapshot()["quarantined"]:
            quarantined = QUARANTINE.is_quarantined(
                self._template_fp(handle))
            if quarantined is not None:
                with self._cond:
                    self.counters["quarantineRejected"] += 1
                raise QueryQuarantinedError(
                    f"query template is quarantined after "
                    f"{len(quarantined)} worker/device kills; "
                    "submission refused", strikes=quarantined)
        with self._cond:
            if self._shutdown:
                raise ColumnarProcessingError(
                    "query service is shut down")
            # DEGRADED mode sheds the lowest-weight pool's load first:
            # a service recovering from worker/device loss keeps its
            # high-weight tenants served and pushes back on the rest.
            # Forward progress beats the shed (memory-gate precedent):
            # the DEGRADED latch only pays down as queries FINISH, so
            # an otherwise-idle service must admit the shed pool — its
            # completions are the only way back to HEALTHY when no
            # higher-weight traffic is flowing
            if (pool == self._shed_pool
                    and (self._running > 0
                         or any(self._queued_per_pool.values()))
                    and self._health_state_locked() == "DEGRADED"):
                self.counters["rejected"] += 1
                raise QueryRejectedError(
                    f"service is DEGRADED; shedding lowest-weight pool "
                    f"{pool!r} load — retry later",
                    retry_after_ms=self._retry_after_ms_locked(pool))
            if self._queued_per_pool[pool] >= self.queue_depth:
                self.counters["rejected"] += 1
                raise QueryRejectedError(
                    f"pool {pool!r} queue is full "
                    f"({self.queue_depth} queued); retry later",
                    retry_after_ms=self._retry_after_ms_locked(pool))
            self._activate_locked(pool, tenant)
            self._queues.setdefault((pool, tenant),
                                    deque()).append(handle)
            self._queued_per_pool[pool] += 1
            self.counters["submitted"] += 1
            # notify_all: the deadline sweeper shares the condition, so
            # a single notify could wake it instead of a worker
            self._cond.notify_all()
        return handle

    def _resolve(self, query):
        from spark_rapids_tpu.plan import DataFrame
        from spark_rapids_tpu.plan.nodes import PlanNode
        if isinstance(query, str):
            df = self.session.sql(query)
            return df.plan, query
        if isinstance(query, DataFrame):
            return query.plan, getattr(query, "sql_text", None)
        if isinstance(query, PlanNode):
            return query, None
        raise TypeError(
            f"cannot submit {type(query).__name__}; want DataFrame, "
            "PlanNode, or SQL text")

    def _template_fp(self, handle: QueryHandle) -> Optional[str]:
        """The quarantine key: the handle's literal-stripped structural
        template (plan/fingerprint.py — PR 6), computed AT MOST ONCE
        and only when actually needed (a clean process's submit path
        pays no plan walk). None for plans too dynamic to fingerprint;
        those cannot be quarantined (each run is structurally unique,
        so a strike ledger would never match)."""
        if not handle._template_fp_done:
            from spark_rapids_tpu.plan.fingerprint import (
                template_fingerprint,
            )
            handle.template_fp = template_fingerprint(handle.plan,
                                                      self.conf)
            handle._template_fp_done = True
        return handle.template_fp

    def _handle_has_strikes(self, handle: QueryHandle) -> bool:
        """Does this handle's template carry poison strikes? (the v4
        event-log ``quarantined`` flag). Fingerprint computed only when
        the ledger has any strikes at all."""
        if not QUARANTINE.snapshot()["strikes"]:
            return False
        return QUARANTINE.strike_count(self._template_fp(handle)) > 0

    def _retry_after_ms_locked(self, pool: str) -> int:
        mean_run = (sum(self._recent_run_s) / len(self._recent_run_s)
                    if self._recent_run_s else 0.1)
        backlog = self._queued_per_pool[pool] + self._running
        est = mean_run * backlog / max(self.max_concurrent, 1)
        return max(50, int(est * 1000))

    #: tenant-clock entries kept for idle tenants before pruning — the
    #: re-activation lift makes pruning fairness-neutral, so this only
    #: bounds memory/scan cost under ephemeral per-user tenant ids
    _MAX_IDLE_CLOCKS = 4096

    def _activate_locked(self, pool: str, tenant: str) -> None:
        """A tenant (re)gaining queued work joins the fair-share race at
        no less than the pool's ACTIVE minimum clock: idle time must not
        bank credit a returning burst could spend monopolizing workers
        (standard WFQ virtual-time lift). Pools likewise."""
        key = (pool, tenant)
        busy = [c for (p, t), c in self._tenant_clock.items()
                if p == pool and (p, t) != key
                and self._queues.get((p, t))]
        cur = self._tenant_clock.get(key, 0.0)
        self._tenant_clock[key] = max(cur, min(busy)) if busy else cur
        busy_pools = [self._pool_clock[p] for p in self.pools
                      if p != pool and self._queued_per_pool.get(p)]
        if busy_pools and not self._queued_per_pool.get(pool):
            self._pool_clock[pool] = max(self._pool_clock[pool],
                                         min(busy_pools))
        if len(self._tenant_clock) > self._MAX_IDLE_CLOCKS:
            # ephemeral tenant ids: drop idle entries (no queued work);
            # if they return, the lift above restores a fair position
            for k in [k for k in self._tenant_clock
                      if k != key and not self._queues.get(k)]:
                del self._tenant_clock[k]

    def _drop_if_empty_locked(self, key) -> None:
        """Empty per-tenant deques are DELETED so the sweep and pick
        scans stay proportional to tenants with pending work, not to
        every tenant ever seen."""
        dq = self._queues.get(key)
        if dq is not None and not dq:
            del self._queues[key]

    # -- scheduling ----------------------------------------------------------
    def _remove_queued(self, handle: QueryHandle) -> bool:
        """Pull a still-queued handle out (cancel path). True when the
        handle was queued and is now removed."""
        with self._cond:
            key = (handle.pool, handle.tenant)
            dq = self._queues.get(key)
            if dq is not None:
                try:
                    dq.remove(handle)
                except ValueError:
                    return False
                self._queued_per_pool[handle.pool] -= 1
                self._drop_if_empty_locked(key)
                return True
            return False

    def _sweep_expired_locked(self) -> None:
        """Time out / cancel queued handles whose deadline passed or
        whose cancel flag is set, without running them."""
        for (pool, _tenant), dq in list(self._queues.items()):
            kept = [h for h in dq]
            for h in kept:
                if h.scope.cancelled.is_set():
                    dq.remove(h)
                    self._queued_per_pool[pool] -= 1
                    self._terminal(h, QueryState.CANCELLED, "cancelled",
                                   error=QueryCancelledError(
                                       "cancelled while queued"))
                elif h.scope.expired():
                    dq.remove(h)
                    self._queued_per_pool[pool] -= 1
                    self._terminal(h, QueryState.TIMED_OUT, "timed_out",
                                   error=QueryTimeoutError(
                                       "deadline expired while queued"))
            self._drop_if_empty_locked((pool, _tenant))

    def _memory_gate_open_locked(self) -> bool:
        """The spill-catalog admission gate: admit when under the high
        water mark, or when nothing is running (forward progress)."""
        limit = self.admission_max_device_bytes
        if limit <= 0 or self._running == 0:
            return True
        try:
            used = int(self._memory_probe())
        except Exception:
            return True  # a broken probe must not wedge the service
        return used <= limit

    def _pick_locked(self) -> Optional[QueryHandle]:
        """WFQ pop: least-charged pool, then least-charged tenant
        within it, FIFO within the tenant. The virtual clocks are
        ALREADY weight-normalized (_charge_locked adds elapsed/weight),
        so the pick compares them raw — dividing again here would give
        a weight-W party a W^2 share."""
        candidates = [(p, t, dq) for (p, t), dq in self._queues.items()
                      if dq]
        if not candidates:
            return None
        if not self._memory_gate_open_locked():
            if self._memory_gate_was_open:
                # count held ADMISSION EPISODES, not poll wakeups
                self._held_for_memory += 1
                self._memory_gate_was_open = False
            return None
        self._memory_gate_was_open = True
        best = min(
            candidates,
            key=lambda c: (
                self._pool_clock[c[0]],
                self._tenant_clock[(c[0], c[1])],
                c[2][0].query_id,
            ))
        pool, tenant, dq = best
        handle = dq.popleft()
        self._queued_per_pool[pool] -= 1
        self._drop_if_empty_locked((pool, tenant))
        return handle

    def _terminal(self, handle: QueryHandle, state: str, counter: str,
                  *, error=None, result=None) -> bool:
        """A terminal transition and its lifecycle counter as ONE step
        under the scheduler lock: the transition wakes the handle's
        waiters, and a stats() one of them takes next must already
        count it (counters are read under this lock too). A completed
        query also pays down the DEGRADED latch — the service proved it
        can finish work again. False when the transition lost a race."""
        with self._cond:
            if not handle._transition(state, error=error, result=result):
                return False
            self.counters[counter] += 1
            if counter == "finished" and self._degraded_pending > 0:
                self._degraded_pending -= 1
        return True

    def _charge_locked(self, handle: QueryHandle, elapsed_s: float):
        w_t = self.tenant_weights.get(handle.tenant, 1.0)
        key = (handle.pool, handle.tenant)
        self._pool_clock[handle.pool] += elapsed_s / self.pools[handle.pool]
        # .get: the idle-clock prune may have dropped the entry while
        # this query ran (the tenant had nothing else queued)
        self._tenant_clock[key] = (self._tenant_clock.get(key, 0.0)
                                   + elapsed_s / w_t)
        self._recent_run_s.append(max(elapsed_s, 1e-4))

    # -- workers -------------------------------------------------------------
    def _sweeper_loop(self):
        while True:
            with self._cond:
                if self._shutdown:
                    return
                self._sweep_expired_locked()
                self._cond.wait(timeout=self._SWEEP_INTERVAL_S)

    # -- survivability plumbing (watchdog + health, PR 7) --------------------

    #: times a handle is requeued after its worker/device died under it
    #: before it fails with the typed error (a bound, not a conf: the
    #: quarantine strike budget is the operator-facing knob)
    _DEVICE_LOSS_REPLAYS = 3
    _WORKER_LOSS_REPLAYS = 3
    #: completed queries that clear the DEGRADED latch after a
    #: worker/device loss (event-count based — deterministic in tests)
    _DEGRADE_CLEAR_SUCCESSES = 2

    def _spawn_worker_locked(self) -> "_Worker":
        self._worker_seq += 1
        w = _Worker(f"rapids-svc-worker-{self._worker_seq}")
        w.thread = threading.Thread(target=self._worker_loop, args=(w,),
                                    name=w.name, daemon=True)
        self._workers.append(w)
        w.thread.start()
        return w

    def _drop_worker_locked(self, w: "_Worker") -> None:
        if w in self._workers:
            self._workers.remove(w)

    def _note_worker_lost_locked(self, w: "_Worker") -> None:
        """One worker is gone (dead thread or watchdog-abandoned):
        count it, latch DEGRADED, and spawn a replacement so pool
        capacity holds. Caller holds the condition lock."""
        self._drop_worker_locked(w)
        self._workers_lost += 1
        self._health_metrics.add("workersLost", 1)
        self._degraded_pending = self._DEGRADE_CLEAR_SUCCESSES
        if not self._shutdown:
            self._spawn_worker_locked()
            self._workers_respawned += 1
            self._health_metrics.add("workersRespawned", 1)

    def _strike_locked(self, handle: QueryHandle, reason: str) -> bool:
        """Record a poison strike against the handle's template
        (fingerprint computed here on first need); returns True when
        this strike quarantined it."""
        return QUARANTINE.strike(self._template_fp(handle), reason,
                                 self.quarantine_max_strikes)

    def _requeue_locked(self, handle: QueryHandle) -> bool:
        """Put a handle whose worker/device died under it back at the
        FRONT of its queue (it already waited once; retrying promptly
        beats re-joining behind the backlog). Gated on the QUEUED
        transition: a handle some other path already drove terminal
        (e.g. the watchdog's hard timeout) must not be re-enqueued —
        a worker would pop it only to discard it, and the requeued
        counter the chaos bounds assert against would inflate."""
        if not handle._transition(QueryState.QUEUED):
            return False
        handle.requeues += 1
        self._activate_locked(handle.pool, handle.tenant)
        self._queues.setdefault((handle.pool, handle.tenant),
                                deque()).appendleft(handle)
        self._queued_per_pool[handle.pool] += 1
        self.counters["requeued"] += 1
        self._cond.notify_all()
        return True

    def _on_worker_death(self, w: "_Worker", handle: QueryHandle,
                         exc: BaseException) -> None:
        """The worker's runner machinery raised OUTSIDE the query (the
        ``service.worker_crash`` chaos point, or something genuinely
        broken): the thread is about to exit. Correct the pool
        accounting, respawn, strike the query's template, and requeue
        the handle — or fail it once its replay budget (or the
        quarantine budget) is spent."""
        fail_with = None
        with self._cond:
            if not w.lost:
                # the watchdog may have abandoned this worker already
                # (hard timeout fired while the runner was dying) — it
                # then owns both corrections
                w.lost = True
                self._running -= 1
                self._note_worker_lost_locked(w)
            else:
                self._drop_worker_locked(w)
            if not handle.done:
                quarantined_now = self._strike_locked(
                    handle, f"worker {w.name} killed by "
                            f"{type(exc).__name__}: {exc}")
                blocked = (quarantined_now or QUARANTINE.is_quarantined(
                    handle.template_fp) is not None)
                if (not self._shutdown and not blocked
                        and handle.requeues < self._WORKER_LOSS_REPLAYS
                        and self._requeue_locked(handle)):
                    pass
                elif blocked:
                    fail_with = QueryQuarantinedError(
                        "query template quarantined: it killed "
                        f"{len(QUARANTINE.history(handle.template_fp))}"
                        " worker(s)/device(s)",
                        strikes=QUARANTINE.history(handle.template_fp))
                else:
                    fail_with = WorkerLostError(
                        f"worker {w.name} died running this query "
                        f"({type(exc).__name__}: {exc}); replay budget "
                        f"spent after {handle.requeues} requeues")
            self._cond.notify_all()
        if fail_with is not None:
            self._terminal(handle, QueryState.FAILED, "failed",
                           error=fail_with)

    def _on_device_lost(self, handle: QueryHandle,
                        exc: DeviceLostError) -> None:
        """The device died under this query. The session's recovery
        (runtime/health.py) already reinitialized the backend and
        invalidated the device-referencing caches — DeviceLostError is
        RETRYABLE, so the service replays the query against the
        recovered backend up to its budget (CPU-only latch included:
        the replay then plans onto the CPU path and completes)."""
        fail_with: BaseException = exc
        with self._cond:
            self._degraded_pending = self._DEGRADE_CLEAR_SUCCESSES
            if handle.done:
                # already terminal (the watchdog's hard timeout beat
                # this loss to the handle): the device recovery
                # happened, but there is nothing to strike or replay —
                # a phantom strike would push an innocent template
                # toward quarantine
                return
            quarantined_now = self._strike_locked(
                handle, f"device loss during execution: {exc}")
            blocked = (quarantined_now or QUARANTINE.is_quarantined(
                handle.template_fp) is not None)
            if (not self._shutdown and not blocked
                    and handle.requeues < self._DEVICE_LOSS_REPLAYS
                    and self._requeue_locked(handle)):
                return
            if blocked:
                fail_with = QueryQuarantinedError(
                    "query template quarantined: it killed the device "
                    f"{len(QUARANTINE.history(handle.template_fp))} "
                    "time(s)",
                    strikes=QUARANTINE.history(handle.template_fp))
        self._terminal(handle, QueryState.FAILED, "failed", error=fail_with)

    def _worker_loop(self, w: "_Worker"):
        while True:
            with self._cond:
                handle = None
                while handle is None:
                    if self._shutdown or w.lost:
                        self._drop_worker_locked(w)
                        return
                    self._sweep_expired_locked()
                    handle = self._pick_locked()
                    if handle is None:
                        self._cond.wait(timeout=self._SWEEP_INTERVAL_S)
                if not handle._transition(QueryState.ADMITTED):
                    continue  # terminal while queued; take another
                self._running += 1
                w.handle = handle
            died = False
            try:
                self._run(handle)
            except BaseException as exc:
                # the RUNNER died, not the query (_run absorbs query
                # failures): hand off to the death protocol and exit
                # this thread — a replacement is already spawned
                died = True
                self._on_worker_death(w, handle, exc)
                return
            finally:
                if not died:
                    with self._cond:
                        w.handle = None
                        lost = w.lost
                        if lost:
                            # the watchdog abandoned us mid-query and
                            # already corrected the running count;
                            # this thread just disappears
                            self._drop_worker_locked(w)
                        else:
                            self._running -= 1
                        self._cond.notify_all()
                    if lost:
                        return

    def _run(self, handle: QueryHandle):
        # mesh services serialize the WHOLE launch window, and do it
        # BEFORE the RUNNING transition: the hard wall measures from
        # RUNNING, so gate wait books as queue time — one wedged
        # holder (abandoned by the watchdog mid-dispatch) must not
        # cascade-abandon every worker queued behind the gate while
        # its stalled dispatch drains
        if self._mesh_gate is not None:
            with self._mesh_gate:
                self._run_exclusive(handle)
        else:
            self._run_exclusive(handle)

    def _run_exclusive(self, handle: QueryHandle):
        if not handle._transition(QueryState.RUNNING):
            return
        # RL-FAULT-POINT service.worker_crash: an exception HERE is the
        # WORKER dying (outside the query's own try), so it propagates
        # to _worker_loop's death protocol — respawn + requeue, not a
        # query failure
        fault_point("service.worker_crash")
        t0 = time.monotonic()
        try:
            # a cancel/deadline that raced the pop must win BEFORE any
            # serve — a cache hit is still a completion the caller was
            # told would not happen
            handle.scope.check()
            # epoch VECTOR before execution (global + the epochs of
            # every table this plan reads): a write landing while this
            # query runs must stale the entry we fill, not be masked by
            # it — and entries scoped to their read set survive commits
            # to unrelated tables
            epochs = (epoch_snapshot(plan_table_ids(handle.plan))
                      if self.result_cache is not None else None)
            fp = (fingerprint(handle.plan, self.conf)
                  if self.result_cache is not None else None)
            cached = (self.result_cache.get(fp)
                      if self.result_cache is not None else None)
            if cached is not None:
                handle.cache_hit = True
                self._emit_cache_hit_record(
                    handle, cached, time.monotonic() - t0)
                if self._terminal(handle, QueryState.FINISHED, "finished",
                                  result=cached.table):
                    self._note_finished(handle)
                return
            with cancel_scope(handle.scope):
                self.session.next_query_tag = handle.tag
                if handle.sql_text:
                    self.session.next_query_sql = handle.sql_text
                self.session.next_query_service = {
                    "tenant": handle.tenant,
                    "pool": handle.pool,
                    "queueWaitS": round(handle.queue_wait_s or 0.0, 6),
                    "cacheHit": False,
                    "quarantined": self._handle_has_strikes(handle),
                }
                table = self.session.execute(handle.plan)
            # raw thread-local read: THIS query's record or None, never
            # the session-wide mirror of some other worker's query
            handle.event_record = self.session._q.event_record
            if self.result_cache is not None:
                self.result_cache.put(fp, table, handle.event_record,
                                      epochs=epochs)
            if self._terminal(handle, QueryState.FINISHED, "finished",
                              result=table):
                self._note_finished(handle)
        except QueryCancelledError as exc:
            self._terminal(handle, QueryState.CANCELLED, "cancelled",
                           error=exc)
        except QueryTimeoutError as exc:
            self._terminal(handle, QueryState.TIMED_OUT, "timed_out",
                           error=exc)
        except DeviceLostError as exc:
            # retryable by contract: the backend already recovered
            # (runtime/health.py) — requeue against it, or fail typed
            # once the replay/quarantine budget is spent
            self._on_device_lost(handle, exc)
        except BaseException as exc:
            self._terminal(handle, QueryState.FAILED, "failed", error=exc)
        finally:
            with self._cond:
                self._charge_locked(handle, time.monotonic() - t0)

    def _emit_cache_hit_record(self, handle: QueryHandle, entry,
                               serve_s: float) -> None:
        """A cache hit still shows up in the query event log: the
        filling run's record replays with hit attribution (tenant,
        pool, queue wait, cacheHit=true, serve wall time) so offline
        tools see served traffic, not just executed traffic."""
        from spark_rapids_tpu.obs import events as E
        if entry.event_record is None or not bool(
                self.conf.get_entry(E.EVENT_LOG_ENABLED)):
            return
        s = self.session
        with s._obs_lock:
            idx = s._obs_query_seq
            s._obs_query_seq += 1
        rec = dict(entry.event_record)
        rec.update({
            "queryIndex": idx,
            "queryTag": handle.tag,
            "wallS": round(serve_s, 6),
            "tenant": handle.tenant,
            "pool": handle.pool,
            "queueWaitS": round(handle.queue_wait_s or 0.0, 6),
            "cacheHit": True,
            # nothing executed on a result-cache serve: the filling
            # run's compile/bucket numbers must not replay as traffic
            "compileMs": 0.0,
            "executableCacheHit": False,
            "padWasteRows": 0,
            # v4 survivability fields at SERVE time (the filling run's
            # health deltas must not replay either)
            "healthState": HEALTH.state(),
            "quarantined": self._handle_has_strikes(handle),
            "deviceReinits": 0,
            "workerRestarts": 0,
            # v6 mesh fields at SERVE time: nothing crossed ICI for a
            # cached serve; meshShape reflects the mesh now active
            "meshShape": _mesh_shape(),
            "iciBytes": 0,
            # v17: nothing exchanged or re-landed either
            "meshExchanges": 0,
            "meshExchangeRows": 0,
            "meshExchangeBytes": 0,
            "meshRelandRows": 0,
            "shardSkew": 0.0,
            # v7 mesh fault-domain fields: a cached serve gathers
            # nothing, so it can neither retry nor trip a checksum
            "meshDegradations": 0,
            "shardRetries": 0,
            "gatherChecksFailed": 0,
            # v8 host fault-domain fields at SERVE time (the schema's
            # documented contract — the filling run's host losses must
            # not replay as this serve's degradation events) and the
            # v9 per-host scan table: a cached serve dispatches nothing
            "hostTopology": _host_topology(),
            "hostsLost": 0,
            "hostRelands": 0,
            "dcnExchanges": 0,
            "hostScans": {},
            # v10 out-of-core fields: a cached serve lands nothing, so
            # no retries/spills replay; budgetPeak reads the arbiter's
            # serve-time peak like healthState reads serve-time health
            "oomRetries": 0,
            "splitRetries": 0,
            "spillBytes": 0,
            "unspills": 0,
            "budgetPeak": _mem_budget_peak(),
            # v11 streaming fields: a cached serve runs no micro-batch
            # and refreshes no view, so every delta is 0; mvEpoch stays
            # the filling run's — it describes the DATA being served,
            # which a valid cache entry still reflects
            "microBatches": 0,
            "mvRefreshes": 0,
            "mvIncrementalRefreshes": 0,
            "mvFullRecomputes": 0,
            "sinkCommits": 0,
            "sinkReplays": 0,
        })
        handle.event_record = rec
        try:
            s._write_event_record(rec)
        except OSError as exc:  # best-effort, like the session's writer
            print(f"spark_rapids_tpu: cache-hit event emission failed: "
                  f"{exc}")

    # -- lifecycle -----------------------------------------------------------
    def shutdown(self, wait: bool = True) -> None:
        """Stop accepting and stop workers after their current query.
        Still-queued handles are CANCELLED so their waiters unblock."""
        with self._cond:
            self._shutdown = True
            for (pool, _t), dq in self._queues.items():
                while dq:
                    h = dq.popleft()
                    self._queued_per_pool[pool] -= 1
                    self._terminal(h, QueryState.CANCELLED, "cancelled",
                                   error=QueryCancelledError(
                                       "service shut down"))
            self._cond.notify_all()
            workers = list(self._workers)
        if wait:
            for w in workers:
                w.thread.join(timeout=30)
            self._sweeper.join(timeout=5)
            self._watchdog.join(timeout=5)
        if self.introspect is not None:
            self.introspect.shutdown()
            self.introspect = None
        # stop recurring streams + detach the MV registry's epoch
        # listener so neither outlives the service
        with self._streams_lock:
            streams, mvs = list(self._streams.values()), self._mvs
            self._streams.clear()
            self._mvs = None
        for s in streams:
            try:
                s.stop(wait=wait)
            except Exception:
                pass
        if mvs is not None:
            mvs.close()

    def __enter__(self) -> "QueryService":
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # -- introspection -------------------------------------------------------

    #: FINISHED handles retained per (pool, tenant) for the rolling
    #: SLO percentiles (a window, not a conf: the introspection
    #: surface is an operator tool, not a tuning target)
    _SLO_WINDOW = 512

    def _note_finished(self, handle: QueryHandle) -> None:
        """Record a FINISHED handle's latency/run wall into the rolling
        SLO window (the /slo endpoint's source)."""
        lat, run = handle.latency_s, handle.run_s
        with self._cond:
            dq = self._finished_lat.setdefault(
                (handle.pool, handle.tenant),
                deque(maxlen=self._SLO_WINDOW))
            dq.append((lat or 0.0, run or 0.0))

    @staticmethod
    def _pcts(vals: List[float]) -> Dict[str, float]:
        ordered = sorted(vals)
        n = len(ordered)

        def pct(q: float) -> float:
            return ordered[min(n - 1, int(q * n))]

        return {"p50S": round(pct(0.50), 6), "p95S": round(pct(0.95), 6)}

    def slo_snapshot(self) -> dict:
        """Rolling per-pool and per-tenant p50/p95 over recently
        FINISHED handles: ``latency`` = submit->finish (queue wait
        included — what a caller experiences), ``run`` = running wall
        only. Empty dicts before any query finishes."""
        with self._cond:
            windows = [((p, t), list(dq))
                       for (p, t), dq in self._finished_lat.items() if dq]
        pools: Dict[str, dict] = {}
        tenants: Dict[str, dict] = {}
        by_pool: Dict[str, list] = {}
        for (pool, tenant), samples in windows:
            by_pool.setdefault(pool, []).extend(samples)
            tenants[f"{pool}/{tenant}"] = {
                "count": len(samples),
                "latency": self._pcts([s[0] for s in samples]),
                "run": self._pcts([s[1] for s in samples]),
            }
        for pool, samples in by_pool.items():
            pools[pool] = {
                "count": len(samples),
                "latency": self._pcts([s[0] for s in samples]),
                "run": self._pcts([s[1] for s in samples]),
            }
        return {"window": self._SLO_WINDOW, "pools": pools,
                "tenants": dict(sorted(tenants.items()))}

    def query_table(self, blocking: bool = True) -> Optional[List[dict]]:
        """The live query table: RUNNING handles (from the workers)
        plus QUEUED handles in pick order context. ``blocking=False``
        is the flight recorder's no-wait contract: the recorder must
        never stall behind a busy scheduler, so a contended condition
        lock yields None ("table unavailable") instead of queueing the
        bundle write on it. (Condition wraps an RLock, so a same-
        thread caller re-enters successfully either way.)"""
        if not self._cond.acquire(blocking=blocking):
            return None
        try:
            now = time.monotonic()
            out: List[dict] = []
            for w in self._workers:
                h = w.handle
                if h is None:
                    continue
                out.append({
                    "id": h.query_id, "state": h.state,
                    "tenant": h.tenant, "pool": h.pool, "tag": h.tag,
                    "worker": w.name,
                    "runningS": (round(now - h.start_t, 3)
                                 if h.start_t is not None else None),
                })
            for (pool, tenant), dq in self._queues.items():
                for h in dq:
                    out.append({
                        "id": h.query_id, "state": "QUEUED",
                        "tenant": tenant, "pool": pool, "tag": h.tag,
                        "queuedS": round(now - h.submit_t, 3),
                    })
        finally:
            self._cond.release()
        return out

    def _fleet_degraded_reason(self) -> Optional[str]:
        """The driver/service unification's shedding input: live host
        strength and arbiter occupancy, read from the same singletons
        the degradation ladders mutate. Legal under the condition lock
        — cluster.runtime(300) and memory.arbiter(740) both rank above
        service.scheduler.cond(200), so these reads only ever acquire
        upward."""
        if self._degrade_on_host_loss:
            from spark_rapids_tpu.runtime.cluster import CLUSTER
            hosts = CLUSTER.health_snapshot()
            if hosts["enabled"]:
                if hosts["singleProcessReason"]:
                    return ("cluster latched single-process: "
                            f"{hosts['singleProcessReason']}")
                if hosts["lostHosts"] or hosts["excludedHosts"]:
                    return (
                        "cluster below declared strength: "
                        f"{len(hosts['liveHosts'])}/"
                        f"{hosts['declaredHosts']} live (lost="
                        f"{hosts['lostHosts']}, excluded="
                        f"{hosts['excludedHosts']})")
        frac = self._degrade_memory_fraction
        if frac > 0.0:
            from spark_rapids_tpu.runtime.memory import MEMORY
            budget = MEMORY.budget_bytes()
            occupancy = MEMORY.occupancy()
            if budget > 0 and occupancy > frac * budget:
                return (f"arbiter occupancy {occupancy}B over "
                        f"{frac:g} x budget {budget}B")
        return None

    def _health_state_locked(self) -> str:
        """HEALTHY → DEGRADED → CPU_ONLY. CPU_ONLY comes from the
        process-wide device latch; DEGRADED while the device is mid
        loss-streak, this service recently lost workers and has not
        yet completed _DEGRADE_CLEAR_SUCCESSES queries, OR the shared
        topology reports the fleet below strength (host loss, arbiter
        over occupancy) — the service IS the cluster driver, so its
        shedding decision consults the cluster's live state. Caller
        holds the condition lock (the degraded counter is mutated
        under it)."""
        device = HEALTH.state()
        if device == "CPU_ONLY":
            return "CPU_ONLY"
        if (device == "DEGRADED" or self._degraded_pending > 0
                or self._fleet_degraded_reason() is not None):
            return "DEGRADED"
        return "HEALTHY"

    def topology_snapshot(self) -> dict:
        """ONE coherent fleet-topology view (hosts + mesh + memory +
        ladders + quarantine) taken with every owning lock held — the
        shared-topology path (runtime/health.py); also served as the
        ``/topology`` introspection route."""
        from spark_rapids_tpu.runtime.health import (
            consistent_topology_snapshot,
        )
        return consistent_topology_snapshot()

    def health(self) -> dict:
        """The service health surface the ISSUE's states machine drives
        admission from (and ``tools loadtest`` reports). The hosts /
        mesh / memory sections come from ONE consistent topology
        snapshot — all owning locks held together — so the view cannot
        tear across a mid-query shrink (a host loss excludes mesh
        devices only after dropping the cluster lock; independent
        section reads could observe the gap)."""
        topo = self.topology_snapshot()
        with self._cond:
            out = {
                "state": self._health_state_locked(),
                "workersLost": self._workers_lost,
                "workersRespawned": self._workers_respawned,
                "workerCount": len(self._workers),
                "degradedPendingSuccesses": self._degraded_pending,
                "shedPool": self._shed_pool,
                "fleetDegradedReason": self._fleet_degraded_reason(),
            }
        out["cpuOnlyReason"] = topo["cpuOnlyReason"]
        out["device"] = topo["backend"]
        out["quarantine"] = topo["quarantine"]
        # the mesh fault domain: current topology (shrunken shape and
        # excluded devices after partial losses, with the degradation
        # reason) plus the ladder's counters — a degraded-but-serving
        # mesh is VISIBLE here, not silently smaller
        out["mesh"] = topo["mesh"]
        # the host fault domain above the mesh: current topology
        # (declared/live/lost/excluded hosts, the single-process latch)
        # plus the host ladder's counters — a cluster serving below
        # declared strength is VISIBLE here, not silently smaller
        out["hosts"] = topo["hosts"]
        # the memory fault domain: arbiter budget/occupancy/peak plus
        # the memory degradation ladder's counters — a query surviving
        # out-of-core is VISIBLE here, not silently slower
        out["memory"] = topo["memory"]
        out["topologyGeneration"] = topo["generation"]
        return out

    def stats(self) -> dict:
        # snapshot EVERYTHING mutated under _cond while holding it —
        # including the survivability fields — so a concurrent worker
        # can never hand back a torn view (pinned by the stats
        # concurrency test)
        with self._cond:
            out = {
                **self.counters,
                "running": self._running,
                "queued": {p: n for p, n in self._queued_per_pool.items()
                           if n},
                "heldForMemory": self._held_for_memory,
                "healthState": self._health_state_locked(),
                "workersLost": self._workers_lost,
                "workersRespawned": self._workers_respawned,
                "poolClocks": {p: round(c, 6)
                               for p, c in self._pool_clock.items()},
                "tenantClocks": {f"{p}/{t}": round(c, 6)
                                 for (p, t), c in
                                 self._tenant_clock.items()},
            }
        out["quarantine"] = QUARANTINE.snapshot()
        if self.result_cache is not None:
            out["resultCache"] = self.result_cache.stats()
        return out

    # -- recurring streams ---------------------------------------------------
    def register_stream(self, stream) -> None:
        """Register a recurring tenant (a StreamingQuery) for the
        introspection surfaces; latest registration wins a name."""
        with self._streams_lock:
            self._streams[stream.name] = stream

    def unregister_stream(self, name: str) -> None:
        with self._streams_lock:
            self._streams.pop(name, None)

    def streams(self) -> List[dict]:
        """Descriptors of every registered recurring stream (name,
        source kind, pool/tenant, batch/offset progress, state) —
        rendered by ``tools top`` and served on /top."""
        with self._streams_lock:
            items = sorted(self._streams.items())
        out = []
        for _, s in items:
            try:
                out.append(s.describe())
            except Exception:
                pass  # a dying stream must not break introspection
        return out

    def mv_registry(self):
        """The service's MaterializedViewRegistry (streaming/mv.py),
        created on first use over the shared session and torn down with
        the service (its epoch listener must not outlive it)."""
        with self._streams_lock:
            if self._mvs is None:
                from spark_rapids_tpu.streaming.mv import (
                    MaterializedViewRegistry,
                )
                self._mvs = MaterializedViewRegistry(self.session)
            return self._mvs
