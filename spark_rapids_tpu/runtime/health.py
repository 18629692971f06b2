"""Device health monitor: device-loss recovery + poison-query quarantine.

Reference (SURVEY.md §5): on a fatal CUDA error the reference captures a
core dump and exits the executor with code 20, trusting Spark's driver
to reschedule the work on a healthy node. ``runtime/crash_handler.py``
implements that capture-and-exit half; this module is the RESCHEDULER
the exit protocol assumes exists — the single-process query service
(service/scheduler.py) has no Spark driver above it, so recovery from a
dead device has to happen in-process:

* **Device-loss recovery** — a fatal non-OOM device error
  (:func:`~spark_rapids_tpu.runtime.crash_handler.is_fatal_device_error`
  — classified DISTINCTLY from the per-op
  :class:`~spark_rapids_tpu.errors.KernelCrashError` the PR-3 circuit
  breaker owns) reinitializes the backend and invalidates every cache
  that references dead device state: the plan→executable cache (cached
  trees hold device-resident constants), the structural kernel-trace
  caches, the interned device const/scalar pools, cached scan device
  images, and jax's own jit caches. The failing query surfaces a typed
  RETRYABLE :class:`~spark_rapids_tpu.errors.DeviceLostError`; the
  query service requeues it against the recovered backend.
* **CPU-only latch** — after
  ``spark.rapids.service.deviceLoss.maxReinits`` CONSECUTIVE device
  losses (no successful query between them) the engine stops trusting
  the device entirely and latches CPU-only degraded mode: the overrides
  layer (PlanMeta.tag) falls every operator back with the latch reason,
  exactly like a circuit-breaker demotion but for the whole device.
  Serving survives at reduced speed instead of crash-looping.
* **Poison-query quarantine** — a template fingerprint
  (plan/fingerprint.py) that kills workers or the device
  ``spark.rapids.service.quarantine.maxStrikes`` times is quarantined:
  subsequent submissions are rejected with a typed
  :class:`~spark_rapids_tpu.errors.QueryQuarantinedError` carrying the
  strike history, and ``explain()`` flags the template.

Counters live in the unified registry's ``health`` scope so the event
log diffs them per query like spill/recovery/shuffle.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional

from spark_rapids_tpu.conf import int_conf
from spark_rapids_tpu.obs.metrics import metric_scope, register_metric
from spark_rapids_tpu.lockorder import ordered_lock

DEVICE_LOSS_MAX_REINITS = int_conf(
    "spark.rapids.service.deviceLoss.maxReinits", 3,
    "Consecutive device losses (fatal non-OOM device errors with no "
    "successful query between them) tolerated before the engine stops "
    "reinitializing the backend and latches CPU-only degraded mode for "
    "the rest of the process (whole-device analog of the per-op "
    "runtime circuit breaker).")

QUARANTINE_MAX_STRIKES = int_conf(
    "spark.rapids.service.quarantine.maxStrikes", 3,
    "Times one query template (literal-stripped structural "
    "fingerprint) may kill a service worker or the device before it is "
    "quarantined: further submissions of the template are rejected "
    "with QueryQuarantinedError carrying the strike history.")

register_metric("deviceLost", "count", "ESSENTIAL",
                "fatal device errors observed (each triggers a "
                "backend reinitialization or the CPU-only latch)")
register_metric("deviceReinits", "count", "ESSENTIAL",
                "backend reinitializations after device loss "
                "(caches invalidated, device re-discovered)")
register_metric("workersLost", "count", "ESSENTIAL",
                "service workers that died or were abandoned by the "
                "watchdog (hard wall-limit breach)")
register_metric("workersRespawned", "count", "ESSENTIAL",
                "replacement service workers spawned so pool capacity "
                "holds through worker loss")
register_metric("hardTimeouts", "count", "ESSENTIAL",
                "queries failed by the watchdog's hard wall limit "
                "(spark.rapids.service.hardTimeoutMs)")
register_metric("quarantineStrikes", "count", "MODERATE",
                "worker/device kills recorded against query templates")
register_metric("quarantinedTemplates", "count", "ESSENTIAL",
                "query templates currently quarantined")
register_metric("meshDeviceLost", "count", "ESSENTIAL",
                "PARTIAL device losses observed (one mesh device dead, "
                "backend otherwise alive — each walks one rung of the "
                "mesh degradation ladder)")
register_metric("meshDegradations", "count", "ESSENTIAL",
                "times the degradation ladder demoted mesh execution "
                "(single-device re-land of an attempt, or a mesh "
                "shrink onto surviving devices)")
register_metric("meshShrinks", "count", "ESSENTIAL",
                "mesh reconfigurations onto surviving devices after "
                "partial device loss (bounded by "
                "spark.rapids.mesh.degrade.maxShrinks)")
register_metric("memoryPressure", "count", "ESSENTIAL",
                "FatalDeviceOOM escalations the memory degradation "
                "ladder handled (each walks one rung: full-spill "
                "retry, chunked re-execution, per-op CPU demotion)")
register_metric("memoryChunkedReexecutions", "count", "ESSENTIAL",
                "query replays forced onto chunked scans by the "
                "memory ladder's 'chunk' rung")
register_metric("memoryCpuDemotions", "count", "ESSENTIAL",
                "operators demoted to the CPU path by the memory "
                "ladder after chunked re-execution still could not "
                "fit the device budget")


def _record_ladder_incident(kind: str, action: str, exc: BaseException,
                            conf) -> None:
    """Flight-recorder hook for every degradation-ladder action
    (obs/telemetry.py). Called AFTER the monitor's lock is released —
    the bundle re-reads the health snapshots — and strictly
    best-effort: the black box must never mask the recovery it
    documents."""
    try:
        from spark_rapids_tpu.obs.telemetry import record_incident
        first = (str(exc).splitlines()[0] if str(exc)
                 else type(exc).__name__)
        reason = f"{type(exc).__name__}: {first}"
        cause = exc.__cause__
        if cause is not None and str(cause):
            # a wrapped escalation (FatalDeviceOOM from a RetryOOM)
            # names the triggering fault point only in its cause — ride
            # it along so the bundle's faultPoint parse still works
            reason += f" (cause: {str(cause).splitlines()[0]})"
        record_incident(kind, action, reason, conf=conf, error=exc)
    except Exception:
        pass


class DeviceHealthMonitor:
    """Process-wide device health state (the device is shared by every
    session in the process, like the circuit breaker and the kernel
    caches). Writes go through the instance lock; the hot-path reads
    (``cpu_only_reason`` in PlanMeta.tag, ``generation`` in the
    executable-cache token) are single attribute loads."""

    def __init__(self):
        self._lock = ordered_lock("health.monitor")
        self._metrics = metric_scope("health")
        self._consecutive_losses = 0
        self._reinits = 0
        self._losses = 0
        #: read LOCK-FREE on the per-node tag() hot path — a plain
        #: attribute load of an immutable str/None (latch is one-way
        #: until reset(), so a torn read cannot un-latch)
        self._cpu_only_reason: Optional[str] = None
        #: coherency generation for the executable cache: bumped per
        #: recovery so a tree checked out across a reinit can neither
        #: re-park into the fresh pool nor corrupt its busy count
        self._generation = 0
        # -- the mesh fault domain (partial device loss) ------------------
        #: consecutive PARTIAL mesh-device losses with no mesh-NATIVE
        #: success between them — drives the degradation ladder. A
        #: success achieved under single-device suppression does NOT
        #: reset it (the mesh was not exercised, so there is no
        #: evidence it recovered)
        self._mesh_consecutive = 0
        self._mesh_losses = 0
        self._mesh_shrinks = 0
        self._mesh_degradations = 0
        # -- the host fault domain (a dead executor PROCESS) --------------
        #: consecutive HOST losses with no cluster-NATIVE success
        #: between them — drives the host degradation ladder. A success
        #: achieved with the cluster inactive (suppressed / latched
        #: single-process) does NOT reset it.
        self._host_consecutive = 0
        self._host_losses = 0
        self._host_shrinks = 0
        # -- the memory fault domain (device budget exhaustion) ------------
        #: consecutive FatalDeviceOOMs with no success between them —
        #: drives the memory degradation ladder (retry-after-full-
        #: spill -> chunked re-execution -> per-op CPU demotion). Any
        #: completed query resets it (memory pressure is workload
        #: pressure, not broken hardware).
        self._mem_consecutive = 0
        self._mem_events = 0
        self._mem_chunked = 0
        self._mem_cpu_demotions = 0

    # -- hot-path reads ------------------------------------------------------
    def cpu_only_reason(self) -> Optional[str]:
        return self._cpu_only_reason

    def generation(self) -> int:
        return self._generation

    def state(self) -> str:
        """HEALTHY / DEGRADED / CPU_ONLY from the device's view alone
        (the query service folds its own worker-loss recency in)."""
        if self._cpu_only_reason is not None:
            return "CPU_ONLY"
        if self._consecutive_losses > 0:
            return "DEGRADED"
        return "HEALTHY"

    # -- the recovery protocol -----------------------------------------------
    def on_device_loss(self, exc: BaseException, conf) -> str:
        """One observed fatal device error: count it, reinitialize the
        backend (invalidating every device-referencing cache), and latch
        CPU-only mode once the consecutive-loss budget is spent. Returns
        the resulting health state. Serialized — two workers observing
        the same dead device recover one at a time, and the second
        recovery is a cheap re-clear of already-empty caches."""
        state = self._on_device_loss_inner(exc, conf)
        _record_ladder_incident("backend.ladder", state, exc, conf)
        return state

    def _on_device_loss_inner(self, exc: BaseException, conf) -> str:
        max_reinits = int(conf.get_entry(DEVICE_LOSS_MAX_REINITS))
        with self._lock:
            self._losses += 1
            self._consecutive_losses += 1
            self._generation += 1
            self._metrics.add("deviceLost", 1)
            if self._cpu_only_reason is not None:
                return "CPU_ONLY"
            if self._consecutive_losses >= max_reinits:
                self._cpu_only_reason = (
                    f"device health: CPU-only mode latched after "
                    f"{self._consecutive_losses} consecutive device "
                    f"losses (last: {type(exc).__name__}: "
                    f"{str(exc).splitlines()[0] if str(exc) else ''})")
                # the dead device's caches still need to go — CPU-only
                # queries must not resolve stale device constants
                self._invalidate_device_caches_locked()
                return "CPU_ONLY"
            self._reinits += 1
            self._metrics.add("deviceReinits", 1)
            self._reinitialize_backend_locked(conf)
            return "DEGRADED"

    def note_success(self, mesh_native: bool = False,
                     cluster_native: bool = False) -> None:
        """A query completed: the device (or the CPU-only path) works,
        so the consecutive-loss budget refills. The MESH ladder only
        resets on a mesh-NATIVE success (``mesh_native``): a query
        that converged under single-device suppression proves nothing
        about the mesh, and resetting on it would ping-pong a truly
        dead device between retry and single-device forever instead of
        walking down to the shrink rung. The HOST ladder resets only
        on a cluster-NATIVE success for the same reason."""
        if (self._consecutive_losses
                or self._mem_consecutive
                or (mesh_native and self._mesh_consecutive)
                or (cluster_native and self._host_consecutive)):
            with self._lock:
                self._consecutive_losses = 0
                # ANY success resets the memory ladder: the budget
                # squeeze was this workload's, not the hardware's
                self._mem_consecutive = 0
                if mesh_native:
                    self._mesh_consecutive = 0
                if cluster_native:
                    self._host_consecutive = 0

    def on_mesh_device_loss(self, exc: BaseException, conf) -> str:
        """One observed PARTIAL device loss (a ``mesh.*`` fault point's
        device_lost, or a real per-device failure classified as
        MeshDeviceLostError): walk the degradation ladder one rung and
        return the recovery action the session should take —

        * ``"retry"`` — first consecutive loss: replay the query on the
          unchanged mesh (transient ICI hiccups are routine on a pod);
        * ``"single_device"`` — second loss: replay THIS query with
          mesh landing suppressed (parallel/mesh.suppressed_mesh), the
          demotion reason riding the hostShuffleFallbacks/explain()
          machinery — the query converges while the mesh is suspect;
        * ``"shrink"`` — third loss on: reconfigure the mesh onto the
          surviving devices (MESH.shrink_excluding — the generation
          bump fences every stale cached tree/dictionary), bounded by
          spark.rapids.mesh.degrade.maxShrinks;
        * ``"DEGRADED"`` / ``"CPU_ONLY"`` — shrink budget spent (or
          nothing left to shrink): escalate to the whole-backend
          ladder (:meth:`on_device_loss` — backend reinit, then the
          CPU-only latch).
        """
        action = self._on_mesh_device_loss_inner(exc, conf)
        _record_ladder_incident("mesh.ladder", action, exc, conf)
        return action

    def _on_mesh_device_loss_inner(self, exc: BaseException, conf) -> str:
        from spark_rapids_tpu.parallel.mesh import (
            MESH,
            MESH_DEGRADE_MAX_SHRINKS,
        )
        max_shrinks = int(conf.get_entry(MESH_DEGRADE_MAX_SHRINKS))
        first = str(exc).splitlines()[0] if str(exc) else type(exc).__name__
        with self._lock:
            if self._cpu_only_reason is not None:
                return "CPU_ONLY"
            self._mesh_losses += 1
            self._mesh_consecutive += 1
            n = self._mesh_consecutive
            self._metrics.add("meshDeviceLost", 1)
            if n == 1:
                return "retry"
            if n == 2:
                self._mesh_degradations += 1
                self._metrics.add("meshDegradations", 1)
                return "single_device"
            # RESERVE the shrink slot while still holding the lock:
            # two workers observing losses concurrently must not both
            # pass a read-only budget check and shrink maxShrinks+1
            # times between them
            budget = self._mesh_shrinks < max(0, max_shrinks)
            if budget:
                self._mesh_shrinks += 1
        shrunk = False
        if budget:
            reason = (f"mesh degraded after {n} consecutive mesh-device "
                      f"losses (last: {type(exc).__name__}: {first})")
            shrunk = MESH.shrink_excluding(
                getattr(exc, "device_id", None), reason)
            if not shrunk:
                with self._lock:
                    self._mesh_shrinks -= 1  # nothing to shrink: return it
        if shrunk:
            with self._lock:
                self._mesh_degradations += 1
                # a fresh ladder for the smaller mesh: its first loss
                # is a retry again, not an instant escalation
                self._mesh_consecutive = 0
                self._metrics.add("meshShrinks", 1)
                self._metrics.add("meshDegradations", 1)
            return "shrink"
        # nothing left to shrink (or budget spent): the whole-backend
        # ladder owns it from here — reinit, then the CPU-only latch
        return self.on_device_loss(exc, conf)

    def mesh_demotion_note(self) -> str:
        """The reason string a single-device-suppressed attempt carries
        (surfaced by ici_demotion_reason / explain())."""
        with self._lock:
            return (f"mesh degraded to single-device landing after "
                    f"{self._mesh_consecutive} consecutive mesh-device "
                    f"losses")

    def mesh_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return self._mesh_snapshot_locked()

    def _mesh_snapshot_locked(self) -> Dict[str, int]:
        return {
            "meshDeviceLost": self._mesh_losses,
            "meshConsecutiveLosses": self._mesh_consecutive,
            "meshShrinks": self._mesh_shrinks,
            "meshDegradations": self._mesh_degradations,
        }

    def on_host_loss(self, exc: BaseException, conf) -> str:
        """One observed HOST loss (a dead executor process — a
        ``host.*`` fault point's device_lost, a dead dispatch socket,
        or the missed-beat sweep's verdict surfacing as a typed
        HostLostError): walk the HOST degradation ladder one rung and
        return the recovery action the session should take —

        * ``"retry"`` — first consecutive loss: replay the query
          against the unchanged topology (a dropped message or a
          transient DCN hiccup is routine across hosts);
        * ``"reland"`` — second loss: declare the host LOST
          (CLUSTER.mark_host_lost) and replay — the replay's scans
          re-land the dead host's shards onto the survivors, and the
          host rejoins later via the heartbeat re-register path;
        * ``"shrink"`` — third loss on: evict the host from the
          topology (CLUSTER.shrink_excluding — its device group
          leaves the mesh's dcn axis, the generation bump fences
          every cached tree), bounded by
          spark.rapids.cluster.maxHostLosses;
        * ``"single_process"`` — shrink budget spent (or one host
          left): latch single-process fallback — every scan lands
          locally, still serving, until a host rejoins;
        * ``"DEGRADED"`` / ``"CPU_ONLY"`` — host losses keep coming
          even under the single-process latch: escalate to the
          whole-backend ladder (:meth:`on_device_loss`).
        """
        action = self._on_host_loss_inner(exc, conf)
        _record_ladder_incident("host.ladder", action, exc, conf)
        return action

    def _on_host_loss_inner(self, exc: BaseException, conf) -> str:
        from spark_rapids_tpu.runtime.cluster import (
            CLUSTER,
            CLUSTER_MAX_HOST_LOSSES,
        )
        max_losses = int(conf.get_entry(CLUSTER_MAX_HOST_LOSSES))
        first = str(exc).splitlines()[0] if str(exc) else type(exc).__name__
        host_id = getattr(exc, "host_id", None)
        already_latched = (
            CLUSTER.health_snapshot()["singleProcessReason"] is not None)
        budget = False
        with self._lock:
            if self._cpu_only_reason is not None:
                return "CPU_ONLY"
            self._host_losses += 1
            self._host_consecutive += 1
            n = self._host_consecutive
            if not already_latched and n >= 3:
                # RESERVE the shrink slot under the lock (the mesh
                # ladder's two-worker argument applies here too)
                budget = self._host_shrinks < max(0, max_losses)
                if budget:
                    self._host_shrinks += 1
        if already_latched:
            # the cluster is already out of the picture and hosts are
            # STILL being lost (injected schedules can do this): the
            # whole-backend ladder owns it from here
            return self.on_device_loss(exc, conf)
        reason = (f"cluster degraded after {n} consecutive host losses "
                  f"(last: {type(exc).__name__}: {first})")
        if n == 1:
            return "retry"
        if n == 2:
            CLUSTER.mark_host_lost(host_id, reason)
            return "reland"
        if budget:
            shrunk = CLUSTER.shrink_excluding(host_id, reason)
            if shrunk:
                with self._lock:
                    # a fresh ladder for the smaller topology
                    self._host_consecutive = 0
                return "shrink"
            with self._lock:
                self._host_shrinks -= 1  # nothing to shrink: return it
        CLUSTER.latch_single_process(
            f"cluster latched single-process after {n} consecutive "
            f"host losses (last: {type(exc).__name__}: {first})")
        return "single_process"

    def host_demotion_note(self) -> str:
        """The reason string a host-ladder replay carries (surfaced in
        explain()/event log alongside the mesh demotion notes)."""
        with self._lock:
            return (f"cluster degraded after {self._host_consecutive} "
                    f"consecutive host losses")

    def host_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return self._host_snapshot_locked()

    def _host_snapshot_locked(self) -> Dict[str, int]:
        return {
            "hostsLost": self._host_losses,
            "hostConsecutiveLosses": self._host_consecutive,
            "hostShrinks": self._host_shrinks,
        }

    def on_memory_pressure(self, exc: BaseException, conf) -> str:
        """One FatalDeviceOOM that escaped the retry framework (spill
        replays AND split-and-retry both exhausted — the working set
        truly does not fit the device budget at this execution shape):
        walk the MEMORY degradation ladder one rung and return the
        recovery action the session should take —

        * ``"retry"`` — first escalation: spill EVERYTHING spillable
          (whole device tier + cached scan images) and replay at the
          same shape — transient co-resident pressure (a concurrent
          query's working set) may have passed;
        * ``"chunk"`` — second escalation: replay with scans FORCED
          onto smaller chunks (runtime/memory.forced_chunking at half
          the normal chunk share) — bounded partitions stream where
          one batch could not fit;
        * ``"cpu_demote"`` — third escalation on: demote the
          attributed operator (``exc.fault_op``) to the CPU path via
          the runtime circuit breaker — the replay re-plans with that
          op off-device, the reason surfaced in explain()/event log
          like every other demotion;
        * ``"abort"`` — no operator attribution to demote (or the
          ladder is exhausted): the session re-raises the typed OOM.

        Each action records a flight-recorder incident bundle
        (``memory.ladder``), like every other domain's ladder."""
        action = self._on_memory_pressure_inner(exc, conf)
        _record_ladder_incident("memory.ladder", action, exc, conf)
        return action

    def _on_memory_pressure_inner(self, exc: BaseException, conf) -> str:
        with self._lock:
            self._mem_events += 1
            self._mem_consecutive += 1
            n = self._mem_consecutive
            self._metrics.add("memoryPressure", 1)
        if n == 1:
            # make maximum room before the same-shape replay
            try:
                from spark_rapids_tpu.columnar.table import (
                    evict_device_caches,
                )
                from spark_rapids_tpu.runtime.spill import BufferCatalog
                evict_device_caches()
                BufferCatalog.get().spill_all_device()
            except Exception:
                pass  # recovery must never raise
            return "retry"
        if n == 2:
            with self._lock:
                self._mem_chunked += 1
                self._metrics.add("memoryChunkedReexecutions", 1)
            try:
                from spark_rapids_tpu.columnar.table import (
                    evict_device_caches,
                )
                evict_device_caches()  # a cached unchunked image would
                # serve the replay the very batch that did not fit
            except Exception:
                pass
            return "chunk"
        op = getattr(exc, "fault_op", None)
        if op is None:
            return "abort"
        from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER
        # force-demote: one recorded failure at threshold 1 trips the
        # breaker, and the replay's re-plan falls the op back to CPU
        CIRCUIT_BREAKER.record_failure(op, exc, max_failures=1)
        with self._lock:
            self._mem_cpu_demotions += 1
            self._metrics.add("memoryCpuDemotions", 1)
        return "cpu_demote"

    def memory_snapshot(self) -> Dict[str, int]:
        with self._lock:
            return self._memory_snapshot_locked()

    def _memory_snapshot_locked(self) -> Dict[str, int]:
        return {
            "memoryPressureEvents": self._mem_events,
            "memoryConsecutive": self._mem_consecutive,
            "memoryChunkedReexecutions": self._mem_chunked,
            "memoryCpuDemotions": self._mem_cpu_demotions,
        }

    def _invalidate_device_caches_locked(self) -> None:
        """Drop every cache that references device state — cached
        executables hold device-resident interned constants, kernel
        traces point at compiled programs on the dead backend, and
        cached scan images ARE device arrays. Today (pre-PR) these
        would all be served stale after a reinit."""
        from spark_rapids_tpu.columnar.table import evict_device_caches
        from spark_rapids_tpu.dispatch import clear_device_constants
        from spark_rapids_tpu.ops.expr import clear_kernel_caches
        from spark_rapids_tpu.parallel.exchange import clear_mesh_caches
        from spark_rapids_tpu.plan.executable_cache import EXEC_CACHE
        EXEC_CACHE.invalidate_all()
        clear_kernel_caches()
        clear_device_constants()
        evict_device_caches()
        # mesh-exchange caches key on device IDS, which survive a reinit
        # unchanged — they'd serve the dead backend's buffers without this
        clear_mesh_caches()
        try:
            import jax
            jax.clear_caches()
        except Exception:
            pass  # recovery must never raise

    def _reinitialize_backend_locked(self, conf) -> None:
        """Re-run device discovery on the live manager (new PJRT client
        state picks up here). Best-effort: a reinit that itself fails
        leaves the next query to fail, bump the consecutive count, and
        drive toward the CPU-only latch."""
        self._invalidate_device_caches_locked()
        try:
            from spark_rapids_tpu.runtime.device_manager import (
                TpuDeviceManager,
            )
            mgr = TpuDeviceManager.current()
            if mgr is not None:
                mgr.initialized = False
                mgr.initialize()
        except Exception:
            pass

    # -- introspection -------------------------------------------------------
    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return self._snapshot_locked()

    def _snapshot_locked(self) -> Dict[str, int]:
        return {
            "deviceLost": self._losses,
            "deviceReinits": self._reinits,
            "consecutiveLosses": self._consecutive_losses,
        }

    def reset(self) -> None:
        with self._lock:
            self._consecutive_losses = 0
            self._reinits = 0
            self._losses = 0
            self._cpu_only_reason = None
            self._generation += 1
            self._mesh_consecutive = 0
            self._mesh_losses = 0
            self._mesh_shrinks = 0
            self._mesh_degradations = 0
            self._host_consecutive = 0
            self._host_losses = 0
            self._host_shrinks = 0
            self._mem_consecutive = 0
            self._mem_events = 0
            self._mem_chunked = 0
            self._mem_cpu_demotions = 0


HEALTH = DeviceHealthMonitor()


class QuarantineRegistry:
    """Strike ledger per query TEMPLATE (literal-stripped structural
    fingerprint): a template that repeatedly kills workers or the
    device is the prime poison suspect, whatever its literals. Plans
    too dynamic to fingerprint (UDF closures) cannot be quarantined —
    they also cannot hit any cache, so each run is independent."""

    def __init__(self):
        self._lock = ordered_lock("health.quarantine")
        self._metrics = metric_scope("health")
        #: template_fp -> ordered strike reasons
        self._strikes: Dict[str, List[str]] = {}
        self._quarantined: Dict[str, List[str]] = {}

    def strike(self, template_fp: Optional[str], reason: str,
               max_strikes: int) -> bool:
        """Record one kill against ``template_fp``; returns True when
        this strike quarantined the template."""
        if template_fp is None:
            return False
        with self._lock:
            history = self._strikes.setdefault(template_fp, [])
            history.append(reason)
            strikes = len(history)
            self._metrics.add("quarantineStrikes", 1)
            already = template_fp in self._quarantined
            quarantined = (not already
                           and strikes >= max(1, int(max_strikes)))
            if quarantined:
                self._quarantined[template_fp] = list(history)
                self._metrics.add("quarantinedTemplates", 1)
        # flight-recorder hook OUTSIDE the registry lock (the bundle
        # re-reads this registry's snapshot) and ASYNC: callers hold
        # the scheduler's condition lock here (worker-loss handling),
        # and a bundle write to a slow dir must not stall the
        # service's submit/pick/finish paths for its duration
        try:
            from spark_rapids_tpu.obs.telemetry import (
                record_incident_async,
            )
            record_incident_async(
                "quarantine", "quarantined" if quarantined else "strike",
                reason, extra={"template": template_fp,
                               "strikes": strikes})
        except Exception:
            pass
        return quarantined

    def is_quarantined(self, template_fp: Optional[str]) -> Optional[List[str]]:
        """The strike history when quarantined, else None."""
        if template_fp is None:
            return None
        with self._lock:
            history = self._quarantined.get(template_fp)
            return list(history) if history is not None else None

    def strike_count(self, template_fp: Optional[str]) -> int:
        if template_fp is None:
            return 0
        with self._lock:
            return len(self._strikes.get(template_fp, ()))

    def history(self, template_fp: Optional[str]) -> List[str]:
        if template_fp is None:
            return []
        with self._lock:
            return list(self._strikes.get(template_fp, ()))

    def snapshot(self) -> dict:
        with self._lock:
            return self._snapshot_locked()

    def _snapshot_locked(self) -> dict:
        return {
            "templatesWithStrikes": len(self._strikes),
            "strikes": sum(len(v) for v in self._strikes.values()),
            "quarantined": len(self._quarantined),
        }

    def reset(self) -> None:
        with self._lock:
            n = len(self._quarantined)
            self._strikes = {}
            self._quarantined = {}
            if n:
                self._metrics.add("quarantinedTemplates", -n)


QUARANTINE = QuarantineRegistry()


def consistent_topology_snapshot() -> dict:
    """ONE coherent view of the whole fleet topology — host cluster,
    device health ladders, quarantine ledger, mesh, memory arbiter —
    taken with every owning lock held simultaneously, so the sections
    cannot tear against each other across a mid-query shrink (a host
    loss updates the cluster under its own lock, releases it, and only
    THEN excludes the host's devices from the mesh; independent
    section reads can observe the half-applied shrink).

    This is the shared-topology path: ``QueryService.health()``, the
    ``/topology`` introspection route, and the fleet closure all read
    it, so admission control and the degradation ladders argue about
    the same topology. Locks nest in declared ascending rank —
    cluster.runtime(300) → health.monitor(400) → health.quarantine(410)
    → mesh.runtime(530) → memory.arbiter(740) — and every body under
    the nest is a pure dict read (RL-LOCK-EFFECT clean). The memory
    budget is resolved BEFORE the nest: budget_bytes() self-acquires
    the arbiter lock, which is non-reentrant by contract."""
    from spark_rapids_tpu.parallel.mesh import MESH
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    from spark_rapids_tpu.runtime.memory import MEMORY
    mem_budget = MEMORY.budget_bytes()
    with CLUSTER._lock:
        with HEALTH._lock:
            with QUARANTINE._lock:
                with MESH._lock:
                    with MEMORY._lock:
                        return {
                            "generation": HEALTH._generation,
                            "state": HEALTH.state(),
                            "cpuOnlyReason": HEALTH.cpu_only_reason(),
                            "backend": HEALTH._snapshot_locked(),
                            "hosts": {
                                **CLUSTER._health_snapshot_locked(),
                                **HEALTH._host_snapshot_locked(),
                            },
                            "mesh": {
                                **MESH._health_snapshot_locked(),
                                **HEALTH._mesh_snapshot_locked(),
                            },
                            "memory": {
                                **MEMORY._snapshot_locked(mem_budget),
                                **HEALTH._memory_snapshot_locked(),
                            },
                            "quarantine": QUARANTINE._snapshot_locked(),
                        }
