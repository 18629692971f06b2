"""Continuous telemetry ring + flight recorder (the cluster black box).

PR 4 built the per-QUERY observability surface (event log, spans,
metrics); PRs 7-12 then grew degradation ladders, a mesh fault domain
and a multi-host runtime whose LIVE state those per-query snapshots
cannot see — when a host dies or a kernel demotes mid-serve, the *why*
is scattered across process-wide counters nobody sampled at the time.
This module is the between-queries half of observability:

* :class:`TelemetryRing` / the process-wide :data:`TELEMETRY` — a
  PASSIVE background sampler: every ``spark.rapids.obs.telemetry.
  intervalMs`` it records one bounded sample — the per-scope DELTAS of
  every MetricRegistry scope (compile / mesh / cluster / health /
  spill / shuffle / write / service / semaphore / recovery) plus the
  health state and mesh/cluster topology — into a bounded ring,
  exportable as JSONL. Sampling must never perturb execution: the
  RL-OBS-PASSIVE lint rule forbids this module device syncs, query
  execution, and the query-path locks (the sampler reads only the
  snapshot surfaces every subsystem already exposes, each of which
  bounds its own lock hold to a dict copy).
* **Flight recorder** (:func:`record_incident`) — any degradation-
  ladder action (mesh / host / whole-backend) or quarantine strike
  dumps one bounded INCIDENT BUNDLE (JSON) to
  ``spark.rapids.obs.flightRecorder.dir``: the trigger (kind, ladder
  action, error, the fault point parsed from an injected error),
  ladder + fault-point state, health/mesh/cluster topology, the
  telemetry tail, recent event-record summaries, and the live query
  table of any registered QueryService. ``python -m spark_rapids_tpu.
  tools incident`` renders bundles offline; the chaos harnesses assert
  one bundle per injected ladder action. Bundles are pruned to
  ``spark.rapids.obs.flightRecorder.maxBundles`` and recording is
  best-effort — an unwritable dir never masks the recovery it
  documents.
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
import weakref
from collections import deque
from typing import Dict, List, Optional

from spark_rapids_tpu.conf import RapidsConf, bool_conf, int_conf, str_conf
from spark_rapids_tpu.obs.metrics import scopes_snapshot
from spark_rapids_tpu.lockorder import ordered_lock

TELEMETRY_ENABLED = bool_conf(
    "spark.rapids.obs.telemetry.enabled", False,
    "Run the passive background telemetry sampler: every intervalMs it "
    "appends one bounded sample (per-scope metric deltas + health/mesh/"
    "cluster topology) to the in-memory ring obs/telemetry.py exports "
    "as JSONL, the query service serves at /telemetry, and the flight "
    "recorder embeds as the incident tail. The sampler takes no "
    "query-path locks and never touches the device (RL-OBS-PASSIVE).",
    commonly_used=True)

TELEMETRY_INTERVAL_MS = int_conf(
    "spark.rapids.obs.telemetry.intervalMs", 500,
    "Telemetry sampling period. Each tick costs a handful of dict "
    "snapshots on the host — no device work, no query-path locks — so "
    "the floor is bounded at 10ms.")

TELEMETRY_RING_SIZE = int_conf(
    "spark.rapids.obs.telemetry.ringSize", 720,
    "Samples the telemetry ring retains (oldest dropped first); the "
    "default holds 6 minutes at the default 500ms interval.")

FLIGHT_RECORDER_ENABLED = bool_conf(
    "spark.rapids.obs.flightRecorder.enabled", True,
    "Dump a bounded incident bundle (trigger, ladder + fault-point "
    "state, topology, telemetry tail, recent event summaries, live "
    "query table) on every degradation-ladder action and quarantine "
    "strike — the black box `python -m spark_rapids_tpu.tools "
    "incident` renders. Best-effort: recording "
    "can never fail or slow the recovery it documents.")

FLIGHT_RECORDER_DIR = str_conf(
    "spark.rapids.obs.flightRecorder.dir", "/tmp/rapids_tpu_flightrec",
    "Directory for flight-recorder incident bundles (one "
    "incident-<ms>-<seq>-<kind>.json per incident, pruned oldest-first "
    "to flightRecorder.maxBundles).")

FLIGHT_RECORDER_MAX_BUNDLES = int_conf(
    "spark.rapids.obs.flightRecorder.maxBundles", 64,
    "Incident bundles retained under flightRecorder.dir; recording the "
    "N+1st deletes the oldest (a crash-looping process must bound its "
    "own black box).")

FLIGHT_RECORDER_TELEMETRY_TAIL = int_conf(
    "spark.rapids.obs.flightRecorder.telemetryTail", 60,
    "Telemetry-ring samples embedded in each incident bundle (the "
    "most recent N — 30s of context at the default interval).")


def _scope_delta(before: Optional[Dict[str, dict]],
                 after: Dict[str, dict]) -> Dict[str, dict]:
    """Per-scope numeric deltas between two scopes_snapshot() calls —
    the event log's scope_delta (one definition of delta semantics),
    with a first-sample guard (no baseline yet -> no movement)."""
    if before is None:
        return {}
    from spark_rapids_tpu.obs.events import scope_delta
    return scope_delta(before, after)


class TelemetryRing:
    """The process-wide passive sampler. ``configure(conf)`` is cheap
    when nothing changed (the FAULTS.arm contract) — the session and
    the query service both call it, so whichever constructs first
    starts the sampler and the flight recorder inherits the same
    conf's recorder settings for conf-less trigger sites."""

    def __init__(self):
        self._lock = ordered_lock("obs.telemetry.ring")
        self._cfg = None
        self._interval_s = 0.5
        self._ring: deque = deque(maxlen=720)
        self._prev_scopes: Optional[Dict[str, dict]] = None
        self._thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._samples = 0
        self._errors = 0

    # -- configuration -------------------------------------------------------
    def configure(self, conf: RapidsConf) -> None:
        enabled = bool(conf.get_entry(TELEMETRY_ENABLED))
        interval = int(conf.get_entry(TELEMETRY_INTERVAL_MS))
        size = max(1, int(conf.get_entry(TELEMETRY_RING_SIZE)))
        # the flight recorder's process defaults ride the same call so
        # conf-less trigger sites (quarantine strikes, kernel
        # demotions) land bundles where the operator pointed the dir
        _configure_flight_recorder(conf)
        key = (enabled, interval, size)
        start = stop = False
        with self._lock:
            if key == self._cfg:
                return
            self._cfg = key
            self._interval_s = max(0.01, interval / 1000.0)
            if size != self._ring.maxlen:
                self._ring = deque(self._ring, maxlen=size)
            # "alive" means a thread that has NOT been told to stop: a
            # disable->enable toggle must start a fresh thread even
            # while the stopped one lingers inside its last wait —
            # keying on is_alive() alone would record the enabled cfg,
            # start nothing, and leave the sampler dead forever (each
            # loop holds its own stop event, so a brief overlap of old
            # and new thread is harmless)
            alive = (self._thread is not None and self._thread.is_alive()
                     and not self._stop.is_set())
            if enabled and not alive:
                self._stop = threading.Event()
                self._thread = threading.Thread(
                    target=self._loop, args=(self._stop,),
                    name="rapids-telemetry-sampler", daemon=True)
                start = True
            elif not enabled and alive:
                stop = True
        if start:
            self._thread.start()
        if stop:
            self._stop.set()

    @property
    def enabled(self) -> bool:
        with self._lock:
            return bool(self._cfg and self._cfg[0])

    # -- sampling ------------------------------------------------------------
    def _loop(self, stop: threading.Event) -> None:
        while True:
            with self._lock:
                interval = self._interval_s
            if stop.wait(interval):
                return
            self.sample_once()

    def sample_once(self) -> Optional[dict]:
        """One sample: per-scope deltas since the previous sample plus
        the health/topology view — every read a bounded snapshot, no
        device work, no query-path locks (RL-OBS-PASSIVE)."""
        try:
            from spark_rapids_tpu.parallel.mesh import MESH
            from spark_rapids_tpu.runtime.cluster import CLUSTER
            from spark_rapids_tpu.runtime.faults import FAULTS
            from spark_rapids_tpu.runtime.health import HEALTH
            from spark_rapids_tpu.runtime.memory import MEMORY
            snap = scopes_snapshot()
            mem = MEMORY.snapshot()  # bounded dict copy, no locks held
            sample = {
                "t": round(time.time(), 3),
                "deltas": _scope_delta(self._prev_scopes, snap),
                "health": HEALTH.state(),
                "meshShape": MESH.shape_str(),
                "hostTopology": CLUSTER.topology_str(),
                "faultFires": sum(FAULTS.counters().values()),
                # device-budget occupancy riding every sample: the
                # between-queries view of out-of-core pressure
                "memOccupancy": mem["occupancyBytes"],
                "memBudget": mem["budgetBytes"],
            }
            with self._lock:
                self._prev_scopes = snap
                self._ring.append(sample)
                self._samples += 1
            return sample
        except Exception:
            with self._lock:
                self._errors += 1
            return None

    # -- reads ---------------------------------------------------------------
    def tail(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            samples = list(self._ring)
        if n is None:
            return samples
        n = int(n)
        return samples[-n:] if n > 0 else []  # [-0:] would be ALL

    def stats(self) -> dict:
        with self._lock:
            return {
                "enabled": bool(self._cfg and self._cfg[0]),
                "intervalMs": int(self._interval_s * 1000),
                "ringSize": self._ring.maxlen,
                "samples": self._samples,
                "buffered": len(self._ring),
                "errors": self._errors,
            }

    def export_jsonl(self, path: str) -> str:
        """Dump the current ring, one sample per line."""
        samples = self.tail()
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            for s in samples:
                f.write(json.dumps(s, sort_keys=True) + "\n")
        return path

    def reset(self) -> None:
        """Test support: drop buffered samples and the delta baseline."""
        with self._lock:
            self._ring.clear()
            self._prev_scopes = None
            self._samples = 0
            self._errors = 0


TELEMETRY = TelemetryRing()


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------

#: registered QueryServices (weak — a shut-down service just drops
#: out); the recorder snapshots their live query tables best-effort
_SERVICES: "weakref.WeakSet" = weakref.WeakSet()
_SERVICES_LOCK = ordered_lock("obs.telemetry.services")


def register_service(service) -> None:
    """Called by QueryService.__init__ so incident bundles can embed
    the live query table of every service in the process."""
    with _SERVICES_LOCK:
        _SERVICES.add(service)


#: process defaults for conf-less trigger sites (quarantine strikes),
#: refreshed by TELEMETRY.configure
_FR_LOCK = ordered_lock("obs.flightrec")
_FR_STATE = {
    "enabled": bool(FLIGHT_RECORDER_ENABLED.default),
    "dir": str(FLIGHT_RECORDER_DIR.default),
    "max_bundles": int(FLIGHT_RECORDER_MAX_BUNDLES.default),
    "tail": int(FLIGHT_RECORDER_TELEMETRY_TAIL.default),
}
_FR_SEQ = [0]

#: the fault-point pattern injected errors carry ("injected host loss
#: at host.dispatch") — parsed into the bundle's triggering fault point
_FAULT_POINT_RE = re.compile(r"\bat ([a-z][a-z0-9_]*(?:\.[a-z0-9_]+)+)")

#: bundle-kind prefix → fault domain.  Cross-domain closures match
#: "one bundle per ladder action" by (seq, faultDomain) instead of
#: timestamp windows, so the attribution must be total: anything not
#: claimed by a hardware/memory/stream prefix belongs to the service
#: plane (backend ladder, quarantine).
_FAULT_DOMAIN_PREFIXES = (
    ("host.", "host"),
    ("mesh.", "mesh"),
    ("memory.", "memory"),
    ("stream.", "stream"),
)


def fault_domain(kind: str) -> str:
    kind = str(kind)
    for prefix, domain in _FAULT_DOMAIN_PREFIXES:
        if kind.startswith(prefix):
            return domain
    return "service"


def _configure_flight_recorder(conf: RapidsConf) -> None:
    with _FR_LOCK:
        _FR_STATE["enabled"] = bool(conf.get_entry(FLIGHT_RECORDER_ENABLED))
        _FR_STATE["dir"] = str(conf.get_entry(FLIGHT_RECORDER_DIR))
        _FR_STATE["max_bundles"] = int(
            conf.get_entry(FLIGHT_RECORDER_MAX_BUNDLES))
        _FR_STATE["tail"] = int(
            conf.get_entry(FLIGHT_RECORDER_TELEMETRY_TAIL))


def _recorder_settings(conf: Optional[RapidsConf]) -> dict:
    if conf is not None:
        try:
            return {
                "enabled": bool(conf.get_entry(FLIGHT_RECORDER_ENABLED)),
                "dir": str(conf.get_entry(FLIGHT_RECORDER_DIR)),
                "max_bundles": int(
                    conf.get_entry(FLIGHT_RECORDER_MAX_BUNDLES)),
                "tail": int(
                    conf.get_entry(FLIGHT_RECORDER_TELEMETRY_TAIL)),
            }
        except Exception:
            pass
    with _FR_LOCK:
        return dict(_FR_STATE)


def _active_query_tables() -> List[dict]:
    """Live query tables of every registered service. NON-BLOCKING by
    contract: a quarantine strike is recorded while the scheduler's
    condition lock is held, and a blocking re-acquire from the same
    thread would deadlock — a service whose lock is busy reports
    'unavailable' instead."""
    out: List[dict] = []
    with _SERVICES_LOCK:
        services = list(_SERVICES)
    for svc in services:
        try:
            table = svc.query_table(blocking=False)
        except Exception:
            table = None
        out.append({"pools": sorted(getattr(svc, "pools", {})),
                    "queries": table,
                    "available": table is not None})
    return out


def _prune_bundles(directory: str, max_bundles: int) -> None:
    names = sorted(n for n in os.listdir(directory)
                   if n.startswith("incident-") and n.endswith(".json"))
    for n in names[:max(0, len(names) - max_bundles)]:
        try:
            os.unlink(os.path.join(directory, n))
        except OSError:
            pass


def record_incident(kind: str, action: str, reason: str,
                    conf: Optional[RapidsConf] = None,
                    error: Optional[BaseException] = None,
                    extra: Optional[dict] = None) -> Optional[str]:
    """Dump one incident bundle; returns its path (None when disabled
    or the dump failed — recording is strictly best-effort and must
    never raise into a recovery path). Callers must NOT hold the
    health/quarantine locks (the bundle re-reads their snapshots)."""
    try:
        settings = _recorder_settings(conf)
        if not settings["enabled"]:
            return None
        from spark_rapids_tpu.parallel.mesh import MESH
        from spark_rapids_tpu.runtime.cluster import CLUSTER
        from spark_rapids_tpu.runtime.faults import (
            CIRCUIT_BREAKER,
            FAULTS,
            RECOVERY,
        )
        from spark_rapids_tpu.runtime.health import HEALTH, QUARANTINE
        reason = str(reason)
        m = _FAULT_POINT_RE.search(reason)
        # the sequence id is allocated BEFORE the bundle is built and
        # embedded in-band: process-monotonic, so a closure can assert
        # exact bundle↔ladder-action correspondence even when wall
        # clocks collide across domains
        with _FR_LOCK:
            _FR_SEQ[0] += 1
            seq = _FR_SEQ[0]
        bundle = {
            "schema": 2,
            "seq": seq,
            "faultDomain": fault_domain(kind),
            "kind": str(kind),
            "action": str(action),
            "reason": reason[:2000],
            "errorType": type(error).__name__ if error is not None
            else None,
            "faultPoint": m.group(1) if m else None,
            "wallClock": round(time.time(), 3),
            "pid": os.getpid(),
            "health": {
                "state": HEALTH.state(),
                "cpuOnlyReason": HEALTH.cpu_only_reason(),
                "backend": HEALTH.snapshot(),
                "meshLadder": HEALTH.mesh_snapshot(),
                "hostLadder": HEALTH.host_snapshot(),
                "memoryLadder": HEALTH.memory_snapshot(),
            },
            "mesh": MESH.health_snapshot(),
            "cluster": CLUSTER.health_snapshot(),
            "memory": _memory_snapshot(),
            "quarantine": QUARANTINE.snapshot(),
            "demotions": CIRCUIT_BREAKER.demoted_ops(),
            "recovery": RECOVERY.snapshot(),
            "faultFires": FAULTS.counters(),
            "scopes": scopes_snapshot(),
            "telemetry": {
                "sampler": TELEMETRY.stats(),
                "tail": TELEMETRY.tail(settings["tail"]),
            },
            "recentEvents": _recent_event_summaries(),
            "activeQueries": _active_query_tables(),
        }
        if extra:
            bundle["extra"] = extra
        directory = settings["dir"]
        os.makedirs(directory, exist_ok=True)
        safe_kind = re.sub(r"[^A-Za-z0-9._-]", "_", str(kind))
        path = os.path.join(
            directory,
            f"incident-{int(time.time() * 1000):013d}-{seq:06d}-"
            f"{safe_kind}.json")
        with open(path, "w") as f:
            json.dump(bundle, f, sort_keys=True)
        _prune_bundles(directory, settings["max_bundles"])
        return path
    except Exception:
        return None  # the black box must never take the plane down


def record_incident_async(kind: str, action: str, reason: str,
                          conf: Optional[RapidsConf] = None,
                          error: Optional[BaseException] = None,
                          extra: Optional[dict] = None) -> None:
    """Fire-and-forget :func:`record_incident` on a short-lived daemon
    thread — for trigger sites that run under a hot lock (the
    quarantine strike records while the scheduler's condition lock is
    held; a slow flight-recorder dir must never stall the service's
    submit/pick/finish paths for the duration of a bundle write)."""
    try:
        threading.Thread(
            target=record_incident,
            args=(kind, action, reason),
            kwargs={"conf": conf, "error": error, "extra": extra},
            name="rapids-flightrec-dump", daemon=True).start()
    except Exception:
        pass  # thread-spawn failure must not mask the strike


def _recent_event_summaries() -> List[dict]:
    from spark_rapids_tpu.obs.events import recent_records
    return recent_records()


def _memory_snapshot() -> dict:
    from spark_rapids_tpu.runtime.memory import MEMORY
    return MEMORY.snapshot()
