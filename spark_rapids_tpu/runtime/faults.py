"""Unified fault injection + runtime recovery bookkeeping.

Reference: the resilience machinery is scattered in the reference —
``RmmSpark.forceRetryOOM`` injects OOM per thread (SURVEY §2.5),
``RapidsShuffleHeartbeatManager`` evicts dead peers (§2.6), and
``onTaskFailed`` handles fatal errors — but each fault class has its own
ad-hoc test hook. This module unifies them: one conf-driven registry of
NAMED fault points (``spark.rapids.test.faults``) threaded through
dispatch, exec execute paths, the shuffle client/server/transport and the
io readers/writers, each armed with a deterministic seeded schedule and a
per-point fire counter, plus the recovery-side state the engine consults:

* ``FAULTS`` — the process-wide :class:`FaultRegistry`; sites call
  :func:`fault_point` (the greppable marker the RL-FAULT-POINT lint rule
  audits against :data:`FAULT_POINTS`).
* ``RECOVERY`` — counters for every recovery action (fetch retries, peer
  exclusions, map recomputes, circuit-breaker demotions, query replays)
  so chaos runs can assert bounded retry counts.
* ``CIRCUIT_BREAKER`` — per-operator non-OOM failure counts; after
  ``spark.rapids.sql.runtimeFallback.maxFailures`` failures of the same
  op it is demoted to the CPU fallback path for the rest of the session
  (surfaced as a fallback reason through PlanMeta/explain).
"""

from __future__ import annotations

import random
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional

from spark_rapids_tpu.errors import (
    ColumnarProcessingError,
    KernelCrashError,
    RetryOOM,
    ShuffleFetchError,
    ShuffleTransportError,
)
from spark_rapids_tpu.lockorder import ordered_lock

#: injectable fault kinds and the failure each simulates
FAULT_KINDS = (
    "oom",         # device allocation failure (RetryOOM; the retry framework survives it)
    "crash",       # non-OOM kernel failure (KernelCrashError; circuit breaker territory)
    "fetch",       # shuffle block fetch failure (ShuffleFetchError; fetch-retry loop)
    "disconnect",  # transport connection drop (ShuffleTransportError; reconnect + retry)
    "corrupt",     # bit-flip a data frame (CRC catches it; refetch recovers)
    "slow",        # slow peer / stall (sleep; exercises timeouts without failing)
    "wedge",       # long stall INSIDE one dispatch (no exception; the cooperative
                   # cancel boundary never runs — watchdog hard-timeout territory)
    "device_lost", # fatal device/PJRT loss (DeviceLostError; health-monitor
                   # recovery: backend reinit + cache invalidation, NOT the breaker)
    "race",        # lost optimistic-concurrency race (DeltaConcurrentModification-
                   # Exception; the transaction's rebase-and-retry loop owns it)
)

#: registered fault points: name -> (module that hosts the call site, doc).
#: The RL-FAULT-POINT repo-lint rule asserts every entry here names an
#: existing ``fault_point("<name>")`` call in that module and that no call
#: site uses an unregistered name.
FAULT_POINTS: Dict[str, tuple] = {
    "dispatch.kernel": (
        "spark_rapids_tpu/dispatch.py",
        "before each jitted kernel dispatch"),
    "stream.batch": (
        "spark_rapids_tpu/streaming/query.py",
        "after a micro-batch's offsets are durably logged, before it "
        "executes (a crash here leaves a pending batch; resume re-runs "
        "the SAME offsets)"),
    "stream.sink.commit": (
        "spark_rapids_tpu/streaming/sink.py",
        "after the sink's replay check, before the transactional "
        "commit (a crash here re-runs the batch; the txn watermark "
        "dedupes the replay)"),
    "exec.execute": (
        "spark_rapids_tpu/runtime/faults.py",
        "at each device exec's execute()/execute_masked() boundary "
        "(installed by install_fault_boundaries; carries op context)"),
    "shuffle.fetch.metadata": (
        "spark_rapids_tpu/shuffle/client_server.py",
        "client metadata round trip"),
    "shuffle.fetch.stream": (
        "spark_rapids_tpu/shuffle/client_server.py",
        "client block reassembly (corrupt applies to completed blocks)"),
    "shuffle.transport.request": (
        "spark_rapids_tpu/shuffle/transport.py",
        "transport request channel"),
    "shuffle.transport.stream": (
        "spark_rapids_tpu/shuffle/transport.py",
        "transport data-window stream (corrupt flips window bytes)"),
    "shuffle.read.partition": (
        "spark_rapids_tpu/shuffle/manager.py",
        "multithreaded manager per-map segment read"),
    "shuffle.write.map": (
        "spark_rapids_tpu/shuffle/manager.py",
        "multithreaded manager map-output write"),
    "io.read.file": (
        "spark_rapids_tpu/io/common.py",
        "file-source per-file decode"),
    "io.write.file": (
        "spark_rapids_tpu/io/writer.py",
        "writer per-file write (BOTH branches: single-file part-00000 "
        "and every dynamic-partition file), before the staged write"),
    "io.write.commit": (
        "spark_rapids_tpu/io/committer.py",
        "task commit, before each staged file's atomic promotion "
        "(os.replace into the final destination)"),
    "io.write.abort": (
        "spark_rapids_tpu/io/committer.py",
        "write-job abort, before the rollback + staging sweep (a crash "
        "here exercises the crash-handler/atexit sweep backstop)"),
    "delta.commit.race": (
        "spark_rapids_tpu/delta/log.py",
        "immediately before the atomic commit-file create; kind "
        "'race' injects a DeltaConcurrentModificationException so the "
        "optimistic rebase-and-retry loop is exercisable without a "
        "real concurrent writer, 'crash' dies mid-commit"),
    "service.worker_crash": (
        "spark_rapids_tpu/service/scheduler.py",
        "service worker runner, after the RUNNING transition and "
        "before the query executes — an exception here kills the "
        "WORKER (not the query), exercising respawn + requeue"),
    "device.lost": (
        "spark_rapids_tpu/dispatch.py",
        "before each jitted kernel dispatch; device_lost simulates a "
        "fatal PJRT client loss (health-monitor recovery path)"),
    "dispatch.wedge": (
        "spark_rapids_tpu/dispatch.py",
        "before each jitted kernel dispatch; wedge stalls INSIDE the "
        "dispatch so only the watchdog's hard wall limit can end it"),
    # -- the mesh fault domain: every stage of the distributed path is
    # injectable, and ``device_lost`` at any ``mesh.*`` point raises the
    # PARTIAL MeshDeviceLostError (one mesh device dead, backend alive)
    # that walks the degradation ladder instead of the whole-backend
    # reinit (runtime/health.py on_mesh_device_loss)
    "mesh.shard.put": (
        "spark_rapids_tpu/parallel/mesh.py",
        "per-shard device landing (jax.device_put under the row "
        "sharding): every mesh-native scan upload and exchange reshard "
        "passes through here, before the transfer"),
    "mesh.ici.exchange": (
        "spark_rapids_tpu/parallel/exchange.py",
        "the ICI all-to-all: a data-less site before each collective "
        "dispatch (crash/device_lost/slow) plus the checksummed "
        "per-partition live-count fetch after the exchange's last "
        "batch (corrupt flips the fetched "
        "bytes; the TPAK-v2 digest riding the same fetch catches the "
        "damage and the intact device value is refetched)"),
    "mesh.gather": (
        "spark_rapids_tpu/execs/mesh.py",
        "the MeshReland device-to-device gather (DeviceTable."
        "unsharded): corrupt damages the LANDED copy (sentinel-driven "
        "device bit-flip) and the row-count+checksum validation trips, "
        "re-landing from the still-sharded source instead of feeding a "
        "wide kernel silently wrong shards"),
    "mesh.dict.upload": (
        "spark_rapids_tpu/parallel/exchange.py",
        "replicated string-dictionary upload (interned_dict_bytes), "
        "before the device_put replication across the mesh"),
    # -- the HOST fault domain: every stage of the multi-host
    # driver/executor protocol is injectable, and ``device_lost`` at any
    # ``host.*`` point raises the typed HostLostError (a whole executor
    # PROCESS died, not a device) that walks the HOST degradation
    # ladder (runtime/health.py on_host_loss) instead of the mesh
    # ladder or a whole-backend reinit
    "host.dispatch": (
        "spark_rapids_tpu/runtime/cluster.py",
        "driver->executor scan dispatch, before the request round "
        "trip (ClusterDriver.scan_host): crash exercises the query-"
        "replay path, device_lost the host degradation ladder"),
    "host.shard.land": (
        "spark_rapids_tpu/runtime/cluster.py",
        "per host-shard landing of an executor's scan response "
        "(ClusterDriver.scan): corrupt damages the landed TPAK frame "
        "and the CRC catches it — the intact received frame re-lands "
        "(hostShardRetries) instead of feeding a scan garbage rows"),
    "host.dcn.exchange": (
        "spark_rapids_tpu/runtime/cluster.py",
        "before a shuffle collective whose mesh spans more than one "
        "cluster host group (the all-to-all crosses the DCN axis; "
        "dcn_exchange_point, called by the ICI exchange)"),
    "host.heartbeat": (
        "spark_rapids_tpu/runtime/cluster.py",
        "executor heartbeat receipt at the driver's ledger: an "
        "injected fault DROPS the beat (counted) — enough dropped "
        "beats and the missed-beat sweep declares the host lost, the "
        "exact path a wedged executor takes"),
    # -- the MEMORY fault domain: out-of-core execution under the hard
    # device budget (runtime/memory.py MemoryArbiter) is injectable at
    # every stage of the reserve->spill->unspill cycle
    "mem.reserve": (
        "spark_rapids_tpu/runtime/memory.py",
        "before the arbiter grants a device-landing reservation: 'oom' "
        "simulates a budget squeeze mid-query (RetryOOM into the "
        "retry framework: spill-replay, split-and-retry, then the "
        "memory degradation ladder)"),
    "mem.spill": (
        "spark_rapids_tpu/runtime/spill.py",
        "before a device->host spill demotion: 'crash' simulates a "
        "spill FAILURE (the demotion path itself dies — circuit-"
        "breaker/replay territory, the buffer stays device-resident)"),
    "mem.unspill": (
        "spark_rapids_tpu/runtime/spill.py",
        "at the disk-tier unspill read: 'corrupt' flips frame bytes "
        "and the TPAK-convention CRC footer catches it — typed "
        "SpillCorruptionError re-lands from the scan cache via query "
        "replay instead of serving wrong bytes"),
}

_SLOW_SLEEP_S = 0.05
#: how long a ``wedge`` fault stalls inside one dispatch — longer than
#: any sane spark.rapids.service.hardTimeoutMs test setting, short
#: enough that a seeded chaos run still terminates promptly
_WEDGE_SLEEP_S = 2.0


class _ArmedFault:
    """One armed '<point>[@<op>]:<kind>:<prob-or-count>[:<seed>]' entry."""

    __slots__ = ("point", "op", "kind", "prob", "remaining", "rng", "fired")

    def __init__(self, point: str, op: Optional[str], kind: str,
                 prob: Optional[float], count: Optional[int], seed: int):
        self.point = point
        self.op = op
        self.kind = kind
        self.prob = prob
        self.remaining = count
        self.rng = random.Random(seed)
        self.fired = 0

    def should_fire(self) -> bool:
        if self.remaining is not None:
            if self.remaining <= 0:
                return False
            self.remaining -= 1
            return True
        return self.rng.random() < (self.prob or 0.0)


def parse_fault_spec(spec: str) -> List[_ArmedFault]:
    """Parse the ``spark.rapids.test.faults`` value. Raises on unknown
    points/kinds so a typo'd chaos schedule fails loudly, not silently."""
    out: List[_ArmedFault] = []
    for i, entry in enumerate(e.strip() for e in spec.split(";")):
        if not entry:
            continue
        parts = entry.split(":")
        if len(parts) not in (3, 4):
            raise ColumnarProcessingError(
                f"bad fault spec entry {entry!r} (want "
                "<point>[@<op>]:<kind>:<prob-or-count>[:<seed>])")
        target, kind, amount = parts[0], parts[1].lower(), parts[2]
        point, _, op = target.partition("@")
        if point not in FAULT_POINTS:
            raise ColumnarProcessingError(
                f"unknown fault point {point!r} (known: "
                f"{', '.join(sorted(FAULT_POINTS))})")
        if kind not in FAULT_KINDS:
            raise ColumnarProcessingError(
                f"unknown fault kind {kind!r} (known: "
                f"{', '.join(FAULT_KINDS)})")
        prob = count = None
        if "." in amount:
            prob = float(amount)
            if not 0.0 < prob <= 1.0:
                raise ColumnarProcessingError(
                    f"fault probability {prob} outside (0, 1]")
        else:
            count = int(amount)
            if count < 1:
                raise ColumnarProcessingError(
                    f"fault count {count} must be >= 1")
        seed = int(parts[3]) if len(parts) == 4 else i
        out.append(_ArmedFault(point, op or None, kind, prob, count, seed))
    return out


class FaultRegistry:
    """Process-wide armed faults + per-point fire counters."""

    def __init__(self):
        self._lock = ordered_lock("faults.registry")
        self._armed: List[_ArmedFault] = []
        self._spec = ""
        self._counters: Dict[str, int] = {}

    def arm(self, spec: str) -> None:
        """(Re-)arm from a spec string. Re-arming the SAME spec is a no-op
        so per-query execute() calls don't reset seeded schedules or
        counters mid-session; a different spec replaces everything."""
        with self._lock:
            if spec == self._spec:
                return
            self._spec = spec
            self._armed = parse_fault_spec(spec) if spec else []
            self._counters = {}

    def disarm(self) -> None:
        with self._lock:
            self._spec = ""
            self._armed = []
            self._counters = {}

    @contextmanager
    def suspended(self):
        """Temporarily disarm WITHOUT losing the armed entries' RNG
        state or counters — for a fault-free interlude (e.g. the chaos
        harness re-collecting a baseline) inside a seeded run whose
        schedule must keep advancing, not reset."""
        with self._lock:
            saved = (self._spec, self._armed, self._counters)
            self._spec, self._armed, self._counters = "", [], {}
        try:
            yield
        finally:
            with self._lock:
                self._spec, self._armed, self._counters = saved

    @property
    def armed(self) -> bool:
        return bool(self._armed)

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def fire(self, point: str, op: Optional[str] = None, data=None):
        """Evaluate every armed entry matching ``point`` (and ``op`` when
        the entry carries an @op filter). Raises the matched kind's
        exception; ``corrupt`` instead returns a damaged copy of
        ``data``; ``slow`` sleeps. Returns ``data`` (possibly corrupted)
        so corruption-capable sites can write ``data = fault_point(...,
        data=data)``."""
        if not self._armed:
            return data
        with self._lock:
            hits = [a for a in self._armed
                    if a.point == point
                    and (a.op is None or a.op == op)
                    # corruption needs bytes to corrupt: a data-less call
                    # at the same point must not consume the schedule
                    and (a.kind != "corrupt" or data is not None)
                    and a.should_fire()]
            for a in hits:
                a.fired += 1
                key = a.point if a.op is None else f"{a.point}@{a.op}"
                self._counters[key] = self._counters.get(key, 0) + 1
        for a in hits:
            where = point if op is None else f"{point}[{op}]"
            if a.kind == "oom":
                raise RetryOOM(f"injected device OOM at {where}")
            if a.kind == "crash":
                # no fault_op here: attribution is the exec fault guards'
                # job (_tag_fault_op), so the breaker only ever counts
                # PLAN-NODE names — a crash injected at a helper exec or
                # kernel propagates to the nearest rule-rooted ancestor
                raise KernelCrashError(f"injected kernel crash at {where}")
            if a.kind == "fetch":
                raise ShuffleFetchError(f"injected fetch error at {where}")
            if a.kind == "disconnect":
                raise ShuffleTransportError(
                    f"injected transport disconnect at {where}")
            if a.kind == "device_lost":
                if point.startswith("host."):
                    # a whole executor PROCESS died (the backend and
                    # its devices are fine) — the HOST degradation
                    # ladder (runtime/health.py on_host_loss) owns
                    # recovery
                    from spark_rapids_tpu.errors import HostLostError
                    raise HostLostError(
                        f"injected host loss at {where}")
                if point.startswith("mesh."):
                    # PARTIAL loss: one mesh device died, the backend
                    # is otherwise alive — the degradation ladder
                    # (runtime/health.py) owns recovery, not the
                    # whole-backend reinit
                    from spark_rapids_tpu.errors import MeshDeviceLostError
                    raise MeshDeviceLostError(
                        f"injected mesh device loss at {where}")
                from spark_rapids_tpu.errors import DeviceLostError
                raise DeviceLostError(
                    f"injected device loss at {where}")
            if a.kind == "race":
                from spark_rapids_tpu.delta.log import (
                    DeltaConcurrentModificationException,
                )
                raise DeltaConcurrentModificationException(
                    f"injected optimistic-concurrency race at {where}")
            if a.kind == "wedge":
                import os
                time.sleep(float(os.environ.get("SRT_WEDGE_SLEEP_S",
                                                _WEDGE_SLEEP_S)))
            elif a.kind == "slow":
                time.sleep(_SLOW_SLEEP_S)
            elif a.kind == "corrupt" and data is not None and len(data):
                buf = bytearray(data)
                pos = a.rng.randrange(len(buf))
                buf[pos] ^= 0xFF
                data = bytes(buf)
        return data


FAULTS = FaultRegistry()


def fault_point(name: str, op: Optional[str] = None, data=None):
    """THE site marker for injectable faults. Every call names a point
    registered in :data:`FAULT_POINTS` (the RL-FAULT-POINT lint rule
    audits both directions). Disarmed cost is one attribute read."""
    if not FAULTS._armed:
        return data
    return FAULTS.fire(name, op=op, data=data)


# ---------------------------------------------------------------------------
# Recovery accounting
# ---------------------------------------------------------------------------


class RecoveryStats:
    """Process-wide counters for every recovery action the engine takes;
    chaos runs snapshot/diff these to report and bound recovery work.
    Backed by the unified metric registry's ``recovery`` scope
    (obs/metrics.py) so the event log reads the same numbers."""

    FIELDS = ("fetch_retries", "peer_exclusions", "recomputed_maps",
              "demotions", "query_replays")

    def __init__(self):
        from spark_rapids_tpu.obs.metrics import (
            metric_scope,
            register_metric,
        )
        self._lock = ordered_lock("faults.recovery")
        self._counts = metric_scope("recovery")
        for f in self.FIELDS:
            register_metric(f, "count", "ESSENTIAL",
                            f"recovery action counter ({f})")
            self._counts.setdefault(f, 0)

    def bump(self, field: str, n: int = 1) -> None:
        if field not in self._counts:
            raise KeyError(field)  # typo'd field, fail loud
        with self._lock:
            self._counts.add(field, n)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def reset(self) -> None:
        with self._lock:
            for f in self.FIELDS:
                self._counts[f] = 0


RECOVERY = RecoveryStats()


def backoff_retry(fn, *, max_retries: int, wait_s: float,
                  backoff_mult: float, retryable, on_failure=None):
    """THE exponential-backoff retry loop both shuffle read paths share
    (p2p peer fetches and the multithreaded manager's file reads —
    one policy, one accounting site). Each failure bumps
    RECOVERY.fetch_retries and calls ``on_failure(exc, attempt)``; a
    truthy return stops retrying immediately (e.g. a chronic-flakiness
    budget). On exhaustion the LAST exception re-raises — callers wrap
    it in MapOutputLostError with their own context."""
    attempt = 0
    wait = wait_s
    while True:
        try:
            return fn()
        except retryable as e:
            attempt += 1
            RECOVERY.bump("fetch_retries")
            stop = on_failure(e, attempt) if on_failure is not None else False
            if stop or attempt > max_retries:
                raise
            time.sleep(wait)
            wait *= backoff_mult


# ---------------------------------------------------------------------------
# Per-operator circuit breaker
# ---------------------------------------------------------------------------


class CircuitBreaker:
    """After N repeated non-OOM device failures of the same operator, the
    op is demoted to the CPU fallback path — PROCESS-WIDE, like the
    speculation blocklist: a kernel that crashes the shared device is
    broken for every session in this engine process, so all of them see
    the demotion until reset(). Keys are PLAN-NODE class names (the unit
    the overrides layer falls back at); the demotion reason feeds
    PlanMeta.reasons so explain() and the plan verifier's
    fallback-hygiene rule surface it."""

    def __init__(self):
        self._lock = ordered_lock("faults.breaker")
        self._failures: Dict[str, int] = {}
        self._reasons: Dict[str, str] = {}

    def record_failure(self, op: str, exc: BaseException,
                       max_failures: int) -> bool:
        """Count one failure of ``op``; returns True when this failure
        crossed the threshold and demoted the op."""
        first_line = str(exc).splitlines()[0] if str(exc) else type(exc).__name__
        with self._lock:
            if op in self._reasons:
                return False
            n = self._failures.get(op, 0) + 1
            self._failures[op] = n
            if n < max_failures:
                return False
            self._reasons[op] = (
                f"runtime circuit breaker: demoted to CPU after {n} device "
                f"failures (last: {type(exc).__name__}: {first_line})")
        RECOVERY.bump("demotions")
        return True

    def demotion_reason(self, op: str) -> Optional[str]:
        with self._lock:
            return self._reasons.get(op)

    def demoted_ops(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._reasons)

    def reset(self) -> None:
        with self._lock:
            self._failures = {}
            self._reasons = {}


CIRCUIT_BREAKER = CircuitBreaker()


# ---------------------------------------------------------------------------
# Exec fault boundaries (op attribution for crashes + the exec.execute
# injection point)
# ---------------------------------------------------------------------------


def _tag_fault_op(exc: BaseException, op: str) -> None:
    """Attach op attribution to a demotable failure. Innermost exec wins
    (the first wrapper the exception crosses sets it); RETRYABLE OOMs
    are excluded — the retry framework owns those. A FatalDeviceOOM
    (retries + splits exhausted) IS tagged: the memory degradation
    ladder's last rung demotes exactly that operator to the CPU path."""
    from spark_rapids_tpu.errors import FatalDeviceOOM
    from spark_rapids_tpu.runtime.crash_handler import is_fatal_device_error
    from spark_rapids_tpu.runtime.retry import is_device_oom
    if getattr(exc, "fault_op", None) is not None:
        return
    if is_device_oom(exc):
        return
    if (isinstance(exc, (KernelCrashError, FatalDeviceOOM))
            or is_fatal_device_error(exc)):
        exc.fault_op = op


def _guard(fn, op: str, tag: bool):
    def wrapped(*args, **kwargs):
        try:
            # inside the try: an injected crash at THIS exec's own
            # boundary gets tagged by this wrapper (the root exec has no
            # ancestor wrapper to do it)
            fault_point("exec.execute", op=op)
            for batch in fn(*args, **kwargs):
                yield batch
        except Exception as exc:
            if tag:
                _tag_fault_op(exc, op)
            raise
    return wrapped


def install_fault_boundaries(executable) -> None:
    """Wrap every device exec's execute()/execute_masked() in the
    converted tree with (a) the ``exec.execute`` fault point and (b)
    op attribution for non-OOM device failures, feeding the circuit
    breaker. Idempotent per exec instance (plans are re-executed)."""
    from spark_rapids_tpu.execs.base import TpuExec
    from spark_rapids_tpu.lore import _iter_tree
    for e in _iter_tree(executable):
        if not isinstance(e, TpuExec) or getattr(e, "_fault_guarded", False):
            continue
        e._fault_guarded = True
        # attribution unit: the PLAN-NODE class this exec was converted
        # from (set by overrides/rules._convert — the granularity the
        # overrides layer can fall back at). Helper execs a convert
        # function builds (coalesce wrappers etc.) carry no origin: they
        # fire the injection point under their own class name but leave
        # tagging to the nearest rule-rooted ancestor the exception
        # crosses, so the breaker only ever counts demotable names.
        origin = getattr(e, "_plan_origin", None)
        op = origin or type(e).__name__
        e.execute = _guard(e.execute, op, tag=origin is not None)
        e.execute_masked = _guard(e.execute_masked, op,
                                  tag=origin is not None)
