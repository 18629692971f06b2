"""Lock registry + rank hierarchy + opt-in runtime lock witness.

PRs 7, 13 and 14 each shipped a hand-diagnosed deadlock fix (the
BufferCatalog ``_instance_lock`` self-deadlock, the quarantine-strike
recording self-deadlock on the scheduler's condition, the
SpillableBatch<->arbiter ABBA cycle) — every one found only AFTER the
bug was written, because the ordering contract between the runtime's
~45 locks lived in tribal knowledge and CHANGES.md prose.  This module
makes the contract a machine-checked artifact:

* :data:`LOCK_ORDER` — THE single ordered hierarchy.  Every
  ``threading.Lock/RLock/Condition/Semaphore`` constructed in the
  concurrent packages (``runtime/``, ``service/``, ``parallel/``,
  ``obs/``, ``io/``, ``columnar/``, ``streaming/``) must be declared
  here with a NAME, a RANK and its construction SITE, and must be
  constructed through the :func:`ordered_lock` family so the
  declaration can never drift from the object it describes
  (lint rule RL-LOCK-DECL audits both directions).

* **The ordering contract**: a thread that blocking-acquires lock B
  while holding lock A must have ``rank(A) < rank(B)`` — acquisition
  order strictly ascends the hierarchy.  Non-blocking acquires
  (``acquire(blocking=False)``) are exempt: a try-acquire can never
  deadlock, and the spill/arbiter paths use exactly that escape (the
  PR-14 ABBA fix).  The static half (``lint/concurrency.py``,
  RL-LOCK-ORDER) builds the held->acquired edge graph over a bounded
  call graph; the runtime half is the WITNESS below.

* **Lock witness** (``spark.rapids.lint.lockWitness``, default off):
  when armed, the factories return thin instrumented wrappers that
  record per-thread acquisition sequences and raise typed
  :class:`LockOrderViolation` on any rank inversion — or on a
  blocking re-acquire of a non-reentrant lock this thread already
  holds (the self-deadlock class) — cross-validating the declared
  hierarchy against real executions where the static pass's bounded
  call graph cannot see (dynamic dispatch, callbacks).  Arming is a
  CONSTRUCTION-TIME election: locks built while the witness is armed
  are instrumented, locks built before stay raw — so the disarmed
  production process pays zero overhead on every hot-path acquire.
  The chaos tier arms it, then constructs the service/arbiter objects
  under test.

``LOCKS.md`` is generated from this registry (``python -m
spark_rapids_tpu.lint --write-docs``) and drift-checked by the lint.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu.conf import bool_conf

LOCK_WITNESS = bool_conf(
    "spark.rapids.lint.lockWitness", False,
    "Arm the runtime lock witness: locks constructed through the "
    "lockorder.py factories while armed are wrapped so every "
    "blocking acquisition is checked against the declared LOCK_ORDER "
    "rank hierarchy, raising typed LockOrderViolation on an inversion "
    "the static RL-LOCK-ORDER pass's bounded call graph missed. "
    "Construction-time election (locks built before arming stay raw); "
    "off by default — enabled under the tier-1 chaos tests.")


class LockOrderViolation(RuntimeError):
    """A thread blocking-acquired a declared lock out of rank order
    (or re-acquired a non-reentrant lock it already holds).  Raised by
    the armed witness INSTEAD of deadlocking; carries the held chain
    so the inversion is diagnosable from the message alone."""


class LockDeclError(RuntimeError):
    """A lock factory was called with an undeclared name, or the
    declared kind does not match the requested primitive."""


@dataclass(frozen=True)
class LockDecl:
    """One declared lock: its place in the single total order.

    ``site`` is ``<repo-relative module>:<qualified attribute>`` — the
    one construction site RL-LOCK-DECL pins the declaration to
    (``Class._attr`` for instance/class locks, the bare global name
    for module-level locks).  ``guards`` documents the state the lock
    protects (LOCKS.md column)."""

    name: str
    rank: int
    site: str
    kind: str  # Lock | RLock | Condition | Semaphore
    guards: str

    @property
    def module(self) -> str:
        return self.site.rsplit(":", 1)[0]

    @property
    def attr(self) -> str:
        """The attribute basename at the construction site."""
        return self.site.rsplit(":", 1)[1].rsplit(".", 1)[-1]


#: THE ordered lock hierarchy.  Ranks ascend from orchestrators (held
#: longest, acquired first) down to leaf bookkeeping locks (held for a
#: dict update, acquired under everything).  Bands of 100 group the
#: layers; gaps leave room to insert without renumbering.  A thread
#: holding rank R may blocking-acquire ranks > R only.
_DECLS: Tuple[LockDecl, ...] = (
    # -- streaming drivers (outermost: they submit queries + commits) --
    LockDecl("streaming.query", 100,
             "spark_rapids_tpu/streaming/query.py:StreamingQuery._lock",
             "Lock", "stream lifecycle: status, trigger thread, last "
                     "batch/offset bookkeeping"),
    LockDecl("streaming.mv.registry", 110,
             "spark_rapids_tpu/streaming/mv.py:"
             "MaterializedViewRegistry._lock",
             "Lock", "registered views + per-table staleness marks"),
    LockDecl("streaming.mv.refresh", 120,
             "spark_rapids_tpu/streaming/mv.py:"
             "MaterializedView._refresh_lock",
             "Lock", "one refresh (incremental or full recompute) at a "
                     "time per view"),
    LockDecl("service.mesh_gate", 150,
             "spark_rapids_tpu/service/scheduler.py:"
             "QueryService._mesh_gate",
             "Lock", "exclusive mesh occupancy: one multi-device "
                     "computation launch at a time when the service "
                     "drives a mesh/cluster topology (two concurrent "
                     "launches interleave their collective rendezvous "
                     "per-device and deadlock); single-chip services "
                     "never construct it. Ranks BELOW the service band "
                     "because it is held across the whole launch "
                     "window, inside which ladder incident capture "
                     "legitimately reads scheduler/handle state"),
    # -- query service -------------------------------------------------
    LockDecl("service.scheduler.cond", 200,
             "spark_rapids_tpu/service/scheduler.py:QueryService._cond",
             "Condition", "queues, WFQ clocks, worker pool, lifecycle "
                          "counters, SLO window, degradation latch — "
                          "ALL scheduler state"),
    LockDecl("service.scheduler.streams", 210,
             "spark_rapids_tpu/service/scheduler.py:"
             "QueryService._streams_lock",
             "Lock", "registered streaming tenants (name -> stream)"),
    LockDecl("service.handle", 220,
             "spark_rapids_tpu/service/query.py:QueryHandle._lock",
             "Lock", "per-handle state machine + result/error slot "
                     "(the watchdog's _cond -> handle order is the "
                     "canonical ranked pair)"),
    LockDecl("service.handle.seq", 230,
             "spark_rapids_tpu/service/query.py:QueryHandle._seq_lock",
             "Lock", "process-wide query id sequence"),
    LockDecl("service.result_cache", 240,
             "spark_rapids_tpu/service/result_cache.py:ResultCache._lock",
             "Lock", "fingerprint -> cached result entries + byte "
                     "accounting"),
    # -- cluster runtime ----------------------------------------------
    LockDecl("cluster.runtime", 300,
             "spark_rapids_tpu/runtime/cluster.py:ClusterRuntime._lock",
             "Lock", "host topology: declared/live/lost/excluded hosts, "
                     "generation"),
    LockDecl("cluster.driver", 310,
             "spark_rapids_tpu/runtime/cluster.py:ClusterDriver._lock",
             "Lock", "executor registry, beat ledger, data channels"),
    LockDecl("cluster.channel", 320,
             "spark_rapids_tpu/runtime/cluster.py:_HostChannel.lock",
             "Lock", "one in-flight wire request per host data channel "
                     "(socket send/recv serialized under it BY DESIGN — "
                     "allowlisted in the effect lint)"),
    # -- health / recovery --------------------------------------------
    LockDecl("health.monitor", 400,
             "spark_rapids_tpu/runtime/health.py:DeviceHealthMonitor._lock",
             "Lock", "loss streaks, reinit/ladder slot reservation, "
                     "backend generation"),
    LockDecl("health.quarantine", 410,
             "spark_rapids_tpu/runtime/health.py:QuarantineRegistry._lock",
             "Lock", "per-template strike history + quarantine set"),
    LockDecl("memory.retry_handler", 420,
             "spark_rapids_tpu/runtime/retry.py:"
             "DeviceMemoryEventHandler._lock",
             "Lock", "OOM-retry state: spill attempt counters per "
                     "allocation failure"),
    # -- device managers ----------------------------------------------
    LockDecl("device.manager.instance", 500,
             "spark_rapids_tpu/runtime/device_manager.py:"
             "TpuDeviceManager._instance_lock",
             "Lock", "singleton construction of the device manager"),
    LockDecl("semaphore.instance", 510,
             "spark_rapids_tpu/runtime/semaphore.py:"
             "TpuSemaphore._instance_lock",
             "Lock", "singleton construction / live resize of the task "
                     "semaphore"),
    LockDecl("semaphore.cond", 520,
             "spark_rapids_tpu/runtime/semaphore.py:TpuSemaphore._lock",
             "Condition", "device concurrency slots: holder map + "
                          "waiter wakeups"),
    LockDecl("mesh.runtime", 530,
             "spark_rapids_tpu/parallel/mesh.py:MeshRuntime._lock",
             "Lock", "mesh topology config, generation, identity token"),
    LockDecl("mesh.dict_intern", 540,
             "spark_rapids_tpu/parallel/exchange.py:_DICT_INTERN_LOCK",
             "Lock", "replicated-dictionary intern table + exchange "
                     "program cache (epoch-guarded late-publish rejection)"),
    LockDecl("profiler", 550,
             "spark_rapids_tpu/runtime/profiler.py:TpuProfiler._lock",
             "Lock", "profiler session state + sample buffers"),
    # -- host memory ---------------------------------------------------
    LockDecl("host_alloc.instance", 600,
             "spark_rapids_tpu/runtime/host_alloc.py:"
             "HostMemoryArbiter._instance_lock",
             "Lock", "singleton construction of the host arbiter"),
    LockDecl("host_alloc.cv", 610,
             "spark_rapids_tpu/runtime/host_alloc.py:HostMemoryArbiter._cv",
             "Condition", "host memory budget waits/wakeups"),
    LockDecl("pinned_pool.instance", 620,
             "spark_rapids_tpu/runtime/host_alloc.py:"
             "PinnedMemoryPool._instance_lock",
             "Lock", "singleton construction of the pinned pool"),
    LockDecl("pinned_pool", 630,
             "spark_rapids_tpu/runtime/host_alloc.py:PinnedMemoryPool._lock",
             "Lock", "pinned-buffer freelist"),
    # -- device memory / spill ----------------------------------------
    LockDecl("spill.batch", 710,
             "spark_rapids_tpu/runtime/spill.py:SpillableBatch._lock",
             "RLock", "one batch's tier payloads + pin count.  BELOW "
                      "the catalog and arbiter: get()/spill hold it "
                      "while registering bytes; the reverse direction "
                      "(catalog spill walk -> batch) is non-blocking "
                      "by contract (the PR-14 ABBA fix)"),
    LockDecl("spill.catalog", 720,
             "spark_rapids_tpu/runtime/spill.py:BufferCatalog._lock",
             "RLock", "spillable registry, disk-file tracking, spill "
                      "counters"),
    LockDecl("spill.catalog.instance", 725,
             "spark_rapids_tpu/runtime/spill.py:"
             "BufferCatalog._instance_lock",
             "Lock", "singleton construction/reset of the catalog.  "
                     "ABOVE spill.batch: a batch unspill's device "
                     "landing accounts through the arbiter, whose "
                     "spill pass reaches BufferCatalog.get() with the "
                     "batch RLock still held.  __init__ must NOT "
                     "re-take it (the PR-7 self-deadlock)"),
    LockDecl("spill.catalog.registry", 730,
             "spark_rapids_tpu/runtime/spill.py:"
             "BufferCatalog._all_catalogs_lock",
             "Lock", "weak set of every catalog (atexit sweep)"),
    LockDecl("memory.arbiter", 740,
             "spark_rapids_tpu/runtime/memory.py:MemoryArbiter._lock",
             "Lock", "device budget ledger: reservations, per-table "
                     "bytes, peak.  Never held across a spill pass "
                     "(_spill_for runs outside it)"),
    # -- io ------------------------------------------------------------
    LockDecl("io.committer.jobs", 800,
             "spark_rapids_tpu/io/committer.py:_ACTIVE_LOCK",
             "Lock", "process-wide in-flight WriteJob registry (crash "
                     "sweep reads it)"),
    LockDecl("io.filecache", 810,
             "spark_rapids_tpu/io/filecache.py:_FileCache._lock",
             "Lock", "scan file-cache entries + byte accounting"),
    # -- fault injection / speculation (taken deep inside anything) ----
    LockDecl("faults.registry", 900,
             "spark_rapids_tpu/runtime/faults.py:FaultRegistry._lock",
             "Lock", "armed fault schedule + fire counters (fault_point "
                     "runs under locks across the engine, so this must "
                     "rank ABOVE every subsystem lock — acquired "
                     "last)"),
    LockDecl("faults.recovery", 910,
             "spark_rapids_tpu/runtime/faults.py:RecoveryStats._lock",
             "Lock", "recovery action counters"),
    LockDecl("faults.breaker", 920,
             "spark_rapids_tpu/runtime/faults.py:CircuitBreaker._lock",
             "Lock", "per-op failure counts + demotion reasons"),
    LockDecl("speculation.blocklist", 930,
             "spark_rapids_tpu/runtime/speculation.py:_BLOCKLIST_LOCK",
             "Lock", "process-wide speculation blocklist"),
    # -- observability (leaf: every layer records into these) ----------
    LockDecl("obs.events.writer", 1000,
             "spark_rapids_tpu/obs/events.py:QueryEventWriter._lock",
             "Lock", "event-log file append + record sequence"),
    LockDecl("obs.events.recent", 1010,
             "spark_rapids_tpu/obs/events.py:_RECENT_LOCK",
             "Lock", "bounded recent-record ring (flight-recorder "
                     "summaries)"),
    LockDecl("obs.spans", 1020,
             "spark_rapids_tpu/obs/spans.py:SpanTracer._lock",
             "Lock", "span buffer + lane bookkeeping"),
    LockDecl("obs.telemetry.services", 1030,
             "spark_rapids_tpu/obs/telemetry.py:_SERVICES_LOCK",
             "Lock", "weak registry of live query services"),
    LockDecl("obs.telemetry.ring", 1040,
             "spark_rapids_tpu/obs/telemetry.py:TelemetryRing._lock",
             "Lock", "sampler config + bounded sample ring"),
    LockDecl("obs.flightrec", 1050,
             "spark_rapids_tpu/obs/telemetry.py:_FR_LOCK",
             "Lock", "incident bundle sequence + prune bookkeeping "
                     "(recording reads live surfaces only through "
                     "non-blocking/snapshot APIs)"),
    LockDecl("obs.metrics.spec", 1060,
             "spark_rapids_tpu/obs/metrics.py:_SPEC_LOCK",
             "Lock", "metric spec registry"),
    LockDecl("obs.metrics.scopes", 1070,
             "spark_rapids_tpu/obs/metrics.py:_SCOPE_LOCK",
             "Lock", "scope-name -> LockedMetricSet registry"),
    LockDecl("obs.metrics.scope", 1080,
             "spark_rapids_tpu/obs/metrics.py:LockedMetricSet._lock",
             "Lock", "one metric scope's counters — THE leaf lock: "
                     "metric adds happen under everything above"),
)

#: name -> declaration (THE registry; insertion order == rank order)
LOCK_ORDER: Dict[str, LockDecl] = {d.name: d for d in _DECLS}


def _validate_registry() -> None:
    ranks: Dict[int, str] = {}
    sites: Dict[str, str] = {}
    prev = None
    for d in _DECLS:
        if d.rank in ranks:
            raise LockDeclError(
                f"locks {ranks[d.rank]!r} and {d.name!r} share rank "
                f"{d.rank} — the hierarchy must be a total order")
        if d.site in sites:
            raise LockDeclError(
                f"locks {sites[d.site]!r} and {d.name!r} share site "
                f"{d.site}")
        if prev is not None and d.rank <= prev:
            raise LockDeclError(
                f"LOCK_ORDER entries out of rank order at {d.name!r}")
        ranks[d.rank] = d.name
        sites[d.site] = d.name
        prev = d.rank
    if len(LOCK_ORDER) != len(_DECLS):
        raise LockDeclError("duplicate lock name in LOCK_ORDER")


_validate_registry()


# ---------------------------------------------------------------------------
# runtime witness
# ---------------------------------------------------------------------------

#: construction-time election flag (see module docstring).  Reads are
#: a plain attribute load; writes happen in arm/disarm only.
_WITNESS_ARMED = False

#: process-monotonic count of witness violations DETECTED (each one also
#: raises LockOrderViolation at the acquire site).  Chaos closures record
#: the delta in-band — a committed artifact carries
#: ``lockWitnessViolations: 0`` as evidence, not as a vibe.
_WITNESS_VIOLATIONS = [0]
_WITNESS_VIOLATIONS_LOCK = threading.Lock()

_held_local = threading.local()


def _held() -> List[Tuple[int, LockDecl, bool]]:
    """This thread's live acquisitions: (lock object id, decl,
    underlying-is-reentrant)."""
    stack = getattr(_held_local, "stack", None)
    if stack is None:
        stack = _held_local.stack = []
    return stack


def arm_witness() -> None:
    """Arm the witness for locks constructed FROM NOW ON."""
    global _WITNESS_ARMED
    _WITNESS_ARMED = True


def disarm_witness() -> None:
    global _WITNESS_ARMED
    _WITNESS_ARMED = False


def witness_armed() -> bool:
    return _WITNESS_ARMED


def configure(conf) -> None:
    """Arm/disarm from conf (arm()-cheap; the session and the query
    service both call it before constructing their lock-owning
    objects, so a conf-armed witness covers every per-instance lock
    those builds create)."""
    if bool(conf.get_entry(LOCK_WITNESS)):
        arm_witness()
    else:
        disarm_witness()


def held_snapshot() -> List[str]:
    """Names of the declared locks THIS thread currently holds (test
    and diagnostic surface)."""
    return [d.name for _oid, d, _r in _held()]


def witness_violations() -> int:
    """Process-monotonic count of detected lock-order violations.
    Closures sample it before/after and assert the delta is zero."""
    with _WITNESS_VIOLATIONS_LOCK:
        return _WITNESS_VIOLATIONS[0]


def reset_witness_violations() -> None:
    """Test hook: zero the counter and drop the evidence records. A
    test that PROVOKES violations on purpose must reset afterwards or
    every later in-process closure reads its deliberate inversions as
    real ones."""
    with _WITNESS_VIOLATIONS_LOCK:
        _WITNESS_VIOLATIONS[0] = 0
        _WITNESS_RECORDS.clear()


#: evidence for the counter: the first N violations' (lock, held
#: chain, acquiring call site) — a raised LockOrderViolation often
#: lands in a best-effort except (telemetry, flight recorder) and
#: vanishes, so the count alone is undebuggable
_WITNESS_RECORDS: List[dict] = []
_WITNESS_RECORDS_MAX = 20


def witness_violation_records() -> List[dict]:
    """The recorded evidence behind :func:`witness_violations` (first
    ``_WITNESS_RECORDS_MAX`` only) — what a failing closure dumps."""
    with _WITNESS_VIOLATIONS_LOCK:
        return [dict(r) for r in _WITNESS_RECORDS]


def _count_violation(lock_name: str, chain: str) -> None:
    import traceback
    site = "".join(traceback.format_stack(limit=8)[:-2])
    with _WITNESS_VIOLATIONS_LOCK:
        _WITNESS_VIOLATIONS[0] += 1
        if len(_WITNESS_RECORDS) < _WITNESS_RECORDS_MAX:
            _WITNESS_RECORDS.append(
                {"lock": lock_name, "heldChain": chain, "site": site})


def _check_blocking_acquire(decl: LockDecl, oid: int,
                            reentrant: bool) -> None:
    for hoid, hdecl, hreent in _held():
        if hoid == oid:
            if reentrant:
                continue
            _count_violation(decl.name, decl.name)
            raise LockOrderViolation(
                f"witness: thread re-acquiring non-reentrant lock "
                f"{decl.name!r} (rank {decl.rank}) it already holds — "
                "guaranteed self-deadlock")
        if hdecl.rank >= decl.rank:
            chain = " -> ".join(
                f"{d.name}({d.rank})" for _o, d, _r in _held())
            _count_violation(decl.name, chain)
            raise LockOrderViolation(
                f"witness: blocking acquire of {decl.name!r} (rank "
                f"{decl.rank}) while holding {hdecl.name!r} (rank "
                f"{hdecl.rank}) inverts the declared order; held "
                f"chain: {chain}.  Either acquire in ascending rank, "
                "use acquire(blocking=False), or fix LOCK_ORDER")


def _note_acquired(decl: LockDecl, oid: int, reentrant: bool) -> None:
    _held().append((oid, decl, reentrant))


def _note_released(oid: int) -> None:
    stack = _held()
    for i in range(len(stack) - 1, -1, -1):
        if stack[i][0] == oid:
            del stack[i]
            return


class _WitnessedLock:
    """Rank-checking proxy over one threading primitive.  Only exists
    while the witness is armed at construction; delegates everything
    after bookkeeping, so lock SEMANTICS are unchanged — the witness
    raises instead of deadlocking, never the reverse."""

    _reentrant = False

    def __init__(self, inner, decl: LockDecl):
        self._inner = inner
        self._decl = decl

    def acquire(self, blocking: bool = True, timeout: float = -1):
        oid = id(self)
        if blocking:
            _check_blocking_acquire(self._decl, oid, self._reentrant)
            got = (self._inner.acquire(timeout=timeout)
                   if timeout is not None and timeout >= 0
                   else self._inner.acquire())
        else:
            got = self._inner.acquire(blocking=False)
        if got:
            _note_acquired(self._decl, oid, self._reentrant)
        return got

    def release(self):
        self._inner.release()
        _note_released(id(self))

    def locked(self):
        return self._inner.locked()

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def __repr__(self):
        return (f"<witnessed {self._decl.kind} {self._decl.name!r} "
                f"rank={self._decl.rank}>")


class _WitnessedRLock(_WitnessedLock):
    _reentrant = True


class _WitnessedSemaphore(_WitnessedLock):
    # a semaphore with multiple permits can be "re-acquired" by one
    # thread legitimately; the rank check still applies against OTHER
    # held locks
    _reentrant = True

    def locked(self):  # semaphores have no locked()
        raise AttributeError("locked")


class _WitnessedCondition(_WitnessedLock):
    # threading.Condition's default lock is an RLock
    _reentrant = True

    def wait(self, timeout: Optional[float] = None):
        # wait() RELEASES the condition lock for its duration: the
        # witness must not count it as held, or a wakeup path that
        # correctly re-acquires in rank order would be flagged
        oid = id(self)
        stack = _held()
        depth = sum(1 for e in stack if e[0] == oid)
        for _ in range(depth):
            _note_released(oid)
        try:
            return self._inner.wait(timeout)
        finally:
            for _ in range(depth):
                _note_acquired(self._decl, oid, self._reentrant)

    def wait_for(self, predicate, timeout: Optional[float] = None):
        oid = id(self)
        stack = _held()
        depth = sum(1 for e in stack if e[0] == oid)
        for _ in range(depth):
            _note_released(oid)
        try:
            return self._inner.wait_for(predicate, timeout)
        finally:
            for _ in range(depth):
                _note_acquired(self._decl, oid, self._reentrant)

    def notify(self, n: int = 1):
        return self._inner.notify(n)

    def notify_all(self):
        return self._inner.notify_all()


def _resolve(name: str, kind: str) -> LockDecl:
    decl = LOCK_ORDER.get(name)
    if decl is None:
        raise LockDeclError(
            f"lock {name!r} is not declared in "
            "lockorder.LOCK_ORDER — add a LockDecl with a rank "
            "and the construction site (RL-LOCK-DECL)")
    if decl.kind != kind:
        raise LockDeclError(
            f"lock {name!r} declared as {decl.kind} but constructed as "
            f"{kind}")
    return decl


def ordered_lock(name: str) -> threading.Lock:
    """A declared, rank-ordered ``threading.Lock`` (witnessed when the
    witness is armed at construction time)."""
    decl = _resolve(name, "Lock")
    inner = threading.Lock()
    return _WitnessedLock(inner, decl) if _WITNESS_ARMED else inner


def ordered_rlock(name: str) -> threading.RLock:
    decl = _resolve(name, "RLock")
    inner = threading.RLock()
    return _WitnessedRLock(inner, decl) if _WITNESS_ARMED else inner


def ordered_condition(name: str) -> threading.Condition:
    decl = _resolve(name, "Condition")
    inner = threading.Condition()
    return _WitnessedCondition(inner, decl) if _WITNESS_ARMED else inner


def ordered_semaphore(name: str, value: int = 1) -> threading.Semaphore:
    decl = _resolve(name, "Semaphore")
    inner = threading.Semaphore(value)
    return _WitnessedSemaphore(inner, decl) if _WITNESS_ARMED else inner


# ---------------------------------------------------------------------------
# LOCKS.md generator
# ---------------------------------------------------------------------------


def generate_locks_md() -> str:
    """The committed LOCKS.md: the hierarchy as a reviewable table
    (CONFIGS.md convention — regenerated by ``--write-docs``,
    drift-checked by RA-DOC-DRIFT-LOCKS)."""
    lines = [
        "# Lock order registry",
        "",
        "Generated from `spark_rapids_tpu/lockorder.py` "
        "(`python -m spark_rapids_tpu.lint --write-docs`). "
        "Do not edit by hand.",
        "",
        "The concurrency contract: a thread blocking-acquires locks in "
        "strictly ASCENDING rank only; non-blocking "
        "(`acquire(blocking=False)`) try-acquires are exempt (they "
        "cannot deadlock). `lint/concurrency.py` enforces the contract "
        "statically (RL-LOCK-DECL / RL-LOCK-ORDER / RL-LOCK-EFFECT); "
        "the runtime lock witness (`spark.rapids.lint.lockWitness`) "
        "cross-validates it under the chaos tiers.",
        "",
        "| Rank | Name | Kind | Owning module | Guarded state |",
        "|---:|---|---|---|---|",
    ]
    for d in _DECLS:
        site = d.site.replace("spark_rapids_tpu/", "")
        lines.append(
            f"| {d.rank} | `{d.name}` | {d.kind} | `{site}` | "
            f"{d.guards} |")
    lines.append("")
    return "\n".join(lines)
