"""Device memory arbiter: out-of-core execution under a hard HBM budget.

Reference (SURVEY.md §2.5): the reference enforces its device budget at
the allocator — RMM's pool is sized to ``spark.rapids.memory.gpu.
allocFraction`` and an allocation past it triggers
``DeviceMemoryEventHandler`` spills, then the RmmSpark OOM state machine
(RetryOOM / SplitAndRetryOOM). On TPU XLA owns the real allocator, so
budget enforcement moves UP a layer: this module is the engine-side
ledger that accounts every device LANDING (``DeviceTable.from_host``)
against a hard conf-driven byte budget (default: the backend-reported
HBM limit), synchronously spills idle BufferCatalog entries when a
reservation would exceed it, and raises :class:`RetryOOM` into the
existing retry framework when spilling cannot make room — which is how
ROADMAP item 2's "query whose working set exceeds HBM" survives instead
of dying at the first oversized batch:

* **reserve → land → account**: a landing reserves its ESTIMATED device
  bytes first (``mem.reserve`` fault point — the budget-squeeze
  injection site), spilling idle spillables / evicting cached scan
  images when the reservation would cross the budget; the landed table
  is then accounted at its ACTUAL device bytes for as long as the
  object lives (weakref-finalized — a spilled or dropped table releases
  its bytes the moment the last reference goes).
* **chunked scans**: :func:`scan_chunks` bounds one scan batch to
  ``spark.rapids.memory.device.scanChunkFraction`` of the budget —
  a host batch that would exceed its budget share lands as several
  bounded partitions instead of one resident table (the out-of-core
  scan half of ROADMAP item 2). The memory degradation ladder
  (runtime/health.py ``on_memory_pressure``) can force a smaller chunk
  target for a whole replay attempt via :func:`forced_chunking`.
* **zero-violation contract**: accounting an actual landing that still
  exceeds the budget after a synchronous spill pass counts a
  ``budgetViolations`` — the chaos closure (scale_test.py
  ``--device-budget``) asserts it stays 0.

Counters live in the unified registry's ``memory`` scope so the event
log (schema v10) diffs them per query like spill/recovery/mesh.
"""

from __future__ import annotations

import contextlib
import contextvars
import threading
import weakref
from typing import Dict, Optional

from spark_rapids_tpu.conf import float_conf, int_conf
from spark_rapids_tpu.errors import RetryOOM
from spark_rapids_tpu.obs.metrics import metric_scope, register_metric
from spark_rapids_tpu.lockorder import ordered_lock

DEVICE_BUDGET_BYTES = int_conf(
    "spark.rapids.memory.device.budgetBytes", 0,
    "Hard device-memory budget the memory arbiter (runtime/memory.py) "
    "enforces on every device landing: a reservation that would exceed "
    "it synchronously spills idle BufferCatalog entries and, when "
    "spilling cannot make room, raises RetryOOM into the retry "
    "framework (spill-replay, then split-and-retry, then the memory "
    "degradation ladder: chunked re-execution and per-op CPU "
    "demotion). 0 = the backend-reported HBM limit "
    "(spark.rapids.memory.gpu.allocFraction applied), overridable for "
    "tests and out-of-core scale runs.", commonly_used=True)

DEVICE_SCAN_CHUNK_FRACTION = float_conf(
    "spark.rapids.memory.device.scanChunkFraction", 0.25,
    "Largest share of the device budget one scan batch may occupy: a "
    "host batch whose estimated device bytes exceed "
    "budgetBytes * fraction lands as several bounded partitions "
    "(chunked out-of-core scan) instead of one resident table. The "
    "memory degradation ladder halves the effective chunk target when "
    "it replays a query under the 'chunk' rung.")

register_metric("oomRetries", "count", "ESSENTIAL",
                "spill-and-replay retries the OOM retry framework "
                "performed (RetryOOM survived — injected or real)")
register_metric("splitRetries", "count", "ESSENTIAL",
                "split-and-retry escalations: an input batch halved by "
                "rows and both halves replayed after same-size retries "
                "stopped helping")
register_metric("spillBytes", "bytes", "ESSENTIAL",
                "device bytes freed by spill demotions (the memory "
                "scope's mirror of the spill scope's device counter — "
                "the out-of-core work a budgeted query paid)")
register_metric("unspills", "count", "ESSENTIAL",
                "spilled batches brought back to the device "
                "(host or disk tier re-landed)")
register_metric("spillCorruptions", "count", "ESSENTIAL",
                "disk-tier spill frames whose CRC footer failed on "
                "unspill — caught and re-landed from the scan cache "
                "via query replay instead of serving wrong bytes")
register_metric("scanChunks", "count", "MODERATE",
                "bounded partitions chunked scans landed in place of "
                "over-budget single batches")
register_metric("arbiterSpills", "count", "MODERATE",
                "synchronous spill passes the memory arbiter ran to "
                "fit a reservation under the device budget")
register_metric("budgetRaises", "count", "MODERATE",
                "reservations the arbiter refused with RetryOOM after "
                "spilling could not make room")
register_metric("budgetViolations", "count", "ESSENTIAL",
                "actual landings that exceeded the device budget even "
                "after a synchronous spill pass (the chaos closure "
                "asserts this stays 0)")

#: the process-wide ``memory`` scope (shared with retry.py's
#: oomRetries/splitRetries bumps and spill.py's spillBytes mirror)
MEM_SCOPE = metric_scope("memory")

#: per-attempt chunk-target override installed by the memory
#: degradation ladder's 'chunk' rung (runtime/health.py) — like
#: parallel.mesh.suppressed_mesh, per-THREAD so concurrent service
#: workers replay independently
_FORCED_CHUNK_BYTES: contextvars.ContextVar[Optional[int]] = \
    contextvars.ContextVar("rapids_forced_chunk_bytes", default=None)


@contextlib.contextmanager
def forced_chunking(nbytes: int):
    """Force every scan in this thread/attempt to chunk its batches to
    at most ``nbytes`` of estimated device memory — the ladder's
    chunked re-execution rung."""
    token = _FORCED_CHUNK_BYTES.set(max(1, int(nbytes)))
    try:
        yield
    finally:
        _FORCED_CHUNK_BYTES.reset(token)


def forced_chunk_bytes() -> Optional[int]:
    return _FORCED_CHUNK_BYTES.get()


#: approximate per-row DEVICE bytes by logical type (data word +
#: validity byte): strings land as i32 dictionary codes, decimal128 as
#: two i64 limbs, small ints natively. Estimation only — the ledger
#: re-accounts the ACTUAL device bytes after the landing.
def _device_row_bytes(dtype) -> int:
    from spark_rapids_tpu import types as T
    if isinstance(dtype, T.StringType):
        return 4 + 1
    if isinstance(dtype, T.DecimalType) and dtype.precision > 18:
        return 16 + 1
    if isinstance(dtype, (T.ByteType, T.BooleanType)):
        return 1 + 1
    if isinstance(dtype, T.ShortType):
        return 2 + 1
    if isinstance(dtype, (T.IntegerType, T.FloatType, T.DateType)):
        return 4 + 1
    # LONG / DOUBLE / TIMESTAMP / small decimals / unknown: 8B words
    return 8 + 1


def estimate_device_nbytes(host, capacity: Optional[int] = None) -> int:
    """Estimated device bytes a HostTable lands as (padded to its
    capacity bucket)."""
    if not host.columns:
        return 0
    if capacity is None:
        from spark_rapids_tpu.columnar.column import bucket_for
        capacity = bucket_for(max(host.num_rows, 1))
    return sum(_device_row_bytes(c.dtype) for c in host.columns) * capacity


class MemoryReservation:
    """Short-lived grant covering one landing: ``MEMORY.account(table,
    reservation)`` converts it into ledger bytes; ``release()`` returns
    the estimate (upload failed). Usable as a context manager."""

    __slots__ = ("arbiter", "nbytes", "_done")

    def __init__(self, arbiter: "MemoryArbiter", nbytes: int):
        self.arbiter = arbiter
        self.nbytes = int(nbytes)
        self._done = False

    def release(self) -> None:
        if not self._done:
            self._done = True
            self.arbiter._release_reserved(self.nbytes)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()
        return False


class MemoryArbiter:
    """Process-wide device-byte budget + landing ledger.

    The ledger maps a monotonically increasing token to the device
    bytes of one live accounted DeviceTable; a ``weakref.finalize`` on
    the table returns the bytes the instant the last reference drops
    (a spill demotion drops the device reference, so spilling IS the
    release path). Occupancy = reserved + ledger bytes. Reads are
    bounded dict work — safe from the passive telemetry sampler."""

    def __init__(self):
        self._lock = ordered_lock("memory.arbiter")
        self._cfg = None
        #: resolved hard budget; <=0 means "not yet configured" and
        #: enforcement resolves the backend HBM limit lazily
        self._budget = 0
        self._chunk_fraction = float(DEVICE_SCAN_CHUNK_FRACTION.default)
        self._reserved = 0
        self._ledger: Dict[int, int] = {}
        #: running sum of the ledger — occupancy reads are O(1) so the
        #: hot reserve/account paths (and the passive telemetry
        #: sampler's snapshot) never walk the live-table dict under
        #: the lock
        self._ledger_total = 0
        self._by_table_id: Dict[int, int] = {}
        self._next_token = 0
        self._peak = 0
        self._violations = 0
        #: grants made since the process began (landings())
        self._landings = 0
        self._metrics = MEM_SCOPE

    # -- configuration -------------------------------------------------------
    def configure(self, conf) -> None:
        """Cheap when unchanged (the FAULTS.arm contract) — called per
        query by the session and at QueryService construction."""
        budget = int(conf.get_entry(DEVICE_BUDGET_BYTES))
        fraction = float(conf.get_entry(DEVICE_SCAN_CHUNK_FRACTION))
        key = (budget, fraction)
        with self._lock:
            if key == self._cfg:
                return
            self._cfg = key
            self._budget = (budget if budget > 0
                            else self._backend_budget(conf))
            self._chunk_fraction = min(max(fraction, 0.001), 1.0)

    @staticmethod
    def _backend_budget(conf=None) -> int:
        """The backend-reported HBM limit with allocFraction and the
        reserve applied: the live device manager's when one has
        initialized, else read from the first local device."""
        from spark_rapids_tpu.runtime.device_manager import (
            TpuDeviceManager,
            hbm_budget_bytes,
        )
        mgr = TpuDeviceManager.current()
        if mgr is not None and mgr.info is not None:
            return int(mgr.info.hbm_limit_bytes)
        import jax
        from spark_rapids_tpu.conf import RapidsConf
        return hbm_budget_bytes(conf if conf is not None else RapidsConf(),
                                jax.local_devices()[0])

    def budget_bytes(self) -> int:
        with self._lock:
            if self._budget <= 0:
                self._budget = self._backend_budget()
            return self._budget

    def scan_chunk_bytes(self) -> int:
        """The largest estimated device size one scan batch may land
        as — the attempt-scoped forced override (degradation ladder),
        else budget * scanChunkFraction."""
        forced = _FORCED_CHUNK_BYTES.get()
        if forced is not None:
            return forced
        budget = self.budget_bytes()
        with self._lock:
            return max(1, int(budget * self._chunk_fraction))

    # -- accounting ----------------------------------------------------------
    def occupancy(self) -> int:
        with self._lock:
            return self._reserved + self._ledger_total

    def _note_peak_locked(self) -> None:
        occ = self._reserved + self._ledger_total
        if occ > self._peak:
            self._peak = occ

    def _release_reserved(self, nbytes: int) -> None:
        with self._lock:
            self._reserved -= nbytes

    def _drop(self, token: int, table_id: int) -> None:
        with self._lock:
            self._ledger_total -= self._ledger.pop(token, 0)
            if self._by_table_id.get(table_id) == token:
                self._by_table_id.pop(table_id, None)

    def _spill_for(self, need: int) -> int:
        """One synchronous make-room pass: cached scan images first
        (lowest priority, weakly dropped), then idle spillables through
        the catalog tiers. Returns catalog bytes freed (cache evictions
        release through their finalizers)."""
        from spark_rapids_tpu.columnar.table import evict_device_caches
        from spark_rapids_tpu.runtime.spill import BufferCatalog
        self._metrics.add("arbiterSpills", 1)
        evict_device_caches()
        return BufferCatalog.get().synchronous_spill(max(need, 1))

    def reserve(self, nbytes: int, label: str = "") -> MemoryReservation:
        """Grant ``nbytes`` of device budget for an imminent landing.
        Over budget: spill idle catalog entries; still over: raise
        RetryOOM (the retry framework spills more and replays, then
        splits, then the memory ladder takes the attempt)."""
        from spark_rapids_tpu.runtime.faults import fault_point
        fault_point("mem.reserve", op=label or None)
        nbytes = max(0, int(nbytes))
        budget = self.budget_bytes()
        with self._lock:
            occ = self._reserved + self._ledger_total
            if occ + nbytes <= budget:
                return self._grant_locked(nbytes)
        self._spill_for(occ + nbytes - budget)
        with self._lock:
            occ = self._reserved + self._ledger_total
            if occ + nbytes <= budget:
                return self._grant_locked(nbytes)
        self._metrics.add("budgetRaises", 1)
        raise RetryOOM(
            f"device budget exhausted: want {nbytes}B"
            + (f" for {label}" if label else "")
            + f", {occ}/{budget}B accounted — spilling freed no room")

    def _grant_locked(self, nbytes: int) -> MemoryReservation:
        self._reserved += nbytes
        self._landings += 1
        self._note_peak_locked()
        return MemoryReservation(self, nbytes)

    def landings(self) -> int:
        """Grants made since the process began. Every host-to-device
        landing takes one first (DeviceTable.from_host), so a count
        that did not move says nothing was uploaded meanwhile (the
        ledger cannot say it: it also accounts views of resident
        tables, a coalesce's buffered batches)."""
        with self._lock:
            return self._landings

    def account(self, table,
                reservation: Optional[MemoryReservation] = None):
        """Record one live DeviceTable against the budget (actual
        device bytes; released by weakref finalizer when the table
        dies). Consumes ``reservation``. An actual landing that still
        exceeds the budget after a spill pass counts a violation —
        enforcement failed, and the chaos closure asserts it never
        does. Returns the table for call-through use."""
        if reservation is not None:
            reservation.release()
        try:
            nbytes = int(table.device_nbytes())
        except Exception:
            return table
        with self._lock:
            if id(table) in self._by_table_id:
                return table  # already accounted (cache re-serve)
            self._next_token += 1
            token = self._next_token
            self._ledger[token] = nbytes
            self._ledger_total += nbytes
            self._by_table_id[id(table)] = token
            weakref.finalize(table, self._drop, token, id(table))
            self._note_peak_locked()
            budget = self._budget if self._budget > 0 else None
            occ = self._reserved + self._ledger_total
        if budget is not None and occ > budget:
            self._spill_for(occ - budget)
            with self._lock:
                occ = self._reserved + self._ledger_total
                if occ > budget:
                    self._violations += 1
                    self._metrics.add("budgetViolations", 1)
        return table

    # -- introspection -------------------------------------------------------
    def snapshot(self) -> dict:
        budget = self.budget_bytes()
        with self._lock:
            return self._snapshot_locked(budget)

    def _snapshot_locked(self, budget: int) -> dict:
        """Snapshot body for callers already holding ``self._lock``.
        ``budget`` must be computed BEFORE entering the lock
        (budget_bytes() self-acquires, and ordered locks are
        non-reentrant by contract)."""
        ledger = self._ledger_total
        return {
            "budgetBytes": budget,
            "occupancyBytes": self._reserved + ledger,
            "ledgerBytes": ledger,
            "reservedBytes": self._reserved,
            "peakBytes": self._peak,
            "accountedTables": len(self._ledger),
            "budgetViolations": self._violations,
        }

    def peak_bytes(self) -> int:
        with self._lock:
            return self._peak

    def reset(self) -> None:
        """Test support: drop the ledger/peak and force reconfigure.
        Live finalizers keep working (their _drop pops by token)."""
        with self._lock:
            self._cfg = None
            self._budget = 0
            self._reserved = 0
            self._ledger = {}
            self._ledger_total = 0
            self._by_table_id = {}
            self._peak = 0
            self._violations = 0


MEMORY = MemoryArbiter()


def scan_chunks(host) -> list:
    """Split one scan host batch into bounded partitions so no single
    landing exceeds its device-budget share — the chunked out-of-core
    scan. Returns ``[host]`` unchanged when the batch fits (the common
    case is one cheap estimate)."""
    limit = MEMORY.scan_chunk_bytes()
    n = host.num_rows
    from spark_rapids_tpu.columnar.column import MIN_BUCKET, bucket_for
    if n <= MIN_BUCKET or not host.columns:
        return [host]
    cap = bucket_for(n)
    est = estimate_device_nbytes(host, cap)
    if est <= limit:
        return [host]
    per_row = max(est / cap, 1e-9)
    rows = max(MIN_BUCKET, int(limit / per_row))
    # chunk rows align DOWN to a full capacity bucket: every chunk's
    # landed capacity equals its row count exactly, so a downstream
    # concat of the chunks re-buckets to (about) the UNCHUNKED upload's
    # capacity instead of inflating it (bucket_for over a sum of
    # already-rounded chunk capacities can double twice)
    bucket = MIN_BUCKET
    while bucket * 2 <= rows:
        bucket *= 2
    rows = bucket
    chunks = [host.slice(i, min(rows, n - i)) for i in range(0, n, rows)]
    MEM_SCOPE.add("scanChunks", len(chunks))
    return chunks
