"""Exception hierarchy, mirroring the reference's OOM/retry protocol.

Reference: spark-rapids-jni exception types (SURVEY.md §2.9) --
GpuRetryOOM / GpuSplitAndRetryOOM / CpuRetryOOM / CpuSplitAndRetryOOM /
GpuOOM -- thrown by the RmmSpark per-thread state machine and caught by
RmmRapidsRetryIterator.withRetry (RmmRapidsRetryIterator.scala:33-757).

On TPU the analogs are raised when a PJRT/XLA device allocation fails (or
when the runtime's HBM budget tracker decides a batch will not fit), and by
the test-only OOM injection hooks.
"""

from __future__ import annotations


class RapidsTpuError(Exception):
    """Base for all engine errors."""


class RetryOOM(RapidsTpuError):
    """Device allocation failed; caller should spill and replay the same
    input (reference: GpuRetryOOM)."""


class SplitAndRetryOOM(RapidsTpuError):
    """Device allocation failed and replay alone will not help; caller should
    split the input (halve rows) and replay (reference: GpuSplitAndRetryOOM)."""


class CpuRetryOOM(RapidsTpuError):
    """Host allocation failed; spill host buffers and replay."""


class CpuSplitAndRetryOOM(RapidsTpuError):
    """Host allocation failed; split input and replay."""


class FatalDeviceOOM(RapidsTpuError):
    """Unrecoverable device OOM after retries exhausted (reference: GpuOOM)."""


class ColumnarProcessingError(RapidsTpuError):
    """An operator failed on device in a way that is not an OOM."""


class KernelCrashError(ColumnarProcessingError):
    """A device kernel failed with a non-OOM runtime fault (injected by the
    chaos harness, or a real XLA INTERNAL-class failure re-raised with op
    attribution). Carries ``fault_op`` — the plan-node class name of the
    nearest enclosing operator — which feeds the runtime circuit breaker
    (runtime/faults.py)."""

    def __init__(self, message: str, fault_op=None):
        super().__init__(message)
        if fault_op is not None:
            self.fault_op = fault_op


class ShuffleFetchError(ColumnarProcessingError):
    """A shuffle block fetch failed in a RETRYABLE way (peer error frame,
    short transfer, bounce-pool exhaustion, injected fetch fault). The
    fetch-retry loop (shuffle manager / p2p env) replays the fetch with
    exponential backoff before declaring the map output lost."""


class ShuffleTransportError(ShuffleFetchError):
    """The transport connection itself failed (socket error, peer
    disconnect, protocol desync). Retryable like a fetch error, but the
    connection is evicted so the retry reconnects."""


class CorruptFrameError(ShuffleFetchError):
    """A serialized shuffle frame failed integrity checks (bad TPAK
    magic/version, CRC mismatch, truncated buffer). Retryable: the source
    of truth (catalog blob / shuffle file / upstream lineage) is intact,
    so a refetch or recompute recovers."""


class MapOutputLostError(RapidsTpuError):
    """Shuffle map output is unreachable — a fetch exhausted its retries or
    the owning peer was evicted. Carries ``executor_id`` (the lost peer,
    '' when local) and ``map_ids`` (the missing map outputs; None =
    unknown, recompute everything). The shuffle exchange catches this and
    re-runs the missing upstream partitions from the retained plan
    lineage instead of failing the query."""

    def __init__(self, message: str, executor_id: str = "",
                 map_ids=None):
        super().__init__(message)
        self.executor_id = executor_id
        self.map_ids = None if map_ids is None else sorted(set(map_ids))


class UnsupportedOnTpu(RapidsTpuError):
    """Raised when an operator/expression is asked to run on device but was
    tagged unsupported; indicates a bug in the plan-rewrite layer (normal
    operation converts such nodes back to CPU)."""


class PlanVerificationError(RapidsTpuError):
    """A converted plan violated a structural invariant
    (spark.rapids.sql.planVerify.mode=error). Carries the structured
    diagnostics in ``.diagnostics``; the message lists rule id + plan
    path per finding."""

    def __init__(self, diagnostics):
        self.diagnostics = list(diagnostics)
        super().__init__(
            "plan verification failed:\n" +
            "\n".join(f"  {d}" for d in self.diagnostics))


class DeviceLostError(RapidsTpuError):
    """The device (or its PJRT client) was lost mid-query: a fatal
    non-OOM runtime failure classified by
    ``runtime.crash_handler.is_fatal_device_error``. RETRYABLE — by the
    time the caller sees this, the health monitor (runtime/health.py)
    has already reinitialized the backend and invalidated every cache
    that referenced dead device state, so a resubmission plans and
    traces fresh. The query service requeues these automatically."""


class MeshDeviceLostError(DeviceLostError):
    """PARTIAL device loss: one device of the execution mesh died (or
    its ICI link to it) while the backend as a whole is still alive —
    classified DISTINCTLY from whole-backend :class:`DeviceLostError`
    so recovery can walk the mesh degradation ladder
    (runtime/health.py ``on_mesh_device_loss``: retry → re-land
    single-device → mesh reconfiguration onto surviving devices →
    full backend reinit → CPU-only latch) instead of jumping straight
    to a backend reinitialization. Carries ``device_id`` when the
    failing device is known (None for injected losses — the ladder
    then excludes the mesh's last device)."""

    def __init__(self, message: str, device_id=None):
        super().__init__(message)
        self.device_id = device_id


class HostLostError(DeviceLostError):
    """A whole executor HOST (process) of the cluster died or went
    unreachable — a dead dispatch socket, a missed-heartbeat eviction,
    or an injected ``device_lost`` at a ``host.*`` fault point.
    Classified DISTINCTLY from whole-backend :class:`DeviceLostError`
    (the local backend is fine) and from partial
    :class:`MeshDeviceLostError` (a device died, not a process):
    recovery walks the HOST degradation ladder (runtime/health.py
    ``on_host_loss``: retry → re-land the dead host's shards onto
    survivors → shrink the dcn axis → single-process fallback →
    escalate to the whole-backend ladder). Carries ``host_id`` when
    the failing host is known (None for injected losses — the ladder
    then marks the last usable host)."""

    def __init__(self, message: str, host_id=None):
        super().__init__(message)
        self.host_id = host_id


class MeshGatherError(KernelCrashError):
    """The row-count + checksum validation at a mesh gather boundary
    (MeshReland / the ICI exchange's live-count fetch — the TPAK-v2
    frame-CRC pattern applied to device-to-device relands) kept
    failing past ``spark.rapids.mesh.maxShardRetries`` local
    re-gathers. A KernelCrashError subclass on purpose: the still-
    sharded source (or the still-resident device value) is intact, so
    the query-replay machinery re-lands from the scan cache rather
    than surfacing silently wrong results."""


class SpillCorruptionError(KernelCrashError):
    """A disk-tier spill frame failed its CRC footer on unspill (bit
    rot, a torn write, or an injected ``mem.unspill`` corruption). A
    KernelCrashError subclass on purpose — the MeshGatherError
    pattern: the corrupt frame is dropped (never served), and the
    query-replay machinery re-lands the data from the scan cache /
    source lineage rather than surfacing silently wrong bytes."""


class WorkerLostError(RapidsTpuError):
    """The service worker executing this query died (its runner
    machinery raised outside the query) or was abandoned by the
    watchdog. The pool respawned a replacement; the query itself was
    requeued up to its replay budget before this error surfaced."""


class SemaphoreTimeoutError(RapidsTpuError, TimeoutError):
    """TpuSemaphore acquisition timed out: ``max_tasks`` queries already
    hold device residency and none released within the caller's timeout.
    A typed signal (not a bare TimeoutError, though it still IS one for
    callers catching broadly) so the query service can report
    backpressure distinctly from deadline expiry."""


class QueryRejectedError(RapidsTpuError):
    """The query service refused admission — the target pool's queue is
    at ``spark.rapids.service.queueDepth``. Carries ``retry_after_ms``,
    the service's backpressure hint for when capacity is likely free
    (the HTTP 429 Retry-After analog)."""

    def __init__(self, message: str, retry_after_ms: int = 100):
        super().__init__(message)
        self.retry_after_ms = int(retry_after_ms)


class QueryCancelledError(RapidsTpuError):
    """The query was cancelled via ``QueryHandle.cancel()``. Raised
    cooperatively between batches at the exec boundary (service/query.py
    install_cancellation), so a running plan stops at the next pull
    instead of after the query."""


class QueryTimeoutError(RapidsTpuError):
    """The query's deadline (submit time + timeout) expired — while
    queued, or cooperatively between batches while running."""


class HardTimeoutError(QueryTimeoutError):
    """The watchdog's HARD wall limit
    (``spark.rapids.service.hardTimeoutMs``) expired while the query was
    RUNNING. Distinct from the cooperative deadline: that one fires at
    exec-boundary batch pulls, so a worker wedged INSIDE a single
    dispatch never observes it — the watchdog abandons that worker,
    respawns a replacement, and fails the handle with this error."""


class QueryQuarantinedError(RapidsTpuError):
    """The query's template was quarantined: plans with this structural
    fingerprint killed workers or the device
    ``spark.rapids.service.quarantine.maxStrikes`` times, so the service
    refuses to run it again. Carries ``strikes`` — the recorded strike
    history (list of reason strings) — so the submitter can see what the
    template did."""

    def __init__(self, message: str, strikes=None):
        super().__init__(message)
        self.strikes = list(strikes or ())


class AnsiViolation(RapidsTpuError, ArithmeticError):
    """ANSI mode (spark.sql.ansi.enabled) runtime error: overflow, divide
    by zero, invalid cast, or array index out of bounds — the engine's
    SparkArithmeticException. Device kernels record the violation as a
    device flag that rides the collect fetch (like speculation flags);
    the CPU oracle raises at evaluation."""
