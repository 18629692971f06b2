"""OOM retry framework.

Reference (SURVEY.md §2.5): RmmRapidsRetryIterator.scala — withRetry /
withRetryNoSplit / withRestoreOnRetry catch GpuRetryOOM / GpuSplitAndRetryOOM
thrown by the RmmSpark per-thread state machine; on retry the thread spills
and replays; on split-and-retry the input halves and both halves replay.
OOM *injection* for tests = RmmSpark.forceRetryOOM.

TPU mapping: a device OOM surfaces as an XlaRuntimeError with
RESOURCE_EXHAUSTED from PJRT. The retry driver spills registered spillables
through the BufferCatalog and replays the jitted computation; escalation
splits the input batch in half by rows (sound for row-wise operators; ops
with cross-row semantics use with_retry_no_split)."""

from __future__ import annotations

import contextvars
import threading
from typing import Callable, Iterator, List, Optional, Sequence, Union

import jax.numpy as jnp

from spark_rapids_tpu.columnar import DeviceTable, bucket_for
from spark_rapids_tpu.errors import (
    CpuRetryOOM,
    FatalDeviceOOM,
    RetryOOM,
    SplitAndRetryOOM,
)
from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch
from spark_rapids_tpu.lockorder import ordered_lock


def is_device_oom(exc: BaseException) -> bool:
    """True when an exception is a retryable allocation failure — device
    (XLA RESOURCE_EXHAUSTED / RetryOOM family) or host (CpuRetryOOM from
    the HostAlloc arbiter; the reference routes CpuRetryOOM through the
    same retry framework)."""
    if isinstance(exc, (RetryOOM, SplitAndRetryOOM, CpuRetryOOM)):
        return True
    name = type(exc).__name__
    msg = str(exc)
    if name in _DEVICE_ERROR_TYPES:
        # the runtime's own error: XlaRuntimeError on older JAX, renamed
        # JaxRuntimeError (an enqueue that finds the device full raises
        # it as RESOURCE_EXHAUSTED)
        return ("RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
                or "out of memory" in msg)
    # a program that does not fit where it is loaded: the runtime raises
    # ValueError("RESOURCE_EXHAUSTED: Error loading program ...")
    return isinstance(exc, ValueError) and msg.startswith(
        "RESOURCE_EXHAUSTED") and "Error loading program" in msg


#: the type names the device runtime raises its errors under
_DEVICE_ERROR_TYPES = ("XlaRuntimeError", "JaxRuntimeError")


class RetryStateMachine:
    """Per-thread injected-OOM bookkeeping (RmmSpark thread state analog).

    ``force_retry_oom(n)`` arms n RetryOOM throws at the next n retry
    blocks on the calling thread; ``force_split_and_retry_oom(n)``
    likewise for the escalation path."""

    def __init__(self):
        self._local = threading.local()

    def _state(self):
        st = getattr(self._local, "st", None)
        if st is None:
            st = {"retry": 0, "split": 0, "retry_count": 0, "split_count": 0}
            self._local.st = st
        return st

    def force_retry_oom(self, num_ooms: int = 1):
        self._state()["retry"] += num_ooms

    def force_split_and_retry_oom(self, num_ooms: int = 1):
        self._state()["split"] += num_ooms

    def maybe_inject(self):
        st = self._state()
        if st["retry"] > 0:
            st["retry"] -= 1
            raise RetryOOM("injected RetryOOM (test)")
        if st["split"] > 0:
            st["split"] -= 1
            raise SplitAndRetryOOM("injected SplitAndRetryOOM (test)")

    def note_retry(self):
        self._state()["retry_count"] += 1
        # the process-wide memory scope mirrors retry traffic so the
        # event log (schema v10) attributes oomRetries per query
        from spark_rapids_tpu.runtime.memory import MEM_SCOPE
        MEM_SCOPE.add("oomRetries", 1)

    def note_split(self):
        self._state()["split_count"] += 1
        from spark_rapids_tpu.runtime.memory import MEM_SCOPE
        MEM_SCOPE.add("splitRetries", 1)

    @property
    def retry_count(self) -> int:
        return self._state()["retry_count"]

    @property
    def split_count(self) -> int:
        return self._state()["split_count"]

    def clear(self):
        self._local.st = None


RMM_TPU = RetryStateMachine()

#: spark.rapids.memory.gpu.oomMaxRetries, set per-query by the session so
#: every retry site (execs have no conf handle) honors the user's setting.
MAX_RETRIES_VAR = contextvars.ContextVar("rapids_oom_max_retries", default=2)


def split_device_table_in_half(dt: DeviceTable) -> List[DeviceTable]:
    """Halve a batch by rows (splitSpillableInHalfByRows analog). Slicing
    device arrays re-buckets each half to the smaller capacity."""
    if any(getattr(c, "is_nested", False) for c in dt.columns):
        raise FatalDeviceOOM(
            "cannot row-split a batch with nested (array/struct/map) "
            "columns (rebuilding offsets under OOM is unsupported; reduce "
            "batch size instead)")
    dt = dt.compacted()  # masked batches: prefix order before row slicing
    n = dt.num_rows
    if n < 2:
        raise FatalDeviceOOM(
            f"cannot split a {n}-row batch further (GpuSplitAndRetryOOM at floor)")
    first = n // 2
    second = n - first
    outs = []
    for start, cnt in ((0, first), (first, second)):
        cap = bucket_for(cnt)
        cols = []
        for c in dt.columns:
            data = jnp.zeros(cap, dtype=c.data.dtype).at[:cnt].set(
                c.data[start:start + cnt])
            validity = jnp.zeros(cap, dtype=jnp.bool_).at[:cnt].set(
                c.validity[start:start + cnt])
            cols.append(c.with_arrays(data, validity))
        outs.append(DeviceTable(dt.names, cols, cnt, cap))
    return outs


SpillableOrTable = Union[SpillableBatch, DeviceTable]


def _as_spillable(x: SpillableOrTable, catalog: BufferCatalog) -> SpillableBatch:
    if isinstance(x, SpillableBatch):
        return x
    return SpillableBatch(x, catalog)



class DeviceMemoryEventHandler:
    """Allocator-failure callback (DeviceMemoryEventHandler.scala:108
    analog): on an allocation failure, spill synchronously and report
    whether the allocation should be retried. Retrying stops when a spill
    pass frees nothing twice in a row ON THE SAME CATALOG — the state the
    reference escalates to the OOM state machine. Thread-safe; the
    catalog is a call argument, never shared mutable state."""

    def __init__(self, catalog: Optional[BufferCatalog] = None):
        self._default_catalog = catalog
        self._lock = ordered_lock("memory.retry_handler")
        self.alloc_failure_count = 0
        self.spilled_bytes = 0
        self.spill_crashes = 0
        self._fruitless: dict = {}  # id(catalog) -> consecutive count

    def on_alloc_failure(self, catalog: Optional[BufferCatalog] = None
                         ) -> bool:
        from spark_rapids_tpu.columnar.table import evict_device_caches
        from spark_rapids_tpu.dispatch import clear_device_constants
        from spark_rapids_tpu.parallel.exchange import clear_mesh_caches
        catalog = catalog or self._default_catalog or BufferCatalog.get()
        evict_device_caches()
        clear_device_constants()  # interned aux/remap arrays re-upload lazily
        clear_mesh_caches()  # pinned replicated dict matrices re-intern lazily
        try:
            freed = catalog.synchronous_spill(1 << 62)
        except Exception:
            # the spill pass itself died mid-demotion (a real I/O
            # failure, or the mem.spill chaos point): OOM RECOVERY
            # MUST NOT DIE RECOVERING — whatever the pass freed before
            # failing stays freed, the crash is counted, and the
            # replay proceeds (bounded by the caller's max_retries)
            with self._lock:
                self.spill_crashes += 1
            freed = 0
        with self._lock:
            self.alloc_failure_count += 1
            self.spilled_bytes += freed
            key = id(catalog)
            if freed > 0:
                self._fruitless[key] = 0
                return True
            n = self._fruitless.get(key, 0) + 1
            self._fruitless[key] = n
            return n < 2

    def reset_fruitless(self, catalog: BufferCatalog):
        """Called at retry-block entry: a new operator's memory pressure is
        a fresh situation; stale fruitless counts must not pre-escalate."""
        with self._lock:
            self._fruitless.pop(id(catalog), None)


DEVICE_MEMORY_EVENT_HANDLER = DeviceMemoryEventHandler()


def _free_device_memory(catalog: BufferCatalog) -> bool:
    """Release everything releasable before a replay: cached scan images
    first (lowest priority), then registered spillables through the
    catalog tiers. Returns False when further same-size retries are
    pointless (two fruitless spill passes on this catalog)."""
    return DEVICE_MEMORY_EVENT_HANDLER.on_alloc_failure(catalog)


def _free_memory_for(exc: BaseException, catalog: BufferCatalog) -> bool:
    """Route the spill response to the EXHAUSTED tier: a host OOM
    (CpuRetryOOM from the HostAlloc arbiter) frees HOST memory by pushing
    the host tier to disk — spilling device buffers into host RAM would
    worsen it. Device OOMs take the device demotion chain."""
    if isinstance(exc, CpuRetryOOM):
        catalog.spill_host_to_disk()
        # a blocked-then-raised host alloc may succeed after other tasks
        # release grants, so a replay is always worthwhile
        return True
    return _free_device_memory(catalog)

def with_retry(
    inputs: Union[SpillableOrTable, Sequence[SpillableOrTable]],
    fn: Callable[[DeviceTable], object],
    *,
    splittable: bool = True,
    max_retries: Optional[int] = None,
    catalog: Optional[BufferCatalog] = None,
) -> Iterator[object]:
    """Run ``fn`` over input batch(es), surviving device OOM.

    Per attempt: injection hook fires first (tests), then fn runs; on OOM the
    catalog spills and the SAME input replays (up to max_retries), after
    which the input splits in half by rows and both halves replay
    recursively (when ``splittable``). Results stream out as an iterator —
    one result per final (possibly split) input batch.

    The reference contract this mirrors: withRetry(spillable)(fn) —
    RmmRapidsRetryIterator.scala:62; withRetryNoSplit :126."""
    catalog = catalog or BufferCatalog.get()
    if max_retries is None:
        max_retries = MAX_RETRIES_VAR.get()
    DEVICE_MEMORY_EVENT_HANDLER.reset_fruitless(catalog)
    stack: List[SpillableBatch] = []
    if isinstance(inputs, (SpillableBatch, DeviceTable)):
        inputs = [inputs]
    for x in reversed(list(inputs)):
        stack.append(_as_spillable(x, catalog))

    sb = None
    try:
        while stack:
            sb = stack.pop()
            attempts = 0
            while True:
                try:
                    from spark_rapids_tpu.runtime.speculation import guard_attempt
                    RMM_TPU.maybe_inject()
                    with sb.pinned_batch() as dt:
                        result = guard_attempt(lambda: fn(dt))
                    sb.release()
                    sb = None
                    yield result
                    break
                except Exception as exc:
                    oom = is_device_oom(exc)
                    escalate = isinstance(exc, SplitAndRetryOOM) or (
                        oom and attempts >= max_retries)
                    if oom and not escalate:
                        attempts += 1
                        RMM_TPU.note_retry()
                        # free everything we can, then replay the same
                        # input — unless spilling freed nothing twice on
                        # this catalog, in which case a same-size replay
                        # is pointless and we escalate straight to split
                        if _free_memory_for(exc, catalog):
                            continue
                        escalate = True
                    if escalate:
                        tier = ("host" if isinstance(exc, CpuRetryOOM)
                                else "device")
                        if not splittable:
                            raise FatalDeviceOOM(
                                f"{tier} OOM and operator cannot split "
                                "its input") from exc
                        RMM_TPU.note_split()
                        _free_memory_for(exc, catalog)
                        with sb.pinned_batch() as dt:
                            halves = split_device_table_in_half(dt)
                        sb.release()
                        sb = None
                        for h in reversed(halves):
                            stack.append(_as_spillable(h, catalog))
                        break
                    raise
    finally:
        # abandonment (limit upstream), FatalDeviceOOM, or any error: drop
        # every still-registered input so the catalog never leaks buffers
        if sb is not None:
            sb.release()
        for pending in stack:
            pending.release()


def with_retry_no_split(
    inputs: Union[SpillableOrTable, Sequence[SpillableOrTable]],
    fn: Callable[[DeviceTable], object],
    *,
    max_retries: Optional[int] = None,
    catalog: Optional[BufferCatalog] = None,
) -> Iterator[object]:
    return with_retry(inputs, fn, splittable=False, max_retries=max_retries,
                      catalog=catalog)


def retry_block(fn: Callable[[], object], *, max_retries: Optional[int] = None,
                catalog: Optional[BufferCatalog] = None) -> object:
    """Retry an arbitrary device computation that has no single input batch
    (joins, merges): spill-and-replay only, no split escalation."""
    catalog = catalog or BufferCatalog.get()
    if max_retries is None:
        max_retries = MAX_RETRIES_VAR.get()
    DEVICE_MEMORY_EVENT_HANDLER.reset_fruitless(catalog)
    from spark_rapids_tpu.runtime.speculation import guard_attempt
    attempts = 0
    while True:
        try:
            RMM_TPU.maybe_inject()
            return guard_attempt(fn)
        except Exception as exc:
            if is_device_oom(exc) and attempts < max_retries:
                attempts += 1
                RMM_TPU.note_retry()
                # replay even when the spill pass freed nothing: a
                # retry_block has no split escalation, the replay
                # budget is already bounded by max_retries, and a
                # blocked-then-raised budget reservation (or an
                # injected OOM) can succeed on replay without new
                # spillables appearing — the with_retry fruitless
                # check exists to stop SAME-SIZE replays when a split
                # is the better move, which has no analog here
                _free_memory_for(exc, catalog)
                continue
            if is_device_oom(exc):
                tier = "host" if isinstance(exc, CpuRetryOOM) else "device"
                raise FatalDeviceOOM(
                    f"{tier} OOM persisted after {attempts} "
                    "spill-retries") from exc
            raise
