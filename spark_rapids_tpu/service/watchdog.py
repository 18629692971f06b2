"""Worker watchdog: hard wall limits + self-healing worker pool.

Reference: Spark's executor heartbeat + task reaper
(``spark.task.reaper.*``) — the driver kills tasks that blow their
wall budget and replaces executors that stop heartbeating. This
service's workers are threads over ONE shared session, so the analog
is in-process:

* **Hard wall limit** (``spark.rapids.service.hardTimeoutMs``) — the
  cooperative deadline (PR 5) fires at exec-boundary batch pulls; a
  worker wedged INSIDE one dispatch (a stuck device round trip, the
  ``dispatch.wedge`` chaos fault) never reaches the next pull, so that
  deadline can never fire. The watchdog sweeps RUNNING queries against
  the hard limit and, past it, ABANDONS the worker: the handle fails
  with a typed :class:`~spark_rapids_tpu.errors.HardTimeoutError`, a
  replacement worker spawns so pool capacity holds, and the abandoned
  thread exits on its own when (if) the dispatch ever returns — Python
  threads cannot be killed, only disowned.
* **Liveness backstop** — a worker thread that died without running
  the scheduler's own death handling (it catches everything, so this
  means something catastrophic) is detected dead, its handle failed,
  and a replacement spawned.

Lifecycle counters (``workersLost`` / ``workersRespawned`` /
``hardTimeouts``) live in the ``health`` metric scope
(runtime/health.py) next to the device-loss counters.
"""

from __future__ import annotations

import threading
import time

from spark_rapids_tpu.conf import int_conf
from spark_rapids_tpu.errors import HardTimeoutError, WorkerLostError
from spark_rapids_tpu.service.query import QueryState

HARD_TIMEOUT_MS = int_conf(
    "spark.rapids.service.hardTimeoutMs", 0,
    "HARD per-query wall limit from the RUNNING transition, "
    "milliseconds — distinct from the cooperative "
    "defaultTimeoutMs/submit(timeout_ms=) deadline, which only fires "
    "between batches: past this limit the watchdog abandons the "
    "worker (it may be wedged inside a single dispatch), fails the "
    "handle with HardTimeoutError, and spawns a replacement worker. "
    "0 disables the hard limit; the liveness backstop still runs.")


class _Worker:
    """One pool worker's bookkeeping: the thread, the handle it is
    currently running (None between queries), and the ``lost`` flag the
    watchdog sets when it abandons the worker — the worker's own loop
    checks it under the scheduler lock and exits without touching the
    (already-corrected) running count."""

    __slots__ = ("thread", "handle", "lost", "name")

    def __init__(self, name: str):
        self.thread: threading.Thread = None
        self.handle = None
        self.lost = False
        self.name = name

    def __repr__(self):
        return (f"_Worker({self.name}, lost={self.lost}, "
                f"handle={self.handle})")


class WorkerWatchdog:
    """Sweeper thread over the service's worker pool. All pool state is
    read and corrected under the service's condition lock; handle
    transitions happen under each handle's own lock (no ordering cycle:
    handle locks never acquire the scheduler lock)."""

    def __init__(self, service):
        self.service = service
        self.hard_timeout_ms = int(
            service.conf.get_entry(HARD_TIMEOUT_MS))
        self._thread = threading.Thread(
            target=self._loop, name="rapids-svc-watchdog", daemon=True)
        self._thread.start()

    def join(self, timeout: float = 5.0) -> None:
        self._thread.join(timeout)

    def _loop(self):
        svc = self.service
        while True:
            with svc._cond:
                if svc._shutdown:
                    return
                self._sweep_locked()
                svc._cond.wait(timeout=svc._SWEEP_INTERVAL_S)

    def _sweep_locked(self):
        svc = self.service
        # executor heartbeat sweep (runtime/cluster.py): hosts that
        # missed spark.rapids.cluster.missedBeats beats are declared
        # lost here too — the service's watchdog is the cross-host
        # health authority when a cluster driver is attached (the
        # driver's own sweeper covers driverless harness runs).
        # Best-effort and lock-free on our side: the cluster never
        # takes the service lock, so no ordering cycle.
        try:
            from spark_rapids_tpu.runtime.cluster import (
                sweep_cluster_hosts,
            )
            sweep_cluster_hosts()
        except Exception:
            pass  # host health must never break worker health
        now = time.monotonic()
        for w in list(svc._workers):
            if w.lost:
                continue
            h = w.handle
            if not w.thread.is_alive():
                # backstop: the worker loop's own death handling catches
                # BaseException, so a dead thread with lost unset means
                # something catastrophic killed it outside that net.
                # The thread is gone regardless of any handle race —
                # always respawn
                self._abandon_locked(
                    w, h, WorkerLostError(
                        f"service worker {w.name} died unexpectedly"),
                    QueryState.FAILED, count="failed",
                    require_transition=False)
            elif (h is not None and self.hard_timeout_ms > 0
                    and h.start_t is not None
                    and h.state == QueryState.RUNNING
                    and (now - h.start_t) * 1000.0 > self.hard_timeout_ms):
                self._abandon_locked(
                    w, h, HardTimeoutError(
                        f"query {h.query_id} exceeded the hard wall "
                        f"limit ({self.hard_timeout_ms}ms) — worker "
                        f"{w.name} abandoned (wedged inside a "
                        "dispatch?)"),
                    QueryState.TIMED_OUT, count="timed_out",
                    require_transition=True)

    def _abandon_locked(self, w, handle, error, terminal, count: str,
                        require_transition: bool):
        """Fail ``handle`` with ``error`` and mark ``w`` lost (it exits
        its loop without decrementing the running count — corrected
        here); respawn a replacement. With ``require_transition`` the
        whole abandonment is gated on WINNING the handle's terminal
        transition: a query that completed between the sweep's state
        read and this call keeps its healthy worker — abandoning it
        would count a phantom hard timeout and discard a good thread.
        Caller holds the service condition lock."""
        svc = self.service
        transitioned = (handle._transition(terminal, error=error)
                        if handle is not None else False)
        if require_transition and not transitioned:
            return  # lost the race: the query finished; worker is fine
        if transitioned:
            svc.counters[count] += 1
            if count == "timed_out":
                svc._health_metrics.add("hardTimeouts", 1)
                svc.counters["hardTimeouts"] += 1
            # if the wedged dispatch ever returns, the next cooperative
            # boundary aborts the (already-failed) query immediately
            handle.scope.cancel()
            svc._strike_locked(handle, str(error))
        w.lost = True
        if handle is not None:
            # the abandoned worker no longer counts toward concurrency
            svc._running -= 1
        svc._note_worker_lost_locked(w)
        svc._cond.notify_all()
