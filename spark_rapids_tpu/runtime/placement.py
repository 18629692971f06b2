"""Placement layer: WHERE query data lives and how it is drained.

The deliberate driver/placement split (ROADMAP item 1): ``TpuSession``
keeps the DRIVER half — SQL front end, catalog, planning, overrides/AQE
conversion, verification, the executable/result caches and the
observability envelope — while this layer owns everything about device
PLACEMENT and execution residency:

* realizing the mesh config (``spark.rapids.mesh.*`` ->
  :class:`~spark_rapids_tpu.parallel.mesh.MeshRuntime`) BEFORE planning,
  so the plan fingerprint and the executable-cache generation see the
  mesh the query will execute under (shard dispatch then happens in the
  scan execs, which land each shard per-device);
* the device semaphore: fully-fallen-back plans must not consume a
  device-concurrency slot, so residency gating keys off whether the
  converted tree holds any device exec;
* the speculative drain (operator sizing validated by the collect's
  packed fetch, with blocklist-and-replay on failure) and the
  conf-driven tuning constants it pushes into the kernel layers;
* async result-fetch resolution: enqueued ``PendingHostTable`` batches
  complete their d2h round trip AFTER the semaphore released.

On a multi-host deployment this layer is what a per-host executor would
implement; single-process it is the seam the mesh runtime, the
semaphore and the drain hang off.
"""

from __future__ import annotations

from typing import List


def uses_device(executable) -> bool:
    """Does a converted plan contain any device exec? (Transitions wrap
    TpuExec trees in DeviceToHost; CPU nodes may hold them via
    InputAdapter.)"""
    from spark_rapids_tpu.execs.base import DeviceToHost, InputAdapter, TpuExec
    if isinstance(executable, (DeviceToHost, TpuExec)):
        return True
    if isinstance(executable, InputAdapter):
        return uses_device(executable.source)
    for c in getattr(executable, "children", ()):
        if uses_device(c):
            return True
    return False


class PlacementLayer:
    """One session's placement half (stateless between queries: the
    conf is re-read per call so ``session.set_conf`` takes effect like
    every other per-query knob)."""

    def __init__(self, session):
        self._session = session

    @property
    def _conf(self):
        return self._session.conf

    # -- mesh ----------------------------------------------------------------
    def prepare(self) -> None:
        """Realize the placement config for the coming query. Called by
        the driver BEFORE fingerprinting/planning: the mesh runtime must
        reflect this query's ``spark.rapids.mesh.*`` conf when the plan
        fingerprint folds the mesh identity token and the executable
        cache stamps its generation."""
        from spark_rapids_tpu.parallel.mesh import MESH
        MESH.configure(self._conf)
        # the host topology above the mesh (runtime/cluster.py): the
        # fingerprint folds its identity token right next to the mesh's
        from spark_rapids_tpu.runtime.cluster import CLUSTER
        CLUSTER.configure(self._conf)

    # -- drain ---------------------------------------------------------------
    def drain(self, executable) -> List:
        """Drain the converted plan under a speculation context
        (speculative operator sizing, validated by the collect's packed
        fetch). A failed speculation blocklists the failing sites
        process-wide and replays once — the replay takes the exact
        sync-per-operator path there, so a repeated query shape never
        replays twice (runtime/speculation.py).

        The device semaphore is held around each DRAIN only: with async
        result fetch the root transition yields enqueued
        PendingHostTable batches, and their d2h round trips complete
        AFTER the semaphore releases — the device slot frees as soon as
        the last kernel is in flight. Resolution stays INSIDE the
        speculation attempt so a flag failure riding the packed buffer
        still replays."""
        from spark_rapids_tpu.conf import (
            JOIN_DIRECT_TABLE_MULT,
            MASKED_BATCHES,
            SPECULATIVE_SIZING,
        )
        from spark_rapids_tpu.execs.base import MASKED_ENABLED
        from spark_rapids_tpu.execs.join import DIRECT_TABLE_MULT
        from spark_rapids_tpu.dispatch import phase_span
        from spark_rapids_tpu.runtime import (
            TpuSemaphore,
            speculation as spec,
        )

        conf = self._conf
        # the semaphore gates DEVICE residency: fully-fallen-back plans
        # must not consume a device-concurrency slot
        sem = None
        if uses_device(executable):
            sem = TpuSemaphore.initialize(conf.concurrent_tpu_tasks)

        self.apply_tuning_confs()
        from spark_rapids_tpu.conf import ANSI_ENABLED
        from spark_rapids_tpu.dispatch import ANSI_MODE
        tok_m = MASKED_ENABLED.set(bool(conf.get_entry(MASKED_BATCHES)))
        tok_d = DIRECT_TABLE_MULT.set(
            conf.get_entry(JOIN_DIRECT_TABLE_MULT))
        tok_a = ANSI_MODE.set(bool(conf.get_entry(ANSI_ENABLED)))

        def drain_once():
            if sem is None:
                batches = list(executable.execute_cpu())
            else:
                # the wait for a device slot is its own range and phase
                # (semaphoreWaitS): 0 with one client, the queue under
                # concurrent ones
                with phase_span("semaphoreWaitS", "semaphore", "wait"):
                    sem.acquire_if_necessary()
                try:
                    batches = list(executable.execute_cpu())
                finally:
                    sem.release_if_held()
            return self.resolve_pending(executable, batches)

        try:
            if not conf.get_entry(SPECULATIVE_SIZING):
                return drain_once()
            # each failed attempt blocklists its sites, so every replay
            # makes strict progress (a site never fails twice); the cap
            # guards a pathological plan by dropping to the exact path
            for _attempt in range(8):
                tok = spec.activate()
                try:
                    batches = drain_once()
                    spec.current().validate_remaining()
                    if _attempt and hasattr(executable, "metrics"):
                        # replays re-execute operators, double-counting
                        # their metrics; record how many times so the
                        # numbers can be interpreted (ADVICE r3)
                        executable.metrics["speculationReplays"] = _attempt
                    return batches
                except spec.SpeculationFailed as sf:
                    spec.blocklist(sf.sites)
                finally:
                    spec.deactivate(tok)
            return drain_once()
        finally:
            MASKED_ENABLED.reset(tok_m)
            DIRECT_TABLE_MULT.reset(tok_d)
            ANSI_MODE.reset(tok_a)

    def resolve_pending(self, executable, batches) -> List:
        """Complete enqueued async downloads — the device semaphore is
        already released; only the device round trip remains. Records
        resultFetchTime plus the root transition's deferred output-row
        count (plain HostTable batches pass through untouched)."""
        from spark_rapids_tpu.columnar.table import PendingHostTable
        if not any(isinstance(b, PendingHostTable) for b in batches):
            return batches
        import time as _time
        t0 = _time.perf_counter()
        out = []
        rows = 0
        for b in batches:
            if isinstance(b, PendingHostTable):
                b = b.resolve()
                rows += b.num_rows
            out.append(b)
        if hasattr(executable, "add_metric"):
            executable.add_metric("resultFetchTime",
                                  _time.perf_counter() - t0)
            executable.add_metric("numOutputRows", rows)
        return out

    def apply_tuning_confs(self) -> None:
        """Push registry-tunable constants into the modules that consume
        them (RapidsConf -> class attrs; execs/expressions hold no conf
        handle — same pattern as the retry/masked contextvars)."""
        from spark_rapids_tpu import conf as C
        from spark_rapids_tpu.columnar.table import DeviceTable
        from spark_rapids_tpu.execs import broadcast as B
        from spark_rapids_tpu.ops.collections import Sequence
        get = self._conf.get_entry
        from spark_rapids_tpu.columnar import column as CCol
        CCol.set_bucket_policy(str(get(C.SHAPE_BUCKETS)),
                               int(get(C.SHAPE_BUCKETS_MIN)))
        Sequence.SEQ_ELEMENT_MULT = int(get(C.SEQUENCE_ELEMENT_MULT))
        DeviceTable.EMBED_NROWS_CAP = int(get(C.COLLECT_EMBED_ROWS_CAP))
        DeviceTable.EMBED_MAX_BYTES = int(get(C.COLLECT_EMBED_MAX_BYTES))
        B.PAIR_BUDGET = int(get(C.NLJ_PAIR_BUDGET))
        from spark_rapids_tpu.ops import segsum as SS
        SS.BLOCK = int(get(C.SEGSUM_BLOCK_ROWS))
        SS.MAX_PARTIALS = int(get(C.SEGSUM_MAX_PARTIALS))
        SS.MATMUL_MAX_SEGMENTS = int(get(C.SEGSUM_MATMUL_MAX_SEGMENTS))
        SS.SPLIT_MAX_ABS = float(get(C.SPLIT_SUM_MAX_ABS))
        # mesh fault-domain tunables (the gather-integrity boundary and
        # the ICI exchange hold no conf handle, like every other exec)
        from spark_rapids_tpu.parallel import mesh as PM
        PM.MAX_SHARD_RETRIES = int(get(PM.MESH_MAX_SHARD_RETRIES))
        PM.GATHER_VERIFY = bool(get(PM.MESH_GATHER_VERIFY))
