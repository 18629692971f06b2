"""Load driver: the TPC-H corpus through the concurrent query service.

``python -m spark_rapids_tpu.tools loadtest`` (and
``scale_test.py --concurrency N``) fire q1-q22 across simulated tenants
at a configured worker concurrency and report the serving story the
serial harnesses cannot: aggregate wall clock vs the serial sum,
p50/p95 submit-to-finish latency, queue wait, and result-cache hit
rate — while asserting every concurrent result BIT-IDENTICAL to its
fault-free serial execution (the correctness bar every other harness in
this repo holds).

Workload shape: every tenant submits every selected query, so with T
tenants the service sees T x Q submissions. The serial comparator
models exactly what a one-at-a-time server would do with the same
T x Q request stream: the FIRST submission of each query pays the cold
(compile-inclusive) wall, the remaining T-1 pay the warm wall —
serialSumS = sum(cold) + (T-1) * sum(warm). The concurrent side pays
the same per-query compiles (on misses), so the speedup and the
below-serial-sum acceptance gate compare like for like; both
components are reported separately.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, List, Optional

#: recovery-work ceilings a service-chaos run asserts (whole-run; the
#: fault schedule is COUNT-based, so recovery work is bounded by
#: construction — a violation means a retry loop ran away)
SERVICE_CHAOS_BOUNDS = {
    "deviceReinits": 8,
    "workersLost": 8,
    "workersRespawned": 8,
    "requeued": 24,
    "hardTimeouts": 8,
}

#: handle errors a chaos run accepts as TYPED survivability outcomes —
#: anything else failing a submission fails the run
_CHAOS_TYPED_ERRORS = ("HardTimeoutError", "WorkerLostError",
                       "DeviceLostError", "QueryQuarantinedError")


def service_chaos_spec(seed: int) -> str:
    """The seeded SERVICE-level fault schedule: worker deaths, device
    losses and one wedged dispatch — count-based so total disruption is
    deterministic regardless of corpus size (probabilities would scale
    chaos with load and unbound the recovery counters)."""
    return ";".join([
        f"service.worker_crash:crash:2:{seed * 100 + 1}",
        f"device.lost:device_lost:2:{seed * 100 + 2}",
        f"dispatch.wedge:wedge:1:{seed * 100 + 3}",
    ])


#: how long the injected wedge stalls its dispatch during a chaos
#: loadtest (SRT_WEDGE_SLEEP_S) — must exceed hardTimeoutMs so the
#: watchdog provably fires, with margin so the abandoned (still
#: sleeping, semaphore-holding) thread outlives the verdict
_CHAOS_WEDGE_SLEEP_S = 45.0


def service_chaos_settings(concurrency: int) -> dict:
    """The service conf a chaos run needs BESIDES the fault schedule —
    shared with ``scale_test.py --service-faults`` so the two harnesses
    cannot drift apart on the survivability contract."""
    return {
        # hard limit well under the wedge stall so the watchdog
        # provably fires, but FAR above the worst legitimate run: a
        # device loss mid-run clears every kernel cache, so
        # post-recovery queries pay cold re-traces CONCURRENTLY (every
        # worker compiling at once multiplies the ~3s p95 serial cold
        # wall several-fold) — a tight limit here reads honest
        # recovery work as a wedge and cascades worker loss
        "spark.rapids.service.hardTimeoutMs": "25000",
        # one semaphore slot per worker: the abandoned wedged thread
        # keeps sleeping INSIDE its dispatch holding a slot — with
        # slots == workers the remaining workers keep flowing (a fixed
        # slot count below the worker count would stack semaphore wait
        # into RUNNING wall and cascade hard timeouts)
        "spark.rapids.sql.concurrentGpuTasks": str(max(1, concurrency)),
        # injected faults are NOT the query's fault — a strike budget
        # above the schedule's kill count keeps an innocent template
        # out of quarantine (quarantine is pinned by its own tier-1
        # tests, which inject repeat kills into ONE template)
        "spark.rapids.service.quarantine.maxStrikes": "8",
    }


def _chaos_conf(seed: int, concurrency: int) -> dict:
    conf = {"spark.rapids.test.faults": service_chaos_spec(seed)}
    conf.update(service_chaos_settings(concurrency))
    return conf


@contextmanager
def wedge_stall_env():
    """Arm the chaos wedge stall (SRT_WEDGE_SLEEP_S) for the scope of
    one chaos run, restoring whatever was there before — shared by both
    harnesses so the stall/hard-limit relationship cannot drift."""
    import os
    before = os.environ.get("SRT_WEDGE_SLEEP_S")
    os.environ["SRT_WEDGE_SLEEP_S"] = str(_CHAOS_WEDGE_SLEEP_S)
    try:
        yield
    finally:
        if before is None:
            os.environ.pop("SRT_WEDGE_SLEEP_S", None)
        else:
            os.environ["SRT_WEDGE_SLEEP_S"] = before


def drive_health_probes(svc, make_query, *, timeout_s: float,
                        max_probes: int = 4) -> int:
    """Prove return-to-HEALTHY after a chaos run: the DEGRADED latch
    pays down on COMPLETED queries, so a loss landing on the corpus
    tail leaves nothing to pay it — drive a few probe queries, exactly
    what live traffic would do. Returns probes driven. Callers skip
    this when submissions HUNG (the run already failed; waiting out
    probe timeouts would only delay the verdict)."""
    probes = 0
    while svc.health()["state"] == "DEGRADED" and probes < max_probes:
        try:
            hp = svc.submit(make_query(), tenant="health-probe",
                            tag=f"probe{probes}")
        except Exception:
            break  # probe template quarantined/shed: report as-is
        hp.wait(timeout=timeout_s)
        probes += 1
    return probes


def _percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (q in [0, 1]) of a non-empty list."""
    vals = sorted(values)
    idx = min(len(vals) - 1, max(0, int(round(q * (len(vals) - 1)))))
    return vals[idx]


def _scope_delta(before: dict, after: dict) -> dict:
    out = {}
    for k, v in after.items():
        d = v - before.get(k, 0)
        if d:
            out[k] = round(d, 4) if isinstance(d, float) else d
    return out


def run_loadtest(sf: float = 0.05, seed: int = 0, queries=None,
                 use_sql: bool = False, concurrency: int = 4,
                 tenants: int = 2, eventlog_dir: Optional[str] = None,
                 timeout_s: float = 600.0,
                 warmup_from: Optional[str] = None,
                 chaos: bool = False) -> dict:
    """Run the loadtest and return the JSON-ready report dict.
    ``report["ok"]`` is False when any result diverged from serial or
    any submission failed — callers exit non-zero on it.

    ``warmup_from``: an event-log dir to AOT-warm from first
    (``tools warmup`` in-process, sharing this run's tables/session so
    the executable cache warms by table identity) — the serial "cold"
    pass then measures warmed-cold latency; compare coldP95S against a
    run without warmup to price the warmup.

    ``chaos``: arm the seeded SERVICE-level fault schedule
    (:func:`service_chaos_spec` — worker crashes, device losses, a
    wedged dispatch) on the service session only. The run then asserts
    the survivability contract instead of all-finished: every
    submission reaches a TERMINAL state (zero hangs), every FINISHED
    result is bit-identical to the fault-free serial baseline, any
    failure carries a typed survivability error, recovery counters stay
    within SERVICE_CHAOS_BOUNDS, and the service health returns to
    HEALTHY. The report gains a ``chaos`` section with the schedule,
    fire counts, recovery counters and terminal-state census."""
    from spark_rapids_tpu.dispatch import COMPILE_SCOPE
    from spark_rapids_tpu.lint.golden import _load_scale_test
    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.plan.executable_cache import EXEC_CACHE
    from spark_rapids_tpu.runtime.faults import FAULTS
    from spark_rapids_tpu.runtime.health import HEALTH, QUARANTINE
    from spark_rapids_tpu.service import QueryService
    from spark_rapids_tpu.session import TpuSession

    st = _load_scale_test()
    specs = scale_test_specs(sf)
    tables = {name: spec.generate_table(sf, seed=seed)
              for name, spec in specs.items()}

    def _conf(extra=None):
        conf = dict(extra or {})
        if eventlog_dir:
            conf["spark.rapids.sql.eventLog.enabled"] = "true"
            conf["spark.rapids.sql.eventLog.dir"] = eventlog_dir
        return conf

    build = st.build_sql_queries if use_sql else st.build_queries

    # -- serial baseline: cold once + warm for the repeat submissions -------
    serial_session = TpuSession(_conf())

    warmup_report = None
    if warmup_from:
        from spark_rapids_tpu.tools.warmup import run_warmup
        warmup_report = run_warmup(
            warmup_from, sf=sf, seed=seed, use_sql=use_sql,
            tables=tables, session=TpuSession())

    scope_t0 = dict(COMPILE_SCOPE)
    serial_queries = build(serial_session, tables)
    wanted = [q for q in (queries or list(serial_queries))]
    expected: Dict[str, object] = {}
    serial_cold: Dict[str, float] = {}
    serial_warm: Dict[str, float] = {}
    for name in wanted:
        serial_session.next_query_tag = f"{name}_serial_cold"
        t0 = time.perf_counter()
        expected[name] = serial_queries[name]().collect_table()
        serial_cold[name] = time.perf_counter() - t0
        serial_session.next_query_tag = f"{name}_serial"
        t0 = time.perf_counter()
        serial_queries[name]().collect_table()
        serial_warm[name] = time.perf_counter() - t0
    serial_sum = (sum(serial_cold.values())
                  + (tenants - 1) * sum(serial_warm.values()))
    scope_serial = dict(COMPILE_SCOPE)

    # -- concurrent run through the service ---------------------------------
    n_submissions = len(wanted) * tenants
    svc_conf = {
        "spark.rapids.service.maxConcurrentQueries": str(concurrency),
        "spark.rapids.service.queueDepth": str(max(n_submissions, 64)),
    }
    from contextlib import ExitStack
    health_before = HEALTH.snapshot()
    chaos_env = ExitStack()
    if chaos:
        svc_conf.update(_chaos_conf(seed, concurrency))
        chaos_env.enter_context(wedge_stall_env())
    svc = QueryService(_conf(svc_conf))
    svc_queries = build(svc.session, tables)
    mismatches: List[str] = []
    failures: List[str] = []
    rejected: List[str] = []
    handles = []
    hung: List[str] = []
    svc_health_live = None
    health_probes = 0
    t0 = time.perf_counter()
    try:
        with svc:
            for t in range(tenants):
                for name in wanted:
                    label = f"{name}@tenant{t}"
                    try:
                        handles.append((name, f"tenant{t}", svc.submit(
                            svc_queries[name](), tenant=f"tenant{t}",
                            tag=label)))
                    except Exception as exc:
                        # under chaos a DEGRADED shed / quarantine can
                        # refuse admission — a typed rejection IS a
                        # terminal outcome, not a hang
                        if not chaos:
                            raise
                        rejected.append(
                            f"{label}: {type(exc).__name__}: {exc}")
            for name, tenant, h in handles:
                if not h.wait(timeout=timeout_s):
                    hung.append(
                        f"{name}@{tenant}: still {h.state} after "
                        f"{timeout_s}s")
                    failures.append(hung[-1])
            if chaos and not hung:
                health_probes = drive_health_probes(
                    svc, svc_queries[wanted[0]], timeout_s=timeout_s)
            # capture health while the pool is still up (post-shutdown
            # the workers have deregistered and workerCount reads 0)
            svc_health_live = svc.health()
    finally:
        chaos_fires = FAULTS.counters() if chaos else {}
        if chaos:
            FAULTS.disarm()
        chaos_env.close()
    wall = time.perf_counter() - t0
    scope_conc = dict(COMPILE_SCOPE)

    latencies, queue_waits, per_query = [], [], {}
    cache_hits = 0
    chaos_outcomes: List[dict] = []
    for name, tenant, h in handles:
        if h.state != "FINISHED":
            typed = type(h.error).__name__ in _CHAOS_TYPED_ERRORS
            if chaos and typed:
                # survivable terminal outcome: reported, not a failure
                chaos_outcomes.append({
                    "query": f"{name}@{tenant}", "state": h.state,
                    "error": f"{type(h.error).__name__}: {h.error}",
                    "requeues": h.requeues})
                continue
            failures.append(f"{name}@{tenant}: {h.state} ({h.error})")
            continue
        diff = st.tables_differ(expected[name], h.result_table)
        if diff is not None:
            mismatches.append(f"{name}@{tenant}: {diff}")
        latencies.append(h.latency_s)
        queue_waits.append(h.queue_wait_s or 0.0)
        cache_hits += 1 if h.cache_hit else 0
        entry = per_query.setdefault(name, {
            "serialColdS": round(serial_cold[name], 4),
            "serialWarmS": round(serial_warm[name], 4), "runs": []})
        entry["runs"].append({
            "tenant": tenant, "latencyS": round(h.latency_s, 4),
            "queueWaitS": round(h.queue_wait_s or 0.0, 4),
            "cacheHit": h.cache_hit, "identical": diff is None,
            "requeues": h.requeues})

    # compile-breakdown per phase: the serial pass traces every cold
    # shape (unless warmed); the concurrent pass repeats templates and
    # must trace NOTHING new — executable-cache hit rate 1.0 on the
    # queries it executed (result-cache serves never look up)
    serial_phase = _scope_delta(scope_t0, scope_serial)
    conc_phase = _scope_delta(scope_serial, scope_conc)
    conc_lookups = (conc_phase.get("executableCacheHits", 0)
                    + conc_phase.get("executableCacheMisses", 0))
    compile_report = {
        "serialPhase": serial_phase,
        "concurrentPhase": conc_phase,
        "repeatPassNewTraces": int(conc_phase.get("kernelTraces", 0)),
        # exact-tree checkouts / lookups: a burst of one query wider
        # than the variant's tree pool converts fresh (counted a miss)
        # but still shares every compiled kernel via its template
        "executableCacheHitRate": (
            round(conc_phase.get("executableCacheHits", 0)
                  / conc_lookups, 4) if conc_lookups else None),
        # template-known / lookups: the rate that governs TRACING —
        # 1.0 means no executed query saw an unknown template, so the
        # repeat pass compiles nothing (repeatPassNewTraces 0)
        "templateHitRate": (
            round((conc_phase.get("executableCacheHits", 0)
                   + conc_phase.get("executableCacheTemplateHits", 0))
                  / conc_lookups, 4) if conc_lookups else None),
        "executableCache": EXEC_CACHE.stats(),
    }
    cold_vals = list(serial_cold.values())
    warm_vals = list(serial_warm.values())

    # -- chaos verdicts ------------------------------------------------------
    chaos_report = None
    if chaos:
        health_after = HEALTH.snapshot()
        svc_stats = svc.stats()
        svc_health = svc_health_live or svc.health()
        recovery = {
            "deviceReinits": health_after["deviceReinits"]
            - health_before["deviceReinits"],
            "deviceLost": health_after["deviceLost"]
            - health_before["deviceLost"],
            "workersLost": svc_stats["workersLost"],
            "workersRespawned": svc_stats["workersRespawned"],
            "requeued": svc_stats["requeued"],
            "hardTimeouts": svc_stats["hardTimeouts"],
        }
        bounds_violations = [
            f"{k}={recovery[k]} exceeds the chaos bound {bound}"
            for k, bound in SERVICE_CHAOS_BOUNDS.items()
            if recovery.get(k, 0) > bound]
        returned_healthy = svc_health["state"] == "HEALTHY"
        chaos_report = {
            "faultSpec": service_chaos_spec(seed),
            "faultFires": chaos_fires,
            "recovery": recovery,
            "bounds": dict(SERVICE_CHAOS_BOUNDS),
            "boundsViolations": bounds_violations,
            "typedOutcomes": chaos_outcomes,
            "rejectedSubmissions": rejected,
            "hungSubmissions": hung,
            "quarantine": QUARANTINE.snapshot(),
            "healthAtEnd": svc_health,
            "healthProbes": health_probes,
            "returnedToHealthy": returned_healthy,
        }
        if bounds_violations:
            failures.extend(bounds_violations)
        if not returned_healthy:
            failures.append(
                f"service did not return to HEALTHY: {svc_health}")

    import jax
    report = {
        "mode": "loadtest",
        "scaleFactor": sf,
        "seed": seed,
        # which backend these numbers measured (a CPU-backend artifact
        # must say so in-band, not in prose)
        "backend": jax.default_backend(),
        "form": "sql" if use_sql else "dsl",
        "concurrency": concurrency,
        "tenants": tenants,
        "submissions": n_submissions,
        "wallClockS": round(wall, 4),
        "serialSumS": round(serial_sum, 4),
        "serialColdSumS": round(sum(serial_cold.values()), 4),
        "serialWarmSumS": round(sum(serial_warm.values()), 4),
        "coldP50S": round(_percentile(cold_vals, 0.50), 4)
        if cold_vals else None,
        "coldP95S": round(_percentile(cold_vals, 0.95), 4)
        if cold_vals else None,
        "warmP50S": round(_percentile(warm_vals, 0.50), 4)
        if warm_vals else None,
        "warmP95S": round(_percentile(warm_vals, 0.95), 4)
        if warm_vals else None,
        "warmup": warmup_report,
        "compile": compile_report,
        "chaos": chaos_report,
        "speedupVsSerial": round(serial_sum / wall, 3) if wall else None,
        "throughputQps": round(n_submissions / wall, 3) if wall else None,
        "latencyP50S": round(_percentile(latencies, 0.50), 4)
        if latencies else None,
        "latencyP95S": round(_percentile(latencies, 0.95), 4)
        if latencies else None,
        "queueWaitP50S": round(_percentile(queue_waits, 0.50), 4)
        if queue_waits else None,
        "queueWaitP95S": round(_percentile(queue_waits, 0.95), 4)
        if queue_waits else None,
        # over FINISHED submissions (the population hits can occur in),
        # matching the latency/queue-wait percentile population
        "cacheHitRate": round(cache_hits / len(latencies), 4)
        if latencies else None,
        "resultCache": (svc.result_cache.stats()
                        if svc.result_cache is not None else None),
        "service": svc.stats(),
        "allIdentical": not mismatches and not failures,
        "belowSerialSum": wall < serial_sum,
        "mismatches": mismatches,
        "failures": failures,
        "queries": per_query,
        # chaos mode: typed survivable outcomes and bounded recovery
        # are the CONTRACT, not failures — ok still requires zero
        # hangs, zero mismatches, zero untyped failures, bounds held,
        # and the service back at HEALTHY (folded into failures above)
        "ok": not mismatches and not failures,
    }
    return report


def render_loadtest(report: dict) -> str:
    lines = [
        f"Loadtest: {report['submissions']} submissions "
        f"({report['tenants']} tenants x "
        f"{len(report['queries'])} queries, {report['form']}) "
        f"at concurrency {report['concurrency']}",
        f"  wall clock      {report['wallClockS']:.3f}s  "
        f"(serial sum {report['serialSumS']:.3f}s, "
        f"speedup {report['speedupVsSerial']}x)",
        f"  throughput      {report['throughputQps']} q/s",
        f"  latency p50/p95 {report['latencyP50S']}s / "
        f"{report['latencyP95S']}s",
        f"  queue p50/p95   {report['queueWaitP50S']}s / "
        f"{report['queueWaitP95S']}s",
        f"  cache hit rate  {report['cacheHitRate']}",
        f"  cold p50/p95    {report['coldP50S']}s / {report['coldP95S']}s"
        + ("  (AOT-warmed)" if report.get("warmup") else ""),
        f"  repeat pass     {report['compile']['repeatPassNewTraces']} "
        f"new traces, executable-cache hit rate "
        f"{report['compile']['executableCacheHitRate']} "
        f"(template {report['compile']['templateHitRate']})",
        f"  all identical   {report['allIdentical']}",
    ]
    if report.get("warmup"):
        w = report["warmup"]
        lines.append(
            f"  warmup          {w['programsCompiled']} compiled / "
            f"{w['programsSkipped']} skipped in {w['wallS']:.2f}s "
            f"({w['newTraces']} traces)")
    if report.get("chaos"):
        c = report["chaos"]
        r = c["recovery"]
        lines.append(
            f"  chaos           fires {sum(c['faultFires'].values())} "
            f"({', '.join(f'{k}={v}' for k, v in sorted(c['faultFires'].items()))})")
        lines.append(
            f"    recovery      deviceReinits={r['deviceReinits']} "
            f"workersLost={r['workersLost']} respawned="
            f"{r['workersRespawned']} requeued={r['requeued']} "
            f"hardTimeouts={r['hardTimeouts']}")
        lines.append(
            f"    outcomes      {len(c['typedOutcomes'])} typed "
            f"non-finished, {len(c['rejectedSubmissions'])} rejected, "
            f"{len(c['hungSubmissions'])} hung; health at end: "
            f"{c['healthAtEnd']['state']}")
    if report["mismatches"]:
        lines.append("  MISMATCHES:")
        lines += [f"    {m}" for m in report["mismatches"]]
    if report["failures"]:
        lines.append("  FAILURES:")
        lines += [f"    {f}" for f in report["failures"]]
    return "\n".join(lines)
