"""Observability subsystem tests: unified metric registry + levels,
exec observation boundary (ESSENTIAL metrics), host span tracing +
Chrome trace export, the query event log (golden schema), and the
offline tools (profile report, A/B compare, CLI smoke)."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.ops.expr import col, lit
from spark_rapids_tpu.session import TpuSession


def _table_data(n=200):
    return {"k": np.array(["a", "b", "a", "c"] * (n // 4), dtype=object),
            "v": np.arange(n, dtype=np.int64)}


def _agg_df(s, n=200):
    df = s.create_dataframe(_table_data(n))
    return (df.filter(col("v") > lit(10))
            .group_by("k").agg(F.sum("v").alias("sv")))


def _exec_tree(session):
    from spark_rapids_tpu.lore import _iter_tree
    return list(_iter_tree(session._last_executable))


# ---------------------------------------------------------------------------
# metric registry
# ---------------------------------------------------------------------------


def test_metric_spec_conflict_raises():
    from spark_rapids_tpu.obs.metrics import register_metric
    register_metric("obsTestMetricA", "count", "MODERATE")
    register_metric("obsTestMetricA", "count", "MODERATE")  # idempotent
    with pytest.raises(ValueError):
        register_metric("obsTestMetricA", "timing", "MODERATE")
    with pytest.raises(ValueError):
        register_metric("obsTestMetricB", "weird", "MODERATE")


def test_metric_set_spec_level_and_typed():
    from spark_rapids_tpu.obs.metrics import (
        MetricSet,
        set_metrics_level,
        spec_for,
    )
    m = MetricSet()
    try:
        set_metrics_level("ESSENTIAL")
        m.add("opTime", 0.5)          # ESSENTIAL spec -> kept
        m.add("somethingTime", 1.0)   # inferred MODERATE -> dropped
        assert dict(m) == {"opTime": 0.5}
        set_metrics_level("MODERATE")
        m.add("somethingTime", 1.0)
        m.add("fooBytesRead", 3)
        t = m.typed()
        assert t["opTime"] == {"value": 0.5, "kind": "timing",
                               "level": "ESSENTIAL"}
        assert t["somethingTime"]["kind"] == "timing"
        assert t["fooBytesRead"]["kind"] == "bytes"
        assert spec_for("randomCounter").kind == "count"
    finally:
        set_metrics_level("MODERATE")


def test_metrics_level_applies_to_transitions():
    """DeviceToHost routes through the same level machinery as execs:
    at ESSENTIAL, its ESSENTIAL metrics survive and MODERATE exec
    metrics (scanCacheMiss) are dropped; at DEBUG everything records."""
    s = TpuSession({"spark.rapids.sql.metrics.level": "ESSENTIAL"})
    _agg_df(s).collect_table()
    tree = _exec_tree(s)
    d2h = tree[0]
    assert "d2hTime" in d2h.metrics
    assert "numOutputRows" in d2h.metrics
    all_metrics = set().union(*(t.metrics for t in tree))
    assert "scanCacheMiss" not in all_metrics  # MODERATE, dropped

    s2 = TpuSession({"spark.rapids.sql.metrics.level": "DEBUG"})
    _agg_df(s2).collect_table()
    all2 = set().union(*(t.metrics for t in _exec_tree(s2)))
    assert "scanCacheMiss" in all2


def test_every_exec_emits_essential_metrics():
    from spark_rapids_tpu.execs.base import DeviceToHost, TpuExec
    from spark_rapids_tpu.lint.registry_audit import audit_exec_metrics_tree
    from spark_rapids_tpu.obs.metrics import ESSENTIAL_EXEC_METRICS
    from spark_rapids_tpu.obs.spans import finalize_observation
    s = TpuSession()
    out = _agg_df(s).collect_table()
    assert out.num_rows == 3
    finalize_observation(s._last_executable)
    tree = _exec_tree(s)
    execs = [e for e in tree if isinstance(e, (TpuExec, DeviceToHost))]
    assert len(execs) >= 3
    for e in execs:
        for k in ESSENTIAL_EXEC_METRICS:
            assert k in e.metrics, (type(e).__name__, k, dict(e.metrics))
    # the positive side of the RA-ESSENTIAL-METRICS audit
    diags = []
    audit_exec_metrics_tree(s._last_executable, diags)
    assert diags == []
    # row counts are real, not placeholders: the scan saw all 200 rows
    scan = [e for e in execs if type(e).__name__ == "TpuScanExec"
            or "Scan" in type(e).__name__]
    assert scan and scan[0].metrics["numOutputRows"] == 200


def test_subsystem_scopes_record():
    from spark_rapids_tpu.obs.metrics import metric_scope
    before = dict(metric_scope("shuffle"))
    s = TpuSession({
        "spark.rapids.shuffle.localDeviceSplit.enabled": "false"})
    df = s.create_dataframe(_table_data(80), num_batches=2)
    df.repartition(4, "k").group_by("k").agg(
        F.count("v").alias("c")).collect_table()
    after = dict(metric_scope("shuffle"))
    assert after.get("shuffleBytesWritten", 0) > before.get(
        "shuffleBytesWritten", 0)
    assert after.get("shuffleBytesRead", 0) > before.get(
        "shuffleBytesRead", 0)


# ---------------------------------------------------------------------------
# spans + chrome trace
# ---------------------------------------------------------------------------


def test_chrome_trace_export_schema(tmp_path):
    s = TpuSession({"spark.rapids.trace.enabled": "true",
                    "spark.rapids.trace.dir": str(tmp_path)})
    _agg_df(s).collect_table()
    path = tmp_path / "query_0.trace.json"
    assert path.exists()
    trace = json.loads(path.read_text())
    events = trace["traceEvents"]
    assert events, "empty trace"
    names = set()
    for ev in events:
        assert ev["ph"] in ("X", "M")
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] >= 0
            assert isinstance(ev["name"], str) and ev["name"]
            assert isinstance(ev["cat"], str)
            names.add(ev["name"])
        else:
            assert ev["name"] == "thread_name"
    # exec boundaries, phases and the d2h transfer all show up
    assert "TpuHashAggregateExec" in names or any(
        "Aggregate" in n for n in names)
    assert {"plan", "execute", "collect"} <= names
    assert "DeviceToHost" in names


def test_tracer_disabled_is_default_and_cheap():
    import jax
    from spark_rapids_tpu.obs.spans import TRACER, span
    assert TRACER.enabled is False
    # no profiler session, no envelope: the range IS the annotation —
    # nothing else is allocated, nothing is recorded
    rng = span("nothing", cat="op")
    assert type(rng) is jax.profiler.TraceAnnotation
    with rng:
        pass
    assert TRACER._spans == []
    s = TpuSession()
    _agg_df(s).collect_table()
    assert TRACER.enabled is False
    assert type(span("after", cat="op")) is jax.profiler.TraceAnnotation
    # the thread left its query: later ranges carry no query index
    assert getattr(TRACER._tls, "query", None) is None


def test_span_union_seconds():
    from spark_rapids_tpu.obs.spans import union_seconds
    assert union_seconds([]) == 0.0
    assert union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert union_seconds([(0, 5), (1, 2)]) == pytest.approx(5.0)


def _sql_session(tmp_path, **conf):
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path), **conf})
    s.create_dataframe(_table_data(400), num_batches=2) \
        .create_or_replace_temp_view("t")
    return s


_SQL = "select k, sum(v) as sv, count(*) as c from t where v > 10 group by k"

#: the ranges of one query, inside its srt.query (parse runs in sql(),
#: before execute() opens the query)
_QUERY_RANGES = ("srt.phase.configure", "srt.phase.plan", "srt.phase.arm",
                 "srt.phase.execute", "srt.phase.collect",
                 "srt.phase.account", "srt.phase.observe",
                 "srt.fetch.resolve", "srt.fetch.wait", "srt.fetch.unpack",
                 "srt.wait.semaphore", "srt.eventlog.write")


def _xprof_query(s, run, trace_dir):
    """Run ``run()`` under a jax.profiler session; the /host:CPU plane
    of its .xplane.pb."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                        recursive=True)
    host = [p for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU"]
    assert host, "no /host:CPU plane in the trace"
    return host[0]


def test_srt_spans_lie_on_the_profilers_host_timeline(tmp_path):
    """One primitive, two sinks: with a jax.profiler session running,
    the engine's ranges are events of /host:CPU in the .xplane.pb — the
    clock the device planes share — named srt.<cat>.<name>, each inside
    the srt.query of its thread and carrying the query index."""
    s = _sql_session(tmp_path)
    s.sql(_SQL).collect_table()  # warm: keep compiles out of the trace
    host = _xprof_query(s, lambda: s.sql(_SQL).collect_table(),
                        str(tmp_path / "xprof"))
    qidx = s.last_event_record["queryIndex"]
    by_line = {}
    for line in host.lines:
        # a collection opens srt.gc.gen<N> on whichever thread it falls
        # (test_a_collection_inside_a_query_is_a_range_and_a_count)
        events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                   dict(e.stats)) for e in line.events
                  if e.name.startswith("srt.")
                  and not e.name.startswith("srt.gc.")]
        if events:
            by_line[line.name] = events
    assert len(by_line) == 1, f"srt ranges on lines {sorted(by_line)}"
    (events,) = by_line.values()
    names = [e[0] for e in events]
    queries = [e for e in events if e[0] == "srt.query"]
    assert len(queries) == 1
    _, q0, q1, qstats = queries[0]
    assert qstats.get("query") == qidx
    for want in _QUERY_RANGES:
        assert want in names, f"{want} missing from {sorted(set(names))}"
    assert any(n.startswith("srt.dispatch.") for n in names)
    assert any(n.startswith("srt.exec.") for n in names)
    assert "srt.dispatch.kernel" not in names
    parse = [e for e in events if e[0] == "srt.phase.parse"]
    assert len(parse) == 1 and parse[0][2] <= q0, \
        "sql() lowers the statement before execute() opens the query"
    for name, t0, t1, stats in events:
        if name == "srt.phase.parse":
            continue
        assert q0 <= t0 and t1 <= q1, f"{name} outside its srt.query"
        assert stats.get("query") == qidx, (name, stats)


def _collect_forcing_gc(monkeypatch):
    """The collect phase runs Python's collector once (generation 2)."""
    import gc

    from spark_rapids_tpu.columnar import HostTable
    concat = HostTable.concat

    def collecting_concat(batches):
        gc.collect()
        return concat(batches)

    monkeypatch.setattr(HostTable, "concat", staticmethod(collecting_concat))


def test_a_collection_inside_a_query_is_a_range_and_a_count(
        tmp_path, monkeypatch):
    """Python's collector, seen by the program: a collection forced
    inside a query is the range srt.gc.gen2 inside that query's
    srt.query, on its thread and carrying its index, and its seconds are
    the record's phasesS.gcS."""
    from spark_rapids_tpu.obs import spans

    s = _sql_session(tmp_path)
    s.sql(_SQL).collect_table()
    assert s.last_event_record["phasesS"]["gcS"] >= 0
    _collect_forcing_gc(monkeypatch)
    host = _xprof_query(s, lambda: s.sql(_SQL).collect_table(),
                        str(tmp_path / "xprof"))
    rec = s.last_event_record
    (line,) = [ln for ln in host.lines
               if any(e.name == "srt.query" for e in ln.events)]
    (query,) = [e for e in line.events if e.name == "srt.query"]
    gen2 = [e for e in line.events if e.name == "srt.gc.gen2"]
    assert len(gen2) >= 1
    for e in gen2:
        assert query.start_ns <= e.start_ns
        assert e.start_ns + e.duration_ns <= query.start_ns \
            + query.duration_ns
        assert dict(e.stats).get("query") == rec["queryIndex"]
    in_query = sum(e.duration_ns for e in line.events
                   if e.name.startswith("srt.gc.")
                   and query.start_ns <= e.start_ns
                   and e.start_ns < query.start_ns + query.duration_ns)
    # the counter and the ranges time the same collections: the counter
    # reads inside the ranges' bounds, rounded to the microsecond
    assert rec["phasesS"]["gcS"] > 0
    assert rec["phasesS"]["gcS"] <= in_query / 1e9 + 1e-5
    assert rec["phasesS"]["gcS"] <= rec["wallS"]
    assert spans.gc_seconds() > 0


def test_the_collector_hook_is_installed_once(tmp_path):
    import gc

    from spark_rapids_tpu.obs import spans
    for i in range(2):
        s = _sql_session(tmp_path / str(i))
        s.sql(_SQL).collect_table()
        assert gc.callbacks.count(spans._on_gc) == 1
    spans.install_gc_hook()
    assert gc.callbacks.count(spans._on_gc) == 1


def test_gc_seconds_are_the_collecting_threads():
    """A collection counts on the thread that ran it (the one whose
    allocation set it off), as the phase accumulators do: another
    thread's collection is not this thread's query's gcS."""
    import gc
    import threading

    from spark_rapids_tpu.obs import spans
    spans.install_gc_hook()
    was_enabled = gc.isenabled()
    gc.disable()  # only the explicit collection below runs
    try:
        before = spans.gc_seconds()
        seen = {}

        def other():
            start = spans.gc_seconds()
            gc.collect()
            seen["other"] = spans.gc_seconds() - start

        t = threading.Thread(target=other)
        t.start()
        t.join(timeout=60)
        assert seen["other"] > 0
        assert spans.gc_seconds() == before
        gc.collect(0)
        assert spans.gc_seconds() > before
    finally:
        if was_enabled:
            gc.enable()


def _tpu_jit_sites():
    """(file:line, name) of every tpu_jit(...) call in the package, the
    name None where it is not a string literal."""
    import ast
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "spark_rapids_tpu")
    sites = []
    for dirpath, _dirs, files in os.walk(root):
        for fname in sorted(files):
            if not fname.endswith(".py"):
                continue
            path = os.path.join(dirpath, fname)
            for node in ast.walk(ast.parse(open(path).read())):
                if isinstance(node, ast.Call) and getattr(
                        node.func, "id", getattr(node.func, "attr", "")
                        ) == "tpu_jit":
                    lit_ = [k.value.value for k in node.keywords
                            if k.arg == "name"
                            and isinstance(k.value, ast.Constant)]
                    sites.append((
                        f"{os.path.relpath(path, root)}:{node.lineno}",
                        lit_[0] if lit_ else None))
    return sorted(sites)


_TPU_JIT_SITES = _tpu_jit_sites()


def test_every_tpu_jit_site_is_found():
    assert len(_TPU_JIT_SITES) >= 41
    # the sliced aggregate and the aggregate on a mesh's shards are
    # programs of their own on the device timeline
    assert {"agg_fast", "agg_fast_sliced", "agg_fast_mesh",
            "agg_fast_group"} <= {
        n for _, n in _TPU_JIT_SITES}


@pytest.mark.parametrize("site,name", _TPU_JIT_SITES,
                         ids=[s for s, _ in _TPU_JIT_SITES])
def test_tpu_jit_site_names_its_program(site, name):
    """The name is the XLA module (jit_<name>), the srt.dispatch.<name>
    range and the op of the dispatch fault points: a short, stable,
    unique literal — never the old catch-all `kernel`."""
    import re
    assert isinstance(name, str), f"{site}: name= is not a string literal"
    assert re.fullmatch(r"[a-z0-9_]+", name), (site, name)
    assert name != "kernel"
    assert [n for _, n in _TPU_JIT_SITES].count(name) == 1, \
        f"{site}: {name!r} names more than one program"


def test_tpu_jit_names_the_xla_module():
    import jax.numpy as jnp
    from spark_rapids_tpu.dispatch import tpu_jit

    def kernel(x):
        return x + 1

    fn = tpu_jit(kernel, name="plus_one")
    assert fn.__wrapped__.lower(jnp.arange(4)).as_text().startswith(
        "module @jit_plus_one")
    assert kernel.__name__ == "kernel"  # wrapped, not renamed in place
    assert int(fn(jnp.arange(4))[0]) == 1


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------


#: budgetPeak is the memory arbiter's PROCESS-WIDE peak — earlier tests
#: in the same process move it, so the golden pins presence, not value
_VOLATILE_INT_KEYS = {"dispatches", "hostSyncs", "spanCount", "tid",
                      "budgetPeak"}

#: scopes whose per-query delta depends on PROCESS WARMTH, not the
#: query (the compile scope reports kernelTraces on a cold process and
#: kernelTraceCacheHits on a warm one — both correct, neither golden)
_VOLATILE_SCOPES = {"compile"}


def _normalize(obj, key=None):
    """Normalize volatile values (timings, counters that shift with the
    engine's dispatch strategy) so the golden pins SCHEMA + stable
    semantics, not wall-clock noise."""
    if isinstance(obj, dict):
        if key == "scopes":
            obj = {k: v for k, v in obj.items()
                   if k not in _VOLATILE_SCOPES}
        return {k: _normalize(v, k) for k, v in sorted(obj.items())}
    if isinstance(obj, list):
        return [_normalize(v) for v in obj]
    if isinstance(obj, bool):
        return obj
    if isinstance(obj, float):
        return 0.0
    if isinstance(obj, int) and key in _VOLATILE_INT_KEYS:
        return 0
    return obj


def _run_eventlog_query(tmp_path, tag="golden"):
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path)})
    s.next_query_tag = tag
    _agg_df(s).collect_table()
    return s


def test_event_log_written_and_valid(tmp_path):
    s = _run_eventlog_query(tmp_path)
    assert s.last_event_path and os.path.exists(s.last_event_path)
    lines = open(s.last_event_path).read().strip().splitlines()
    assert len(lines) == 1
    rec = json.loads(lines[0])
    # schema v16: phasesS gains gcS (the query thread's seconds in
    # Python's collector); v15: joinS (the join execs' build and probe
    # batches); v14: relandS (mesh re-lands); v13: coalesceS
    # (the coalesce exec's multi-batch flushes); v12: the tracing PR added hostSyncs and the
    # dispatch / sync / fetch / semaphore seconds under phasesS (tested
    # below);
    # v11: the streaming PR added the streaming-scope deltas
    # (microBatches / mvRefreshes / mvIncrementalRefreshes /
    # mvFullRecomputes / sinkCommits / sinkReplays — all 0 on a
    # stream-free process) and mvEpoch (null unless the record serves
    # a materialized view) on top of v10's out-of-core fields, v9's
    # hostScans, v8's multi-host fault-domain fields, v7's mesh
    # fault-domain fields, v6's mesh-native fields, v5's
    # transactional-write fields and v4's survivability fields — see
    # obs/events.py
    assert rec["schema"] == 16
    assert rec["healthState"] == "HEALTHY"
    assert rec["quarantined"] is False
    assert rec["deviceReinits"] == 0 and rec["workerRestarts"] == 0
    assert rec["filesWritten"] == 0 and rec["bytesWritten"] == 0
    assert rec["commitRetries"] == 0
    assert rec["meshShape"] is None
    assert rec["iciBytes"] == 0 and rec["shardSkew"] == 0.0
    assert rec["meshDegradations"] == 0
    assert rec["shardRetries"] == 0 and rec["gatherChecksFailed"] == 0
    assert rec["hostTopology"] is None
    assert rec["hostsLost"] == 0 and rec["hostRelands"] == 0
    assert rec["dcnExchanges"] == 0
    assert rec["hostScans"] == {}
    assert rec["oomRetries"] == 0 and rec["splitRetries"] == 0
    assert rec["spillBytes"] == 0 and rec["unspills"] == 0
    assert isinstance(rec["budgetPeak"], int) and rec["budgetPeak"] >= 0
    assert rec["microBatches"] == 0 and rec["mvRefreshes"] == 0
    assert rec["mvIncrementalRefreshes"] == 0
    assert rec["mvFullRecomputes"] == 0
    assert rec["sinkCommits"] == 0 and rec["sinkReplays"] == 0
    assert rec["mvEpoch"] is None
    assert rec["event"] == "queryCompleted"
    assert rec["queryTag"] == "golden"
    assert rec["wallS"] > 0
    assert rec["spans"]["attributedS"] > 0
    assert rec["tenant"] is None and rec["pool"] is None
    assert rec["queueWaitS"] is None and rec["cacheHit"] is False
    # a fresh session over a fresh table: no cached executable to hit,
    # compileMs/padWasteRows present and typed
    assert rec["executableCacheHit"] is False
    assert isinstance(rec["compileMs"], float) and rec["compileMs"] >= 0
    assert isinstance(rec["padWasteRows"], int) and rec["padWasteRows"] >= 0
    # per-op metrics are typed in the plan tree
    agg = rec["plan"]["children"][0]
    assert agg["metrics"]["opTime"]["kind"] == "timing"
    assert agg["metrics"]["numOutputRows"]["value"] == 3


def test_event_log_golden_schema(tmp_path):
    """Golden record: normalized timings, byte-stable schema. A failure
    here means the event-log record shape changed — bump
    EVENT_SCHEMA_VERSION, regenerate tests/golden_eventlog.json (this
    test prints the new normalized record on mismatch) and check the
    offline tools still read it.

    Schema history: v1 = the PR-4 record; v2 = query-service fields
    (tenant, pool, queueWaitS, cacheHit — null/false when the query ran
    outside the service; a cache-hit serve replays the filling run's
    record with cacheHit=true and its own queueWaitS/wallS); v3 =
    serving-latency fields (compileMs — wall spent on new XLA traces,
    0.0 fully warm; executableCacheHit — the query checked out a cached
    converted executable; padWasteRows — dead rows padding batches to
    their capacity buckets; result-cache serves carry 0.0/false/0);
    v4 = survivability fields (healthState — HEALTHY/DEGRADED/CPU_ONLY
    at record time; quarantined — the template carries poison strikes;
    deviceReinits/workerRestarts — per-record deltas of the health
    scope's recovery counters, 0 on a quiet process);
    v5 = transactional-write fields (filesWritten/bytesWritten — data
    files the committer promoted during this query's wall and their
    bytes; commitRetries — Delta optimistic commits rebased after
    losing the version race; per-record deltas of the write scope,
    all 0 for read-only queries and result-cache serves);
    v6 = mesh-native execution fields (meshShape — the active device
    mesh topology, null off-mesh; iciBytes — payload bytes through ICI
    all-to-all collectives, a per-record delta of the mesh scope;
    shardSkew — max per-shard map-output max/median over the query's
    collective exchanges, measured from real shard live counts;
    result-cache serves carry serve-time meshShape and 0/0.0);
    v7 = mesh fault-domain fields (meshDegradations — degradation-
    ladder demotions during this query's wall, a health-scope delta;
    shardRetries / gatherChecksFailed — local re-gathers paid and
    checksum validations tripped at mesh gather boundaries, mesh-scope
    deltas; all 0 on a healthy mesh and for result-cache serves);
    v8 = multi-host fault-domain fields (hostTopology — the active
    cluster host topology at record time, '2' full / '1/2' degraded /
    '0/2' latched single-process, null off-cluster; hostsLost /
    hostRelands / dcnExchanges — executor hosts declared lost, lost
    hosts' shards re-landed onto survivors, and collectives that
    crossed the DCN axis during this query's wall — per-record deltas
    of the cluster scope; all 0/null off-cluster and for result-cache
    serves);
    v9 = flight-recorder fields (hostScans — per-executor-host scan
    attribution merged from cluster scan replies: {host: {scans,
    files, bytes, wallS, execWallS, crcRetries}}; {} off-cluster, for
    local-fallback scans and for result-cache serves — a cached serve
    dispatches nothing);
    v10 = out-of-core fields (oomRetries / splitRetries / spillBytes /
    unspills — per-record deltas of the memory scope: spill-and-replay
    retries survived, split-and-retry escalations, device bytes freed
    by spill demotions, spilled batches re-landed; all 0 on an
    unbudgeted quiet process and for result-cache serves; budgetPeak —
    the memory arbiter's peak accounted device bytes, absolute and
    process-wide, normalized in the golden);
    v11 = streaming fields (microBatches / mvRefreshes /
    mvIncrementalRefreshes / mvFullRecomputes / sinkCommits /
    sinkReplays — per-record deltas of the streaming scope: micro-batch
    executions, materialized-view refreshes split by maintenance
    strategy, and the exactly-once sink's commits and deduped replays;
    all 0 on a stream-free process and zeroed on result-cache serves;
    mvEpoch — the Delta version a served materialized view reflects,
    null for everything that is not an MV serve);
    v12 = tracing fields (hostSyncs — blocking device->host fetches:
    host_fetch calls plus root-result resolves, normalized in the
    golden like dispatches; phasesS gains dispatchS / syncWaitS /
    fetchWaitS / fetchUnpackS / semaphoreWaitS — host seconds taken
    where the work happens, inside executeS + collectS — and parseS
    for queries that came through sql());
    v13 = phasesS gains coalesceS (host seconds inside the coalesce
    exec's multi-batch flushes, the range srt.coalesce.flush; its
    jit_coalesce dispatch counts in dispatchS too; 0.0 where every
    coalesce passed its batches on);
    v14 = phasesS gains relandS (host seconds inside mesh re-lands, the
    range srt.mesh.reland; 0.0 where no sharded batch was gathered to
    one device);
    v15 = phasesS gains joinS (host seconds inside the join execs'
    ranges srt.join.build and srt.join.batch; 0.0 for a query without
    a join);
    v16 = phasesS gains gcS (host seconds the query's thread spent in
    Python's collector, each collection also the range srt.gc.gen<N>;
    0.0 for a query no collection interrupted).
    Exec metrics in the plan tree are no schema fields (no bump): every
    TpuHashAggregateExec node carries partialCountReads (partials whose
    row count the streaming loop read to shrink them) and runAheadWaits
    (times its run-ahead bound waited), slicedAggBatches (batches the
    fast kernel walked in slices inside one program) and aggSlices (the
    slices they held), meshAggBatches (batches aggregated on a mesh's
    resident shards) and meshAggShards (the shards they held),
    groupedAggPrograms (programs enqueued for two or more resident
    batches) and groupedAggBatches (the batches they held), all 0 for
    the golden's single-batch aggregate."""
    s = _run_eventlog_query(tmp_path)
    got = _normalize(s.last_event_record)
    golden_path = os.path.join(os.path.dirname(__file__),
                               "golden_eventlog.json")
    golden = json.load(open(golden_path))
    assert got == golden, (
        "event-log record drifted from the golden schema; new normalized "
        "record:\n" + json.dumps(got, indent=1, sort_keys=True))


@pytest.mark.parametrize("slice_rows,batches,slices", [
    (1024, 3, 6), (2048, 0, 0)], ids=["two-slices-a-batch", "one-slice"])
def test_record_counts_the_sliced_aggregates(tmp_path, monkeypatch,
                                             slice_rows, batches, slices):
    """A streamed aggregate over three 2,048-row batches: with a slice of
    1,024 rows each batch is one `agg_fast_sliced` dispatch over two
    slices and the record's plan tree says so; with a slice of the
    batch's own capacity nothing is sliced and both counts read 0."""
    from spark_rapids_tpu.execs import aggregate as A
    from spark_rapids_tpu.plan import from_host_table
    from tests.asserts import plan_metric_total
    from tests.data_gen import DoubleGen, StringGen, gen_table
    monkeypatch.setattr(A, "AGG_SLICE", slice_rows)
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path),
                    "spark.rapids.sql.batchSizeBytes": "1024"})
    table = gen_table({"k": StringGen(cardinality=5),
                       "d": DoubleGen(nullable=False)}, 4500, 3)
    from_host_table(table, s, 3).group_by("k").agg(
        F.count().alias("n"), F.min("d").alias("m")).collect_table()
    rec = s.last_event_record
    assert plan_metric_total(s, "partialAggBatches") == 3
    assert plan_metric_total(s, "slicedAggBatches") == batches
    assert plan_metric_total(s, "aggSlices") == slices
    on_disk = [json.loads(line) for line in open(s.last_event_path)]
    assert on_disk[-1]["plan"] == rec["plan"]


@pytest.mark.parametrize("cap,programs,held", [(4, 1, 4), (1, 0, 0)],
                         ids=["grouped", "one-at-a-time"])
def test_record_counts_the_grouped_aggregates(tmp_path, monkeypatch, cap,
                                              programs, held):
    """A streamed aggregate over four cached batches, warm: with a cap of
    4 they are ONE `agg_fast_group` dispatch and the record's plan tree
    says so, in memory and on disk; with a cap of 1 each is its own
    `agg_fast` as before and both counts read 0."""
    from spark_rapids_tpu.execs import aggregate as A
    from tests.asserts import plan_metric_total
    from tests.data_gen import DoubleGen, StringGen, gen_table
    monkeypatch.setattr(A, "AGG_GROUP", cap)
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path),
                    "spark.rapids.sql.batchSizeBytes": "1024"})
    table = gen_table({"k": StringGen(cardinality=5),
                       "d": DoubleGen(nullable=False)}, 6000, 3)
    s.create_dataframe(table, num_batches=4) \
        .create_or_replace_temp_view("grouped_rec")
    df = s.table("grouped_rec").group_by("k").agg(
        F.count().alias("n"), F.min("d").alias("m"))
    df.collect_table()            # uploads: nothing resident yet
    assert plan_metric_total(s, "groupedAggPrograms") == 0
    df.collect_table()
    rec = s.last_event_record
    assert plan_metric_total(s, "partialAggBatches") == 4
    assert plan_metric_total(s, "groupedAggPrograms") == programs
    assert plan_metric_total(s, "groupedAggBatches") == held
    on_disk = [json.loads(line) for line in open(s.last_event_path)]
    assert on_disk[-1]["plan"] == rec["plan"]


_NEW_PHASES = ("parseS", "dispatchS", "syncWaitS", "fetchWaitS",
               "fetchUnpackS", "semaphoreWaitS", "coalesceS", "relandS",
               "joinS", "gcS")


def _check_phases(rec):
    ph = rec["phasesS"]
    for key in _NEW_PHASES + ("planS", "executeS", "collectS"):
        assert key in ph and ph[key] >= 0, (key, ph)
    # taken where the work happens, inside execute + collect, and
    # disjoint from one another (rounded to the microsecond each)
    inside = sum(ph[k] for k in ("dispatchS", "syncWaitS", "fetchWaitS",
                                 "fetchUnpackS"))
    assert inside <= ph["executeS"] + ph["collectS"] + 1e-5, ph
    assert ph["planS"] + ph["executeS"] + ph["collectS"] \
        <= rec["wallS"] + 1e-5
    assert ph["dispatchS"] > 0 and rec["dispatches"] >= 1
    # the root DeviceToHost's resolve is a host sync
    assert rec["hostSyncs"] >= 1


def test_record_holds_the_phases_taken_inside_the_program(tmp_path):
    s = _sql_session(tmp_path)
    s.sql(_SQL).collect_table()
    s.sql(_SQL).collect_table()
    rec = s.last_event_record
    _check_phases(rec)
    on_disk = [json.loads(line) for line in open(s.last_event_path)]
    assert on_disk[-1]["phasesS"] == rec["phasesS"]
    assert on_disk[-1]["hostSyncs"] == rec["hostSyncs"]
    # a DataFrame-built query was never parsed: no parseS, the rest stay
    _agg_df(s).collect_table()
    built = s.last_event_record["phasesS"]
    assert "parseS" not in built
    assert set(_NEW_PHASES[1:]) <= set(built)
    # and tools report carries the new count through
    from spark_rapids_tpu.tools import build_profile, load_events
    report = build_profile(load_events(str(tmp_path)))
    assert all(q["hostSyncs"] >= 1 for q in report["queries"])


def test_phase_accumulators_are_per_thread(tmp_path):
    """The _ThreadCounter contract: a query's dispatch / sync / fetch
    seconds and host-sync count accumulate on the thread that executes
    it, so two queries on two threads never share them."""
    import threading
    import time

    from spark_rapids_tpu import dispatch

    # the accumulators themselves
    dispatch.reset_query_phases()
    gate = threading.Barrier(2, timeout=60)
    seen = {}

    def other():
        dispatch.reset_query_phases()
        with dispatch.phase_span("syncWaitS", "host_fetch", "sync"):
            time.sleep(0.01)
        dispatch.count_host_sync()
        gate.wait()
        seen["other"] = (dispatch.phase_seconds(),
                         dispatch.host_fetch_count())

    t = threading.Thread(target=other)
    t.start()
    gate.wait()
    t.join(timeout=60)
    assert not t.is_alive()
    assert seen["other"][0]["syncWaitS"] >= 0.01
    assert seen["other"][1] == 1
    assert dispatch.phase_seconds() == dict.fromkeys(dispatch.PHASE_KEYS,
                                                     0.0)
    assert dispatch.host_fetch_count() == 0

    # and two whole queries in flight at once
    s = _sql_session(tmp_path)
    for _ in range(3):  # the first runs size the plan's intermediates
        s.sql(_SQL).collect_table()
    serial = s.last_event_record
    start = threading.Barrier(2, timeout=60)
    recs = {}

    def run(i):
        start.wait()
        for _ in range(3):
            s.sql(_SQL).collect_table()
        recs[i] = s.last_event_record  # this thread's own

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert sorted(recs) == [0, 1]
    for rec in recs.values():
        _check_phases(rec)
        assert rec["hostSyncs"] == serial["hostSyncs"]
        assert rec["dispatches"] == serial["dispatches"]


def test_event_log_disabled_writes_nothing(tmp_path):
    s = TpuSession({"spark.rapids.sql.eventLog.dir": str(tmp_path)})
    _agg_df(s).collect_table()
    assert s.last_event_path is None
    assert list(tmp_path.iterdir()) == []


def test_sql_text_recorded(tmp_path):
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path)})
    s.create_dataframe(_table_data()).create_or_replace_temp_view("t")
    s.sql("SELECT k, SUM(v) AS sv FROM t GROUP BY k").collect_table()
    rec = s.last_event_record
    assert "SUM(v)" in rec["sqlText"]


def test_worker_thread_attribution_meets_floor(tmp_path):
    """A query executed from a NON-main thread must attribute its wall
    time against the EXECUTING thread's spans (PR 4 unioned main-thread
    intervals, under-attributing every off-main-thread query — the
    query service runs all queries off-main)."""
    import threading

    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path)})
    _agg_df(s).collect_table()  # warm: compile noise off the floor
    box = {"covs": []}

    def run():
        # best of three: the attribution BUG this pins (main-thread
        # interval union -> ~0 coverage off-main) fails every run; a
        # millisecond scheduler hiccup on a ~15ms query only fails one
        for _ in range(3):
            _agg_df(s).collect_table()
            rec = s.last_event_record  # thread-local, not a mirror
            box["covs"].append(rec["spans"]["attributedS"]
                               / rec["wallS"])

    t = threading.Thread(target=run, name="obs-worker")
    t.start()
    t.join(timeout=120)
    assert len(box["covs"]) == 3
    cov = max(box["covs"])
    assert cov >= 0.95, f"off-main-thread span coverage {cov:.3f} < 0.95"


def test_concurrent_queries_write_distinct_records(tmp_path):
    """Two sessions' queries executing CONCURRENTLY from worker threads
    must produce self-consistent records (no cross-thread span or
    envelope bleed): every record attributes >= 95% of its own wall."""
    import threading

    sessions = [
        TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path)})
        for _ in range(2)]
    for s in sessions:  # warm: measure attribution, not XLA compiles
        _agg_df(s, n=400).collect_table()
    covs = {0: [], 1: []}

    def run(i):
        # best of three per session (see the off-main-thread test: the
        # pinned bug fails every run, scheduler noise only one)
        for _ in range(3):
            _agg_df(sessions[i], n=400).collect_table()
            rec = sessions[i].last_event_record
            covs[i].append(rec["spans"]["attributedS"] / rec["wallS"])

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    for i in (0, 1):
        assert len(covs[i]) == 3
        cov = max(covs[i])
        assert cov >= 0.95, f"session {i} coverage {cov:.3f} < 0.95"


def test_nested_query_rides_outer_envelope(tmp_path):
    """A broadcast-join query materializes its build side through a
    nested execute; only ONE record per top-level query is written."""
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path),
                    "spark.rapids.sql.broadcastSizeBytes": str(1 << 20)})
    left = s.create_dataframe(_table_data(100))
    right = s.create_dataframe({"k": np.array(["a", "b"], dtype=object),
                                "w": np.array([1, 2], dtype=np.int64)})
    left.join(right, on=["k"], how="inner").collect_table()
    lines = open(s.last_event_path).read().strip().splitlines()
    assert len(lines) == 1
    assert json.loads(lines[0])["queryIndex"] == 0


# ---------------------------------------------------------------------------
# offline tools
# ---------------------------------------------------------------------------


def _two_runs(tmp_path):
    dir_a, dir_b = tmp_path / "a", tmp_path / "b"
    for d in (dir_a, dir_b):
        s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                        "spark.rapids.sql.eventLog.dir": str(d)})
        s.next_query_tag = "q"
        _agg_df(s).collect_table()
    return str(dir_a), str(dir_b)


def test_tools_profile_report(tmp_path):
    from spark_rapids_tpu.tools import (
        build_profile,
        load_events,
        render_profile,
    )
    s = _run_eventlog_query(tmp_path, tag="q1")
    report = build_profile(load_events(str(tmp_path)))
    assert report["queryCount"] == 1
    q = report["queries"][0]
    assert q["query"] == "q1"
    att = q["attribution"]
    assert 0.0 < att["coverage"] <= 1.0
    assert att["attributedS"] + att["untrackedS"] == pytest.approx(
        q["wallS"], rel=0.01)
    b = q["breakdown"]
    assert b["wallS"] == pytest.approx(
        b["computeS"] + b["transferS"] + b["shuffleS"] + b["spillS"]
        + b["untrackedS"], rel=0.01)
    tops = q["topOpsBySelfTime"]
    assert tops and all(t["selfTimeS"] >= 0 for t in tops)
    # self times nest under total: sum of self <= wall-ish envelope
    assert sum(t["selfTimeS"] for t in tops) <= q["wallS"] * 1.05
    text = render_profile(report)
    assert "Top operators by self time" in text
    assert "q1" in text
    del s


def test_tools_compare(tmp_path):
    from spark_rapids_tpu.tools import build_compare, render_compare
    dir_a, dir_b = _two_runs(tmp_path)
    cmp = build_compare(dir_a, dir_b)
    assert cmp["matchedQueries"] == 1
    q = cmp["queries"][0]
    assert q["query"] == "q"
    assert q["aWallS"] > 0 and q["bWallS"] > 0
    common = [e for e in q["ops"] if e["status"] == "common"]
    assert common, "no matched ops"
    assert all("deltaOpTimeS" in e for e in common)
    assert q["newFallbacks"] == [] and q["resolvedFallbacks"] == []
    text = render_compare(cmp)
    assert "Matched queries: 1" in text


def test_tools_schema_mismatch_rejected(tmp_path):
    from spark_rapids_tpu.tools import load_events
    p = tmp_path / "bad.jsonl"
    p.write_text(json.dumps({"schema": 99, "event": "queryCompleted"})
                 + "\n")
    with pytest.raises(ValueError, match="schema"):
        load_events(str(p))


def test_tools_cli_smoke(tmp_path):
    """The acceptance smoke: run q1 (golden corpus), analyze its event
    log through the real CLI."""
    import scale_test
    from spark_rapids_tpu.lint.golden import golden_tables
    tables = golden_tables(0.005)
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path)})
    queries = scale_test.build_queries(s, tables)
    s.next_query_tag = "q1"
    queries["q1"]().collect_table()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "profile",
         str(tmp_path)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert out.returncode in (0, 2), out.stderr
    assert "Queries: 1" in out.stdout
    assert "q1" in out.stdout
    out_json = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "profile",
         "--json", str(tmp_path)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    report = json.loads(out_json.stdout)
    assert report["queryCount"] == 1


def test_lore_stripped_exec_keeps_metricset():
    """A LORE-dumped exec must round-trip with a usable MetricSet —
    add_metric on the replayed exec would crash on a plain dict."""
    import pickle

    from spark_rapids_tpu.execs.basic import TpuScanExec
    from spark_rapids_tpu.lore import _strip_for_pickle
    from spark_rapids_tpu.obs.metrics import MetricSet
    s = TpuSession()
    _agg_df(s).collect_table()
    scan = [e for e in _exec_tree(s)
            if isinstance(e, TpuScanExec)][0]
    clone = pickle.loads(pickle.dumps(_strip_for_pickle(scan)))
    assert isinstance(clone.metrics, MetricSet)
    clone.add_metric("scanRows", 5)
    assert clone.metrics["scanRows"] == 5


def test_tools_compare_aggregates_duplicate_tags(tmp_path):
    """Three warm runs per tag compare as medians, not last-run-wins."""
    from spark_rapids_tpu.tools import build_compare
    dirs = []
    for sub in ("a", "b"):
        d = tmp_path / sub
        s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                        "spark.rapids.sql.eventLog.dir": str(d)})
        for _ in range(3):
            s.next_query_tag = "q"
            _agg_df(s).collect_table()
        dirs.append(str(d))
    cmp = build_compare(*dirs)
    q = cmp["queries"][0]
    assert q["aRuns"] == 3 and q["bRuns"] == 3
    assert q["aWallMinS"] <= q["aWallS"]


# ---------------------------------------------------------------------------
# overhead guard
# ---------------------------------------------------------------------------


def test_disabled_observability_leaves_no_span_state():
    """With event log and tracing off (the default), executing queries
    must not accumulate span state or enable the tracer."""
    from spark_rapids_tpu.obs.spans import TRACER
    s = TpuSession()
    for _ in range(3):
        _agg_df(s).collect_table()
    assert TRACER.enabled is False
    assert TRACER._spans == []
