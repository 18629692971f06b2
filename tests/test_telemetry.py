"""Tier-1 slice of the cluster flight recorder (ISSUE 14).

The full closure is ``python scale_test.py --hosts 2 --chaos`` (q1-q22
with executor-span/trace, per-host profile and incident-bundle
assertions); this slice keeps every mechanism exercised in the tier-1
gate without the corpus cost:

* telemetry ring: sampler delta correctness, bounded ring, JSONL
  export, the background sampler thread;
* flight recorder: one bundle per host-ladder action (with the
  triggering fault point, rung and telemetry tail), quarantine-strike
  bundles through the conf-less default path, bundle pruning to
  maxBundles;
* cross-host trace propagation: a 2-host THREAD-mode cluster scan
  merges executor-lane spans into the driver's Chrome trace and
  attributes per-host scans bit-exactly in the v9 event record
  (hostScans), CRC retries attributed to the corrupted host;
* live introspection: `tools top` over a real QueryService's loopback
  endpoint (subprocess smoke) + the rolling SLO surface;
* `tools incident` subprocess smoke over recorded bundles;
* `tools compare`/`profile` accept OLDER event schemas with one
  warning instead of crashing on mixed-version dirs.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from spark_rapids_tpu.conf import RapidsConf

pytestmark = [pytest.mark.chaos]

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_obs_state():
    """Telemetry/flight-recorder/ladder state is PROCESS state —
    restore all of it so the rest of the suite sees defaults (the
    test_hosts hygiene pattern)."""
    from spark_rapids_tpu.obs.telemetry import TELEMETRY
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER, FAULTS
    from spark_rapids_tpu.runtime.health import HEALTH, QUARANTINE
    from spark_rapids_tpu.session import TpuSession

    def reset():
        FAULTS.disarm()
        CIRCUIT_BREAKER.reset()
        HEALTH.reset()
        QUARANTINE.reset()
        CLUSTER.restore()
        TELEMETRY.configure(RapidsConf({}))  # recorder defaults too
        TELEMETRY.reset()

    reset()
    yield
    reset()
    # leave the process-wide cluster (and mesh) OFF for the suite
    TpuSession().placement.prepare()


# ---------------------------------------------------------------------------
# telemetry ring
# ---------------------------------------------------------------------------


def test_sampler_delta_correctness():
    """Each sample carries the per-scope DELTAS since the previous
    sample plus the health/topology view; an idle interval records no
    phantom movement."""
    from spark_rapids_tpu.obs.metrics import metric_scope
    from spark_rapids_tpu.obs.telemetry import TELEMETRY
    scope = metric_scope("ttestScope")
    base = TELEMETRY.sample_once()
    assert base is not None
    scope.add("ttestCounter", 5)
    s1 = TELEMETRY.sample_once()
    assert s1["deltas"]["ttestScope"]["ttestCounter"] == 5
    assert s1["health"] in ("HEALTHY", "DEGRADED", "CPU_ONLY")
    assert "meshShape" in s1 and "hostTopology" in s1
    assert isinstance(s1["t"], float)
    s2 = TELEMETRY.sample_once()
    assert "ttestScope" not in s2["deltas"]  # nothing moved


def test_ring_bounded_export_and_background_thread(tmp_path):
    """The ring drops oldest past ringSize, exports as JSONL, and the
    conf-driven background thread actually samples."""
    from spark_rapids_tpu.obs.telemetry import TELEMETRY
    TELEMETRY.configure(RapidsConf({
        "spark.rapids.obs.telemetry.ringSize": "5"}))
    for _ in range(9):
        TELEMETRY.sample_once()
    tail = TELEMETRY.tail()
    assert len(tail) == 5
    assert TELEMETRY.tail(2) == tail[-2:]
    path = TELEMETRY.export_jsonl(str(tmp_path / "tele.jsonl"))
    lines = open(path).read().strip().splitlines()
    assert len(lines) == 5
    assert all("deltas" in json.loads(ln) for ln in lines)
    # background sampler: enabled -> samples accrue without any query
    TELEMETRY.configure(RapidsConf({
        "spark.rapids.obs.telemetry.enabled": "true",
        "spark.rapids.obs.telemetry.intervalMs": "20",
        "spark.rapids.obs.telemetry.ringSize": "5"}))
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if TELEMETRY.stats()["samples"] >= 3:
            break
        time.sleep(0.02)
    assert TELEMETRY.stats()["samples"] >= 3
    assert TELEMETRY.stats()["errors"] == 0
    TELEMETRY.configure(RapidsConf({}))  # thread stops


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_bundle_per_host_ladder_action(tmp_path):
    """Every on_host_loss invocation dumps one bundle carrying the
    triggering fault point, the ladder rung taken, topology, and the
    telemetry tail."""
    from spark_rapids_tpu.errors import HostLostError
    from spark_rapids_tpu.obs.telemetry import TELEMETRY
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    from spark_rapids_tpu.runtime.health import HEALTH
    CLUSTER.configure(RapidsConf({
        "spark.rapids.cluster.enabled": "true",
        "spark.rapids.cluster.hosts": "2"}))
    conf = RapidsConf({
        "spark.rapids.obs.flightRecorder.dir": str(tmp_path)})
    TELEMETRY.sample_once()  # something for the tail
    exc = HostLostError("injected host loss at host.dispatch",
                        host_id="h1")
    assert HEALTH.on_host_loss(exc, conf) == "retry"
    assert HEALTH.on_host_loss(exc, conf) == "reland"
    from spark_rapids_tpu.tools.incident import load_bundles
    bundles = [b for b in load_bundles(str(tmp_path))
               if b["kind"] == "host.ladder"]
    assert [b["action"] for b in bundles] == ["retry", "reland"]
    b = bundles[-1]
    assert b["faultPoint"] == "host.dispatch"
    assert b["errorType"] == "HostLostError"
    assert b["health"]["hostLadder"]["hostsLost"] == 2
    assert "h1" in b["cluster"]["lostHosts"]
    assert isinstance(b["telemetry"]["tail"], list)
    assert b["telemetry"]["tail"], "telemetry tail missing"
    assert "host.ladder" in os.path.basename(b["_path"])


def test_flight_recorder_quarantine_carries_demotions(tmp_path):
    """The conf-less trigger site (QUARANTINE.strike) lands its bundles
    in the PROCESS-configured recorder dir (the one the last
    TELEMETRY.configure saw), and a bundle's demotions are the exec
    circuit breaker's."""
    from spark_rapids_tpu.errors import KernelCrashError
    from spark_rapids_tpu.obs.telemetry import TELEMETRY
    from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER
    from spark_rapids_tpu.runtime.health import QUARANTINE
    TELEMETRY.configure(RapidsConf({
        "spark.rapids.obs.flightRecorder.dir": str(tmp_path)}))
    assert CIRCUIT_BREAKER.record_failure(
        "TpuSortExec", KernelCrashError("injected kernel crash"), 1)
    assert QUARANTINE.strike("fp-ttest", "killed a worker", 2) is False
    assert QUARANTINE.strike("fp-ttest", "killed another", 2) is True
    from spark_rapids_tpu.tools.incident import load_bundles
    # strike bundles dump ASYNC (the strike site runs under the
    # scheduler's condition lock) — wait until both are written whole
    deadline = time.monotonic() + 20.0
    bundles = []
    while time.monotonic() < deadline:
        if os.listdir(tmp_path):
            bundles = load_bundles(str(tmp_path))
            if len(bundles) >= 2 and all(
                    b["kind"] != "unreadable" for b in bundles):
                break
        time.sleep(0.02)
    kinds = [(b["kind"], b["action"]) for b in bundles]
    assert ("quarantine", "strike") in kinds
    assert ("quarantine", "quarantined") in kinds
    for b in bundles:
        assert list(b["demotions"]) == ["TpuSortExec"]
        assert "circuit breaker" in b["demotions"]["TpuSortExec"]


def test_flight_recorder_prunes_to_max_bundles(tmp_path):
    from spark_rapids_tpu.obs.telemetry import record_incident
    conf = RapidsConf({
        "spark.rapids.obs.flightRecorder.dir": str(tmp_path),
        "spark.rapids.obs.flightRecorder.maxBundles": "3"})
    paths = [record_incident("ttest", f"a{i}", f"r{i}", conf=conf)
             for i in range(5)]
    assert all(paths)
    left = sorted(os.listdir(tmp_path))
    assert len(left) == 3
    # newest survive
    assert os.path.basename(paths[-1]) in left
    assert os.path.basename(paths[0]) not in left


def test_flight_recorder_disabled_records_nothing(tmp_path):
    from spark_rapids_tpu.obs.telemetry import record_incident
    conf = RapidsConf({
        "spark.rapids.obs.flightRecorder.enabled": "false",
        "spark.rapids.obs.flightRecorder.dir": str(tmp_path)})
    assert record_incident("ttest", "a", "r", conf=conf) is None
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------------
# cross-host trace propagation (2-host THREAD-mode cluster)
# ---------------------------------------------------------------------------


@pytest.fixture()
def thread_cluster(tmp_path_factory):
    """Driver + 2 thread-mode executors (the cheap protocol harness)
    over a 4-file parquet corpus."""
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.io.parquet import write_parquet
    from spark_rapids_tpu.runtime.cluster import (
        CLUSTER,
        ClusterDriver,
        spawn_executor,
    )
    base = tmp_path_factory.mktemp("tele_corpus")
    n = 400
    t = HostTable.from_pydict({
        "k": [f"k{i % 5}" for i in range(n)],
        "v": np.arange(n, dtype=np.int64)})
    for i in range(4):
        write_parquet(t.slice(i * 100, 100), str(base / f"c{i:03d}"))
    driver = ClusterDriver(2, RapidsConf({}))
    executors = [spawn_executor(driver.address, f"h{i}", mode="thread")
                 for i in range(2)]
    driver.wait_ready(2, timeout_s=30.0)
    CLUSTER.attach_driver(driver)
    yield str(base)
    CLUSTER.attach_driver(None)
    driver.shutdown()
    for h in executors:
        h.terminate()


def _cluster_session(tmp_path, extra=None):
    from spark_rapids_tpu.session import TpuSession
    conf = {"spark.rapids.cluster.enabled": "true",
            "spark.rapids.cluster.hosts": "2",
            "spark.rapids.sql.eventLog.enabled": "true",
            "spark.rapids.sql.eventLog.dir": str(tmp_path / "ev"),
            "spark.rapids.trace.enabled": "true",
            "spark.rapids.trace.dir": str(tmp_path / "tr")}
    conf.update(extra or {})
    return TpuSession(conf)


def test_cross_host_span_merge_and_host_scan_attribution(
        thread_cluster, tmp_path):
    """The core propagation contract: a cluster-routed scan's event
    record attributes every dispatch/frame/byte to its executor host
    BIT-EXACTLY (2 hosts x 2 files each, bytes = the landed TPAK
    frames), and the Chrome trace carries the driver's per-host
    cluster.scan spans plus the executor-lane spans merged from the
    replies."""
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    s = _cluster_session(tmp_path)
    before = dict(scopes_snapshot().get("cluster", {}))
    out = s.read_parquet(thread_cluster).collect_table()
    assert out.num_rows == 400
    after = dict(scopes_snapshot().get("cluster", {}))
    assert after.get("hostShardsLanded", 0) - before.get(
        "hostShardsLanded", 0) == 4

    rec = s.last_event_record
    scans = rec["hostScans"]
    assert sorted(scans) == ["h0", "h1"]
    for host in ("h0", "h1"):
        st = scans[host]
        assert st["scans"] == 1
        assert st["files"] == 2  # 4 files split contiguously over 2
        assert st["bytes"] > 0
        assert st["wallS"] >= st["execWallS"] > 0
        assert st["crcRetries"] == 0
    # bit-exact: the frames landed ARE the frames attributed
    assert sum(st["files"] for st in scans.values()) == 4

    trace = json.loads(open(os.path.join(
        str(tmp_path / "tr"),
        f"query_{rec['queryIndex']}.trace.json")).read())
    events = trace["traceEvents"]
    cluster_spans = [e for e in events if e["name"] == "cluster.scan"]
    assert {e["args"]["host"] for e in cluster_spans} == {"h0", "h1"}
    lanes = {e["args"]["name"] for e in events if e["ph"] == "M"
             and str(e["args"].get("name", "")).startswith("executor-")}
    assert lanes == {"executor-h0", "executor-h1"}
    # per file: one decode span + one pack span, per executor
    exec_spans = [e for e in events if e.get("cat") == "exec-scan"]
    assert len(exec_spans) == 8
    assert {e["name"] for e in exec_spans} == {"executor.scan.file",
                                               "executor.pack"}
    # remote spans stay OFF the attribution thread: coverage intact
    assert rec["spans"]["attributedS"] / rec["wallS"] >= 0.5


def test_crc_retry_attributed_to_the_corrupt_host(thread_cluster,
                                                  tmp_path):
    """A corrupt shard landing's CRC retry shows up against the host
    whose frame was damaged."""
    s = _cluster_session(tmp_path, {
        "spark.rapids.test.faults": "host.shard.land:corrupt:1:3"})
    s.read_parquet(thread_cluster).collect_table()
    rec = s.last_event_record
    retries = {h: st["crcRetries"] for h, st in rec["hostScans"].items()}
    assert sum(retries.values()) == 1, retries


# ---------------------------------------------------------------------------
# live introspection + tools smokes
# ---------------------------------------------------------------------------


def _svc_query(svc):
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.ops.expr import col, lit
    df = svc.session.create_dataframe({
        "k": np.array(["a", "b"] * 40, dtype=object),
        "v": np.arange(80, dtype=np.int64)})
    return (df.filter(col("v") > lit(3))
            .group_by("k").agg(F.sum("v").alias("sv")))


def test_tools_top_over_live_service(tmp_path):
    """Subprocess smoke: `tools top` polls a real service's loopback
    endpoint and renders health + SLOs + telemetry."""
    from spark_rapids_tpu.service import QueryService
    with QueryService({
            "spark.rapids.service.introspect.enabled": "true",
            "spark.rapids.obs.telemetry.enabled": "true",
            "spark.rapids.obs.telemetry.intervalMs": "50"}) as svc:
        assert svc.introspect_port
        q = _svc_query(svc)
        for tenant in ("alice", "bob"):
            svc.submit(q, tenant=tenant).result(timeout=120)
        slo = svc.slo_snapshot()
        assert slo["pools"]["default"]["count"] == 2
        assert set(slo["tenants"]) == {"default/alice", "default/bob"}
        assert slo["pools"]["default"]["latency"]["p95S"] >= \
            slo["pools"]["default"]["latency"]["p50S"] >= 0
        assert svc.query_table() == []  # nothing live between queries
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        out = subprocess.run(
            [sys.executable, "-m", "spark_rapids_tpu.tools", "top",
             "--port", str(svc.introspect_port)],
            capture_output=True, text=True, env=env, cwd=_REPO_ROOT,
            timeout=120)
        assert out.returncode == 0, out.stderr
        assert "Service: HEALTHY" in out.stdout
        assert "pool   default" in out.stdout
        assert "Telemetry: on" in out.stdout
        out_json = subprocess.run(
            [sys.executable, "-m", "spark_rapids_tpu.tools", "top",
             "--port", str(svc.introspect_port), "--json"],
            capture_output=True, text=True, env=env, cwd=_REPO_ROOT,
            timeout=120)
        doc = json.loads(out_json.stdout)
        assert doc["stats"]["finished"] == 2
        assert doc["slo"]["pools"]["default"]["count"] == 2
    # unreachable endpoint -> exit 1 with a pointer, not a traceback
    gone = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "top",
         "--port", str(svc.introspect_port)],
        capture_output=True, text=True, env=env, cwd=_REPO_ROOT,
        timeout=120)
    assert gone.returncode == 1
    assert "introspect" in gone.stderr


def test_tools_incident_subprocess_smoke(tmp_path):
    from spark_rapids_tpu.obs.telemetry import record_incident
    conf = RapidsConf({
        "spark.rapids.obs.flightRecorder.dir": str(tmp_path)})
    p = record_incident(
        "host.ladder", "reland",
        "HostLostError: injected host loss at host.dispatch",
        conf=conf)
    assert p
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "incident",
         str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=_REPO_ROOT,
        timeout=120)
    assert out.returncode == 0, out.stderr
    assert "Incident bundles: 1" in out.stdout
    assert "kind=host.ladder action=reland" in out.stdout
    assert "faultPoint=host.dispatch" in out.stdout
    assert "trigger: HostLostError" in out.stdout
    assert "telemetry tail:" in out.stdout
    out_json = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "incident",
         "--json", str(tmp_path)],
        capture_output=True, text=True, env=env, cwd=_REPO_ROOT,
        timeout=120)
    bundles = json.loads(out_json.stdout)
    assert len(bundles) == 1 and bundles[0]["action"] == "reland"
    # a missing dir is a clean exit 1, not a stack trace
    missing = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "incident",
         str(tmp_path / "nope")],
        capture_output=True, text=True, env=env, cwd=_REPO_ROOT,
        timeout=120)
    assert missing.returncode == 1


def test_tools_accept_older_schemas_with_one_warning(tmp_path, capsys):
    """Satellite: mixed-version event-log dirs load with a single
    warning — per-version fields default to 0/absent — instead of a
    KeyError/ValueError crash (`tools compare` over logs written
    before an engine upgrade)."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.ops.expr import col, lit
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools import build_compare
    from spark_rapids_tpu.tools.report import build_profile, load_events

    def run(d):
        s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                        "spark.rapids.sql.eventLog.dir": str(d)})
        s.next_query_tag = "q"
        df = s.create_dataframe({"k": np.array(["a", "b"] * 20,
                                               dtype=object),
                                 "v": np.arange(40, dtype=np.int64)})
        (df.filter(col("v") > lit(1)).group_by("k")
         .agg(F.sum("v").alias("s"))).collect_table()
        return s.last_event_record

    rec = run(tmp_path / "b")
    # an OLD (v8-era) record: no hostScans field, schema 8
    old = {k: v for k, v in rec.items() if k != "hostScans"}
    old["schema"] = 8
    os.makedirs(tmp_path / "a")
    with open(tmp_path / "a" / "events-old.jsonl", "w") as f:
        f.write(json.dumps(old) + "\n")
    capsys.readouterr()
    records = load_events(str(tmp_path / "a"))
    assert len(records) == 1
    err = capsys.readouterr().err
    assert err.count("older event schema") == 1
    # both tools run over the mixed pair without crashing
    cmp = build_compare(str(tmp_path / "a"), str(tmp_path / "b"))
    assert cmp["matchedQueries"] == 1
    prof = build_profile(load_events(str(tmp_path / "a")))
    assert prof["queryCount"] == 1
    assert prof["hostResilience"]["perHost"] == {}
    # a FUTURE schema still refuses loudly
    with open(tmp_path / "a" / "events-future.jsonl", "w") as f:
        f.write(json.dumps({**old, "schema": 99}) + "\n")
    with pytest.raises(ValueError, match="schema"):
        load_events(str(tmp_path / "a"))
