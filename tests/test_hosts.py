"""Tier-1 multi-host slice: the driver/executor protocol and the host
fault domain (runtime/cluster.py).

The full closure is ``python scale_test.py --hosts 2 --chaos`` (q1-q22
through N executor subprocesses under the seeded host.* schedule with
a scripted mid-corpus SIGKILL + rejoin — MULTIHOST_r01); this
marker-gated slice keeps every host recovery mechanism exercised in
the tier-1 gate without the corpus cost:

* 2 REAL executor subprocesses scanning their by-host file
  assignments, bit-identical to a single-process scan over the same
  files (and the v8 event-log hostTopology field);
* injected host losses (``device_lost`` at a ``host.*`` point) walking
  the ladder retry -> re-land-on-survivors, converging bit-identically
  with the loss visible in the health surfaces;
* corrupt shard landings caught by the TPAK CRC and re-landed;
* a real SIGKILL: the heartbeat machinery declares the host lost, a
  respawned executor REJOINS through the registration path, and the
  topology returns to full strength;
* missed-beat sweep eviction (the wedged-but-connected path);
* typed-error classification: HostLostError vs MeshDeviceLostError vs
  whole-backend DeviceLostError, and the full ladder walk down to the
  single-process latch + escalation;
* RL-FAULT-POINT covers the ``host.*`` domain in both directions;
* ``scale_test.py validate_flags`` rejects the --hosts combos the
  harness does not implement.
"""

import os
import time

import numpy as np
import pytest

from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER, FAULTS

pytestmark = [pytest.mark.multihost, pytest.mark.chaos]

_HB_MS = 200


@pytest.fixture(autouse=True)
def _clean_host_fault_state():
    """Host chaos mutates PROCESS state (fault registry, breaker,
    health ladders, cluster topology, quarantine) — restore all of it
    so the rest of the suite sees a healthy full-strength process."""
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    from spark_rapids_tpu.runtime.health import HEALTH, QUARANTINE
    from spark_rapids_tpu.session import TpuSession
    FAULTS.disarm()
    CIRCUIT_BREAKER.reset()
    HEALTH.reset()
    QUARANTINE.reset()
    CLUSTER.restore()
    yield
    FAULTS.disarm()
    CIRCUIT_BREAKER.reset()
    HEALTH.reset()
    QUARANTINE.reset()
    CLUSTER.restore()
    # leave the process-wide cluster (and mesh) OFF for the suite
    TpuSession().placement.prepare()


def _wait_for(predicate, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.05)
    return predicate()


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """A small parquet table split across 4 files (row slices in
    order) — real by-host partitioning work for 2 hosts."""
    from spark_rapids_tpu.columnar import HostTable
    from spark_rapids_tpu.io.parquet import write_parquet
    base = tmp_path_factory.mktemp("hosts_corpus")
    n = 600
    t = HostTable.from_pydict({
        "k": [f"k{i % 7}" for i in range(n)],
        "v": np.arange(n, dtype=np.int64),
        "x": np.arange(n, dtype=np.float64) * 0.5,
    })
    chunk = n // 4
    for i in range(4):
        length = chunk if i < 3 else n - 3 * chunk
        write_parquet(t.slice(i * chunk, length),
                      str(base / f"c{i:03d}"))
    return str(base)


@pytest.fixture(scope="module")
def cluster2():
    """Driver + 2 REAL executor subprocesses, registered and attached
    (the 2-process sim harness, shared across this module's tests).
    The missed-beat window is huge on purpose — the driver process
    runs jax compiles that hold the GIL for seconds, and a spurious
    eviction would flake the module; real kills are detected through
    the beat-connection EOF path, which this window does not gate."""
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.runtime.cluster import (
        CLUSTER,
        ClusterDriver,
        spawn_executor,
    )
    driver = ClusterDriver(2, RapidsConf({
        "spark.rapids.cluster.heartbeatIntervalMs": str(_HB_MS),
        "spark.rapids.cluster.missedBeats": "150",
    }))
    executors = {f"h{i}": spawn_executor(driver.address, f"h{i}",
                                         heartbeat_ms=_HB_MS,
                                         mode="process")
                 for i in range(2)}
    driver.wait_ready(2, timeout_s=90.0)
    CLUSTER.attach_driver(driver)
    yield driver, executors
    CLUSTER.attach_driver(None)
    driver.shutdown()
    for h in executors.values():
        try:
            h.terminate()
        except Exception:
            pass


def _session(extra=None):
    from spark_rapids_tpu.session import TpuSession
    conf = {"spark.rapids.cluster.enabled": "true",
            "spark.rapids.cluster.hosts": "2",
            "spark.rapids.cluster.heartbeatIntervalMs": str(_HB_MS),
            "spark.rapids.cluster.missedBeats": "150"}
    conf.update(extra or {})
    return TpuSession(conf)


def _agg(s, corpus):
    from spark_rapids_tpu import functions as F
    return (s.read_parquet(corpus).group_by("k")
            .agg(F.sum("v").alias("sv"), F.sum("x").alias("sx"),
                 F.count("v").alias("n")))


def _cluster_scope():
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    return dict(scopes_snapshot().get("cluster", {}))


def test_two_process_scan_bit_identity(cluster2, corpus, tmp_path):
    """The core sim-harness contract: a scan fanned out to 2 executor
    SUBPROCESSES reassembles byte-identically to a local scan of the
    same files — and the v8 event record carries the host topology."""
    import scale_test as st
    from spark_rapids_tpu.session import TpuSession
    single = TpuSession()
    expected_scan = single.read_parquet(corpus).collect_table()
    expected_agg = _agg(single, corpus).collect_table()

    s = _session({"spark.rapids.sql.eventLog.enabled": "true",
                  "spark.rapids.sql.eventLog.dir": str(tmp_path)})
    before = _cluster_scope()
    got_scan = s.read_parquet(corpus).collect_table()
    assert st.tables_differ(expected_scan, got_scan) is None
    got_agg = _agg(s, corpus).collect_table()
    assert st.tables_differ(expected_agg, got_agg) is None
    after = _cluster_scope()
    # one batch per file, every file through an executor
    assert after.get("hostShardsLanded", 0) - before.get(
        "hostShardsLanded", 0) == 8
    rec = s.last_event_record
    assert rec["schema"] == 16
    assert rec["hostTopology"] == "2"
    assert rec["hostsLost"] == 0 and rec["hostRelands"] == 0


def test_injected_host_loss_walks_ladder_and_recovers(cluster2, corpus,
                                                      monkeypatch):
    """device_lost at a host.* point raises the typed HostLostError
    and the ladder walks retry -> re-land-on-survivors: the query
    converges bit-identically, the loss is visible in the health
    surfaces, and the provably-alive host is restored by the sweep."""
    import scale_test as st
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    from spark_rapids_tpu.runtime.health import HEALTH
    from spark_rapids_tpu.session import TpuSession
    expected = _agg(TpuSession(), corpus).collect_table()
    s = _session({
        "spark.rapids.test.faults": "host.dispatch:device_lost:2:3",
        "spark.rapids.sql.runtimeFallback.enabled": "true"})
    before = _cluster_scope()
    # the sweep restores a marked host that still beats within 100 ms:
    # held off during the query, or it may restore the host before the
    # replay's scan re-lands its slice (then no re-land to count)
    driver, _ = cluster2
    monkeypatch.setattr(driver, "sweep_once", lambda: [])
    got = _agg(s, corpus).collect_table()
    monkeypatch.undo()
    assert st.tables_differ(expected, got) is None
    snap = HEALTH.host_snapshot()
    assert snap["hostsLost"] == 2  # retry rung + reland rung
    after = _cluster_scope()
    assert after.get("hostsLost", 0) - before.get("hostsLost", 0) >= 1
    assert after.get("hostRelands", 0) - before.get(
        "hostRelands", 0) >= 1
    # the marked host's executor never died: the sweep restores it on
    # evidence of health (beating, open channels)
    assert _wait_for(
        lambda: not CLUSTER.health_snapshot()["lostHosts"], 20.0), \
        CLUSTER.health_snapshot()


def test_corrupt_shard_landing_caught_and_relanded(cluster2, corpus):
    """A corrupted host shard frame trips the TPAK CRC at the
    host.shard.land boundary and re-lands from the intact received
    frame instead of feeding the scan garbage rows."""
    import scale_test as st
    from spark_rapids_tpu.session import TpuSession
    expected = _agg(TpuSession(), corpus).collect_table()
    s = _session({
        "spark.rapids.test.faults": "host.shard.land:corrupt:2:5"})
    before = _cluster_scope()
    got = _agg(s, corpus).collect_table()
    assert st.tables_differ(expected, got) is None
    after = _cluster_scope()
    assert after.get("hostShardRetries", 0) - before.get(
        "hostShardRetries", 0) == 2


def test_kill_rejoin_restore(cluster2, corpus):
    """A real SIGKILL: the heartbeat machinery declares the host lost
    promptly (beat-connection EOF), scans re-land its shards onto the
    survivor bit-identically, and a respawned executor REJOINS through
    the registration path — topology back at full strength."""
    import scale_test as st
    from spark_rapids_tpu.runtime.cluster import CLUSTER, spawn_executor
    from spark_rapids_tpu.session import TpuSession
    driver, executors = cluster2
    expected = _agg(TpuSession(), corpus).collect_table()

    executors["h1"].terminate()
    assert _wait_for(
        lambda: "h1" in CLUSTER.health_snapshot()["lostHosts"], 30.0), \
        CLUSTER.health_snapshot()
    before = _cluster_scope()
    got = _agg(_session(), corpus).collect_table()
    assert st.tables_differ(expected, got) is None
    after = _cluster_scope()
    assert after.get("hostRelands", 0) - before.get(
        "hostRelands", 0) >= 1
    assert CLUSTER.topology_str() == "1/2"

    executors["h1"] = spawn_executor(driver.address, "h1",
                                     heartbeat_ms=_HB_MS,
                                     mode="process")
    assert _wait_for(
        lambda: not CLUSTER.health_snapshot()["lostHosts"], 60.0), \
        CLUSTER.health_snapshot()
    assert CLUSTER.topology_str() == "2"
    got2 = _agg(_session(), corpus).collect_table()
    assert st.tables_differ(expected, got2) is None


def test_missed_beat_sweep_declares_host_lost():
    """The wedged-but-connected path: an executor that registered but
    stops beating is evicted by the missed-beat sweep and its host
    declared lost (no sockets involved — the ledger half alone)."""
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.runtime.cluster import CLUSTER, ClusterDriver
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.shuffle.transport import PeerInfo
    drv = ClusterDriver(3, RapidsConf({
        "spark.rapids.cluster.heartbeatIntervalMs": "100",
        "spark.rapids.cluster.missedBeats": "2"}))
    try:
        # a 3-host topology: h2 exists only in this driver's ledger,
        # so the module cluster's sweep (h2 never beats there, no data
        # channel) cannot auto-restore it as provably alive
        _session({"spark.rapids.cluster.hosts": "3"}).placement.prepare()
        drv._hb.register_executor(PeerInfo(executor_id="h2"))
        time.sleep(0.5)  # > missedBeats * interval
        # the driver's own sweeper (or this explicit sweep — whichever
        # wins the race) must have evicted the silent executor and
        # declared its host lost
        drv.sweep_once()
        assert _wait_for(
            lambda: "h2" in CLUSTER.health_snapshot()["lostHosts"], 10.0)
    finally:
        drv.shutdown()


def test_typed_error_classification():
    """host.* device_lost raises HostLostError — a DeviceLostError
    (the service requeue machinery applies) but NOT the mesh's partial
    loss, and carrying the host attribution the ladder uses."""
    from spark_rapids_tpu.errors import (
        DeviceLostError,
        HostLostError,
        MeshDeviceLostError,
    )
    from spark_rapids_tpu.runtime.faults import fault_point
    FAULTS.arm("host.dispatch:device_lost:1:1")
    with pytest.raises(HostLostError) as ei:
        fault_point("host.dispatch")
    assert isinstance(ei.value, DeviceLostError)
    assert not isinstance(ei.value, MeshDeviceLostError)
    assert ei.value.host_id is None  # injected: ladder picks victim
    FAULTS.disarm()
    FAULTS.arm("mesh.gather:device_lost:1:1")
    with pytest.raises(MeshDeviceLostError) as ei2:
        fault_point("mesh.gather")
    assert not isinstance(ei2.value, HostLostError)


def test_host_ladder_rungs_and_single_process_latch(monkeypatch):
    """The full ladder contract on HEALTH.on_host_loss: retry ->
    reland -> shrink (bounded by maxHostLosses) -> single-process
    latch -> escalation to the whole-backend ladder; a cluster-native
    success resets the consecutive count."""
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.errors import HostLostError
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    from spark_rapids_tpu.runtime.health import HEALTH
    # the losses here are declared, not real: the module's cluster2
    # driver still sweeps every 100 ms and its h1 executor is alive
    # and beating, so the sweep would rejoin the host the ladder just
    # marked lost (it did, between the rung and the assertion, on a
    # loaded host)
    monkeypatch.setattr(CLUSTER, "restore_host", lambda host_id: False)
    _session().placement.prepare()  # declared 2-host topology
    conf = RapidsConf({"spark.rapids.cluster.maxHostLosses": "1"})
    e = HostLostError("injected", host_id="h1")
    assert HEALTH.on_host_loss(e, conf) == "retry"
    assert HEALTH.on_host_loss(e, conf) == "reland"
    assert "h1" in CLUSTER.health_snapshot()["lostHosts"]
    assert HEALTH.on_host_loss(e, conf) == "shrink"
    assert "h1" in CLUSTER.health_snapshot()["excludedHosts"]
    # shrink reset the consecutive count — a fresh ladder
    assert HEALTH.on_host_loss(e, conf) == "retry"
    assert HEALTH.on_host_loss(e, conf) == "reland"
    # shrink budget (1) spent: the bottom cluster rung latches
    assert HEALTH.on_host_loss(e, conf) == "single_process"
    snap = CLUSTER.health_snapshot()
    assert snap["singleProcessReason"] is not None
    # losses under the latch escalate to the whole-backend ladder
    assert HEALTH.on_host_loss(e, conf) in ("DEGRADED", "CPU_ONLY")
    # a cluster-native success resets the consecutive count
    HEALTH.reset()
    CLUSTER.restore()
    assert HEALTH.on_host_loss(e, conf) == "retry"
    HEALTH.note_success(cluster_native=True)
    assert HEALTH.on_host_loss(e, conf) == "retry"


def test_rl_fault_point_host_domain():
    """The host fault domain rides the SAME two-direction audit as
    every other point class: an UNREGISTERED host point at a call site
    is flagged, and a registered ``host.*`` point whose call site
    disappears (the multi-host path silently losing chaos coverage)
    is flagged from the registry side."""
    import ast

    from spark_rapids_tpu.lint.repo_lint import (
        _check_fault_registry,
        _check_fault_sites,
    )
    from spark_rapids_tpu.runtime.faults import FAULT_POINTS

    # direction 1: a host-looking point nobody registered
    src = ("from spark_rapids_tpu.runtime.faults import fault_point\n"
           "fault_point('host.reland.unregistered')\n")
    diags = []
    _check_fault_sites("spark_rapids_tpu/runtime/foo.py",
                       ast.parse(src), {}, diags)
    hits = [d for d in diags if d.rule_id == "RL-FAULT-POINT"]
    assert len(hits) == 1 and "not registered" in hits[0].message

    # direction 2: every registered host.* point with NO call site ->
    # one registry-side diagnostic each (the points exist)
    host_points = [n for n in FAULT_POINTS if n.startswith("host.")]
    assert len(host_points) == 4, host_points
    calls2 = {name: [f"{module}:1"]
              for name, (module, _) in FAULT_POINTS.items()
              if not name.startswith("host.")}
    diags2 = []
    _check_fault_registry(calls2, diags2)
    uncalled = [d for d in diags2 if "no fault_point" in d.message]
    assert len(uncalled) == len(host_points)
    assert any("host.heartbeat" in d.message for d in uncalled)


def test_hosts_flag_validation():
    """validate_flags rejects the --hosts combinations the harness
    does not implement, naming the supported modes."""
    from types import SimpleNamespace

    import scale_test as st

    def args(**kw):
        base = dict(mesh=0, hosts=0, streaming=False, concurrency=0,
                    service_faults=False,
                    cpu_baseline=False, require_tpu=False, chaos=False,
                    device_budget=0)
        base.update(kw)
        return SimpleNamespace(**base)

    st.validate_flags(args(hosts=2))  # supported
    st.validate_flags(args(hosts=2, chaos=True))  # supported
    # two planes together route to the fleet closure
    st.validate_flags(args(hosts=2, concurrency=2))
    for bad in (args(hosts=1),
                args(hosts=2, mesh=4),
                args(hosts=2, cpu_baseline=True),
                args(hosts=2, require_tpu=True),
                args(hosts=2, chaos=True, service_faults=True)):
        with pytest.raises(SystemExit) as ei:
            st.validate_flags(bad)
        assert "supported modes" in str(ei.value)
