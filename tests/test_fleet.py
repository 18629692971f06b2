"""Fleet closure: composable chaos planes, the shared-topology path,
and the --fleet flag surface.

The tentpole contract under test, WITHOUT paying for a fleet run:

* fault planes COMPOSE — ``validate_flags`` accepts the plane
  combinations (--fleet, --hosts x --device-budget x --concurrency)
  and still fails fast on the combinations no harness implements;
* ``--fleet --dry-run`` is an under-5s subprocess smoke: it builds the
  plan, validates the merged cross-domain schedule through the real
  spec parser, prints JSON and exits 0 — no backend, no cluster;
* incident bundles carry the process-monotonic ``seq`` id and the
  ``faultDomain`` classification the closure matches ladder actions
  against;
* the runtime lock witness counts rank inversions in-band
  (``lockorder.witness_violations``) — what every chaos artifact
  records as ``lockWitnessViolations``;
* ``consistent_topology_snapshot`` serves hosts + mesh + memory +
  quarantine under every owning lock at once, and
  ``QueryService.health()`` reads it (fleetDegradedReason,
  topologyGeneration).
"""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from spark_rapids_tpu.conf import RapidsConf

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# flag matrix: composable planes accepted, unimplemented combos rejected
# ---------------------------------------------------------------------------


def _args(**kw):
    base = dict(mesh=0, hosts=0, streaming=False, concurrency=0,
                service_faults=False, cpu_baseline=False,
                require_tpu=False, chaos=False, device_budget=0,
                fleet=False, dry_run=False)
    base.update(kw)
    return SimpleNamespace(**base)


def test_fleet_flag_matrix_accepted():
    """Plane combinations route to the fleet closure: --fleet alone,
    --fleet with explicit knobs, and any two of --hosts /
    --device-budget / --concurrency without the flag."""
    import scale_test as st

    for ok in (_args(fleet=True),
               _args(fleet=True, hosts=2),
               _args(fleet=True, device_budget=8192),
               _args(fleet=True, concurrency=4),
               _args(fleet=True, dry_run=True),
               _args(fleet=True, hosts=3, device_budget=8192,
                     concurrency=4, service_faults=True, chaos=True),
               # composition WITHOUT --fleet: two planes together
               _args(hosts=2, concurrency=4),
               _args(hosts=2, device_budget=8192),
               _args(device_budget=8192, concurrency=4),
               _args(hosts=2, device_budget=8192, concurrency=4)):
        st.validate_flags(ok)


def test_fleet_flag_matrix_rejected():
    """The combinations no harness implements still fail fast, naming
    the supported modes — including the floors inside the fleet path
    and --dry-run outside it."""
    import scale_test as st

    for bad in (_args(fleet=True, mesh=8),
                _args(fleet=True, streaming=True),
                _args(fleet=True, cpu_baseline=True),
                _args(fleet=True, require_tpu=True),
                _args(fleet=True, hosts=1),
                _args(fleet=True, device_budget=100),
                _args(dry_run=True),             # --dry-run needs --fleet
                _args(dry_run=True, chaos=True)):
        with pytest.raises(SystemExit) as ei:
            st.validate_flags(bad)
        assert "supported modes" in str(ei.value)


def test_single_plane_rejections_retained():
    """Composing planes did NOT loosen the single-plane modes: a lone
    mode keeps its original harness and its original rejections."""
    import scale_test as st

    # still supported single-plane invocations
    st.validate_flags(_args(chaos=True, concurrency=4,
                            service_faults=True))
    st.validate_flags(_args(hosts=2, chaos=True))
    st.validate_flags(_args(device_budget=8192, chaos=True))
    for bad in (_args(cpu_baseline=True, chaos=True),
                _args(mesh=8, concurrency=4),
                _args(hosts=2, service_faults=True),
                _args(streaming=True, device_budget=8192),
                _args(device_budget=100)):
        with pytest.raises(SystemExit) as ei:
            st.validate_flags(bad)
        assert "supported modes" in str(ei.value)


# ---------------------------------------------------------------------------
# --fleet --dry-run: the under-5s plan-and-validate subprocess smoke
# ---------------------------------------------------------------------------


def test_fleet_dry_run_subprocess_smoke():
    """``scale_test.py --fleet --dry-run`` plans the run, validates the
    merged schedule parses, prints the plan JSON and exits 0 — with
    no cluster boot (the subprocess's timeout is the bound: a wall
    time on a shared CPU is no measurement)."""
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "scale_test.py"),
         "--fleet", "--dry-run"],
        capture_output=True, text=True, timeout=30, cwd=_REPO)
    assert proc.returncode == 0, proc.stderr
    plan = json.loads(proc.stdout.strip().splitlines()[-1])
    assert plan["mode"] == "fleet-plan"
    assert set(plan["planes"]) == {"host", "mesh", "memory", "service",
                                   "exec"}
    # the merged schedule covers every assertable fault domain
    spec = plan["merged_fault_spec"]
    for prefix in ("host.", "mesh.", "mem.", "service."):
        assert prefix in spec
    assert plan["merged_fault_points"] == len(
        [e for e in spec.split(";") if e])
    # merged bounds are the per-plane maxima
    assert plan["merged_bounds"]["oomRetries"] == 4000
    assert plan["merged_bounds"]["query_replays"] == 30
    assert plan["merged_bounds"]["workersLost"] == 8


def test_fleet_plan_merges_planes_deterministically():
    import scale_test as st

    planes = st.fleet_planes(7)
    spec = st.fleet_fault_spec(7)
    assert spec == ";".join(p["spec"] for p in planes.values())
    # same seed -> same schedule; different seed -> different streams
    assert st.fleet_fault_spec(7) == spec
    assert st.fleet_fault_spec(8) != spec
    # the merged spec parses through the real arm-time parser
    from spark_rapids_tpu.runtime.faults import parse_fault_spec
    assert len(parse_fault_spec(spec)) >= 10
    bounds = st.fleet_bounds(planes)
    for plane in planes.values():
        for field, b in plane["bounds"].items():
            assert bounds[field] >= b


def test_fleet_point_domain_classification():
    import scale_test as st

    assert st._fleet_point_domain("host.dispatch") == "host"
    assert st._fleet_point_domain("mesh.gather") == "mesh"
    assert st._fleet_point_domain("mem.reserve") == "memory"
    assert st._fleet_point_domain("stream.batch") == "stream"
    for svc_point in ("service.worker_crash", "device.lost",
                      "dispatch.wedge", "exec.execute"):
        assert st._fleet_point_domain(svc_point) == "service"


# ---------------------------------------------------------------------------
# incident bundles: seq id + faultDomain
# ---------------------------------------------------------------------------


def test_incident_bundle_seq_and_fault_domain(tmp_path):
    """Every bundle carries a process-monotonic seq id (unique even
    when wall clocks collide) and the faultDomain its kind classifies
    into — what the fleet closure matches ladder actions against."""
    from spark_rapids_tpu.obs.telemetry import record_incident
    from spark_rapids_tpu.tools.incident import load_bundles
    conf = RapidsConf({
        "spark.rapids.obs.flightRecorder.dir": str(tmp_path)})
    expect = {"host.ladder": "host", "mesh.ladder": "mesh",
              "memory.ladder": "memory", "backend.ladder": "service",
              "stream.resume": "stream", "quarantine": "service"}
    for kind in expect:
        assert record_incident(kind, "act", "r", conf=conf)
    bundles = load_bundles(str(tmp_path))
    assert len(bundles) == len(expect)
    seqs = [b["seq"] for b in bundles]
    assert len(set(seqs)) == len(seqs)
    assert seqs == sorted(seqs)  # load_bundles sorts by filename = seq order
    for b in bundles:
        assert b["schema"] == 2
        assert b["faultDomain"] == expect[b["kind"]]


def test_fault_domain_prefix_table():
    from spark_rapids_tpu.obs.telemetry import fault_domain
    assert fault_domain("host.ladder") == "host"
    assert fault_domain("mesh.ladder") == "mesh"
    assert fault_domain("memory.ladder") == "memory"
    assert fault_domain("stream.resume") == "stream"
    assert fault_domain("backend.ladder") == "service"
    assert fault_domain("quarantine") == "service"


# ---------------------------------------------------------------------------
# the runtime lock witness violation counter
# ---------------------------------------------------------------------------


def test_lock_witness_violation_counter():
    """Rank inversions are COUNTED, not just raised — the in-band
    evidence every chaos artifact records as lockWitnessViolations."""
    from spark_rapids_tpu import lockorder
    lockorder.arm_witness()
    try:
        before = lockorder.witness_violations()
        low = lockorder.ordered_lock("streaming.query")     # rank 100
        high = lockorder.ordered_lock("memory.arbiter")     # rank 740
        with low:
            with high:
                pass
        assert lockorder.witness_violations() == before  # ascending: clean
        with high:
            with pytest.raises(lockorder.LockOrderViolation):
                low.acquire()
        assert lockorder.witness_violations() == before + 1
        with pytest.raises(lockorder.LockOrderViolation):
            with low:
                low.acquire()  # self-deadlock counts too
        assert lockorder.witness_violations() == before + 2
        assert len(lockorder.witness_violation_records()) >= 2
    finally:
        lockorder.disarm_witness()
        # the counter is process-global: leave it clean or every later
        # in-process chaos closure reads these deliberate inversions
        lockorder.reset_witness_violations()


# ---------------------------------------------------------------------------
# the shared-topology path
# ---------------------------------------------------------------------------


def test_consistent_topology_snapshot_shape():
    """One generation-stamped document with hosts + mesh + memory +
    quarantine read under every owning lock at once — the view the
    service's admission control and the ladders both consult."""
    from spark_rapids_tpu.runtime.health import (
        consistent_topology_snapshot,
    )
    topo = consistent_topology_snapshot()
    assert set(topo) >= {"generation", "state", "backend", "hosts",
                         "mesh", "memory", "quarantine"}
    assert isinstance(topo["generation"], int)
    assert topo["state"] in ("HEALTHY", "DEGRADED", "CPU_ONLY")
    assert "hostsLost" in topo["hosts"]
    assert "meshDeviceLost" in topo["mesh"]
    assert "memoryPressureEvents" in topo["memory"]
    assert "budgetBytes" in topo["memory"]


def test_service_health_reads_fleet_topology():
    """QueryService.health() consults the shared topology: the merged
    view rides in-band (fleetDegradedReason, topologyGeneration) and
    /topology serves the same document."""
    from spark_rapids_tpu.service.scheduler import QueryService
    with QueryService({"spark.rapids.service.introspect.enabled":
                       "true"}) as svc:
        h = svc.health()
        assert "fleetDegradedReason" in h
        assert h["fleetDegradedReason"] is None  # quiet fleet: no reason
        assert isinstance(h["topologyGeneration"], int)
        topo = svc.topology_snapshot()
        assert topo["generation"] == h["topologyGeneration"]
        import urllib.request
        url = f"http://127.0.0.1:{svc.introspect_port}/topology"
        with urllib.request.urlopen(url, timeout=10) as resp:
            doc = json.loads(resp.read().decode("utf-8"))
        assert set(doc) == set(topo)
        assert doc["hosts"].keys() == topo["hosts"].keys()
