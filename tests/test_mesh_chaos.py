"""Tier-1 mesh-chaos slice: the distributed path under seeded faults.

The full closure is ``python scale_test.py --mesh 8 --chaos`` (q1-q22
mesh-native under the seeded mesh-fault schedule — MULTICHIP_r07); this
marker-gated slice keeps every mesh recovery mechanism exercised in the
tier-1 gate without the corpus cost:

* ``mesh.shard.put`` crash -> query replay, bit-identical;
* ``mesh.ici.exchange`` corrupt -> the checksummed live-count fetch
  trips and REFETCHES the intact device value;
* ``mesh.gather`` corrupt -> the MeshReland row-count/checksum
  validation trips and re-lands from the still-sharded source;
* partial device loss (``device_lost`` at a ``mesh.*`` point) walks the
  degradation ladder retry -> single-device -> SHRINK onto surviving
  devices — visible in MESH.health_snapshot(), HEALTH.mesh_snapshot(),
  explain() and the event log — not straight to CPU-only;
* ladder exhaustion (shrink budget 0, reinit budget 1) latches CPU-only
  mode and the query still completes;
* the digest-kernel cache rejects late publishes after
  clear_mesh_caches (the PR-9 epoch contract, two-thread pin).
"""

import threading

import numpy as np
import pytest

from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER, FAULTS

pytestmark = [pytest.mark.multichip, pytest.mark.chaos]


@pytest.fixture(autouse=True)
def _clean_mesh_fault_state():
    """Mesh chaos mutates PROCESS state (fault registry, breaker,
    health ladder, mesh exclusions, quarantine strikes) — restore all
    of it so the rest of the suite sees a healthy full-strength
    process."""
    from spark_rapids_tpu.parallel.mesh import MESH
    from spark_rapids_tpu.runtime.health import HEALTH, QUARANTINE
    from spark_rapids_tpu.session import TpuSession
    FAULTS.disarm()
    CIRCUIT_BREAKER.reset()
    HEALTH.reset()
    QUARANTINE.reset()
    MESH.restore("test setup")
    yield
    FAULTS.disarm()
    CIRCUIT_BREAKER.reset()
    HEALTH.reset()
    QUARANTINE.reset()
    MESH.restore("test teardown")
    # leave the process-wide mesh OFF for the rest of the suite
    TpuSession().placement.prepare()


def _data(n=600):
    return {"k": [f"k{i % 7}" for i in range(n)],
            "v": np.arange(n, dtype=np.int64),
            "x": (np.arange(n, dtype=np.float64) * 0.5)}


def _agg(s):
    from spark_rapids_tpu import functions as F
    return (s.create_dataframe(_data())
            .group_by("k")
            .agg(F.sum("x").alias("sx"), F.count("v").alias("c")))


def _gathered(s):
    """An aggregate the mesh does NOT run on the resident shards (a
    computed key: the sorted path), so its sharded input is re-landed
    through the verified gather: the ``mesh.gather`` fault site. (_agg's
    dictionary-keyed fast path aggregates where the shards lie and
    gathers nothing: execs/aggregate.py ``_shards_of``.)"""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.ops.expr import col, lit
    return (s.create_dataframe(_data())
            .group_by((col("v") % lit(7)).alias("g"))
            .agg(F.sum("x").alias("sx"), F.count("v").alias("c")))


def _exchange(s):
    """A string-keyed 8-way repartition (the q7 shape): lowers to the
    ICI all-to-all on the 8-device mesh."""
    from spark_rapids_tpu import functions as F
    return (s.create_dataframe(_data())
            .repartition(8, "k")
            .group_by("k")
            .agg(F.sum("v").alias("s")))


def _mesh_scope():
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    return dict(scopes_snapshot().get("mesh", {}))


def _delta(before, after):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)
            if after.get(k, 0) != before.get(k, 0)}


def _identical(expected, got):
    import scale_test as ST
    return ST.tables_differ(expected, got)


def test_shard_put_crash_replays_bit_identical():
    from spark_rapids_tpu.session import TpuSession
    expected = _agg(TpuSession()).collect_table()
    s = TpuSession({"spark.rapids.mesh.enabled": "true",
                    "spark.rapids.test.faults":
                        "mesh.shard.put:crash:1:11"})
    got = _agg(s).collect_table()
    assert _identical(expected, got) is None
    assert s.last_fault_replays >= 1
    assert FAULTS.counters().get("mesh.shard.put", 0) == 1


def test_ici_exchange_corrupt_refetches_counts():
    from spark_rapids_tpu.session import TpuSession
    expected = _exchange(TpuSession()).collect_table()
    s = TpuSession({"spark.rapids.mesh.enabled": "true",
                    "spark.rapids.test.faults":
                        "mesh.ici.exchange:corrupt:1:12"})
    before = _mesh_scope()
    got = _exchange(s).collect_table()
    d = _delta(before, _mesh_scope())
    assert _identical(expected, got) is None
    assert d.get("iciExchanges", 0) >= 1, d
    # the corrupted fetch was CAUGHT by the digest and refetched
    assert d.get("gatherChecksFailed", 0) >= 1, d
    assert d.get("shardRetries", 0) >= 1, d


def test_gather_checksum_trip_relands_from_source():
    from spark_rapids_tpu.session import TpuSession
    expected = _gathered(TpuSession()).collect_table()
    s = TpuSession({"spark.rapids.mesh.enabled": "true",
                    "spark.rapids.test.faults":
                        "mesh.gather:corrupt:1:13"})
    before = _mesh_scope()
    got = _gathered(s).collect_table()
    d = _delta(before, _mesh_scope())
    assert _identical(expected, got) is None
    assert d.get("gatherChecksFailed", 0) >= 1, d
    assert d.get("shardRetries", 0) >= 1, d
    # zero replays: the re-land converged LOCALLY from the intact
    # sharded source, no query re-execution needed
    assert not s.last_fault_replays


def test_gather_check_exhaustion_raises_typed():
    """Every re-gather corrupted (count exceeds the retry budget):
    the boundary raises typed MeshGatherError — which IS a
    KernelCrashError, so with the runtime fallback disabled it
    surfaces instead of silently wrong results."""
    from spark_rapids_tpu.errors import MeshGatherError
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({"spark.rapids.mesh.enabled": "true",
                    "spark.rapids.mesh.maxShardRetries": "1",
                    "spark.rapids.sql.runtimeFallback.enabled": "false",
                    "spark.rapids.test.faults":
                        "mesh.gather:corrupt:99:14"})
    with pytest.raises(MeshGatherError):
        _gathered(s).collect_table()


def test_partial_device_loss_walks_ladder_to_shrink(tmp_path):
    """device_lost x3 at a mesh point: retry (1), single-device
    re-land with the demotion reason surfaced (2), then a mesh SHRINK
    onto the 7 surviving devices (3) — results bit-identical
    throughout, shrink visible in health snapshots, explain() and the
    event log. NOT straight to CPU-only: the device stays trusted,
    only the mesh shrank."""
    from spark_rapids_tpu.parallel.mesh import MESH
    from spark_rapids_tpu.runtime.health import HEALTH
    from spark_rapids_tpu.session import TpuSession
    expected = _gathered(TpuSession()).collect_table()
    s = TpuSession({"spark.rapids.mesh.enabled": "true",
                    "spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path),
                    "spark.rapids.test.faults":
                        "mesh.gather:device_lost:3:15"})
    # run 1: loss -> retry -> loss -> single-device re-land (converges
    # suppressed; the suppressed success does NOT reset the ladder)
    got = _gathered(s).collect_table()
    assert _identical(expected, got) is None
    assert HEALTH.mesh_snapshot()["meshDegradations"] >= 1
    assert MESH.health_snapshot()["excludedDeviceIds"] == []
    # run 2: the third loss walks the ladder to the SHRINK rung
    got = _gathered(s).collect_table()
    assert _identical(expected, got) is None
    snap = MESH.health_snapshot()
    assert snap["excludedDeviceIds"], snap
    assert snap["shape"] == "7", snap
    assert "mesh degraded" in (snap["degradedReason"] or "")
    assert HEALTH.mesh_snapshot()["meshShrinks"] == 1
    assert HEALTH.state() == "HEALTHY", \
        "a partial loss must not degrade whole-device health"
    # the shrink is visible in the event log (meshShape of the landed
    # run) and in explain()
    assert s.last_event_record["meshShape"] == "7"
    explain = s.explain(_gathered(s).plan)
    assert "mesh degraded" in explain and "7-device" in explain
    # ...and keeps serving bit-identically on the smaller mesh
    got = _gathered(s).collect_table()
    assert _identical(expected, got) is None
    # quarantine strikes recorded against the template that kept
    # killing mesh execution (below the quarantine threshold here)
    from spark_rapids_tpu.runtime.health import QUARANTINE
    assert QUARANTINE.snapshot()["strikes"] >= 1


def test_ladder_exhaustion_latches_cpu_only():
    """Shrink budget 0 + reinit budget 1: repeated partial losses
    escalate through the whole-backend rungs to the CPU-only latch —
    and the query STILL completes (on the CPU path, with the latch
    reason in explain())."""
    from spark_rapids_tpu.runtime.health import HEALTH
    from spark_rapids_tpu.session import TpuSession
    s = TpuSession({"spark.rapids.mesh.enabled": "true",
                    "spark.rapids.mesh.degrade.maxShrinks": "0",
                    "spark.rapids.service.deviceLoss.maxReinits": "1",
                    "spark.rapids.test.faults":
                        "mesh.gather:device_lost:6:16"})
    got1 = _gathered(s).collect_table()  # retry -> single-device, converges
    assert HEALTH.state() == "HEALTHY"
    got2 = _gathered(s).collect_table()  # third loss: no shrink budget ->
    assert HEALTH.state() == "CPU_ONLY"  # reinit budget 1 -> latch
    # the latched process serves the SAME results through the CPU path
    # (baseline re-collected post-latch, like the chaos harness does:
    # the latch is process-wide, so the fresh session is latched too)
    expected = _gathered(TpuSession()).collect_table()
    assert _identical(expected, got2) is None
    assert sorted(got1.to_pydict()["g"]) == sorted(
        expected.to_pydict()["g"])
    explain = s.explain(_gathered(s).plan)
    assert "CPU-only mode latched" in explain


def test_digest_cache_rejects_late_publish():
    """The gather-digest kernel cache closes its check-then-build
    window the way PR 9 closed MeshExchange._cache: a builder that
    started BEFORE clear_mesh_caches ran (a device-loss reinit racing
    an in-flight gather) serves its kernel to that caller only and
    never re-seeds the cleared cache (two-thread pin)."""
    from spark_rapids_tpu.parallel import exchange as EX

    EX.clear_mesh_caches()
    built = threading.Event()
    proceed = threading.Event()
    results = []

    def build():
        built.set()
        proceed.wait(timeout=5)
        return "stale-kernel"

    t = threading.Thread(target=lambda: results.append(
        EX.digest_kernel(("pin", "late"), build)))
    t.start()
    assert built.wait(timeout=5)
    # the invalidation lands MID-BUILD (device-loss reinit)
    EX.clear_mesh_caches()
    proceed.set()
    t.join(timeout=5)
    assert results == ["stale-kernel"]  # served to its caller only...
    with EX._DICT_INTERN_LOCK:
        assert ("pin", "late") not in EX._DIGEST_CACHE, \
            "a pre-invalidation builder re-seeded the cleared cache"
    # a fresh builder AFTER the clear publishes normally
    assert EX.digest_kernel(("pin", "late"), lambda: "fresh") == "fresh"
    with EX._DICT_INTERN_LOCK:
        assert EX._DIGEST_CACHE.get(("pin", "late")) == "fresh"
    EX.clear_mesh_caches()


def test_scale_test_flag_validation():
    """Unsupported mode combinations fail fast with the supported
    combinations named — never a silently-ignored flag."""
    import scale_test as ST

    class A:
        mesh = 8
        hosts = 0
        streaming = False
        chaos = False
        concurrency = 0
        service_faults = False
        cpu_baseline = False
        require_tpu = False
        device_budget = 0

    ST.validate_flags(A())  # plain --mesh: fine
    A.chaos = True
    ST.validate_flags(A())  # --mesh --chaos: the composed harness
    for attr, val in (("concurrency", 4), ("service_faults", True),
                      ("cpu_baseline", True)):
        bad = A()
        setattr(bad, attr, val)
        with pytest.raises(SystemExit) as ei:
            ST.validate_flags(bad)
        assert "supported modes" in str(ei.value)
    lone = A()
    lone.mesh = 0
    lone.chaos = False
    lone.service_faults = True
    with pytest.raises(SystemExit) as ei:
        ST.validate_flags(lone)
    assert "--service-faults" in str(ei.value)
    one_dev = A()
    one_dev.mesh = 1
    with pytest.raises(SystemExit):
        ST.validate_flags(one_dev)
