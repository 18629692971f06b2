"""Out-of-core execution under a hard HBM budget (ISSUE 15).

The memory fault domain end to end: the MemoryArbiter's hard budget
(runtime/memory.py) enforced at every device landing, spill/unspill
round trips staying bit-identical, chunked scans, the CRC footer on
disk-tier spill frames, the injected ``mem.*`` ladder walk (retry ->
split-and-retry -> chunked re-execution -> per-op CPU demotion) with
explain()/incident-bundle visibility, admission consulting the
arbiter's live occupancy, and arbiter accounting exactness under
concurrency.
"""

import glob
import json
import os
import threading

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.errors import SpillCorruptionError
from spark_rapids_tpu.obs.metrics import scopes_snapshot
from spark_rapids_tpu.ops.expr import col
from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER, FAULTS, RECOVERY
from spark_rapids_tpu.runtime.health import HEALTH
from spark_rapids_tpu.runtime.memory import (
    MEMORY,
    MemoryArbiter,
    estimate_device_nbytes,
    forced_chunking,
    scan_chunks,
)
from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableDeviceTable
from spark_rapids_tpu.columnar import DeviceTable, HostTable
from spark_rapids_tpu.session import TpuSession

from tests.asserts import plan_metric_total

pytestmark = pytest.mark.chaos


def _reset_process_state():
    from spark_rapids_tpu.runtime.retry import RMM_TPU
    FAULTS.disarm()
    CIRCUIT_BREAKER.reset()
    HEALTH.reset()
    MEMORY.reset()
    RMM_TPU.clear()
    # tests squeeze the host tier to 4KB over a per-test tmp dir —
    # later suites must get the default catalog back, not a tier
    # pointed at a removed directory
    BufferCatalog.reset()


@pytest.fixture(autouse=True)
def _clean_process_state():
    """Every test starts from, and leaves, clean process-wide
    fault/health/arbiter state (the file rides tier-1 between other
    suites: tables an earlier suite's abandoned workers still hold
    stay in the arbiter's ledger, and a 160KB budget is then spent
    before the test's first landing)."""
    _reset_process_state()
    yield
    _reset_process_state()


def _mem_scope():
    return dict(scopes_snapshot().get("memory", {}))


def _data(n=20000, seed=0):
    rng = np.random.default_rng(seed)
    return {"k": rng.integers(0, 50, n).astype(np.int64),
            "v": rng.random(n),
            "s": np.array(["a", "bb", "ccc"], dtype=object)[
                rng.integers(0, 3, n)]}


def _agg(s, data, nb=6):
    return sorted(s.create_dataframe(data, num_batches=nb)
                  .group_by("k")
                  .agg(F.sum(col("v")).alias("sv"),
                       F.count(col("s")).alias("c"))
                  .collect())


def _cpu():
    return TpuSession({"spark.rapids.sql.enabled": "false"})


#: the out-of-core JOIN workload: the build side stays resident across
#: streaming probe chunks, so a budget below (build + pipeline) forces
#: the build through spill/unspill cycles between batches — the
#: textbook out-of-core hash join. Grouping key is LOW cardinality so
#: the merge table fits any budget (a high-cardinality grouping's
#: merge is legitimately output-sized).
def _join_data(seed=0):
    rng = np.random.default_rng(seed)
    left = {"k": rng.integers(0, 3000, 20000).astype(np.int64),
            "g": rng.integers(0, 40, 20000).astype(np.int64),
            "v": rng.random(20000)}
    right = {"k": np.arange(3000).astype(np.int64),
             "w": rng.random(3000), "x": rng.random(3000),
             "y": rng.random(3000)}
    return left, right


def _join_q(s, left, right, nb=4):
    ldf = s.create_dataframe(left, num_batches=nb)
    rdf = s.create_dataframe(right)
    return sorted(ldf.join(rdf, on=["k"], how="inner").group_by("g")
                  .agg(F.sum(col("v")).alias("sv"),
                       F.sum(col("w")).alias("sw"))
                  .collect())


_BUDGET = 160 * 1024
_SHARE = int(_BUDGET * 0.1)


def _budget_conf(extra=None):
    conf = {"spark.rapids.memory.device.budgetBytes": str(_BUDGET),
            "spark.rapids.memory.device.scanChunkFraction": "0.1"}
    conf.update(extra or {})
    return conf


def _shape_baseline(left, right):
    """The same-shape baseline: a PLAIN session under forced_chunking
    at the budget's chunk share executes the exact batch structure the
    budgeted run takes — with zero enforcement — so the budgeted run
    must be BITWISE identical to it (spills/unspills/retries must not
    change one bit; the scale harness's contract)."""
    plain = TpuSession()
    with forced_chunking(_SHARE):
        return _join_q(plain, left, right)


# ---------------------------------------------------------------------------
# budget-enforced spill/unspill bit-identity
# ---------------------------------------------------------------------------


def test_budget_enforced_spill_unspill_bit_identity(tmp_path):
    """A 160KB budget below (build + pipeline): the join build side
    rides spill/unspill cycles between probe chunks (host tier
    squeezed to 4KB so the DISK tier and its CRC footers see traffic)
    — and the result is BITWISE identical to the same-shape baseline:
    enforcement's spills, unspills and evictions changed nothing."""
    left, right = _join_data()
    BufferCatalog.reset(host_limit_bytes=4096, disk_dir=str(tmp_path))
    before = _mem_scope()
    s = TpuSession(_budget_conf())
    got = _join_q(s, left, right)
    moved = {k: v - before.get(k, 0) for k, v in _mem_scope().items()}
    assert moved.get("spillBytes", 0) > 0, moved
    assert moved.get("unspills", 0) > 0, moved
    assert moved.get("scanChunks", 0) > 0, moved
    want = _shape_baseline(left, right)
    assert got == want  # bitwise: sorted rows of python-native values


def test_chunked_scan_identity_vs_unchunked():
    """Chunked landings compute the same ANSWER as one batch (row
    multiset; f64 merge order may move final ulps — the bitwise
    contract runs against the same-shape baseline above), and the
    chunked run reports its chunks."""
    data = _data(8000, seed=3)
    plain = TpuSession()
    want = _agg(plain, data, nb=1)
    before = _mem_scope()
    budgeted = TpuSession({
        # chunk share ~16KB: an 8k-row 3-column table must split
        "spark.rapids.memory.device.budgetBytes": str(256 * 1024),
        "spark.rapids.memory.device.scanChunkFraction": "0.0625",
    })
    got = _agg(budgeted, data, nb=1)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert g[0] == w[0] and g[2] == w[2], (g, w)
        assert abs(g[1] - w[1]) <= 1e-9 * max(1.0, abs(w[1])), (g, w)
    moved = {k: v - before.get(k, 0) for k, v in _mem_scope().items()}
    assert moved.get("scanChunks", 0) > 1, moved
    # metric surfaced on the scan exec too
    assert "scanChunks" in budgeted.last_metrics()
    # and the bitwise contract against the SAME chunk structure
    share = int(256 * 1024 * 0.0625)
    with forced_chunking(share):
        same_shape = _agg(plain, data, nb=1)
    assert got == same_shape


def test_scan_chunks_respects_forced_override():
    h = HostTable.from_pydict(_data(4000, seed=4))
    MEMORY.reset()
    assert scan_chunks(h) == [h]  # HBM-sized default budget: no chunking
    est = estimate_device_nbytes(h)
    with forced_chunking(est // 4):
        chunks = scan_chunks(h)
    assert len(chunks) > 1
    assert sum(c.num_rows for c in chunks) == h.num_rows
    # each chunk fits the forced share (bucket-padded estimate)
    for c in chunks:
        assert estimate_device_nbytes(c) <= est // 2


# ---------------------------------------------------------------------------
# CRC footer on disk spill frames
# ---------------------------------------------------------------------------


def test_crc_corrupt_unspill_raises_typed(tmp_path):
    """Bit-rot on a disk-tier spill frame is CAUGHT by the CRC footer:
    unspill raises typed SpillCorruptionError (a KernelCrashError —
    the replay machinery re-lands from the scan cache), counts the
    corruption, and drops the frame instead of serving wrong bytes."""
    cat = BufferCatalog.reset(disk_dir=str(tmp_path))
    dt = DeviceTable.from_host(HostTable.from_pydict(_data(500, seed=5)))
    sb = SpillableDeviceTable(dt, cat)
    del dt
    sb.spill_to_host()
    sb.spill_to_disk()
    path = sb._disk_path
    raw = open(path, "rb").read()
    flipped = raw[:8] + bytes([raw[8] ^ 0xFF]) + raw[9:]
    open(path, "wb").write(flipped)
    before = _mem_scope()
    with pytest.raises(SpillCorruptionError):
        sb.get()
    assert not os.path.exists(path)  # corrupt frame dropped, not kept
    moved = {k: v - before.get(k, 0) for k, v in _mem_scope().items()}
    assert moved.get("spillCorruptions", 0) == 1
    sb.release()


def test_injected_unspill_corruption_replays_bit_identical(tmp_path):
    """End to end: a seeded ``mem.unspill`` corruption under a budget
    that forces disk-tier round trips — the query replays and
    completes bit-identical to the same-shape baseline (re-landed
    from the scan source), never serving the corrupt frame."""
    left, right = _join_data(seed=6)
    BufferCatalog.reset(host_limit_bytes=4096, disk_dir=str(tmp_path))
    replays_before = RECOVERY.snapshot()["query_replays"]
    before = _mem_scope()
    s = TpuSession(_budget_conf({
        "spark.rapids.sql.runtimeFallback.enabled": "true",
        "spark.rapids.test.faults": "mem.unspill:corrupt:1:11",
    }))
    got = _join_q(s, left, right)
    moved = {k: v - before.get(k, 0) for k, v in _mem_scope().items()}
    assert moved.get("spillCorruptions", 0) >= 1, moved
    assert FAULTS.counters().get("mem.unspill", 0) >= 1
    assert RECOVERY.snapshot()["query_replays"] > replays_before
    FAULTS.disarm()
    assert got == _shape_baseline(left, right)


# ---------------------------------------------------------------------------
# the memory degradation ladder
# ---------------------------------------------------------------------------


def test_memory_ladder_unit_walk(tmp_path):
    """on_memory_pressure rung by rung: retry -> chunk -> cpu_demote
    (attributed) / abort (unattributed), one incident bundle per
    action, and any completed query resets the ladder."""
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.errors import FatalDeviceOOM
    conf = RapidsConf({
        "spark.rapids.obs.flightRecorder.dir": str(tmp_path)})
    HEALTH.reset()
    exc = FatalDeviceOOM("device OOM persisted after 2 spill-retries")
    assert HEALTH.on_memory_pressure(exc, conf) == "retry"
    assert HEALTH.on_memory_pressure(exc, conf) == "chunk"
    # unattributed third escalation: nothing to demote -> abort
    assert HEALTH.on_memory_pressure(exc, conf) == "abort"
    exc.fault_op = "SomeOp"
    assert HEALTH.on_memory_pressure(exc, conf) == "cpu_demote"
    assert CIRCUIT_BREAKER.demotion_reason("SomeOp") is not None
    snap = HEALTH.memory_snapshot()
    assert snap["memoryPressureEvents"] == 4
    assert snap["memoryChunkedReexecutions"] == 1
    assert snap["memoryCpuDemotions"] == 1
    bundles = [json.load(open(p))
               for p in glob.glob(str(tmp_path / "incident-*.json"))]
    actions = sorted(b["action"] for b in bundles
                     if b["kind"] == "memory.ladder")
    assert actions == ["abort", "chunk", "cpu_demote", "retry"]
    # every bundle embeds the arbiter snapshot + memory ladder state
    assert all("memory" in b and "memoryLadder" in b["health"]
               for b in bundles)
    # ANY success resets the consecutive count
    HEALTH.note_success()
    assert HEALTH.memory_snapshot()["memoryConsecutive"] == 0


def test_memory_ladder_end_to_end_cpu_demotion(tmp_path):
    """A sustained budget squeeze (every reservation refused for 10
    grants) walks the full ladder end to end: spill-retry and
    split-and-retry inside the retry framework, then retry ->
    chunked re-execution -> per-op CPU demotion — and the query STILL
    completes with the right answer, the demotion visible in
    explain()-style surfaces (breaker reason + event record) and one
    incident bundle per ladder action."""
    data = {"k": [1, 2, 3] * 100, "v": [1.0] * 300}
    s = TpuSession({
        "spark.rapids.test.faults": "mem.reserve:oom:10:3",
        "spark.rapids.sql.runtimeFallback.enabled": "true",
        "spark.rapids.obs.flightRecorder.dir": str(tmp_path),
        "spark.rapids.sql.eventLog.enabled": "true",
        "spark.rapids.sql.eventLog.dir": str(tmp_path / "ev"),
    })
    got = sorted(s.create_dataframe(data).group_by("k")
                 .agg(F.sum(col("v")).alias("sv")).collect())
    assert got == [(1, 100.0), (2, 100.0), (3, 100.0)]
    demoted = CIRCUIT_BREAKER.demoted_ops()
    assert demoted, "the ladder never reached the CPU-demotion rung"
    assert any("OOM" in reason or "oom" in reason
               for reason in demoted.values())
    snap = HEALTH.memory_snapshot()
    assert snap["memoryChunkedReexecutions"] >= 1
    assert snap["memoryCpuDemotions"] >= 1
    # incident bundles: >= 1 per ladder action taken
    bundles = [json.load(open(p))
               for p in glob.glob(str(tmp_path / "incident-*.json"))]
    mem_bundles = [b for b in bundles if b["kind"] == "memory.ladder"]
    assert len(mem_bundles) >= snap["memoryPressureEvents"]
    assert {"retry", "chunk", "cpu_demote"} <= {
        b["action"] for b in mem_bundles}
    # the escalation's triggering fault point parses from the cause
    assert any(b.get("faultPoint") == "mem.reserve"
               for b in mem_bundles)
    # event record carries the demotion map (explain() convention)
    rec = s.last_event_record
    assert rec["schema"] == 16
    assert any(op in rec["demotions"] for op in demoted)
    assert rec["oomRetries"] > 0


def test_split_and_retry_under_budget():
    """splitRetries (schema v10) counts the split-and-retry rung: an
    injected SplitAndRetryOOM halves the input and both halves replay,
    the reassembled output bit-identical to the unsplit input."""
    from spark_rapids_tpu.runtime.retry import RMM_TPU, with_retry
    data = _data(2000, seed=7)
    host = HostTable.from_pydict(data)
    dt = DeviceTable.from_host(host)
    before = _mem_scope()
    RMM_TPU.force_split_and_retry_oom(1)
    outs = list(with_retry(dt, lambda d: d.to_host()))
    assert len(outs) == 2  # halved by rows, both halves replayed
    moved = {k: v - before.get(k, 0) for k, v in _mem_scope().items()}
    assert moved.get("splitRetries", 0) >= 1, moved
    merged = HostTable.concat(outs)
    assert merged.to_pydict() == host.to_pydict()


# ---------------------------------------------------------------------------
# the streaming aggregate's run-ahead bound
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("nb", [1, 6], ids=["one-batch-chunked", "six"])
def test_streaming_agg_run_ahead_bounded_by_budget(tmp_path, monkeypatch, nb):
    """With no row-count read per partial, the arbiter's budget is the
    streaming aggregate's back-pressure: under a 160KB budget the loop
    waits for its oldest partial before it pulls another chunk, the
    answer is bitwise the same-shape baseline's, and the ledger's peak
    is no higher than with a count read after every partial (+ one
    input chunk). At the default budget it never waits."""
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    data = _data(20000, seed=nb)
    log = {"spark.rapids.sql.eventLog.enabled": "true",
           "spark.rapids.sql.eventLog.dir": str(tmp_path)}

    budgeted = TpuSession(_budget_conf(log))
    got = _agg(budgeted, data, nb=nb)
    batches = plan_metric_total(budgeted, "partialAggBatches")
    assert batches > nb  # the scan chunked
    waits = plan_metric_total(budgeted, "runAheadWaits")
    assert waits > 0
    assert plan_metric_total(budgeted, "partialCountReads") == 0
    assert budgeted.last_event_record["hostSyncs"] == 1 + waits
    peak = MEMORY.peak_bytes()

    MEMORY.reset()
    plain = TpuSession(log)
    with forced_chunking(_SHARE):
        want = _agg(plain, data, nb=nb)
    assert got == want  # bitwise
    assert plan_metric_total(plain, "partialAggBatches") == batches
    assert plan_metric_total(plain, "runAheadWaits") == 0
    assert plain.last_event_record["hostSyncs"] == 1

    # the loop as it was: every partial's count read before the next pull
    real = TpuHashAggregateExec._aggregate

    def synced(self, *a, **k):
        out = real(self, *a, **k)
        out.num_rows
        return out
    monkeypatch.setattr(TpuHashAggregateExec, "_aggregate", synced)
    MEMORY.reset()
    assert _agg(TpuSession(_budget_conf(log)), data, nb=nb) == want
    assert peak <= MEMORY.peak_bytes() + _SHARE


# ---------------------------------------------------------------------------
# admission + arbiter accounting
# ---------------------------------------------------------------------------


def test_admission_probe_consults_arbiter_occupancy():
    """The service's default memory probe reads the arbiter's LIVE
    ledger — bytes accounted outside the spill catalog (plain landed
    tables) gate admission too."""
    from spark_rapids_tpu.service.scheduler import _default_memory_probe
    MEMORY.reset()
    dt = DeviceTable.from_host(HostTable.from_pydict(_data(2000, seed=8)))
    occ = MEMORY.occupancy()
    assert occ > 0
    # nothing registered in the catalog, yet the probe sees the bytes
    assert _default_memory_probe() >= occ
    del dt
    assert MEMORY.occupancy() == 0


def test_admission_forward_progress_escape_pinned():
    """admission.maxDeviceBytes below live occupancy still admits when
    NOTHING is running — the existing forward-progress escape survives
    the arbiter-backed probe."""
    from spark_rapids_tpu.service.scheduler import QueryService
    MEMORY.reset()
    # pin real accounted occupancy far above the gate
    pinned = DeviceTable.from_host(
        HostTable.from_pydict(_data(4000, seed=9)))
    assert MEMORY.occupancy() > 64
    svc = QueryService({
        "spark.rapids.service.admission.maxDeviceBytes": "64",
        "spark.rapids.service.maxConcurrentQueries": "1",
    })
    try:
        df = svc.session.create_dataframe({"a": [1, 2, 3]})
        h = svc.submit(df)
        out = h.result(timeout=30)
        assert out.num_rows == 3
        assert svc.health()["state"] == "HEALTHY"
        assert "memory" in svc.health()
        assert svc.health()["memory"]["occupancyBytes"] >= 0
    finally:
        svc.shutdown()
        del pinned


def test_arbiter_accounting_exact_under_threads():
    """Reserve/account/release exactness under 4 threads: occupancy
    returns to zero, the peak never exceeds the budget when every
    grant goes through reserve, and no reservation leaks."""
    arb = MemoryArbiter()

    class _Conf:
        def get_entry(self, entry):
            return {"spark.rapids.memory.device.budgetBytes": 1 << 20,
                    "spark.rapids.memory.device.scanChunkFraction":
                        0.25}[entry.key]

    arb.configure(_Conf())
    errors = []

    def worker(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(200):
                n = int(rng.integers(1, 2048))
                r = arb.reserve(n)
                assert arb.occupancy() >= n
                r.release()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    snap = arb.snapshot()
    assert snap["occupancyBytes"] == 0
    assert snap["reservedBytes"] == 0
    assert 0 < snap["peakBytes"] <= snap["budgetBytes"]
    assert snap["budgetViolations"] == 0


def test_reserve_refuses_when_spilling_cannot_make_room():
    """A reservation past the budget with nothing spillable raises
    RetryOOM (the retry framework's signal), not a silent grant."""
    from spark_rapids_tpu.errors import RetryOOM
    arb = MemoryArbiter()

    class _Conf:
        def get_entry(self, entry):
            return {"spark.rapids.memory.device.budgetBytes": 4096,
                    "spark.rapids.memory.device.scanChunkFraction":
                        0.25}[entry.key]

    arb.configure(_Conf())
    r = arb.reserve(4000)
    with pytest.raises(RetryOOM):
        arb.reserve(4000)
    r.release()
    arb.reserve(4000).release()  # room again once the first released


# ---------------------------------------------------------------------------
# event-log schema v10
# ---------------------------------------------------------------------------


def test_device_budget_flag_validation():
    """validate_flags rejects the --device-budget combinations the
    memory harness does not implement, naming the supported modes."""
    from types import SimpleNamespace

    import scale_test as st

    def args(**kw):
        base = dict(mesh=0, hosts=0, streaming=False, concurrency=0,
                    service_faults=False,
                    cpu_baseline=False, require_tpu=False, chaos=False,
                    device_budget=0)
        base.update(kw)
        return SimpleNamespace(**base)

    st.validate_flags(args(device_budget=4_000_000))  # supported
    st.validate_flags(args(device_budget=4_000_000, chaos=True))
    # planes COMPOSE now: budget x hosts / budget x concurrency route
    # to the fleet closure instead of being rejected
    st.validate_flags(args(device_budget=4_000_000, hosts=2))
    st.validate_flags(args(device_budget=4_000_000, concurrency=4))
    for bad in (args(device_budget=100),
                args(device_budget=4_000_000, mesh=8),
                args(device_budget=4_000_000, cpu_baseline=True),
                args(device_budget=4_000_000, require_tpu=True)):
        with pytest.raises(SystemExit) as ei:
            st.validate_flags(bad)
        assert "supported modes" in str(ei.value)


def test_event_log_v10_memory_fields(tmp_path):
    """spillBytes/unspills ride the record as per-query memory-scope
    deltas; budgetPeak reads the arbiter's peak."""
    left, right = _join_data(seed=10)
    BufferCatalog.reset(host_limit_bytes=4096, disk_dir=str(tmp_path))
    s = TpuSession(_budget_conf({
        "spark.rapids.sql.eventLog.enabled": "true",
        "spark.rapids.sql.eventLog.dir": str(tmp_path / "ev"),
    }))
    _join_q(s, left, right)
    rec = s.last_event_record
    assert rec["schema"] == 16
    assert rec["spillBytes"] > 0
    assert rec["unspills"] > 0
    assert rec["budgetPeak"] > 0
    # and the tools read them back (profile Memory line)
    from spark_rapids_tpu.tools.report import build_profile, render_profile
    prof = build_profile([rec])
    assert prof["memory"]["spillBytes"] > 0
    assert "Memory:" in render_profile(prof)
