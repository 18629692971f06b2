"""Tier-1 streaming + materialized-view pipeline tests (ISSUE 16).

Covers the streaming subsystem's load-bearing contracts on a small,
seeded corpus:

* exactly-once across a mid-micro-batch kill — a resumed stream re-runs
  the pending batch and the sink's txn watermark dedupes, so the sink
  row set is bit-identical to a fault-free run;
* MV incremental refresh (append + re-aggregate strategies) bit-identical
  to a from-scratch recompute at every epoch;
* the full-recompute fallback and its reason surfaced in explain();
* per-table invalidation epochs: a commit to table B does not evict a
  cached result over table A;
* event-log schema v11 fields (microBatches … sinkReplays, mvEpoch).
"""

import json
import os

import pytest

from spark_rapids_tpu.columnar.table import HostTable
from spark_rapids_tpu.ops.expr import col, lit


def _rows(t):
    return sorted(zip(*[c.to_pylist() for c in t.columns]))


def _svc(tmp_path, **conf):
    from spark_rapids_tpu.service.scheduler import QueryService
    base = {"spark.rapids.service.maxConcurrentQueries": 2}
    base.update(conf)
    return QueryService(base)


def _make_delta(session, path, data, cdf=True):
    from spark_rapids_tpu.delta.commands import DeltaTable
    from spark_rapids_tpu.delta.table import write_delta
    from spark_rapids_tpu.plan.dataframe import from_host_table
    write_delta(from_host_table(HostTable.from_pydict(data), session).plan,
                session, path, mode="error")
    if cdf:
        DeltaTable(session, path).set_properties(
            {"delta.enableChangeDataFeed": "true"})
    return DeltaTable(session, path)


def _append(session, path, data):
    from spark_rapids_tpu.delta.table import write_delta
    from spark_rapids_tpu.plan.dataframe import from_host_table
    write_delta(from_host_table(HostTable.from_pydict(data), session).plan,
                session, path, mode="append")


# ---------------------------------------------------------------------------
# offset log protocol
# ---------------------------------------------------------------------------


def test_offset_log_pending_protocol(tmp_path):
    from spark_rapids_tpu.streaming import OffsetLog
    log = OffsetLog(str(tmp_path / "ck"))
    assert log.latest_batch_id() == -1
    assert log.pending_batch() is None
    log.write_offsets(0, {"start": 0, "end": 10})
    # offsets without a commit = the batch to re-run on resume
    assert log.pending_batch() == (0, {"start": 0, "end": 10})
    log.write_commit(0, {"outcome": "committed"})
    assert log.pending_batch() is None
    assert log.last_end_offset() == 10
    # planning out of order is a protocol violation, not silent data loss
    from spark_rapids_tpu.errors import ColumnarProcessingError
    with pytest.raises(ColumnarProcessingError):
        log.write_offsets(5, {"start": 10, "end": 20})


# ---------------------------------------------------------------------------
# exactly-once across a mid-micro-batch kill
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_stream_exactly_once_after_kill(tmp_path):
    """Kill a stream mid-micro-batch (after its offsets are logged,
    before the sink commit), resume from the checkpoint, and require the
    sink row set to be bit-identical to a fault-free run — no lost and
    no duplicated rows."""
    from spark_rapids_tpu.delta.commands import DeltaTable
    from spark_rapids_tpu.delta.log import DeltaLog
    from spark_rapids_tpu.errors import KernelCrashError
    from spark_rapids_tpu.runtime.faults import FAULTS
    from spark_rapids_tpu.streaming import (
        DeltaStreamSink,
        OffsetLog,
        RateSource,
        StreamingQuery,
    )
    svc = _svc(tmp_path)
    try:
        s = svc.session
        # fault-free baseline: same seeded source into its own sink
        base_sink = str(tmp_path / "baseline_sink")
        q0 = StreamingQuery(
            svc, RateSource(rows_per_batch=20, seed=7, total_rows=60),
            DeltaStreamSink(base_sink, "base"), str(tmp_path / "ck0"),
            name="base")
        assert q0.process_available() == 3
        expected = _rows(s.execute(DeltaTable(s, base_sink).to_df().plan))

        # chaos run: second micro-batch dies between offset log and sink
        sink = str(tmp_path / "sink")
        ck = str(tmp_path / "ck")

        def fresh_query():
            return StreamingQuery(
                svc, RateSource(rows_per_batch=20, seed=7, total_rows=60),
                DeltaStreamSink(sink, "s1"), ck, name="s1")

        q = fresh_query()
        assert q.run_one_batch()
        FAULTS.arm("stream.batch:crash:1")
        try:
            with pytest.raises(KernelCrashError):
                q.run_one_batch()
        finally:
            FAULTS.disarm()
        # the killed batch is pending: offsets logged, no commit marker
        olog = OffsetLog(ck)
        assert olog.pending_batch() is not None
        # a fresh stream over the same checkpoint resumes exactly-once
        assert fresh_query().process_available() == 2
        got = _rows(s.execute(DeltaTable(s, sink).to_df().plan))
        assert got == expected

        # harder window: sink commit landed but the commit marker did
        # not — replay must dedupe via the txn watermark, not re-append
        last = olog.latest_committed_id()
        os.remove(os.path.join(olog.commits_dir, f"{last}.json"))
        assert fresh_query().process_available() == 1  # the replay
        got2 = _rows(s.execute(DeltaTable(s, sink).to_df().plan))
        assert got2 == expected
        assert DeltaLog(sink).last_txn_version("s1") == 2
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# MV incremental maintenance
# ---------------------------------------------------------------------------


def test_mv_incremental_bit_identity_every_epoch(tmp_path):
    """Aggregate (re-aggregate strategy) and projection (append strategy)
    MVs must serve tables bit-identical to a from-scratch recompute of
    the registered plan at the same epoch, after EVERY commit — with at
    least one refresh actually served incrementally."""
    import spark_rapids_tpu.functions as F
    svc = _svc(tmp_path)
    try:
        s = svc.session
        base = str(tmp_path / "base")
        dt = _make_delta(s, base, {"k": [1, 2, 3, 1], "v": [10, 20, 30, 40]})
        reg = svc.mv_registry()
        df = dt.to_df()
        mv_agg = reg.register(
            "agg", df.group_by(col("k")).agg(F.sum(col("v")).alias("sv"),
                                             F.count(col("v")).alias("c")))
        mv_proj = reg.register(
            "proj", df.filter(col("v") > lit(12)).select(col("k"), col("v")))
        assert mv_agg.strategy == "reaggregate"
        assert mv_proj.strategy == "append"

        commits = [
            {"k": [2, 4], "v": [5, 100]},
            {"k": [4, 1], "v": [7, 3]},
            {"k": [3], "v": [1000]},
        ]
        for data in commits:
            _append(s, base, data)
            assert mv_agg.stale and mv_proj.stale
            for mv in (mv_agg, mv_proj):
                served = mv.read()
                assert _rows(served) == _rows(mv.recompute_at_epoch()), \
                    f"{mv.name} diverged at epoch {mv.epoch()}"
        assert mv_agg.incremental_refreshes >= 1
        assert mv_proj.incremental_refreshes >= 1
        assert mv_agg.last_refresh_mode == "incremental-reaggregate"
        assert mv_proj.last_refresh_mode == "incremental-append"
    finally:
        svc.shutdown()


def test_mv_full_recompute_fallback_surfaced(tmp_path):
    """Non-whitelisted plans (joins) register with strategy=full, and an
    append-strategy view hit by non-insert changes falls back to a full
    recompute — both with the reason in explain()."""
    svc = _svc(tmp_path)
    try:
        from spark_rapids_tpu.delta.commands import DeltaTable
        s = svc.session
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        _make_delta(s, a, {"k": [1, 2], "x": [10, 20]})
        _make_delta(s, b, {"k": [1, 2], "y": [7, 8]}, cdf=False)
        reg = svc.mv_registry()
        joined = DeltaTable(s, a).to_df().join(
            DeltaTable(s, b).to_df(), on=["k"])
        mv_join = reg.register("j", joined)
        assert mv_join.strategy == "full"
        text = mv_join.explain()
        assert "strategy=full" in text and "fallback:" in text
        # join MV still refreshes correctly (full recompute) on commit
        _append(s, a, {"k": [2], "x": [100]})
        served = mv_join.read()
        assert mv_join.last_refresh_mode == "full-recompute"
        assert _rows(served) == _rows(mv_join.recompute_at_epoch())

        # append-strategy view + an UPDATE delta -> full fallback, with
        # the non-insert reason surfaced
        mv_p = reg.register(
            "p", DeltaTable(s, a).to_df().select(col("k"), col("x")))
        DeltaTable(s, a).update(col("k") == lit(1), {"x": lit(0)})
        mv_p.read()
        assert mv_p.last_refresh_mode == "full-recompute"
        assert "non-insert" in mv_p.explain()
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# per-table invalidation epochs
# ---------------------------------------------------------------------------


def test_per_table_epoch_scoping(tmp_path):
    """A Delta commit bumps only ITS table's epoch: cached results over
    other tables keep serving, same-table entries drop, and a global
    bump (catalog-wide) still evicts everything."""
    from spark_rapids_tpu.delta.commands import DeltaTable
    from spark_rapids_tpu.plan.fingerprint import bump_invalidation_epoch
    svc = _svc(tmp_path)
    try:
        s = svc.session
        a, b = str(tmp_path / "a"), str(tmp_path / "b")
        _make_delta(s, a, {"x": [1, 2, 3]}, cdf=False)
        _make_delta(s, b, {"y": [4, 5]}, cdf=False)

        def hit_count():
            return svc.result_cache.stats()["hits"]

        def run_over_a():
            h = svc.submit(DeltaTable(s, a).to_df().select(col("x")))
            h.result(timeout=60)

        run_over_a()               # fill
        run_over_a()               # hit
        assert hit_count() == 1
        _append(s, b, {"y": [6]})  # unrelated commit: table B only
        run_over_a()
        assert hit_count() == 2, "commit to B evicted a result over A"
        _append(s, a, {"x": [9]})  # same-table commit: must invalidate
        run_over_a()
        assert hit_count() == 2
        run_over_a()               # refilled at the new epoch
        assert hit_count() == 3
        bump_invalidation_epoch("catalog-wide test bump")
        run_over_a()
        assert hit_count() == 3, "global bump must evict everything"
    finally:
        svc.shutdown()


# ---------------------------------------------------------------------------
# scale_test flag validation
# ---------------------------------------------------------------------------


def test_streaming_flag_validation():
    """validate_flags rejects the --streaming combinations the harness
    does not implement, naming the supported modes."""
    from types import SimpleNamespace

    import scale_test as st

    def args(**kw):
        base = dict(mesh=0, hosts=0, streaming=False, concurrency=0,
                    service_faults=False, cpu_baseline=False,
                    require_tpu=False, chaos=False, device_budget=0)
        base.update(kw)
        return SimpleNamespace(**base)

    st.validate_flags(args(streaming=True))  # supported
    st.validate_flags(args(streaming=True, chaos=True))  # supported
    for bad in (args(streaming=True, mesh=4),
                args(streaming=True, hosts=2),
                args(streaming=True, device_budget=4_000_000),
                args(streaming=True, concurrency=2),
                args(streaming=True, chaos=True, service_faults=True),
                args(streaming=True, cpu_baseline=True)):
        with pytest.raises(SystemExit) as ei:
            st.validate_flags(bad)
        assert "supported modes" in str(ei.value)


# ---------------------------------------------------------------------------
# schema v11 + introspection surfaces
# ---------------------------------------------------------------------------


def test_schema_v11_streaming_fields(tmp_path):
    """Every v11 record carries the six streaming deltas and mvEpoch;
    an MV serve stamps its epoch; stream work shows up in the log's
    totals; /top and `tools top` show the recurring stream."""
    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.delta.commands import DeltaTable
    from spark_rapids_tpu.service.introspect import _routes
    from spark_rapids_tpu.streaming import (
        DeltaStreamSink,
        RateSource,
        StreamingQuery,
    )
    from spark_rapids_tpu.tools.top import render_top
    svc = _svc(
        tmp_path,
        **{"spark.rapids.sql.eventLog.enabled": True,
           "spark.rapids.sql.eventLog.dir": str(tmp_path / "ev")})
    try:
        s = svc.session
        base = str(tmp_path / "base")
        dt = _make_delta(s, base, {"k": [1, 2, 1], "v": [10, 20, 30]})
        mv = svc.mv_registry().register(
            "agg", dt.to_df().group_by(col("k")).agg(
                F.sum(col("v")).alias("sv")))
        _append(s, base, {"k": [2], "v": [5]})
        mv.read()
        rec = s.last_event_record
        assert rec["schema"] == 16
        assert rec["mvEpoch"] == mv.epoch()
        assert rec["queryTag"] == f"mv:agg@v{mv.epoch()}"

        q = StreamingQuery(
            svc, RateSource(rows_per_batch=25, seed=3, total_rows=50),
            DeltaStreamSink(str(tmp_path / "sink"), "s1"),
            str(tmp_path / "ck"), name="s1")
        svc.register_stream(q)
        assert q.process_available() == 2
        # one more trivial envelope so the trailing scope deltas land
        svc.submit(dt.to_df().select(col("k"))).result(timeout=60)

        records = [json.loads(line)
                   for line in open(s.last_event_path)
                   if line.strip()]
        for r in records:
            for f in ("microBatches", "mvRefreshes",
                      "mvIncrementalRefreshes", "mvFullRecomputes",
                      "sinkCommits", "sinkReplays"):
                assert f in r, f"record missing v11 field {f}"
            assert "mvEpoch" in r
        assert sum(r["microBatches"] for r in records) == 2
        assert sum(r["sinkCommits"] for r in records) == 2
        assert sum(r["mvRefreshes"] for r in records) >= 2

        # the recurring tenant is on the introspection surfaces
        doc = _routes(svc, "/top", {})
        names = [st["name"] for st in doc["streams"]]
        assert "s1" in names
        rendered = render_top(doc)
        assert "Streams: 1 recurring" in rendered and "s1" in rendered
    finally:
        svc.shutdown()
