"""Serving-latency subsystem: shape bucketing, the plan->executable
cache, async result fetch, the dispatch-cache LRU, and AOT warmup."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from spark_rapids_tpu.functions import col, lit
from spark_rapids_tpu.functions import sum as fsum
from spark_rapids_tpu.session import TpuSession


def _df(s, n=50):
    return s.create_dataframe({
        "a": list(range(n)),
        "b": [float(i) * 0.5 for i in range(n)],
    })


# ---------------------------------------------------------------------------
# shared fingerprint module (satellite: one implementation, two keys)
# ---------------------------------------------------------------------------


def test_fingerprints_diverge_exactly_on_literal_values():
    from spark_rapids_tpu.plan.fingerprint import plan_fingerprints
    s = TpuSession()
    df = _df(s)
    p5 = df.filter(col("a") > lit(5)).plan
    p6 = df.filter(col("a") > lit(6)).plan
    p6b = df.filter(col("a") >= lit(6)).plan
    t5, f5 = plan_fingerprints(p5, s.conf)
    t6, f6 = plan_fingerprints(p6, s.conf)
    t6b, f6b = plan_fingerprints(p6b, s.conf)
    # literal-only difference: templates COLLIDE, full keys DIVERGE
    assert t5 == t6
    assert f5 != f6
    # structural difference (>= vs >): BOTH diverge
    assert t6 != t6b and f6 != f6b
    # same plan twice: both stable
    t5x, f5x = plan_fingerprints(
        df.filter(col("a") > lit(5)).plan, s.conf)
    assert (t5x, f5x) == (t5, f5)


def test_result_cache_still_separates_literal_variants():
    """The result cache keys on the FULL fingerprint — literal variants
    must never share a cached result."""
    from spark_rapids_tpu.service.result_cache import fingerprint
    s = TpuSession()
    df = _df(s)
    assert fingerprint(df.filter(col("a") > lit(5)).plan, s.conf) != \
        fingerprint(df.filter(col("a") > lit(6)).plan, s.conf)


# ---------------------------------------------------------------------------
# shape bucketing policy
# ---------------------------------------------------------------------------


def test_bucket_policy_shapes():
    from spark_rapids_tpu.columnar.column import BucketPolicy
    p2 = BucketPolicy("pow2", 128)
    assert [p2.bucket_for(n) for n in (1, 128, 129, 1000)] == \
        [128, 128, 256, 1024]
    p4 = BucketPolicy("pow4", 128)
    assert [p4.bucket_for(n) for n in (1, 129, 600, 3000)] == \
        [128, 512, 2048, 8192]
    ex = BucketPolicy("1024,16384", 128)
    assert ex.bucket_for(5) == 1024
    assert ex.bucket_for(2000) == 16384
    # above the declared maximum: pow2 growth, capacity always exists
    assert ex.bucket_for(20000) == 32768


def test_bucket_capacities_drawn_only_from_declared_set():
    from spark_rapids_tpu.columnar.column import BucketPolicy
    for spec in ("pow2", "pow4", "512,4096,65536"):
        p = BucketPolicy(spec, 128)
        declared = set(p.buckets_up_to(1 << 20))
        for n in (1, 7, 128, 129, 500, 5000, 70000, 1 << 20):
            assert p.bucket_for(n) in declared, (spec, n)
        # the set is BOUNDED: log-many buckets, not one per row count
        assert len(declared) <= 21


def test_bucket_policy_validation():
    from spark_rapids_tpu.columnar.column import BucketPolicy
    from spark_rapids_tpu.errors import ColumnarProcessingError
    for bad in ("100,200", "1024,512", "pow3x", "0"):
        with pytest.raises(ColumnarProcessingError):
            BucketPolicy(bad, 128)
    with pytest.raises(ColumnarProcessingError):
        BucketPolicy("pow2", 100)  # not a lane-width multiple


def test_bucketing_bit_identity_on_scale_corpus_slice():
    """A coarser bucket policy changes kernel shapes, never results:
    scale_test slice runs bit-identical under pow2 (default), pow4 and
    an explicit bucket set."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import scale_test as st
    from spark_rapids_tpu.datagen import scale_test_specs
    sf = 0.003
    specs = scale_test_specs(sf)
    tables = {name: spec.generate_table(sf, seed=3)
              for name, spec in specs.items()}
    wanted = ["q1", "q3", "q6"]
    results = {}
    for policy in ("pow2", "pow4", "256,2048,16384"):
        s = TpuSession({"spark.rapids.sql.shapeBuckets": policy})
        qs = st.build_queries(s, tables)
        results[policy] = {name: qs[name]().collect_table()
                           for name in wanted}
    for policy in ("pow4", "256,2048,16384"):
        for name in wanted:
            diff = st.tables_differ(results["pow2"][name],
                                    results[policy][name])
            assert diff is None, f"{policy}/{name}: {diff}"


def test_pad_waste_metric_counted():
    from spark_rapids_tpu.dispatch import COMPILE_SCOPE
    s = TpuSession()
    before = COMPILE_SCOPE.get("padWasteRows", 0)
    _df(s, n=50).filter(col("a") > lit(10)).collect_table()
    assert COMPILE_SCOPE.get("padWasteRows", 0) > before
    # per-query view: 50 rows pad to the 128 bucket somewhere in the plan
    assert (s.last_pad_waste_rows or 0) >= 78


# ---------------------------------------------------------------------------
# plan -> executable cache
# ---------------------------------------------------------------------------


def test_executable_cache_hit_skips_tracing_bit_identical():
    from spark_rapids_tpu.dispatch import COMPILE_SCOPE
    s = TpuSession()
    df = _df(s, n=100)

    def q(v):
        return (df.filter(col("a") > lit(v)).group_by("a")
                .agg(fsum(col("b")).alias("sb")))

    r1 = q(5).collect_table()
    assert s.last_executable_cache_hit is False
    traces_after_cold = COMPILE_SCOPE.get("kernelTraces", 0)
    r2 = q(5).collect_table()
    assert s.last_executable_cache_hit is True
    assert s.last_compile_ms == 0.0
    # the repeat performed ZERO new XLA traces
    assert COMPILE_SCOPE.get("kernelTraces", 0) == traces_after_cold
    assert r1.to_pydict() == r2.to_pydict()


def test_executable_cache_literal_variant_is_template_hit():
    from spark_rapids_tpu.plan.executable_cache import EXEC_CACHE
    s = TpuSession()
    df = _df(s, n=100)

    def q(v):
        return df.filter(col("a") > lit(v))

    q(5).collect_table()
    before = EXEC_CACHE.stats()
    r = q(6).collect_table()
    after = EXEC_CACHE.stats()
    assert s.last_executable_cache_hit is False
    assert after["templateHits"] == before["templateHits"] + 1
    # and the variant computed its OWN (correct) result
    assert r.to_pydict()["a"] == list(range(7, 100))


def test_executable_cache_invalidated_by_catalog_mutation():
    s = TpuSession()
    df = _df(s, n=40)
    q = df.group_by("a").agg(fsum(col("b")).alias("sb"))
    q.collect_table()
    q.collect_table()
    assert s.last_executable_cache_hit is True
    # any warehouse mutation bumps the epoch -> cached executables stale
    _df(s, n=4).create_or_replace_temp_view("serving_latency_inval_v")
    q.collect_table()
    assert s.last_executable_cache_hit is False
    q.collect_table()
    assert s.last_executable_cache_hit is True


def test_executable_cache_disabled_by_conf():
    s = TpuSession({"spark.rapids.sql.executableCache.enabled": "false"})
    df = _df(s)
    df.filter(col("a") > lit(1)).collect_table()
    df.filter(col("a") > lit(1)).collect_table()
    assert s.last_executable_cache_hit is False


def test_executable_cache_metrics_reset_per_run():
    """A reused tree must report the SECOND query's metrics, not the
    accumulated pair (the event record depends on it)."""
    s = TpuSession()
    df = _df(s, n=64)
    q = df.filter(col("a") > lit(2))
    q.collect_table()
    first = s.last_dispatches
    q.collect_table()
    assert s.last_executable_cache_hit is True
    ex = s._last_executable
    # numOutputRows on the root covers ONE run's 61 rows, not 122
    assert ex.metrics.get("numOutputRows", 0) <= 61 + 3
    assert s.last_dispatches <= first


def test_cached_tree_does_not_inherit_stale_cancel_scope():
    """The cancellation boundary resolves the ACTIVE scope per pull: a
    tree first run under a (later-cancelled) service scope must not
    raise for a plain session re-run."""
    from spark_rapids_tpu.service.query import CancelScope, cancel_scope
    s = TpuSession()
    df = _df(s, n=30)
    q = df.filter(col("a") > lit(3))
    scope = CancelScope()
    with cancel_scope(scope):
        q.collect_table()
    scope.cancel()  # late cancel on a finished query's scope
    out = q.collect_table()  # reuses the cached tree: must NOT raise
    assert s.last_executable_cache_hit is True
    assert out.num_rows == 26


def test_executable_cache_mid_run_write_stales_the_fill():
    """Entries are stamped with the CHECKOUT-time epoch: a write that
    lands while the filling query runs must stale the entry on its
    first lookup, and a pre-write tree must never re-park into a
    post-write pool (review-round coherence fix)."""
    from spark_rapids_tpu.plan.executable_cache import ExecutableCache
    from spark_rapids_tpu.plan.fingerprint import bump_invalidation_epoch
    s = TpuSession()
    plan = _df(s).filter(col("a") > lit(1)).plan
    cache = ExecutableCache()
    tok = cache.checkout(plan, s.conf)
    assert not tok.hit
    bump_invalidation_epoch("test: write lands mid-run")
    tok.fill(object(), None)
    tok.release()
    # the filled entry belongs to the PRE-write generation: the
    # post-write lookup must not serve it
    tok2 = cache.checkout(plan, s.conf)
    assert not tok2.hit
    assert cache.stats()["invalidations"] >= 1 or \
        cache.stats()["idleTrees"] == 0
    tok2.release()


# ---------------------------------------------------------------------------
# dispatch const/scalar cache LRU (satellite)
# ---------------------------------------------------------------------------


def test_const_cache_lru_keeps_hot_key_under_cap_pressure(monkeypatch):
    from spark_rapids_tpu import dispatch as D
    monkeypatch.setattr(D, "_CONST_CACHE_CAP", 8)
    hot = np.arange(7, dtype=np.int32)
    hot_dev = D.device_const(hot)
    for i in range(64):  # 8x the cap of distinct cold keys
        D.device_const(np.arange(8 + i, dtype=np.int32))
        # touch the hot key so LRU keeps it
        assert D.device_const(hot) is hot_dev, \
            "hot constant evicted under cap pressure (wholesale clear?)"
    with D._LOCK:
        assert len(D._CONST_CACHE) <= 8


def test_scalar_cache_lru_keeps_hot_key_under_cap_pressure(monkeypatch):
    from spark_rapids_tpu import dispatch as D
    monkeypatch.setattr(D, "_CONST_CACHE_CAP", 8)
    hot_dev = D.device_scalar(424241)
    for i in range(32):
        D.device_scalar(900000 + i)
        assert D.device_scalar(424241) is hot_dev


# ---------------------------------------------------------------------------
# async result fetch
# ---------------------------------------------------------------------------


def test_async_fetch_bit_identical_and_metered():
    base = {"spark.rapids.sql.executableCache.enabled": "false"}
    s_on = TpuSession(base)
    s_off = TpuSession({**base, "spark.rapids.sql.asyncResultFetch":
                        "false"})
    data = {"a": list(range(300)), "b": [float(i) for i in range(300)]}
    got_on = (s_on.create_dataframe(data).filter(col("a") > lit(3))
              .group_by("a").agg(fsum(col("b")).alias("sb"))
              .collect_table())
    got_off = (s_off.create_dataframe(data).filter(col("a") > lit(3))
               .group_by("a").agg(fsum(col("b")).alias("sb"))
               .collect_table())
    assert got_on.to_pydict() == got_off.to_pydict()
    # the root transition recorded the post-semaphore fetch
    ex = s_on._last_executable
    assert "resultFetchTime" in ex.metrics
    assert ex.metrics.get("asyncFetchBatches", 0) >= 1
    assert "resultFetchTime" not in s_off._last_executable.metrics


def test_pending_host_table_resolve_matches_sync():
    from spark_rapids_tpu.columnar import DeviceTable, HostTable
    from spark_rapids_tpu.columnar.table import PendingHostTable
    host = HostTable.from_pydict({
        "x": [1, 2, None, 4], "y": [1.5, None, 3.5, 4.5]})
    dt = DeviceTable.from_host(host)
    pending = dt.to_host_pending()
    assert isinstance(pending, PendingHostTable)
    assert pending.resolve().to_pydict() == dt.to_host().to_pydict()


# ---------------------------------------------------------------------------
# event-log v3 fields
# ---------------------------------------------------------------------------


def test_event_log_carries_compile_fields(tmp_path):
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(tmp_path)})
    df = _df(s)
    q = df.group_by("a").agg(fsum(col("b")).alias("sb"))
    q.collect_table()
    cold = s.last_event_record
    q.collect_table()
    warm = s.last_event_record
    assert cold["schema"] == 16
    assert cold["executableCacheHit"] is False
    assert warm["executableCacheHit"] is True
    assert warm["compileMs"] == 0.0
    assert cold["compileMs"] >= warm["compileMs"]
    assert cold["padWasteRows"] > 0


# ---------------------------------------------------------------------------
# AOT warmup (subprocess smoke: the tier-1 CLI contract)
# ---------------------------------------------------------------------------


@pytest.mark.slow  # fresh-process jax import + compile; the warmup
# logic (trace/skip/compile accounting) is covered in-process by
# test_warmup_in_process_skips_warm_templates
def test_warmup_cli_subprocess_smoke(tmp_path):
    """End-to-end: write a tiny tagged event log, then `python -m
    spark_rapids_tpu.tools warmup` replays it in a FRESH process and
    reports compiled programs (tiny corpus; tier-1 time budget)."""
    eld = tmp_path / "el"
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(eld)})
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import scale_test as st
    from spark_rapids_tpu.datagen import scale_test_specs
    sf = 0.002
    tables = {name: spec.generate_table(sf, seed=0)
              for name, spec in scale_test_specs(sf).items()}
    qs = st.build_queries(s, tables)
    s.next_query_tag = "q6@smoke"
    qs["q6"]().collect_table()

    out = tmp_path / "warmup.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.tools", "warmup",
         "--eventlog-dir", str(eld), "--sf", str(sf), "--json",
         "--out", str(out)],
        capture_output=True, text=True, timeout=240, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stdout + proc.stderr
    report = json.loads(out.read_text())
    assert report["ok"] is True
    assert report["distinctUnits"] == 1
    assert report["programsCompiled"] == 1  # fresh process: q6 compiles
    assert report["newTraces"] > 0
    assert report["queries"][0]["query"] == "q6"


def test_warmup_in_process_skips_warm_templates(tmp_path):
    """Second warmup over the same corpus in one process: everything is
    already traced -> skipped, zero new traces."""
    from spark_rapids_tpu.tools.warmup import run_warmup
    eld = tmp_path / "el"
    s = TpuSession({"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": str(eld)})
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import scale_test as st
    from spark_rapids_tpu.datagen import scale_test_specs
    sf = 0.002
    tables = {name: spec.generate_table(sf, seed=0)
              for name, spec in scale_test_specs(sf).items()}
    qs = st.build_queries(s, tables)
    s.next_query_tag = "q6"
    qs["q6"]().collect_table()
    first = run_warmup(str(eld), sf=sf, tables=tables, session=s)
    assert first["ok"] and first["distinctUnits"] == 1
    second = run_warmup(str(eld), sf=sf, tables=tables, session=s)
    assert second["newTraces"] == 0
    assert second["programsCompiled"] == 0
    assert second["programsSkipped"] == 1
