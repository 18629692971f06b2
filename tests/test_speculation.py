"""Speculative sizing machinery (runtime/speculation.py + the join/agg
speculation sites) — VERDICT r3 #2: the fail -> replay -> blocklist state
machine needs dedicated coverage, not incidental exercise.

Pattern reference: the reference unit-tests its retry state machine
exhaustively (tests/.../WithRetrySuite.scala)."""

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu.ops.expr import col
from spark_rapids_tpu.runtime import speculation as spec
from spark_rapids_tpu.session import TpuSession
from tests.test_joins import _join_metrics, _logged_session


@pytest.fixture(autouse=True)
def _clean_blocklist():
    saved = set(spec._BLOCKLIST)
    spec._BLOCKLIST.clear()
    yield
    spec._BLOCKLIST.clear()
    spec._BLOCKLIST.update(saved)


def _cpu():
    return TpuSession({"spark.rapids.sql.enabled": "false"})


def _fk_tables(n=20_000, nkeys=500, seed=0):
    rng = np.random.default_rng(seed)
    fact = {"k": rng.integers(0, nkeys, n).astype(np.int64),
            "v": rng.random(n)}
    dim = {"k": np.arange(nkeys, dtype=np.int64),
           "w": (np.arange(nkeys) % 7).astype(np.int64)}
    return fact, dim


def _join_q(s, fact, dim, how="inner"):
    return sorted(
        s.create_dataframe(fact).join(s.create_dataframe(dim), on="k",
                                      how=how)
        .group_by("w").agg(F.count().alias("c"),
                           F.sum(col("v")).alias("sv")).collect())


def _rows_close(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x[0] == y[0] and x[1] == y[1]
        assert abs(x[2] - y[2]) <= 1e-6 * max(1.0, abs(y[2]))


# -- core state machine ------------------------------------------------------

def test_flags_validated_and_cleared_on_success():
    fact, dim = _fk_tables()
    s = TpuSession()
    _rows_close(_join_q(s, fact, dim), _join_q(_cpu(), fact, dim))
    # nothing blocklisted, no flags leaked into a stale context
    assert spec.current() is None
    assert not spec._BLOCKLIST


def _join_metric(s, name):
    """Sum of a TpuJoinExec metric over the last query's plan tree."""
    return sum(m.get(name, 0) for m in _join_metrics(s.last_event_record))


def test_duplicate_build_keys_take_the_sorted_body_without_replay(tmp_path):
    """Duplicate build-side keys rule the direct-address body out. The
    join READS that of its build side before the first probe batch
    (execs/join.py _plan_direct): the sorted body runs, exact, and
    nothing is guessed, replayed or blocklisted, on the first run and
    the second."""
    rng = np.random.default_rng(1)
    n = 8000
    fact = {"k": rng.integers(0, 50, n).astype(np.int64),
            "v": rng.random(n)}
    dup = {"k": np.concatenate([np.arange(50), np.arange(50)]).astype(
        np.int64), "w": np.arange(100, dtype=np.int64)}
    s = _logged_session(tmp_path)
    q = lambda ss: sorted(
        ss.create_dataframe(fact).join(ss.create_dataframe(dup), on="k",
                                       how="inner")
        .group_by("k").agg(F.count().alias("c")).collect())
    want = q(_cpu())
    for _ in range(2):
        assert q(s) == want
        assert not spec._BLOCKLIST
        assert "speculationReplays" not in s.last_metrics()
        assert _join_metric(s, "sortJoinBatches") == 1
        assert _join_metric(s, "directJoinBatches") == 0
        assert _join_metric(s, "joinOutputRows") == 2 * n


def test_sparse_key_range_takes_the_sorted_body_exact(tmp_path):
    """Build keys spread over a range far wider than a direct table may
    be (10^9 against 2^26 slots): the range is read, the sorted body
    runs, the answer is exact and nothing replays."""
    rng = np.random.default_rng(2)
    n = 4000
    sparse_keys = rng.choice(10**9, size=200, replace=False).astype(np.int64)
    fact = {"k": sparse_keys[rng.integers(0, 200, n)],
            "v": rng.random(n)}
    dim = {"k": sparse_keys, "w": np.arange(200, dtype=np.int64)}
    s = _logged_session(tmp_path)
    got = _join_q(s, fact, dim)
    _rows_close(got, _join_q(_cpu(), fact, dim))
    assert not spec._BLOCKLIST
    assert _join_metric(s, "sortJoinBatches") == 1


def test_blocklist_is_per_operator_site():
    """Two same-shaped aggregates at different plan positions blocklist
    independently (ADVICE r3: a site key shared by look-alike
    operators). The join has no speculation site since it reads its
    build side."""
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.ops.expr import BoundReference
    from spark_rapids_tpu import types as T
    mk = lambda: TpuHashAggregateExec.__new__(TpuHashAggregateExec)
    a, b = mk(), mk()
    for j, lid in ((a, 3), (b, 9)):
        j.grouping = [BoundReference(0, T.LONG)]
        j.agg_specs = []
        j._lore_id = lid
    assert a._spec_site_key() != b._spec_site_key()


def test_conf_off_takes_exact_path():
    fact, dim = _fk_tables(seed=3)
    s = TpuSession({"spark.rapids.tpu.speculativeSizing.enabled": "false"})
    _rows_close(_join_q(s, fact, dim), _join_q(_cpu(), fact, dim))
    assert not spec._BLOCKLIST


# -- flag delivery -----------------------------------------------------------

def test_flags_ride_packed_fetch():
    """Small collect: the pending flags embed in the packed d2h fetch
    (to_host consumes ctx.take_pending) and validate there."""
    fact, dim = _fk_tables(n=5000, seed=4)
    s = TpuSession()
    df = (s.create_dataframe(fact)
          .join(s.create_dataframe(dim), on="k", how="inner"))
    out = df.group_by("w").agg(F.count().alias("c"))
    got = sorted(out.collect())
    want = sorted(
        _cpu().create_dataframe(fact).join(
            _cpu().create_dataframe(dim), on="k", how="inner")
        .group_by("w").agg(F.count().alias("c")).collect())
    assert got == want


def test_validate_remaining_catches_unfetched_flags():
    """Flags not consumed by any packed fetch raise at validate_remaining."""
    import jax.numpy as jnp
    tok = spec.activate()
    try:
        ctx = spec.current()
        ctx.add_flag("site-a", jnp.asarray(False))
        ctx.add_flag("site-b", jnp.asarray(True))
        with pytest.raises(spec.SpeculationFailed) as ei:
            ctx.validate_remaining()
        assert ei.value.sites == ["site-b"]
        assert not ctx.pending  # consumed
    finally:
        spec.deactivate(tok)


def test_guard_attempt_drops_flags_from_aborted_attempt():
    import jax.numpy as jnp
    tok = spec.activate()
    try:
        ctx = spec.current()
        ctx.add_flag("kept", jnp.asarray(False))

        def boom():
            ctx.add_flag("aborted", jnp.asarray(True))
            raise RuntimeError("attempt failed")

        with pytest.raises(RuntimeError):
            spec.guard_attempt(boom)
        assert [s for s, _ in ctx.pending] == ["kept"]
    finally:
        spec.deactivate(tok)


# -- interplay ---------------------------------------------------------------

def test_speculation_with_oom_injection():
    fact, dim = _fk_tables(seed=5)
    s = TpuSession({"spark.rapids.sql.test.injectRetryOOM": "retry:2"})
    _rows_close(_join_q(s, fact, dim), _join_q(_cpu(), fact, dim))
    assert not spec._BLOCKLIST  # aborted attempts must not blocklist


def test_speculation_with_multibatch_streaming():
    """Multi-batch probe side: each batch adds its own flags; all validate."""
    rng = np.random.default_rng(6)
    n = 30_000
    fact = {"k": rng.integers(0, 300, n).astype(np.int64),
            "v": rng.random(n)}
    dim = {"k": np.arange(300, dtype=np.int64),
           "w": (np.arange(300) % 5).astype(np.int64)}
    s = TpuSession()
    got = sorted(
        s.create_dataframe(fact, num_batches=4)
        .join(s.create_dataframe(dim), on="k", how="inner")
        .group_by("w").agg(F.count().alias("c")).collect())
    want = sorted(
        _cpu().create_dataframe(fact)
        .join(_cpu().create_dataframe(dim), on="k", how="inner")
        .group_by("w").agg(F.count().alias("c")).collect())
    assert got == want


def test_agg_speculative_shrink_site_blocklists_once():
    """All-distinct-keys aggregate: the shrink speculation misses, the
    site blocklists, and the immediate re-run does not replay again."""
    n = 150_000
    data = {"k": np.arange(n, dtype=np.int64)}
    # force the sort-segment path: dense int keys would otherwise take the
    # domain fast path, which emits a domain-sized output with no shrink
    # speculation at all
    s = TpuSession({"spark.rapids.tpu.agg.maxKeyDomainGroups": 0})
    q = lambda: s.create_dataframe(data).group_by("k").agg(
        F.count().alias("c"))
    r1 = q().collect()
    assert len(r1) == n
    shrink_sites = {x for x in spec._BLOCKLIST if x.endswith(":shrink")}
    assert shrink_sites
    r2 = q().collect()
    assert len(r2) == n
    assert {x for x in spec._BLOCKLIST if x.endswith(":shrink")} == \
        shrink_sites


def test_replay_metric_recorded():
    """A replay is counted: the all-distinct-keys aggregate misses its
    shrink speculation once (the join guesses nothing any more)."""
    n = 150_000
    s = TpuSession({"spark.rapids.tpu.agg.maxKeyDomainGroups": 0})
    _ = s.create_dataframe({"k": np.arange(n, dtype=np.int64)}) \
        .group_by("k").agg(F.count().alias("c")).collect()
    m = s.last_metrics()
    assert "speculationReplays" in m, m
