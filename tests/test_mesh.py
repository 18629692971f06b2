"""Mesh-native distributed execution (the tier-1 multichip slice).

Runs the engine on the virtual 8-device host-platform mesh
(conftest forces --xla_force_host_platform_device_count=8) and pins
the mesh's contracts (execs/mesh.py states the first; the four-chip
benchmark cell `q1-mesh4` holds a real host to it):

* q3 (join->agg) and q6 (window rank) DSL executed mesh-native are
  BIT-IDENTICAL to single-chip; q1 (scan->filter->agg) runs its
  aggregate on the resident shards: exact columns bit-identical, DOUBLE
  sums within the benchmark's limit of the single-chip answer and the
  same bits run after run (tests/test_mesh_aggregate.py has the rest);
* q7 (repartition+agg class) lowers every shuffle exchange to the ICI
  collective — hostShuffleFallbacks=0 — and the warm path performs
  ZERO host->device uploads between exchanges (meshHostUploads);
* repeated exchanges over one string dictionary pay the replicated
  byte-matrix upload ONCE (interned by dictionary identity);
* an ICI-requested exchange that must demote (partition count wider
  than the mesh) surfaces its reason in explain()/describe() and still
  returns correct results through the host shuffle;
* the executable cache is mesh-generation-stamped: a tree cached
  before a mesh reconfiguration can neither serve nor re-park after
  it; the plan fingerprint folds the mesh identity token.
"""

import pytest

pytestmark = pytest.mark.multichip


@pytest.fixture(scope="module")
def tables():
    from spark_rapids_tpu.datagen import scale_test_specs
    sf = 0.01
    return {name: spec.generate_table(sf, seed=3)
            for name, spec in scale_test_specs(sf).items()}


@pytest.fixture(scope="module")
def chip_session():
    from spark_rapids_tpu.session import TpuSession
    return TpuSession()


@pytest.fixture(scope="module")
def mesh_session():
    from spark_rapids_tpu.session import TpuSession
    return TpuSession({"spark.rapids.mesh.enabled": "true"})


def _mesh_scope():
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    return dict(scopes_snapshot().get("mesh", {}))


def _delta(before, after):
    return {k: after.get(k, 0) - before.get(k, 0)
            for k in set(after) | set(before)
            if after.get(k, 0) != before.get(k, 0)}


def _walk_execs(node):
    yield node
    for c in getattr(node, "children", ()):
        yield from _walk_execs(c)
    for attr in ("source", "tpu_exec", "cpu_node"):
        nxt = getattr(node, attr, None)
        if nxt is not None:
            yield from _walk_execs(nxt)


@pytest.mark.parametrize("name", ["q1", "q3", "q6"])
def test_mesh_q1_q3_q6_bit_identical(tables, chip_session, mesh_session,
                                     name):
    """The corpus slice executed mesh-native against single-chip
    execution. join->agg (q3) and a window rank (q6) re-land their wide
    consumers' input and match bit for bit. scan->filter->agg (q1) runs
    its aggregate on the resident shards (execs/mesh.py's contract):
    exact columns bit-identical, DOUBLE sums within the benchmark's 2e-7
    of one chip's, the same bits on a second run, and no row gathered."""
    import scale_test as ST
    chip_q = ST.build_queries(chip_session, tables)
    mesh_q = ST.build_queries(mesh_session, tables)
    before = _mesh_scope()
    expected = chip_q[name]().collect_table()
    got = mesh_q[name]().collect_table()
    moved = _delta(before, _mesh_scope())
    diff = ST.mesh_contract_differ(expected, got,
                                   shard_aggregated=name == "q1")
    if name == "q1":
        again = mesh_q[name]().collect_table()
        assert ST.tables_differ(got, again) is None, \
            "q1 gave other bits on a second run on the same mesh"
        assert moved.get("meshAggBatches", 0) > 0
        assert moved.get("meshRelandRows", 0) == 0
    assert diff is None, f"{name} diverged on the mesh: {diff}"
    # the mesh actually engaged: scans landed per-device shards (the
    # first query over a table; later ones scan the cached shards)
    assert moved.get("shardsDispatched", 0) > 0 \
        or moved.get("meshAggBatches", 0) > 0 \
        or moved.get("meshRelandRows", 0) > 0


def test_the_contract_comparison_is_bitwise_but_for_double_sums():
    """scale_test's mesh gates compare under the contract: without a
    shard-aggregated query every bit counts; with one, a DOUBLE cell may
    move inside MESH_DOUBLE_LIMIT and nothing else may move at all."""
    import scale_test as ST
    from spark_rapids_tpu.columnar import HostTable

    def table(sums, counts=(3, 4), keys=("A", "N")):
        return HostTable.from_pydict(
            {"k": list(keys), "n": list(counts), "s": list(sums)})
    base = table([1000.0, 2.5])
    near = table([1000.0 * (1 + 1e-8), 2.5])
    assert ST.mesh_contract_differ(base, near, False) is not None
    assert ST.mesh_contract_differ(base, near, True) is None
    assert ST.mesh_contract_differ(base, base, False) is None
    far = table([1000.0 * (1 + 1e-6), 2.5])
    assert "beyond" in ST.mesh_contract_differ(base, far, True)
    for other in (table([1000.0, 2.5], counts=(3, 5)),
                  table([1000.0, 2.5], keys=("A", "R")),
                  table([2.5, 1000.0]),
                  table([1000.0, None])):
        assert ST.mesh_contract_differ(base, other, True) is not None


def test_scale_test_mesh_gate_passes_under_the_contract():
    """``scale_test.py --mesh 8`` on a slice of its corpus: q1 (DOUBLE
    sums on the shards), q11 (a decimal128 sum on the shards, exact) and
    q3 (re-landed) hold the contract, and the report says which queries
    ran on the shards and which changed bits."""
    import scale_test as ST
    report = ST.run_mesh(0.01, 3, 8, queries=["q1", "q3", "q11"])
    assert report["ok"] and report["failures"] == []
    assert report["shard_aggregated"] == ["q1", "q11"]
    assert set(report["bits_changed"]) <= {"q1"}
    assert report["queries"]["q3"]["mesh"]["meshRelandRows"] > 0
    assert report["queries"]["q1"]["mesh"]["meshRelandRows"] == 0
    assert report["totals"]["meshAggBatches"] == 2


def test_mesh_q7_every_exchange_ici_and_warm_uploads_zero(
        tables, chip_session, mesh_session):
    """The q7 repartition+agg acceptance class: every shuffle exchange
    lowers to the ICI all-to-all (no host-shuffle fallback) and the
    WARM path pays zero host->device transfers between exchanges —
    shards are device-resident from the (cached) scan through the
    collective (PERF.md: mid-pipeline uploads are the dominant
    distributed cost class)."""
    import scale_test as ST
    chip_q = ST.build_queries(chip_session, tables)
    mesh_q = ST.build_queries(mesh_session, tables)
    expected = chip_q["q7"]().collect_table()
    got = mesh_q["q7"]().collect_table()  # cold: compiles + shard upload
    assert ST.tables_differ(expected, got) is None
    before = _mesh_scope()
    warm = mesh_q["q7"]().collect_table()
    assert ST.tables_differ(expected, warm) is None
    d = _delta(before, _mesh_scope())
    assert d.get("iciExchanges", 0) >= 1, d
    assert d.get("hostShuffleFallbacks", 0) == 0, d
    assert d.get("meshHostUploads", 0) == 0, \
        f"warm mesh path paid host uploads: {d}"


def test_mesh_string_dict_interned_across_exchanges(tables, mesh_session):
    """String partition keys hash via a byte matrix replicated across
    the mesh; repeated exchanges over ONE dictionary (the cached scan's)
    pay that replication upload once — the dispatch.device_const
    pattern lifted to the mesh (pinned by the upload counter)."""
    import scale_test as ST

    # q7's shape on purpose: its string-keyed exchange is already
    # compiled by the test above, so this pins ONLY the intern behavior
    df = ST.build_queries(mesh_session, tables)["q7"]
    df().collect_table()  # cold for this test: interns the dictionary
    before = _mesh_scope()
    df().collect_table()
    d = _delta(before, _mesh_scope())
    assert d.get("iciExchanges", 0) >= 1, d
    assert d.get("meshDictInterns", 0) == 0, \
        f"re-exchange re-replicated an interned dictionary: {d}"
    assert d.get("meshHostUploads", 0) == 0, d


def test_mesh_exchange_demotion_reason_surfaced(tables, mesh_session):
    """Partition count wider than the mesh: the ICI-requested exchange
    demotes to the host-file shuffle WITH the reason surfaced in the
    exec's describe() and counted in hostShuffleFallbacks — and the
    host path still consumes the sharded scan correctly (to_host is a
    sanctioned gather)."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.plan import from_host_table

    before = _mesh_scope()
    got = (from_host_table(tables["customer"], mesh_session)
           .repartition(16, "c_nationkey")
           .group_by("c_nationkey")
           .agg(F.count("c_custkey").alias("n"))
           .collect_table())
    assert got.num_rows > 0
    d = _delta(before, _mesh_scope())
    assert d.get("hostShuffleFallbacks", 0) >= 1, d
    exchanges = [e for e in _walk_execs(mesh_session._last_executable)
                 if isinstance(e, TpuShuffleExchangeExec)]
    assert exchanges and exchanges[0].ici_fallback_reason
    assert "exceeds" in exchanges[0].ici_fallback_reason
    assert "hostShuffleFallback" in exchanges[0].describe()
    # the overrides tagger surfaces the SAME static reason in explain()
    note_lines = [ln for ln in mesh_session._last_meta.explain().splitlines()
                  if "host-shuffle fallback" in ln]
    assert note_lines and "exceeds" in note_lines[0]


def test_explain_before_first_execute_sees_this_confs_mesh(
        tables, chip_session):
    """explain() must report the demotion reasons the exec will act on
    even BEFORE the session's first execute: explain_plan/apply_overrides
    realize the conf's mesh themselves rather than reading whatever a
    previous session left configured."""
    from spark_rapids_tpu.overrides import explain_plan
    from spark_rapids_tpu.plan import from_host_table
    from spark_rapids_tpu.session import TpuSession

    # leave the process-wide mesh OFF (a stale state for the new session)
    chip_session.placement.prepare()
    fresh = TpuSession({"spark.rapids.mesh.enabled": "true"})
    plan = from_host_table(tables["customer"], fresh).repartition(
        16, "c_nationkey").plan
    out = explain_plan(plan, fresh.conf)
    assert "host-shuffle fallback" in out and "exceeds" in out
    chip_session.placement.prepare()


def test_executable_cache_is_mesh_generation_stamped(tables):
    """A converted tree cached under one mesh config can neither SERVE
    nor RE-PARK after a mesh reconfiguration — even when the plan
    fingerprint comes back around (off -> on -> off), the generation
    stamp keeps the pre-reconfiguration tree out."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.plan import from_host_table
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession()

    def q():
        return (from_host_table(tables["customer"], s)
                .group_by("c_nationkey")
                .agg(F.count("c_custkey").alias("n")))

    q().collect_table()
    assert s.last_executable_cache_hit is False
    q().collect_table()
    assert s.last_executable_cache_hit is True

    # reconfigure the mesh (off -> on -> off): the fingerprint is back
    # to the original, but both cached generations are now stale
    from spark_rapids_tpu.parallel.mesh import MESH
    gen0 = MESH.generation()
    mesh_s = TpuSession({"spark.rapids.mesh.enabled": "true"})
    mesh_s.placement.prepare()
    s.placement.prepare()
    assert MESH.generation() >= gen0 + 2
    q().collect_table()
    assert s.last_executable_cache_hit is False, \
        "a pre-reconfiguration tree served after the mesh changed"
    # the fresh tree parks under the NEW generation and serves again
    q().collect_table()
    assert s.last_executable_cache_hit is True


def test_checked_out_tree_cannot_repark_across_reconfiguration(tables):
    """The release half of the stamp: a token checked out BEFORE a mesh
    reconfiguration must not re-park its tree afterwards (the tree's
    cached device tables reference the old placement)."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.overrides import apply_overrides
    from spark_rapids_tpu.plan import from_host_table
    from spark_rapids_tpu.plan.executable_cache import ExecutableCache
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession()
    s.placement.prepare()
    plan = (from_host_table(tables["customer"], s)
            .group_by("c_nationkey")
            .agg(F.count("c_custkey").alias("n")).plan)
    cache = ExecutableCache()
    tok = cache.checkout(plan, s.conf)
    assert not tok.hit
    executable, meta = apply_overrides(plan, s.conf)

    # mesh reconfigures while the tree is checked out
    mesh_s = TpuSession({"spark.rapids.mesh.enabled": "true"})
    mesh_s.placement.prepare()
    s.placement.prepare()

    tok.fill(executable, meta)
    tok2 = cache.checkout(plan, s.conf)
    assert not tok2.hit, \
        "a tree checked out before a mesh reconfiguration re-parked"


def test_fingerprint_folds_mesh_identity(tables):
    """Plans fingerprinted under different mesh configs never collide:
    the ACTIVE mesh identity token (shape/axes/device ids) folds into
    the fingerprint beyond the conf keys."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.parallel.mesh import MESH
    from spark_rapids_tpu.plan import from_host_table
    from spark_rapids_tpu.plan.fingerprint import fingerprint
    from spark_rapids_tpu.session import TpuSession

    s = TpuSession()
    plan = (from_host_table(tables["customer"], s)
            .group_by("c_nationkey")
            .agg(F.count("c_custkey").alias("n")).plan)
    s.placement.prepare()
    assert MESH.identity_token() == "mesh:off"
    fp_off = fingerprint(plan, s.conf)

    mesh_s = TpuSession({"spark.rapids.mesh.enabled": "true"})
    mesh_s.placement.prepare()
    tok_8 = MESH.identity_token()
    assert tok_8.startswith("mesh:8/")
    fp_on = fingerprint(plan, mesh_s.conf)
    assert fp_on != fp_off

    hier = TpuSession({"spark.rapids.mesh.enabled": "true",
                       "spark.rapids.mesh.shape": "2x4"})
    hier.placement.prepare()
    assert MESH.identity_token().startswith("mesh:2x4/")
    assert MESH.row_axes() == ("dcn", "ici")
    assert fingerprint(plan, hier.conf) not in (fp_off, fp_on)

    # leave the process-wide mesh OFF for the rest of the suite
    s.placement.prepare()


def test_unstamped_scan_never_lands_sharded(tables):
    """Sharded placement is bound at CONVERSION, not read from process
    state at execute: a tree converted with the mesh off carries no
    re-land boundaries, so its scans must land single-device even when
    a concurrent session flips the process mesh on mid-query (sharded
    input would let GSPMD repartition a wide float kernel and change
    accumulation order). insert_mesh_relands stamps scans with the
    conversion-time generation; unstamped or stale-stamped scans land
    safe."""
    from spark_rapids_tpu.execs.basic import TpuScanExec
    from spark_rapids_tpu.parallel.mesh import MESH
    from spark_rapids_tpu.session import TpuSession

    mesh_s = TpuSession({"spark.rapids.mesh.enabled": "true"})
    off_s = TpuSession()
    try:
        mesh_s.placement.prepare()  # the "concurrent session" flips mesh on
        scan = TpuScanExec([tables["customer"]], device_cache=False)
        assert all(not b.physically_sharded() for b in scan.execute()), \
            "an unstamped (mesh-off-converted) scan landed sharded"
        scan._mesh_scan_gen = MESH.generation()  # conversion-time stamp
        assert any(b.physically_sharded() for b in scan.execute())
        scan._mesh_scan_gen = MESH.generation() - 1  # stale stamp
        assert all(not b.physically_sharded() for b in scan.execute())
    finally:
        off_s.placement.prepare()


def test_backend_reinit_rebuilds_mesh():
    """Device-loss recovery replaces every jax Device object but leaves
    the mesh conf — and the device IDS the identity token hashes —
    unchanged. configure() folds HEALTH's backend generation into its
    config key, so the next prepare() rebuilds the mesh instead of
    serving Device objects from the dead backend."""
    from spark_rapids_tpu.parallel.mesh import MESH
    from spark_rapids_tpu.runtime.health import HEALTH
    from spark_rapids_tpu.session import TpuSession

    mesh_s = TpuSession({"spark.rapids.mesh.enabled": "true"})
    off_s = TpuSession()
    try:
        mesh_s.placement.prepare()
        m1, g1 = MESH.mesh(), MESH.generation()
        assert m1 is not None
        mesh_s.placement.prepare()  # unchanged conf + backend: no-op
        assert MESH.mesh() is m1 and MESH.generation() == g1
        with HEALTH._lock:  # what a device-loss reinit does
            HEALTH._generation += 1
        mesh_s.placement.prepare()
        # the mesh was REBUILT from the (re-discovered) backend: the
        # generation bumps, staling every cached placement. (jax
        # interns Mesh by (devices, axes), so with the simulated — not
        # real — reinit the rebuilt object may compare identical; the
        # generation is the observable coherency contract.)
        assert MESH.generation() > g1, \
            "mesh built from the dead backend survived the reinit"
    finally:
        off_s.placement.prepare()


def test_clear_mesh_caches_drops_interned_device_state():
    """The mesh-exchange caches (interned replicated dictionary
    matrices, the exchange programs compiled against the mesh) key on
    device IDS, which survive a device-loss backend reinit unchanged —
    so device-loss recovery (runtime/health.py) and the OOM eviction
    path (runtime/retry.py) clear them through clear_mesh_caches like
    every other device-referencing cache."""
    import jax
    import numpy as np
    from spark_rapids_tpu.parallel import exchange as EX

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
    d = np.array(["aa", "b"])
    EX.interned_dict_bytes(d, mesh)
    with EX._DICT_INTERN_LOCK:
        assert EX._DICT_INTERN
    assert EX.clear_mesh_caches() >= 1
    with EX._DICT_INTERN_LOCK:
        assert not EX._DICT_INTERN
    assert not EX._CHIP_EXCHANGES


def test_dict_intern_single_upload_under_concurrency(monkeypatch):
    """Two workers first-exchanging over ONE dictionary concurrently
    (QueryService pattern) pay the replication upload once: the
    in-flight marker makes the loser wait for the winner's interned
    entry instead of racing a second device_put — the warm-path-zero
    meshHostUploads contract must hold under concurrency too."""
    import threading
    import time

    import jax
    import numpy as np
    from spark_rapids_tpu.parallel import exchange as EX

    EX.clear_mesh_caches()
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("data",))
    d = np.array(["x", "yy", "zzz"])
    real = EX.string_dict_bytes

    def slow(dictionary, *a, **k):  # widen the in-flight window
        time.sleep(0.05)
        return real(dictionary, *a, **k)

    monkeypatch.setattr(EX, "string_dict_bytes", slow)
    before = _mesh_scope()
    results = []
    threads = [threading.Thread(
        target=lambda: results.append(EX.interned_dict_bytes(d, mesh)))
        for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    delta = _delta(before, _mesh_scope())
    assert delta.get("meshDictInterns", 0) == 1, delta
    assert delta.get("meshHostUploads", 0) == 2, delta
    assert results[0][0] is results[1][0]  # one canonical device entry
    EX.clear_mesh_caches()


def test_mesh_shape_validation():
    """Malformed or oversized spark.rapids.mesh.shape raises typed."""
    from spark_rapids_tpu.errors import ColumnarProcessingError
    from spark_rapids_tpu.parallel.mesh import _parse_shape

    assert _parse_shape("", 8) == (8,)
    assert _parse_shape("4", 8) == (4,)
    assert _parse_shape("2x4", 8) == (2, 4)
    with pytest.raises(ColumnarProcessingError):
        _parse_shape("banana", 8)
    with pytest.raises(ColumnarProcessingError):
        _parse_shape("2x2x2", 8)
    with pytest.raises(ColumnarProcessingError):
        _parse_shape("16", 8)
