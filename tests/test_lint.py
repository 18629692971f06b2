"""Static-analysis layer tests (ISSUE 2 tentpole).

Four layers:
  * golden suite: the TPC-H q1-q22 corpus (DSL + SQL, AQE on/off)
    converts and verifies CLEAN in error mode — the regression pin that
    every future plan/overrides change runs under;
  * repo lint + registry audit exit clean on the repo itself, and the
    committed SUPPORTED_OPS.md / CONFIGS.md are byte-identical to their
    generators;
  * one NEGATIVE test per lint rule (every id in diagnostics.RULES):
    a deliberately broken plan/registry/source fragment produces exactly
    that rule id at the expected path;
  * pins for the real violations the tooling surfaced (decimal %
    unregistered, avg/stddev over decimal in unscaled units, dec128 ->
    double cast crash in the streaming average merge).
"""

import ast

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.lint.diagnostics import RULES
from spark_rapids_tpu.lint.plan_verifier import (
    verify_converted,
    verify_meta,
)
from spark_rapids_tpu.ops.expr import BoundReference, Expression, Literal, col
from spark_rapids_tpu.plan import from_host_table
from spark_rapids_tpu.plan import nodes as P
from spark_rapids_tpu.session import TpuSession


def _scan_exec(names=("a",), dtypes=(T.LONG,)):
    from spark_rapids_tpu.execs.basic import TpuScanExec
    cols = [HostColumn(dt, np.arange(3, dtype=np.int64).astype(
        dt.np_dtype if not isinstance(dt, T.StringType) else np.int64))
        for dt in dtypes]
    return TpuScanExec([HostTable(list(names), cols)])


def _wrap(exec_):
    from spark_rapids_tpu.execs.base import DeviceToHost
    return DeviceToHost(exec_)


def _ids(diags):
    return {d.rule_id for d in diags}


def _find(diags, rule_id):
    hits = [d for d in diags if d.rule_id == rule_id]
    assert hits, f"no {rule_id} diagnostic in {[str(d) for d in diags]}"
    return hits


# ---------------------------------------------------------------------------
# golden suite: q1-q22 x (dsl, sql) x (aqe on/off) verifies clean
# ---------------------------------------------------------------------------


def test_golden_suite_plans_verify_clean():
    """The whole TPC-H corpus converts with zero diagnostics — the
    regression pin for 'the suite lints clean' (satellite 1)."""
    from spark_rapids_tpu.lint.golden import verify_golden_plans
    diags = verify_golden_plans(scale_factor=0.002)
    assert diags == [], [str(d) for d in diags]


def test_golden_corpus_is_q1_to_q22_in_both_forms():
    from spark_rapids_tpu.lint.golden import _load_scale_test, golden_tables
    scale_test = _load_scale_test()  # repo root may not be on sys.path
    tables = golden_tables(0.002)
    s = TpuSession()
    dsl = scale_test.build_queries(s, tables)
    sql = scale_test.build_sql_queries(s, tables)
    want = {f"q{i}" for i in range(1, 23)}
    assert set(dsl) == want
    assert set(sql) == want


def test_repo_lints_clean():
    from spark_rapids_tpu.lint.repo_lint import lint_repo
    diags = lint_repo()
    assert diags == [], [str(d) for d in diags]


def test_registry_audit_clean():
    from spark_rapids_tpu.lint.registry_audit import audit_registry
    diags = audit_registry()
    assert diags == [], [str(d) for d in diags]


def test_committed_docs_are_byte_identical_to_generators():
    """Drift gate: SUPPORTED_OPS.md and CONFIGS.md must be regenerated
    (python -m spark_rapids_tpu.lint --write-docs) whenever a registry
    changes."""
    import os

    import spark_rapids_tpu
    from spark_rapids_tpu.conf import generate_docs
    from spark_rapids_tpu.lockorder import generate_locks_md
    from spark_rapids_tpu.overrides.docs import generate_supported_ops
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(spark_rapids_tpu.__file__)))
    with open(os.path.join(root, "SUPPORTED_OPS.md")) as f:
        assert f.read() == generate_supported_ops()
    with open(os.path.join(root, "CONFIGS.md")) as f:
        assert f.read() == generate_docs()
    with open(os.path.join(root, "LOCKS.md")) as f:
        assert f.read() == generate_locks_md()


def test_cli_lists_every_rule(capsys):
    from spark_rapids_tpu.lint.__main__ import main
    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in RULES:
        assert rid in out


def test_sessions_run_verifier_in_error_mode():
    """conftest injects planVerify.mode=error into every test session
    (the assert-on-fallback analog)."""
    from spark_rapids_tpu.conf import PLAN_VERIFY_MODE
    s = TpuSession()
    assert str(s.conf.get_entry(PLAN_VERIFY_MODE)).lower() == "error"
    # ...while the production default stays off
    assert RapidsConf().get_entry(PLAN_VERIFY_MODE) == "off"


# ---------------------------------------------------------------------------
# negative tests: plan verifier rules
# ---------------------------------------------------------------------------


def test_pv_schema_pass_through_divergence():
    from spark_rapids_tpu.execs.basic import TpuLimitExec
    ex = TpuLimitExec(_scan_exec(), 5)
    ex.output_schema = lambda: [("other", T.INT)]  # break the contract
    diags = _find(verify_converted(_wrap(ex)), "PV-SCHEMA")
    assert any("pass-through" in d.message and "Limit" in d.path
               for d in diags), [str(d) for d in diags]


def test_pv_schema_malformed_entry():
    from spark_rapids_tpu.execs.basic import TpuLimitExec
    ex = TpuLimitExec(_scan_exec(), 5)
    ex.output_schema = lambda: [("a", "not-a-datatype")]
    diags = _find(verify_converted(_wrap(ex)), "PV-SCHEMA")
    assert any("malformed" in d.message for d in diags)


def test_pv_transition_device_exec_over_host_node():
    from spark_rapids_tpu.execs.basic import TpuLimitExec
    host = P.RangeNode(0, 10)
    ex = TpuLimitExec(host, 5)  # raw PlanNode under a device exec
    diags = _find(verify_converted(_wrap(ex)), "PV-TRANSITION")
    d = diags[0]
    assert "without a HostToDevice transition" in d.message
    assert d.path == "DeviceToHost.Limit"


def test_pv_transition_host_node_over_device_exec():
    f = P.Filter(P.RangeNode(0, 10), col("id") > Literal(3))
    f.children = (_scan_exec(("id",), (T.LONG,)),)  # device exec, no adapter
    diags = _find(verify_converted(f), "PV-TRANSITION")
    assert "InputAdapter(DeviceToHost)" in diags[0].message
    assert diags[0].path == "Filter"  # reported at the consuming parent
    assert "Scan" in diags[0].message


def test_pv_exchange_hash_without_keys():
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    ex = TpuShuffleExchangeExec(_scan_exec(), "hash", 4, [], RapidsConf())
    diags = _find(verify_converted(_wrap(ex)), "PV-EXCHANGE")
    assert "hash partitioning requires keys" in diags[0].message
    assert "ShuffleExchange" in diags[0].path


def test_pv_exchange_key_outside_child_output():
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    ex = TpuShuffleExchangeExec(
        _scan_exec(), "hash", 4, [BoundReference(7, T.LONG)], RapidsConf())
    diags = _find(verify_converted(_wrap(ex)), "PV-EXCHANGE")
    assert any("ordinal 7" in d.message for d in diags)


def test_pv_boundref_ordinal_and_type():
    from spark_rapids_tpu.execs.basic import TpuProjectExec
    ex = TpuProjectExec(_scan_exec(("a",), (T.LONG,)),
                        [BoundReference(3, T.LONG)], ["x"])
    diags = _find(verify_converted(_wrap(ex)), "PV-BOUNDREF")
    assert "ordinal 3" in diags[0].message
    assert "Project" in diags[0].path

    ex2 = TpuProjectExec(_scan_exec(("a",), (T.LONG,)),
                         [BoundReference(0, T.STRING)], ["x"])
    diags2 = _find(verify_converted(_wrap(ex2)), "PV-BOUNDREF")
    assert "typed string" in diags2[0].message


class _UnregisteredExpr(Expression):
    def __init__(self, child):
        self.children = (child,)

    @property
    def data_type(self):
        return T.LONG


def test_pv_typesig_unregistered_expression_on_device():
    from spark_rapids_tpu.execs.basic import TpuProjectExec
    ex = TpuProjectExec(_scan_exec(("a",), (T.LONG,)),
                        [_UnregisteredExpr(BoundReference(0, T.LONG))],
                        ["x"])
    diags = _find(verify_converted(_wrap(ex)), "PV-TYPESIG")
    assert "_UnregisteredExpr" in diags[0].message
    assert "ran on device anyway" in diags[0].message


def test_pv_decimal_result_type_divergence():
    from spark_rapids_tpu.execs.basic import TpuProjectExec
    from spark_rapids_tpu.ops.decimal import DecimalAdd
    e = DecimalAdd(BoundReference(0, T.DecimalType(10, 2)),
                   BoundReference(1, T.DecimalType(10, 2)))
    e._result = T.DecimalType(7, 1)  # tamper: violates the promotion rule
    ex = TpuProjectExec(
        _scan_exec(("a", "b"), (T.DecimalType(10, 2), T.DecimalType(10, 2))),
        [e], ["x"])
    diags = _find(verify_converted(_wrap(ex)), "PV-DECIMAL")
    assert "promotion rule gives decimal(11,2)" in diags[0].message


class _BadNotNull(Expression):
    nullable = False  # plain class attr shadowing the derived property

    def __init__(self, child):
        self.children = (child,)

    @property
    def data_type(self):
        return T.LONG


def test_pv_nullable_plain_attr_over_nullable_child():
    from spark_rapids_tpu.execs.basic import TpuProjectExec
    ex = TpuProjectExec(_scan_exec(("a",), (T.LONG,)),
                        [_BadNotNull(BoundReference(0, T.LONG))], ["x"])
    diags = _find(verify_converted(_wrap(ex)), "PV-NULLABLE")
    assert "_BadNotNull" in diags[0].message
    assert "without overriding the nullable property" in diags[0].message


def test_pv_fallback_empty_reason_and_missing_rule():
    from spark_rapids_tpu.overrides.rules import PlanMeta

    meta = PlanMeta(P.RangeNode(0, 5), RapidsConf())
    meta.reasons = ["   "]
    diags = []
    verify_meta(meta, diags)
    assert any(d.rule_id == "PV-FALLBACK"
               and "empty reason" in d.message for d in diags)

    class _RuleLess(P.PlanNode):
        def output_schema(self):
            return [("x", T.LONG)]

    meta2 = PlanMeta(_RuleLess(), RapidsConf())  # untagged: no reasons
    diags2 = []
    verify_meta(meta2, diags2)
    assert any(d.rule_id == "PV-FALLBACK"
               and "no exec rule" in d.message for d in diags2)


def test_pv_agg_non_aggregate_spec():
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    ex = TpuHashAggregateExec(_scan_exec(("a",), (T.LONG,)),
                              [BoundReference(0, T.LONG)],
                              [("bad", Literal(1))], ["k"])
    diags = _find(verify_converted(_wrap(ex)), "PV-AGG")
    assert "not an AggregateFunction" in diags[0].message
    assert "HashAggregate" in diags[0].path


def test_pv_join_key_type_divergence():
    from spark_rapids_tpu.execs.join import TpuJoinExec
    ls = [("a", T.LONG)]
    rs = [("b", T.INT)]
    ex = TpuJoinExec(_scan_exec(("a",), (T.LONG,)),
                     _scan_exec(("b", ), (T.INT,)), "inner",
                     [BoundReference(0, T.LONG)],
                     [BoundReference(0, T.INT)], None, ls, rs)
    diags = _find(verify_converted(_wrap(ex)), "PV-JOIN")
    assert "types diverge: bigint vs int" in diags[0].message

    ex2 = TpuJoinExec(_scan_exec(("a",), (T.LONG,)),
                      _scan_exec(("b",), (T.LONG,)), "sideways",
                      [BoundReference(0, T.LONG)],
                      [BoundReference(0, T.LONG)], None,
                      [("a", T.LONG)], [("b", T.LONG)])
    diags2 = _find(verify_converted(_wrap(ex2)), "PV-JOIN")
    assert "unsupported join type" in diags2[0].message


# ---------------------------------------------------------------------------
# negative tests: registry auditor rules
# ---------------------------------------------------------------------------


def test_ra_conf_orphan_unread_key():
    """RA-CONF-ORPHAN: a declared key no engine source ever reads (by
    string or by its ConfEntry variable) is flagged; wired keys and the
    allowlist are not."""
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.lint.registry_audit import (
        _audit_conf_referenced,
        _repo_root,
    )
    key = "spark.rapids.sql.test.orphanedProbeKey"
    C.str_conf(key, "", "negative-test probe: intentionally unread")
    try:
        diags = []
        _audit_conf_referenced(diags, _repo_root(None))
        hits = _find(diags, "RA-CONF-ORPHAN")
        assert any(d.path == key for d in hits)
        # a heavily-wired key is never flagged
        assert not any(d.path == "spark.rapids.sql.eventLog.enabled"
                       for d in hits)
    finally:
        C._REGISTRY.pop(key, None)


def test_ra_unregistered_device_expression():
    import spark_rapids_tpu.ops.math as math_mod
    from spark_rapids_tpu.lint.registry_audit import _audit_unregistered

    class FakeDevExpr(Expression):
        def eval_dev(self, ctx, child_vals, prep):  # device kernel
            raise AssertionError

    FakeDevExpr.__module__ = "spark_rapids_tpu.ops.math"
    FakeDevExpr.__name__ = "FakeDevExpr"
    math_mod.FakeDevExpr = FakeDevExpr
    try:
        diags = []
        _audit_unregistered(diags)
        hits = _find(diags, "RA-UNREGISTERED")
        assert any("FakeDevExpr" in d.path for d in hits)
    finally:
        del math_mod.FakeDevExpr


def test_ra_param_arity_overflow():
    from spark_rapids_tpu.lint.registry_audit import _audit_param_arity
    from spark_rapids_tpu.overrides import rules as R
    from spark_rapids_tpu.overrides.typesig import ExprChecks, TypeSig

    class OneArg(Expression):
        def __init__(self, child):
            self.children = (child,)

    sig = TypeSig(T.LongType)
    R._EXPR_CHECKS[OneArg] = ExprChecks((sig, sig, sig))
    try:
        diags = []
        _audit_param_arity(diags)
        hits = _find(diags, "RA-PARAM-ARITY")
        assert any("OneArg" in d.path and "3 parameter" in d.message
                   for d in hits)
    finally:
        del R._EXPR_CHECKS[OneArg]


def test_ra_kill_switch_orphan():
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.lint.registry_audit import _audit_kill_switches
    key = "spark.rapids.sql.exec.NoSuchExecRule"
    C.register_op_kill_switch("exec", "NoSuchExecRule", True, "orphan")
    try:
        diags = []
        _audit_kill_switches(diags)
        hits = _find(diags, "RA-KILL-SWITCH")
        assert any(d.path == key for d in hits)
    finally:
        C._REGISTRY.pop(key, None)


def test_ra_sql_exposure_missing_aggregate(monkeypatch):
    from spark_rapids_tpu.lint import registry_audit as RA
    names = dict(RA._AGG_SQL_NAMES)
    del names["Sum"]
    monkeypatch.setattr(RA, "_AGG_SQL_NAMES", names)
    diags = []
    RA._audit_sql_exposure(diags)
    hits = _find(diags, "RA-SQL-EXPOSURE")
    assert any("Sum" in d.path for d in hits)


def test_ra_essential_metrics_missing():
    from spark_rapids_tpu.execs.base import TpuExec
    from spark_rapids_tpu.lint.registry_audit import audit_exec_metrics_tree

    class HalfMetered(TpuExec):
        pass

    e = HalfMetered()
    e.metrics.add("opTime", 0.1)  # ran, but never counted its output
    diags = []
    audit_exec_metrics_tree(e, diags)
    hits = _find(diags, "RA-ESSENTIAL-METRICS")
    assert any("HalfMetered" in d.path
               and "numOutputRows" in d.message for d in hits)
    # a metric-less ROOT means the observation boundary never installed
    bare = HalfMetered()
    diags2 = []
    audit_exec_metrics_tree(bare, diags2)
    assert any("never installed" in d.message
               for d in _find(diags2, "RA-ESSENTIAL-METRICS"))


def test_ra_doc_drift(tmp_path):
    from spark_rapids_tpu.lint.registry_audit import _audit_doc_drift
    (tmp_path / "SUPPORTED_OPS.md").write_text("stale\n")
    (tmp_path / "LOCKS.md").write_text("stale\n")
    # CONFIGS.md missing entirely
    diags = []
    _audit_doc_drift(diags, str(tmp_path))
    assert any(d.rule_id == "RA-DOC-DRIFT-OPS"
               and "differs from the generator" in d.message for d in diags)
    assert any(d.rule_id == "RA-DOC-DRIFT-CONFIGS"
               and "missing" in d.message for d in diags)
    assert any(d.rule_id == "RA-DOC-DRIFT-LOCKS"
               and "differs from the generator" in d.message for d in diags)


# ---------------------------------------------------------------------------
# negative tests: repo lint rules (synthetic sources)
# ---------------------------------------------------------------------------


def _run_rl(check, rel, src, *extra):
    diags = []
    check(rel, ast.parse(src), *extra, diags)
    return diags


def test_rl_host_sync():
    from spark_rapids_tpu.lint.repo_lint import _check_host_sync
    src = "import jax\nx = jax.device_get(y)\nz = arr.block_until_ready()\n"
    diags = _run_rl(_check_host_sync, "spark_rapids_tpu/execs/foo.py", src)
    hits = _find(diags, "RL-HOST-SYNC")
    assert len(hits) == 2
    assert {d.path.rsplit(":", 1)[1] for d in hits} == {"2", "3"}
    # the import form must not slip past the chain matcher
    imp = "from jax import device_get\nn = device_get(x)\n"
    ihits = _find(_run_rl(_check_host_sync,
                          "spark_rapids_tpu/ops/foo.py", imp),
                  "RL-HOST-SYNC")
    assert len(ihits) == 2  # the import AND the bare call
    # np.asarray/float/int over a provable jax expression sync too...
    dev = ("import jax.numpy as jnp\nimport numpy as np\n"
           "a = np.asarray(jnp.sum(x))\nn = int(jnp.max(y))\n")
    dhits = _find(_run_rl(_check_host_sync,
                          "spark_rapids_tpu/execs/foo.py", dev),
                  "RL-HOST-SYNC")
    assert len(dhits) == 2
    # ...but the sanctioned host_fetch funnel stays clean
    ok = ("from spark_rapids_tpu.dispatch import host_fetch\n"
          "import jax.numpy as jnp\n"
          "n = int(host_fetch(jnp.sum(x)))\n")
    assert _run_rl(_check_host_sync,
                   "spark_rapids_tpu/execs/foo.py", ok) == []
    # the same source OUTSIDE a hot path is fine
    assert _run_rl(_check_host_sync, "spark_rapids_tpu/io/foo.py", src) == []


def test_rl_jnp_scope():
    from spark_rapids_tpu.lint.repo_lint import _check_jnp_scope
    src = "import jax.numpy as jnp\n"
    diags = _run_rl(_check_jnp_scope, "spark_rapids_tpu/sql/analyzer.py", src)
    hits = _find(diags, "RL-JNP-SCOPE")
    assert "outside the device layers" in hits[0].message
    assert _run_rl(_check_jnp_scope,
                   "spark_rapids_tpu/execs/basic.py", src) == []
    # `import jax` + attribute access bypass of the import check
    attr = "import jax\nx = jax.numpy.asarray([1])\n"
    ahits = _find(_run_rl(_check_jnp_scope,
                          "spark_rapids_tpu/sql/analyzer.py", attr),
                  "RL-JNP-SCOPE")
    assert len(ahits) == 1 and "used" in ahits[0].message


def test_rl_conf_key():
    from spark_rapids_tpu.lint.repo_lint import _check_conf_keys
    src = 'k = conf.get("spark.rapids.sql.noSuchKey")\n'
    diags = _run_rl(_check_conf_keys, "spark_rapids_tpu/session.py", src,
                    {"spark.rapids.sql.enabled"})
    hits = _find(diags, "RL-CONF-KEY")
    assert "spark.rapids.sql.noSuchKey" in hits[0].message
    ok = 'k = conf.get("spark.rapids.sql.enabled")\n'
    assert _run_rl(_check_conf_keys, "spark_rapids_tpu/session.py", ok,
                   {"spark.rapids.sql.enabled"}) == []


def test_rl_nondeterminism():
    from spark_rapids_tpu.lint.repo_lint import _check_nondeterminism
    src = ("import time\nt = time.time()\n"
           "import numpy as np\nr = np.random.rand(3)\n"
           "g = np.random.default_rng(0)\n")
    diags = _run_rl(_check_nondeterminism,
                    "spark_rapids_tpu/ops/foo.py", src)
    hits = _find(diags, "RL-NONDETERMINISM")
    assert len(hits) == 2  # time.time + np.random.rand; default_rng is ok
    assert _run_rl(_check_nondeterminism,
                   "spark_rapids_tpu/io/foo.py", src) == []


def test_rl_dead_lambda():
    from spark_rapids_tpu.lint.repo_lint import _check_dead_lambdas
    src = "pn = lambda x: x\nused = lambda y: y\nprint(used(1))\n"
    diags = _run_rl(_check_dead_lambdas, "spark_rapids_tpu/delta/foo.py", src)
    hits = _find(diags, "RL-DEAD-LAMBDA")
    assert len(hits) == 1
    assert "'pn'" in hits[0].message
    assert hits[0].path.endswith(":1")


def test_rl_thread_shared():
    from spark_rapids_tpu.lint.repo_lint import _check_thread_shared
    src = (
        "import threading\n"
        "_CACHE = {}\n"
        "_ITEMS = []\n"
        "_LOCK = threading.Lock()\n"
        "class Mgr:\n"
        "    _instance = None\n"
        "    @classmethod\n"
        "    def get(cls):\n"
        "        cls._instance = Mgr()\n"         # unlocked class attr
        "        return cls._instance\n"
        "def bad(k, v):\n"
        "    _CACHE[k] = v\n"                     # unlocked subscript
        "    _ITEMS.append(v)\n"                  # unlocked mutator
        "def good(k, v):\n"
        "    with _LOCK:\n"
        "        _CACHE[k] = v\n"                 # guarded: clean
        "        _ITEMS.append(v)\n"
        "def rebind():\n"
        "    global _CACHE\n"
        "    _CACHE = {}\n"                       # unlocked global rebind
    )
    diags = _run_rl(_check_thread_shared,
                    "spark_rapids_tpu/runtime/foo.py", src)
    hits = _find(diags, "RL-THREAD-SHARED")
    assert len(hits) == 4, [str(d) for d in hits]
    msgs = " ".join(d.message for d in hits)
    assert "_CACHE[...]" in msgs and "_ITEMS.append" in msgs
    assert "cls._instance (class attribute)" in msgs
    # module-level (import-time) writes and non-scanned dirs are clean
    assert _run_rl(_check_thread_shared,
                   "spark_rapids_tpu/ops/foo.py", src) == []
    init_only = "_REG = {}\n_REG['x'] = 1\n"
    assert _run_rl(_check_thread_shared,
                   "spark_rapids_tpu/shuffle/foo.py", init_only) == []
    # the service package is scanned too
    assert _find(_run_rl(_check_thread_shared,
                         "spark_rapids_tpu/service/foo.py", src),
                 "RL-THREAD-SHARED")
    # the allowlist keys on the CONTAINER name (or the class-attr name),
    # suppressing every finding shape for that state and nothing else
    import spark_rapids_tpu.lint.repo_lint as RL
    saved = dict(RL._THREAD_SHARED_ALLOWLIST)
    try:
        RL._THREAD_SHARED_ALLOWLIST.update({
            "spark_rapids_tpu/runtime/foo.py:_CACHE": "test",
            "spark_rapids_tpu/runtime/foo.py:_instance": "test"})
        left = _find(_run_rl(_check_thread_shared,
                             "spark_rapids_tpu/runtime/foo.py", src),
                     "RL-THREAD-SHARED")
        assert len(left) == 1 and "_ITEMS.append" in left[0].message
    finally:
        RL._THREAD_SHARED_ALLOWLIST.clear()
        RL._THREAD_SHARED_ALLOWLIST.update(saved)


def test_rl_write_commit():
    from spark_rapids_tpu.lint.repo_lint import _check_write_commit
    src = (
        "import os\n"
        "import pyarrow.parquet as pq\n"
        "def write_stuff(t, path):\n"
        "    pq.write_table(t, path)\n"            # outside _write_one
        "    with open(path, 'w') as f:\n"         # write-mode open
        "        f.write('x')\n"
        "    os.replace(path + '.tmp', path)\n"    # promotion
        "def _write_one(tbl, file_path):\n"
        "    pq.write_table(tbl, file_path)\n"     # sanctioned callback
        "    with open(file_path, 'w') as f:\n"
        "        f.write('x')\n"
        "def read_stuff(path):\n"
        "    with open(path) as f:\n"              # default 'r': clean
        "        return f.read()\n"
        "    with open(path, 'rb') as f:\n"
        "        return f.read()\n"
    )
    diags = _run_rl(_check_write_commit, "spark_rapids_tpu/io/foo.py", src)
    hits = _find(diags, "RL-WRITE-COMMIT")
    assert len(hits) == 3, [str(d) for d in hits]
    msgs = " ".join(d.message for d in hits)
    assert "os.replace" in msgs and "committer" in msgs
    # the committer itself and the file cache are exempt, as is
    # anything outside io/
    assert _run_rl(_check_write_commit,
                   "spark_rapids_tpu/io/committer.py", src) == []
    assert _run_rl(_check_write_commit,
                   "spark_rapids_tpu/io/filecache.py", src) == []
    assert _run_rl(_check_write_commit,
                   "spark_rapids_tpu/delta/foo.py", src) == []


def test_rl_mesh_host():
    """RL-MESH-HOST: host materialization inside parallel/ (or the
    placement layer) outside a sanctioned gather point — the static
    guard for 'zero host round-trips between exchanges'."""
    from spark_rapids_tpu.lint.repo_lint import _check_mesh_host
    src = (
        "import jax\n"
        "import numpy as np\n"
        "from spark_rapids_tpu.dispatch import host_fetch\n"
        "def bad(x):\n"
        "    a = np.asarray(x)\n"            # host materialization
        "    b = jax.device_get(x)\n"        # raw device fetch
        "    c = host_fetch(x)\n"            # unsanctioned fetch helper
        "    d = x.block_until_ready()\n"    # device sync
        "    return list(x.addressable_shards)\n"  # per-shard host read
        "def mesh_gather(x):\n"              # allowlisted gather point
        "    return host_fetch(x)\n"
    )
    diags = _run_rl(_check_mesh_host, "spark_rapids_tpu/parallel/foo.py",
                    src)
    hits = _find(diags, "RL-MESH-HOST")
    # 5 in bad() plus foo.py's OWN mesh_gather (the allowlist keys on
    # rel:function, so only mesh.py's gather is sanctioned)
    assert len(hits) == 6, [str(d) for d in hits]
    msgs = " ".join(d.message for d in hits)
    assert "np.asarray" in msgs and "addressable_shards" in msgs
    # the allowlist hook keys on rel:function — mesh.py's mesh_gather
    # is sanctioned, foo.py's is not... and outside the mesh dirs the
    # rule does not apply at all
    allowed = _run_rl(_check_mesh_host,
                      "spark_rapids_tpu/parallel/mesh.py",
                      "from spark_rapids_tpu.dispatch import host_fetch\n"
                      "def mesh_gather(x):\n"
                      "    return host_fetch(x)\n")
    assert allowed == []
    # the allowlist keys on QUALIFIED names: a method merely NAMED
    # mesh_gather (qualname Foo.mesh_gather) is not the sanctioned
    # module-level gather point
    nested = _run_rl(_check_mesh_host,
                     "spark_rapids_tpu/parallel/mesh.py",
                     "from spark_rapids_tpu.dispatch import host_fetch\n"
                     "class Foo:\n"
                     "    def mesh_gather(self, x):\n"
                     "        return host_fetch(x)\n")
    assert len(_find(nested, "RL-MESH-HOST")) == 1
    assert _run_rl(_check_mesh_host, "spark_rapids_tpu/execs/foo.py",
                   src) == []
    # the placement layer is shard-dispatch code: covered
    placed = _run_rl(_check_mesh_host,
                     "spark_rapids_tpu/runtime/placement.py",
                     "import numpy as np\n"
                     "def f(x):\n    return np.asarray(x)\n")
    assert len(_find(placed, "RL-MESH-HOST")) == 1


def test_rl_fault_point():
    from spark_rapids_tpu.lint.repo_lint import (
        _check_fault_registry,
        _check_fault_sites,
    )

    # unregistered name + non-literal name at the site
    src = ("from spark_rapids_tpu.runtime.faults import fault_point\n"
           "fault_point('no.such.point')\n"
           "name = 'dispatch.kernel'\n"
           "fault_point(name)\n")
    calls = {}
    diags = _run_rl(_check_fault_sites, "spark_rapids_tpu/foo.py", src,
                    calls)
    hits = _find(diags, "RL-FAULT-POINT")
    assert len(hits) == 2
    assert "not registered" in hits[0].message
    assert "string literal" in hits[1].message

    # a registered point with NO call site anywhere -> registry-side hit
    diags2 = []
    _check_fault_registry({}, diags2)
    assert diags2 and all(d.rule_id == "RL-FAULT-POINT" for d in diags2)
    assert any("no fault_point" in d.message for d in diags2)

    # a site outside the registered module -> module-drift hit
    good_src = ("from spark_rapids_tpu.runtime.faults import fault_point\n"
                "fault_point('dispatch.kernel')\n")
    calls3 = {}
    assert _run_rl(_check_fault_sites, "spark_rapids_tpu/elsewhere.py",
                   good_src, calls3) == []
    from spark_rapids_tpu.runtime.faults import FAULT_POINTS
    full = {name: [f"{module}:1"]
            for name, (module, _) in FAULT_POINTS.items()}
    full["dispatch.kernel"] = ["spark_rapids_tpu/elsewhere.py:2"]
    diags3 = []
    _check_fault_registry(full, diags3)
    assert len(diags3) == 1
    assert "registered module" in diags3[0].message

    # the real repo is clean in both directions
    diags4 = []
    _check_fault_registry(
        {name: [f"{module}:1"]
         for name, (module, _) in FAULT_POINTS.items()}, diags4)
    assert diags4 == []


def test_rl_fault_point_mesh_domain():
    """The mesh fault domain rides the SAME two-direction audit as
    every other point class: an UNREGISTERED mesh point at a call site
    is flagged, and a registered ``mesh.*`` point whose call site
    disappears (the distributed path silently losing chaos coverage —
    exactly the pre-PR state this issue fixed) is flagged from the
    registry side."""
    from spark_rapids_tpu.lint.repo_lint import (
        _check_fault_registry,
        _check_fault_sites,
    )
    from spark_rapids_tpu.runtime.faults import FAULT_POINTS

    # direction 1: a mesh-looking point nobody registered
    src = ("from spark_rapids_tpu.runtime.faults import fault_point\n"
           "fault_point('mesh.reland.unregistered')\n")
    diags = _run_rl(_check_fault_sites, "spark_rapids_tpu/parallel/foo.py",
                    src, {})
    hits = _find(diags, "RL-FAULT-POINT")
    assert len(hits) == 1 and "not registered" in hits[0].message

    # direction 2: every registered mesh.* point with NO call site ->
    # one registry-side diagnostic each (the points exist)
    mesh_points = [n for n in FAULT_POINTS if n.startswith("mesh.")]
    assert len(mesh_points) == 4, mesh_points
    calls = {name: [f"{module}:1"]
             for name, (module, _) in FAULT_POINTS.items()
             if not name.startswith("mesh.")}
    diags2 = []
    _check_fault_registry(calls, diags2)
    uncalled = [d for d in diags2 if "no fault_point" in d.message]
    assert len(uncalled) == len(mesh_points)
    assert any("mesh.gather" in d.message for d in uncalled)


def test_rl_obs_passive():
    """RL-OBS-PASSIVE: the telemetry sampler may not call host_fetch /
    device syncs, touch jax, drive query execution, or take the
    query-path locks — sampling must never perturb execution (ISSUE 14
    satellite)."""
    from spark_rapids_tpu.lint.repo_lint import _check_obs_passive
    rel = "spark_rapids_tpu/obs/telemetry.py"
    src = (
        "import jax\n"                                     # device work
        "from spark_rapids_tpu.dispatch import host_fetch\n"
        "def bad_sample(session, svc, exe, table):\n"
        "    a = host_fetch(table)\n"                      # host sync
        "    b = jax.device_get(table)\n"                  # host sync
        "    finalize_observation(exe)\n"                  # device fetch
        "    session.execute(table)\n"                     # drives a query
        "    with session._obs_lock:\n"                    # query-path lock
        "        pass\n"
        "    svc._cond.acquire()\n"                        # query-path lock
    )
    diags = _run_rl(_check_obs_passive, rel, src)
    hits = _find(diags, "RL-OBS-PASSIVE")
    assert len(hits) == 7, [str(d) for d in hits]
    msgs = " ".join(d.message for d in hits)
    assert "host sync" in msgs and "query-path lock" in msgs
    assert "drives query execution" in msgs
    # the sampler's own bounded reads are clean: snapshot surfaces,
    # its private ring lock, plain time/json work
    ok = (
        "import threading, time\n"
        "from spark_rapids_tpu.obs.metrics import scopes_snapshot\n"
        "_lock = threading.Lock()\n"
        "def sample():\n"
        "    snap = scopes_snapshot()\n"
        "    with _lock:\n"
        "        return dict(snap)\n"
    )
    assert _run_rl(_check_obs_passive, rel, ok) == []
    # scoped to the telemetry module only
    assert _run_rl(_check_obs_passive,
                   "spark_rapids_tpu/obs/events.py", src) == []
    # and the REAL module is clean under the rule
    import os

    import spark_rapids_tpu
    root = os.path.dirname(os.path.dirname(
        os.path.abspath(spark_rapids_tpu.__file__)))
    real = open(os.path.join(root, rel)).read()
    assert _run_rl(_check_obs_passive, rel, real) == []


def test_rl_mem_account():
    """RL-MEM-ACCOUNT: raw jax.device_put inside execs//ops/ lands
    bytes the memory arbiter never accounts — the static guard for the
    hard device budget's zero-violation contract (ISSUE 15)."""
    from spark_rapids_tpu.lint.repo_lint import _check_mem_account
    src = (
        "import jax\n"
        "from jax import device_put\n"              # banned import form
        "def bad(a, dev):\n"
        "    x = jax.device_put(a, dev)\n"          # raw landing
        "    y = device_put(a, dev)\n"              # bare-name call
        "    return x, y\n"
    )
    for rel in ("spark_rapids_tpu/execs/foo.py",
                "spark_rapids_tpu/ops/foo.py"):
        hits = _find(_run_rl(_check_mem_account, rel, src),
                     "RL-MEM-ACCOUNT")
        assert len(hits) == 3, [str(d) for d in hits]
        assert "from_host" in hits[0].message
    # the accounted landing path itself is clean
    ok = ("from spark_rapids_tpu.columnar import DeviceTable\n"
          "def good(host):\n"
          "    return DeviceTable.from_host(host)\n")
    assert _run_rl(_check_mem_account,
                   "spark_rapids_tpu/execs/foo.py", ok) == []
    # outside execs//ops/ the rule does not apply (columnar/table.py
    # and parallel/mesh.py ARE the sanctioned landing layers)
    assert _run_rl(_check_mem_account,
                   "spark_rapids_tpu/columnar/table.py", src) == []
    # the allowlist hook keys on rel:qualified-function — the mesh
    # re-land's digest-scalar put stays sanctioned with justification
    from spark_rapids_tpu.lint.repo_lint import _MEM_ACCOUNT_ALLOWLIST
    key = "spark_rapids_tpu/execs/mesh.py:reland"
    assert key in _MEM_ACCOUNT_ALLOWLIST
    allow = ("import jax\n"
             "def reland(node, t):\n"
             "    return jax.device_put(t, None)\n")
    assert _run_rl(_check_mem_account,
                   "spark_rapids_tpu/execs/mesh.py", allow) == []


def test_rl_mv_epoch():
    """RL-MV-EPOCH: streaming/ (micro-batch + MV maintenance) may only
    reach the result cache through the invalidation-epoch API — a
    direct mutation there is a second write path into cache coherence
    (ISSUE 16)."""
    from spark_rapids_tpu.lint.repo_lint import _check_mv_epoch
    src = (
        "from spark_rapids_tpu.service.result_cache import ResultCache\n"
        "def bad(service, key, table):\n"
        "    service.result_cache.put(key, table)\n"       # mutator call
        "    service.result_cache._entries.clear()\n"      # raw entries
    )
    hits = _find(_run_rl(_check_mv_epoch,
                         "spark_rapids_tpu/streaming/mv.py", src),
                 "RL-MV-EPOCH")
    # ResultCache import + put() + _entries access (+ the clear() on
    # the _entries chain) each flag
    assert len(hits) >= 3, [str(d) for d in hits]
    assert any("epoch" in h.message for h in hits)
    # the epoch API itself is the sanctioned crossing
    ok = (
        "from spark_rapids_tpu.service.result_cache import (\n"
        "    bump_table_epoch,\n"
        "    register_epoch_listener,\n"
        ")\n"
        "def good(path):\n"
        "    bump_table_epoch('delta:' + path, 'refresh')\n"
    )
    assert _run_rl(_check_mv_epoch,
                   "spark_rapids_tpu/streaming/mv.py", ok) == []
    # outside streaming/ the rule does not apply (the scheduler OWNS
    # the cache and mutates it legitimately)
    assert _run_rl(_check_mv_epoch,
                   "spark_rapids_tpu/service/scheduler.py", src) == []


# ---------------------------------------------------------------------------
# concurrency contracts (ISSUE 17): RL-LOCK-DECL / RL-LOCK-ORDER /
# RL-LOCK-EFFECT negatives over synthetic registries, plus the runtime
# lock witness
# ---------------------------------------------------------------------------


def _cc_registry(*decls):
    """Synthetic LOCK_ORDER: (name, rank, site, kind) tuples."""
    from spark_rapids_tpu.lockorder import LockDecl
    return {name: LockDecl(name, rank, site, kind, "test lock")
            for name, rank, site, kind in decls}


def _run_cc(files, registry, order_allow=None, effect_allow=None):
    from spark_rapids_tpu.lint.concurrency import check_concurrency
    diags = []
    check_concurrency({rel: ast.parse(src) for rel, src in files.items()},
                      diags, registry=registry,
                      order_allow=order_allow or {},
                      effect_allow=effect_allow or {})
    return diags


#: one in-scope module + two declared locks, A(10) under B(20) — the
#: shared fixture most order/effect sub-cases build on
_CC_REL = "spark_rapids_tpu/runtime/cc_mod.py"
_CC_TWO = (("t.a", 10, f"{_CC_REL}:A", "Lock"),
           ("t.b", 20, f"{_CC_REL}:B", "Lock"))
_CC_HDR = ("from spark_rapids_tpu.lockorder import ordered_lock\n"
           'A = ordered_lock("t.a")\n'
           'B = ordered_lock("t.b")\n')


def test_rl_lock_decl_raw_construction():
    """An undeclared raw threading primitive in a concurrent package is
    the core RL-LOCK-DECL negative (and the witness smoke's seed)."""
    src = "import threading\nL = threading.Lock()\n"
    hits = _find(_run_cc({"spark_rapids_tpu/runtime/bad.py": src},
                         _cc_registry()), "RL-LOCK-DECL")
    assert len(hits) == 1 and "raw threading.Lock()" in hits[0].message
    assert hits[0].path == "spark_rapids_tpu/runtime/bad.py:2"
    # the from-import alias spelling cannot slip past
    alias = "from threading import RLock as RL\nx = RL()\n"
    ahits = _find(_run_cc({"spark_rapids_tpu/obs/bad.py": alias},
                          _cc_registry()), "RL-LOCK-DECL")
    assert len(ahits) == 1 and "raw RL()" in ahits[0].message
    # outside the concurrency-scoped packages the rule does not apply
    assert _run_cc({"spark_rapids_tpu/plan/fine.py": src},
                   _cc_registry()) == []


def test_rl_lock_decl_factory_contract():
    reg = _cc_registry(("t.a", 10, _CC_REL + ":A", "Lock"))
    head = "from spark_rapids_tpu.lockorder import ordered_lock\n"
    # undeclared name
    hits = _find(_run_cc({_CC_REL: head + 'X = ordered_lock("nope")\n'},
                         reg), "RL-LOCK-DECL")
    assert any("not declared" in d.message for d in hits)
    # non-literal name defeats the audit
    hits = _find(_run_cc({_CC_REL: head + "X = ordered_lock(name)\n"},
                         reg), "RL-LOCK-DECL")
    assert any("string literal" in d.message for d in hits)
    # declared site and construction site must agree
    hits = _find(_run_cc({_CC_REL: head + 'WRONG = ordered_lock("t.a")\n'},
                         reg), "RL-LOCK-DECL")
    assert any("one declared construction site" in d.message for d in hits)
    # declared kind and factory must agree
    rl = ("from spark_rapids_tpu.lockorder import ordered_rlock\n"
          'A = ordered_rlock("t.a")\n')
    hits = _find(_run_cc({_CC_REL: rl}, reg), "RL-LOCK-DECL")
    assert any("declared as Lock but constructed" in d.message
               for d in hits)
    # a declared lock never constructed at its site = stale entry
    hits = _find(_run_cc({_CC_REL: "x = 1\n"}, reg), "RL-LOCK-DECL")
    assert any("stale registry entry" in d.message for d in hits)
    # ...and the clean construction is exactly zero findings
    assert _run_cc({_CC_REL: head + 'A = ordered_lock("t.a")\n'},
                   reg) == []


def test_rl_lock_order_with_nesting():
    reg = _cc_registry(*_CC_TWO)
    # ascending ranks: clean
    ok = _CC_HDR + "def f():\n    with A:\n        with B:\n            pass\n"
    assert _run_cc({_CC_REL: ok}, reg) == []
    # descending ranks: the inversion finding
    bad = _CC_HDR + "def f():\n    with B:\n        with A:\n            pass\n"
    hits = _find(_run_cc({_CC_REL: bad}, reg), "RL-LOCK-ORDER")
    assert any("'t.a' (rank 10) while holding 't.b' (rank 20)"
               in d.message for d in hits)
    # try-acquire is the sanctioned out-of-order shape
    tryacq = (_CC_HDR + "def f():\n    with B:\n"
              "        if A.acquire(blocking=False):\n"
              "            A.release()\n")
    assert _run_cc({_CC_REL: tryacq}, reg) == []
    # the allowlist hook suppresses a justified site (RL-MESH-HOST shape)
    assert _run_cc({_CC_REL: bad}, reg,
                   order_allow={f"{_CC_REL}:f": "test justification"}) == []


def test_rl_lock_order_through_call_graph():
    """The inversion two frames deep: f holds B and calls g, which
    blocking-acquires A — the bounded call-graph closure reports it at
    f's call site with the `via` evidence."""
    reg = _cc_registry(*_CC_TWO)
    src = (_CC_HDR
           + "def g():\n    with A:\n        pass\n"
           + "def f():\n    with B:\n        g()\n")
    hits = _find(_run_cc({_CC_REL: src}, reg), "RL-LOCK-ORDER")
    assert any("via g()" in d.message for d in hits)


def test_rl_lock_order_cycle_defeats_allowlist():
    """f ascends A->B (clean); g's B->A inversion is allowlisted — but
    the two edges compose into a deadlock cycle, which is reported
    regardless of allowlisting."""
    reg = _cc_registry(*_CC_TWO)
    src = (_CC_HDR
           + "def f():\n    with A:\n        with B:\n            pass\n"
           + "def g():\n    with B:\n        with A:\n            pass\n")
    diags = _run_cc({_CC_REL: src}, reg,
                    order_allow={f"{_CC_REL}:g": "test justification"})
    hits = _find(diags, "RL-LOCK-ORDER")
    assert any(d.path == "lockorder:cycle"
               and "allowlisting cannot suppress" in d.message
               for d in hits)
    # the allowlisted LOCAL finding stayed suppressed: only the cycle
    assert len(hits) == 1


def test_rl_lock_effect():
    reg = _cc_registry(*_CC_TWO)
    src = (_CC_HDR
           + "import subprocess\n"
           + "from spark_rapids_tpu.runtime.faults import fault_point\n"
           + "def f():\n    with A:\n"
           + "        subprocess.run(['x'])\n"
           + "        fault_point('t.point')\n")
    hits = _find(_run_cc({_CC_REL: src}, reg), "RL-LOCK-EFFECT")
    msgs = " | ".join(d.message for d in hits)
    assert "subprocess.run()" in msgs
    assert "fault_point() raise site" in msgs
    assert all("holding lock 't.a'" in d.message for d in hits)
    # the allowlist hook keys on the HOLDER function
    assert _run_cc({_CC_REL: src}, reg,
                   effect_allow={f"{_CC_REL}:f": "test justification"}) == []


def test_rl_lock_effect_condition_wait():
    """Waiting on a Condition while holding a DIFFERENT lock is a
    finding; waiting on the condition you hold is how conditions
    work."""
    rel = _CC_REL
    reg = _cc_registry(("t.a", 10, f"{rel}:A", "Lock"),
                       ("t.cv", 20, f"{rel}:CV", "Condition"))
    head = ("from spark_rapids_tpu.lockorder import ordered_lock, "
            "ordered_condition\n"
            'A = ordered_lock("t.a")\n'
            'CV = ordered_condition("t.cv")\n')
    bad = head + ("def f():\n    with A:\n        with CV:\n"
                  "            CV.wait()\n")
    hits = _find(_run_cc({rel: bad}, reg), "RL-LOCK-EFFECT")
    assert any("wait on Condition 't.cv'" in d.message
               and "'t.a'" in d.message for d in hits)
    ok = head + "def f():\n    with CV:\n        CV.wait()\n"
    assert _run_cc({rel: ok}, reg) == []


def test_lock_witness_rank_inversion_raises():
    """The armed witness turns a would-be deadlock interleaving into a
    typed LockOrderViolation carrying the held chain."""
    from spark_rapids_tpu import lockorder
    lockorder.arm_witness()
    try:
        low = lockorder.ordered_lock("streaming.query")     # rank 100
        high = lockorder.ordered_lock("memory.arbiter")     # rank 740
        # ascending is silent, and the held snapshot tracks it
        with low:
            with high:
                assert lockorder.held_snapshot() == [
                    "streaming.query", "memory.arbiter"]
        assert lockorder.held_snapshot() == []
        # descending raises BEFORE touching the inner lock
        with high:
            with pytest.raises(lockorder.LockOrderViolation) as ei:
                low.acquire()
            assert "held chain" in str(ei.value)
            assert "memory.arbiter" in str(ei.value)
            # try-acquire stays exempt at runtime too
            assert low.acquire(blocking=False)
            low.release()
        assert lockorder.held_snapshot() == []
        # re-acquiring a held non-reentrant lock = self-deadlock
        with pytest.raises(lockorder.LockOrderViolation,
                           match="self-deadlock"):
            with low:
                low.acquire()
    finally:
        lockorder.disarm_witness()
        # the two violations provoked above are this test's own: the
        # witness's process-wide count is read by tests that run after
        # it in the same worker (test_service's chaos slice)
        lockorder.reset_witness_violations()


def test_lock_witness_condition_wait_releases():
    from spark_rapids_tpu import lockorder
    lockorder.arm_witness()
    try:
        cv = lockorder.ordered_condition("service.scheduler.cond")
        with cv:
            assert lockorder.held_snapshot() == ["service.scheduler.cond"]
            cv.wait(timeout=0.01)
            # wait() re-acquired: the held stack is restored
            assert lockorder.held_snapshot() == ["service.scheduler.cond"]
        assert lockorder.held_snapshot() == []
    finally:
        lockorder.disarm_witness()


def test_lock_witness_construction_time_election():
    """configure() arms from conf; disarmed factories hand back RAW
    primitives (zero steady-state overhead), armed ones the witness
    wrappers — elected at construction, not per-acquire."""
    from spark_rapids_tpu import lockorder
    try:
        lockorder.configure(RapidsConf(
            {"spark.rapids.lint.lockWitness": "true"}))
        assert lockorder.witness_armed()
        wrapped = lockorder.ordered_lock("streaming.query")
        assert "witnessed" in repr(wrapped)
        lockorder.configure(RapidsConf())
        assert not lockorder.witness_armed()
        raw = lockorder.ordered_lock("streaming.query")
        assert not hasattr(raw, "_decl")
        # a pre-arming lock stays raw even while the witness is armed
        lockorder.arm_witness()
        with raw:
            pass
        # undeclared names fail fast regardless of arming
        with pytest.raises(lockorder.LockDeclError, match="not declared"):
            lockorder.ordered_lock("no.such.lock")
        with pytest.raises(lockorder.LockDeclError, match="declared as"):
            lockorder.ordered_rlock("streaming.query")
    finally:
        lockorder.disarm_witness()


def test_lock_registry_known_suspects_ranked():
    """ISSUE 17 satellite: the sites previous PRs fixed by hand are now
    pinned by rank so the ordering cannot silently regress."""
    from spark_rapids_tpu.lockorder import LOCK_ORDER, LOCK_WITNESS
    r = {n: d.rank for n, d in LOCK_ORDER.items()}
    # scheduler condition is acquired before the per-query handle lock
    assert r["service.scheduler.cond"] < r["service.handle"]
    # arbiter work happens UNDER a SpillableBatch lock (account/spill)
    assert r["spill.batch"] < r["memory.arbiter"]
    # catalog singleton access sits between batch and the catalog maps
    assert r["spill.batch"] < r["spill.catalog.instance"] \
        < r["spill.catalog.registry"]
    # telemetry/observability rings are leaf locks: above every
    # runtime/service lock they are reached from
    assert r["obs.telemetry.ring"] > r["memory.arbiter"]
    assert r["obs.telemetry.ring"] > r["service.scheduler.cond"]
    # fault registry is consulted from inside every subsystem
    assert r["faults.registry"] > r["memory.arbiter"]
    # ranks form a total order (no ties to hide behind)
    assert len(set(r.values())) == len(r)
    assert LOCK_WITNESS.key == "spark.rapids.lint.lockWitness"


@pytest.mark.chaos
def test_lock_witness_chaos_service_scenario():
    """Tier-1 chaos pin: a concurrent service run under memory pressure
    (admission, scheduler condition, result cache, arbiter, telemetry)
    completes with the witness armed — every blocking acquisition on
    every thread respected LOCK_ORDER, or this raises
    LockOrderViolation."""
    from spark_rapids_tpu import lockorder
    from spark_rapids_tpu.ops.expr import lit
    from spark_rapids_tpu.service import QueryService
    conf = {
        "spark.rapids.lint.lockWitness": "true",
        # a small device budget forces arbiter/spill traffic under load
        "spark.rapids.memory.device.budgetBytes": str(256 * 1024),
    }
    try:
        with QueryService(conf, max_concurrent=3) as svc:
            data = {"k": np.array(["a", "b", "c", "d"] * 60, dtype=object),
                    "v": np.arange(240, dtype=np.int64)}
            df = svc.session.create_dataframe(data, num_batches=6)
            handles = [
                svc.submit(df.filter(col("v") >= lit(i))
                           .group_by("k").agg(F.sum("v").alias("sv")))
                for i in range(8)]
            for h in handles:
                assert h.wait(timeout=60)
            assert all(h.result() is not None for h in handles)
        assert lockorder.held_snapshot() == []
    finally:
        lockorder.disarm_witness()


def test_cli_json_smoke(tmp_path):
    """Satellite: the --json contract in a real subprocess — a clean
    all-skip run exits 0, and a tiny synthetic tree with an undeclared
    lock exits 1 with machine-readable diagnostics."""
    import json
    import subprocess
    import sys
    clean = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.lint", "--json",
         "--skip-repo", "--skip-registry", "--skip-plans",
         "--skip-exec-metrics"],
        capture_output=True, text=True)
    assert clean.returncode == 0, clean.stderr
    out = json.loads(clean.stdout)
    assert out == {"phases": {}, "diagnostics": [], "ok": True}

    bad = tmp_path / "spark_rapids_tpu" / "runtime"
    bad.mkdir(parents=True)
    (bad / "bad.py").write_text("import threading\nL = threading.Lock()\n")
    failing = subprocess.run(
        [sys.executable, "-m", "spark_rapids_tpu.lint", "--json",
         "--repo-root", str(tmp_path), "--skip-registry",
         "--skip-plans", "--skip-exec-metrics"],
        capture_output=True, text=True)
    assert failing.returncode == 1, failing.stderr
    out = json.loads(failing.stdout)
    assert out["ok"] is False and out["phases"]["repo"] >= 1
    assert any(
        d["rule_id"] == "RL-LOCK-DECL"
        and d["path"] == "spark_rapids_tpu/runtime/bad.py:2"
        and d["severity"] == "error"
        for d in out["diagnostics"])


def test_every_rule_has_a_negative_test():
    """Meta-pin: the rule surface and this module's negative coverage
    cannot drift apart (>= 12 rules required by the issue)."""
    module_src = open(__file__).read()
    assert len(RULES) >= 12
    for rid in RULES:
        assert rid in module_src, f"rule {rid} has no negative test"


# ---------------------------------------------------------------------------
# pins for the real violations the tooling surfaced (satellite 1)
# ---------------------------------------------------------------------------


def _dec_table(precision=4, scale=2):
    return HostTable(["d", "e", "g"], [
        HostColumn(T.DecimalType(precision, scale),
                   np.array([100, 200, 300, 400], dtype=np.int64)),
        HostColumn(T.DecimalType(precision, scale),
                   np.array([30, 30, 70, 70], dtype=np.int64)),
        HostColumn(T.LONG, np.array([0, 0, 1, 1], dtype=np.int64))])


def test_decimal_remainder_registered_and_on_device(session, cpu_session):
    """RA-UNREGISTERED catch: DecimalRemainder/DecimalPmod shipped device
    kernels but were never registered — decimal % silently fell back."""
    from spark_rapids_tpu.overrides import rules as R
    from spark_rapids_tpu.ops.decimal import DecimalPmod, DecimalRemainder
    R._build_expr_sigs()
    from spark_rapids_tpu.overrides.typesig import lookup_mro
    assert lookup_mro(R._EXPR_SIGS, DecimalRemainder) is not None
    assert lookup_mro(R._EXPR_SIGS, DecimalPmod) is not None

    t = _dec_table()
    expr = (col("d") % col("e")).alias("r")
    want = from_host_table(t, cpu_session).select(expr).collect()
    got = from_host_table(t, session).select(expr).collect()
    assert got == want
    from tests.asserts import assert_runs_on_tpu
    assert_runs_on_tpu(
        lambda s: from_host_table(t, s).select(expr), session)


def test_avg_decimal_returns_value_units(session, cpu_session):
    """PV/PROBE catch: avg(decimal(4,2)) of [1.00..4.00] must be in VALUE
    units (2.5), not unscaled units (250), on every path."""
    t = _dec_table()
    for s in (session, cpu_session):
        rows = from_host_table(t, s).agg(F.avg("d").alias("a")).collect()
        assert rows == [(2.5,)], (s, rows)
        by_g = sorted(from_host_table(t, s).group_by("g")
                      .agg(F.avg("d").alias("a")).collect())
        assert by_g == [(0, 1.5), (1, 3.5)], (s, by_g)


def test_avg_decimal_streaming_merge_path(session):
    """The streaming partial-merge path casts its dec128 partial sums to
    double — this crashed (two-limb broadcast) before the cast fix."""
    t = _dec_table()
    s = TpuSession({"spark.rapids.sql.batchSizeBytes": "1"})
    rows = sorted(from_host_table(t, s, 4).group_by("g")
                  .agg(F.avg("d").alias("a")).collect())
    assert rows == [(0, 1.5), (1, 3.5)], rows


def test_stddev_decimal_value_units(session, cpu_session):
    import math
    t = _dec_table()
    want = math.sqrt(np.var([1.0, 2.0, 3.0, 4.0], ddof=1))
    for s in (session, cpu_session):
        (got,), = from_host_table(t, s).agg(
            F.stddev(col("d")).alias("x")).collect()
        assert got == pytest.approx(want, rel=1e-9), (s, got)


def test_window_avg_decimal_value_units(session, cpu_session):
    from spark_rapids_tpu.ops.window import Window as W
    t = _dec_table()
    for s in (session, cpu_session):
        rows = sorted(from_host_table(t, s).with_windows(
            a=F.avg(col("d")).over(W.partition_by("g")))
            .select("g", "a").collect())
        assert rows == [(0, 1.5), (0, 1.5), (1, 3.5), (1, 3.5)], (s, rows)


def test_dec128_cast_to_double_on_device(session, cpu_session):
    """Cast(decimal(25,2) -> double) used to broadcast-crash on the
    two-limb device representation."""
    big = 10 ** 20  # needs 128-bit storage at precision 25
    vals = np.array([big * 100 + 25, -big * 100, 0], dtype=object)
    t = HostTable(["d"], [HostColumn(T.DecimalType(25, 2), vals)])
    expr = col("d").cast("double").alias("x")
    want = from_host_table(t, cpu_session).select(expr).collect()
    got = from_host_table(t, session).select(expr).collect()
    # two-limb f64 combine vs one exact division: allow ULP-level skew
    for (g,), (w,) in zip(got, want):
        assert g == pytest.approx(w, rel=1e-13), (g, w)
    assert got[0][0] == pytest.approx(float(big), rel=1e-13)
