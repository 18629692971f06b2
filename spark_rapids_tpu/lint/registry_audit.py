"""Registry auditor (reference: the supported_ops.md generator contract —
docs, tag functions and registries must agree; round-5 VERDICT flagged
exactly this class of drift).

Cross-checks, with one Diagnostic per disagreement (RA-* rules):

* ops/* expression classes carrying a device kernel against the
  overrides ``_EXPR_SIGS`` registrations (unregistered = silently CPU);
* ``_EXPR_CHECKS`` per-parameter signatures against constructor arity;
* per-op kill-switch conf keys against the rule registries;
* device-supported aggregates against the SQL function registry;
* the committed SUPPORTED_OPS.md / CONFIGS.md against their generators.
"""

from __future__ import annotations

import importlib
import inspect
import os
import pkgutil
from typing import List, Optional

from spark_rapids_tpu.lint.diagnostics import Diagnostic, make


def _repo_root(repo_root: Optional[str]) -> str:
    if repo_root:
        return repo_root
    import spark_rapids_tpu
    return os.path.dirname(os.path.dirname(
        os.path.abspath(spark_rapids_tpu.__file__)))


def _import_full_package() -> None:
    """Import every submodule so dynamically-registered rules/confs (file
    formats, delta, profiler, filecache...) are present — the same walk
    conf.generate_docs performs."""
    import spark_rapids_tpu
    for m in pkgutil.walk_packages(spark_rapids_tpu.__path__,
                                   "spark_rapids_tpu."):
        try:
            importlib.import_module(m.name)
        except Exception:
            pass  # optional backends (pyarrow etc.) may be absent


#: ops modules whose Expression subclasses evaluate through a DIFFERENT
#: support registry than _EXPR_SIGS (window functions gate through
#: execs.window.device_window_supported; aggregates register as classes
#: via DEVICE_SUPPORTED_AGGS — both audited separately below)
_NON_SIG_MODULES = ("spark_rapids_tpu.ops.window",)

#: classes that are never evaluated as row expressions, so an _EXPR_SIGS
#: entry would be meaningless: generator markers are consumed by the
#: Generate plan node (tagged by _tag_generate), and the HOF lambda
#: plumbing is rebound into element space by its enclosing function
_NON_EXPR_EVALUATED = {
    "Explode", "ExplodeOuter", "PosExplode", "PosExplodeOuter",
    "LambdaFunction", "NamedLambdaVariable",
}


def _audit_unregistered(diags: List[Diagnostic]) -> None:
    from spark_rapids_tpu.ops.expr import Expression
    from spark_rapids_tpu.overrides import rules as R
    from spark_rapids_tpu.overrides.typesig import lookup_mro
    R._build_expr_sigs()
    import spark_rapids_tpu.ops as ops_pkg
    for m in pkgutil.iter_modules(ops_pkg.__path__, "spark_rapids_tpu.ops."):
        if m.name in _NON_SIG_MODULES:
            continue
        try:
            mod = importlib.import_module(m.name)
        except Exception:
            continue
        for name in dir(mod):
            obj = getattr(mod, name)
            if not (isinstance(obj, type) and issubclass(obj, Expression)
                    and not name.startswith("_")
                    and obj.__module__ == mod.__name__
                    and "_is_expr_base" not in vars(obj)):
                continue
            has_dev = ("eval_dev" in {k for kls in obj.__mro__
                                      for k in vars(kls)}
                       and getattr(obj, "eval_dev", None)
                       is not Expression.eval_dev)
            if name in _NON_EXPR_EVALUATED:
                continue
            if has_dev and lookup_mro(R._EXPR_SIGS, obj) is None:
                diags.append(make(
                    "RA-UNREGISTERED", f"{m.name}.{name}",
                    "expression has a device kernel (eval_dev) but no "
                    "_EXPR_SIGS registration — it silently falls back "
                    "to CPU"))


def _audit_param_arity(diags: List[Diagnostic]) -> None:
    from spark_rapids_tpu.overrides import rules as R
    R._build_expr_sigs()
    for cls, checks in R._EXPR_CHECKS.items():
        try:
            sig = inspect.signature(cls.__init__)
        except (TypeError, ValueError):
            continue
        params = [p for n, p in sig.parameters.items() if n != "self"]
        if any(p.kind == inspect.Parameter.VAR_POSITIONAL for p in params):
            continue  # *args constructors accept any arity
        max_args = len([p for p in params if p.kind in (
            inspect.Parameter.POSITIONAL_ONLY,
            inspect.Parameter.POSITIONAL_OR_KEYWORD)])
        if len(checks.param_sigs) > max_args:
            diags.append(make(
                "RA-PARAM-ARITY",
                f"{cls.__module__}.{cls.__name__}",
                f"ExprChecks declares {len(checks.param_sigs)} parameter "
                f"signatures but the constructor takes at most "
                f"{max_args} positional arguments"))


#: expression kill switches registered outside the sig registries: Hive
#: UDF wrappers tag per-class fallback through _tag_python_udf, not
#: through _EXPR_SIGS (hive_udf.py registers these two at import)
_KNOWN_NON_SIG_SWITCHES = {"HiveSimpleUDF", "HiveGenericUDF"}


def _audit_kill_switches(diags: List[Diagnostic]) -> None:
    from spark_rapids_tpu import conf as C
    from spark_rapids_tpu.overrides import rules as R
    R._build_expr_sigs()
    exec_names = {cls.__name__ for cls in R._EXEC_RULES}
    expr_names = {cls.__name__ for cls in R._EXPR_SIGS}
    for key in C.registry():
        parts = key.split(".")
        if len(parts) != 5 or parts[:3] != ["spark", "rapids", "sql"]:
            continue
        kind, name = parts[3], parts[4]
        if kind == "exec" and name not in exec_names:
            diags.append(make(
                "RA-KILL-SWITCH", key,
                f"kill switch names exec {name!r} but no exec rule is "
                "registered under that class"))
        elif kind == "expression" and name not in expr_names \
                and name not in _KNOWN_NON_SIG_SWITCHES:
            diags.append(make(
                "RA-KILL-SWITCH", key,
                f"kill switch names expression {name!r} but no "
                "expression signature is registered under that class"))


#: device aggregate class -> the SQL builtin name users reach it by;
#: RA-SQL-EXPOSURE fails when a DEVICE_SUPPORTED_AGGS class is missing
#: here or its name is missing from the builtin table
_AGG_SQL_NAMES = {
    "Sum": "sum", "Min": "min", "Max": "max", "Count": "count",
    "Average": "avg", "First": "first", "Last": "last",
    "StddevPop": "stddev_pop", "StddevSamp": "stddev_samp",
    "VariancePop": "var_pop", "VarianceSamp": "var_samp",
    "CollectList": "collect_list", "CollectSet": "collect_set",
    "Percentile": "percentile",
}


def _audit_sql_exposure(diags: List[Diagnostic]) -> None:
    from spark_rapids_tpu.execs.aggregate import DEVICE_SUPPORTED_AGGS
    from spark_rapids_tpu.sql import registry as sql_registry
    try:
        table_probe = sql_registry.builtin("sum")
    except Exception as exc:
        diags.append(make(
            "RA-SQL-EXPOSURE", "sql.registry",
            f"builtin function table fails to build: {exc!r}"))
        return
    if table_probe is None:
        diags.append(make("RA-SQL-EXPOSURE", "sql.registry.sum",
                          "core aggregate 'sum' missing from builtins"))
    for cls in DEVICE_SUPPORTED_AGGS:
        sql_name = _AGG_SQL_NAMES.get(cls.__name__)
        where = f"sql.registry.{cls.__name__}"
        if sql_name is None:
            diags.append(make(
                "RA-SQL-EXPOSURE", where,
                f"device aggregate {cls.__name__} has no known SQL "
                "name (add it to the auditor map AND the SQL registry)"))
        elif sql_registry.builtin(sql_name) is None:
            diags.append(make(
                "RA-SQL-EXPOSURE", where,
                f"device aggregate {cls.__name__} is not callable from "
                f"SQL (builtin {sql_name!r} missing)"))


def _audit_doc_drift(diags: List[Diagnostic], root: str) -> None:
    from spark_rapids_tpu.conf import generate_docs
    from spark_rapids_tpu.overrides.docs import generate_supported_ops
    from spark_rapids_tpu.lockorder import generate_locks_md
    for fname, gen, rule in (
            ("SUPPORTED_OPS.md", generate_supported_ops,
             "RA-DOC-DRIFT-OPS"),
            ("CONFIGS.md", generate_docs, "RA-DOC-DRIFT-CONFIGS"),
            ("LOCKS.md", generate_locks_md, "RA-DOC-DRIFT-LOCKS")):
        path = os.path.join(root, fname)
        if not os.path.exists(path):
            diags.append(make(rule, fname, "committed file is missing"))
            continue
        with open(path) as f:
            on_disk = f.read()
        want = gen()
        if on_disk != want:
            # first diverging line makes the drift actionable
            got_lines = on_disk.splitlines()
            want_lines = want.splitlines()
            where = next((i for i, (a, b) in
                          enumerate(zip(got_lines, want_lines)) if a != b),
                         min(len(got_lines), len(want_lines)))
            diags.append(make(
                rule, f"{fname}:{where + 1}",
                "committed file differs from the generator output — "
                "regenerate via `python -m spark_rapids_tpu.lint "
                "--write-docs`"))


def regenerate_docs(repo_root: Optional[str] = None) -> List[str]:
    """Write SUPPORTED_OPS.md, CONFIGS.md and LOCKS.md from their
    generators; returns the files written (the CLI's --write-docs)."""
    from spark_rapids_tpu.conf import generate_docs
    from spark_rapids_tpu.overrides.docs import generate_supported_ops
    from spark_rapids_tpu.lockorder import generate_locks_md
    root = _repo_root(repo_root)
    written = []
    for fname, gen in (("SUPPORTED_OPS.md", generate_supported_ops),
                       ("CONFIGS.md", generate_docs),
                       ("LOCKS.md", generate_locks_md)):
        path = os.path.join(root, fname)
        with open(path, "w") as f:
            f.write(gen())
        written.append(path)
    return written


#: golden-corpus slice the metrics audit executes: one query per major
#: exec family (agg, join, sort+limit, window, exchange) — enough to
#: observe every hot exec class without running all 22
_METRICS_AUDIT_QUERIES = ("q1", "q3", "q5", "q6", "q7")


def audit_exec_metrics_tree(executable,
                            diags: List[Diagnostic],
                            context: str = "") -> None:
    """RA-ESSENTIAL-METRICS over ONE executed tree: every device exec
    (and the DeviceToHost root) that ran must carry the ESSENTIAL
    opTime/numOutputRows/numOutputBatches metrics. An exec whose
    metrics are entirely empty never ran (a lazily-pulled branch an
    early-terminating consumer abandoned) — skipped, EXCEPT the root,
    whose silence means the observation boundary was never installed."""
    from spark_rapids_tpu.execs.base import DeviceToHost, TpuExec
    from spark_rapids_tpu.lore import _iter_tree
    from spark_rapids_tpu.obs.metrics import ESSENTIAL_EXEC_METRICS

    root = executable
    for e in _iter_tree(executable):
        if not isinstance(e, (TpuExec, DeviceToHost)):
            continue
        name = type(e).__name__
        where = f"{context}{name}[loreId={getattr(e, '_lore_id', '?')}]"
        m = getattr(e, "metrics", None) or {}
        if not m:
            if e is root:
                diags.append(make(
                    "RA-ESSENTIAL-METRICS", where,
                    "root of an executed plan has NO metrics — the "
                    "observation boundary was never installed"))
            continue
        missing = [k for k in ESSENTIAL_EXEC_METRICS if k not in m]
        if missing:
            diags.append(make(
                "RA-ESSENTIAL-METRICS", where,
                f"executed exec is missing ESSENTIAL metric(s) "
                f"{', '.join(missing)}"))


def audit_exec_metrics(scale_factor: float = 0.005,
                       queries=_METRICS_AUDIT_QUERIES) -> List[Diagnostic]:
    """Execute a golden-corpus slice and assert every exec that ran
    emitted its ESSENTIAL metrics (the obs/spans.install_observation
    contract — an exec class overriding execute without riding the
    boundary shows up here, not as silently-missing tool data)."""
    from spark_rapids_tpu.lint.golden import _load_scale_test, golden_tables
    from spark_rapids_tpu.obs.spans import finalize_observation
    from spark_rapids_tpu.session import TpuSession

    st = _load_scale_test()
    tables = golden_tables(scale_factor)
    session = TpuSession()
    corpus = st.build_queries(session, tables)
    diags: List[Diagnostic] = []
    for name in queries:
        corpus[name]().collect_table()
        executable = session._last_executable
        finalize_observation(executable)
        audit_exec_metrics_tree(executable, diags, context=f"{name}:")
    return diags


#: declared keys consumed through a mechanism the text scan cannot see,
#: or seed-era reference-compat placeholders kept so carried-over
#: reference configs don't fail on unknown keys. Add "key: why"
#: entries, never bare keys — NEW keys must wire a reader.
_CONF_ORPHAN_ALLOWLIST: dict = {
    "spark.rapids.sql.reader.batchSizeRows":
        "seed placeholder: reference reader-batching knob; scans "
        "currently batch by bytes only",
    "spark.rapids.sql.hasNans":
        "seed placeholder: reference NaN-handling knob; device kernels "
        "handle NaN unconditionally",
    "spark.rapids.sql.castStringToTimestamp.enabled":
        "seed placeholder: reference cast gate; the cast is "
        "TypeSig-gated instead",
    "spark.rapids.sql.decimalType.enabled":
        "seed placeholder: reference decimal master switch; decimals "
        "gate per-op through TypeSig",
    "spark.rapids.sql.test.strictOracle":
        "seed placeholder: CPU-oracle strictness for a planned "
        "test-harness mode",
}


def _audit_conf_referenced(diags: List[Diagnostic], root: str) -> None:
    """RA-CONF-ORPHAN: every declared conf key must be CONSUMED by the
    engine or its harnesses — a key whose ConfEntry variable and key
    string both appear exactly once (their declaration) was added
    without wiring a reader, so setting it silently does nothing
    (the complement of RL-CONF-KEY, which catches references without a
    declaration). Kill switches are exempt: is_op_enabled reads them
    generically by name."""
    import re
    import sys

    from spark_rapids_tpu.conf import ConfEntry, registry

    sources = []
    pkg_dir = os.path.join(root, "spark_rapids_tpu")
    for dirpath, _dirs, names in os.walk(pkg_dir):
        for n in names:
            if n.endswith(".py"):
                sources.append(os.path.join(dirpath, n))
    p = os.path.join(root, "scale_test.py")
    if os.path.exists(p):
        sources.append(p)
    text = "\n".join(open(p, encoding="utf-8").read() for p in sources)

    #: key -> ConfEntry variable names bound in any engine module
    var_names: dict = {}
    for mod_name, mod in list(sys.modules.items()):
        if not mod_name.startswith("spark_rapids_tpu") or mod is None:
            continue
        for attr, val in list(vars(mod).items()):
            if isinstance(val, ConfEntry):
                var_names.setdefault(val.key, set()).add(attr)

    for key, entry in registry().items():
        parts = key.split(".")
        if (len(parts) == 5 and parts[:3] == ["spark", "rapids", "sql"]
                and parts[3] in ("exec", "expression")):
            continue  # kill switches: read generically by class name
        if key in _CONF_ORPHAN_ALLOWLIST:
            continue
        # boundary-aware: 'a.b' must not match inside 'a.b.c' — a key
        # that is a dotted prefix of another declared key would
        # otherwise count its sibling's declaration as a reference
        key_uses = len(re.findall(re.escape(key) + r"(?![.\w])", text))
        name_uses = sum(
            len(re.findall(rf"\b{re.escape(n)}\b", text))
            for n in var_names.get(key, ()))
        # one key-string occurrence (the declaration) + one occurrence
        # per variable binding (assignment/import) is declaration-only
        if key_uses <= 1 and name_uses <= len(var_names.get(key, ())):
            diags.append(make(
                "RA-CONF-ORPHAN", key,
                "conf key is declared but never read — wire a consumer "
                "or remove it (allowlist with a justification if it is "
                "consumed through a mechanism this scan cannot see)"))


def audit_registry(repo_root: Optional[str] = None) -> List[Diagnostic]:
    _import_full_package()
    diags: List[Diagnostic] = []
    _audit_unregistered(diags)
    _audit_param_arity(diags)
    _audit_kill_switches(diags)
    _audit_sql_exposure(diags)
    _audit_doc_drift(diags, _repo_root(repo_root))
    _audit_conf_referenced(diags, _repo_root(repo_root))
    return diags
