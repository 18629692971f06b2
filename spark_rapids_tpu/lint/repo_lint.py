"""Python-AST repo lint: project invariants the type system can't hold.

The TPU-first rule this codebase lives by (dispatch.py header): NOTHING
transfers host<->device on a warm query outside the sanctioned sites.
The type checker cannot see a stray ``jax.device_get`` in a kernel or a
conf key referenced by a typo'd string — this lint can.

The rules themselves live in per-rule modules under ``lint/rules/``
(see each module's docstring for its contract) plus the concurrency
pass in ``lint/concurrency.py``; this module is the driver —
``lint_repo()`` parses every source file once and runs the shared rule
registry (``lint.rules.REGISTRY``) over the trees — and the stable
import surface: every ``_check_*`` checker and allowlist keeps its
historical name HERE (same objects, re-exported), so callers and tests
are unaffected by the package split.

Rules (RL-*): RL-HOST-SYNC, RL-JNP-SCOPE, RL-CONF-KEY,
RL-NONDETERMINISM, RL-DEAD-LAMBDA, RL-FAULT-POINT, RL-THREAD-SHARED,
RL-MESH-HOST, RL-WRITE-COMMIT, RL-OBS-PASSIVE, RL-MEM-ACCOUNT,
RL-MV-EPOCH, and the concurrency contract (RL-LOCK-DECL,
RL-LOCK-ORDER, RL-LOCK-EFFECT — see ``lint/concurrency.py``).
"""

from __future__ import annotations

import ast
from typing import List, Optional

from spark_rapids_tpu.lint.diagnostics import Diagnostic
from spark_rapids_tpu.lint.rules import REGISTRY, LintContext
# re-exports: the stable import surface (tests and callers patch the
# allowlist DICTS in place — these must stay the same objects the rule
# modules read)
from spark_rapids_tpu.lint.rules.common import (  # noqa: F401
    _attr_chain, _host_sync_call, _is_device_expr, _iter_source_files,
    _rel, _repo_root)
from spark_rapids_tpu.lint.rules.conf_keys import (  # noqa: F401
    _CONF_KEY_RE, _check_conf_keys)
from spark_rapids_tpu.lint.rules.determinism import (  # noqa: F401
    _SEEDED_RANDOM_OK, _check_dead_lambdas, _check_nondeterminism)
from spark_rapids_tpu.lint.rules.device_residency import (  # noqa: F401
    _DEVICE_DIRS, _DEVICE_FILES, _MEM_ACCOUNT_ALLOWLIST,
    _MESH_HOST_ALLOWLIST, _check_host_sync, _check_jnp_scope,
    _check_mem_account, _check_mesh_host)
from spark_rapids_tpu.lint.rules.fault_points import (  # noqa: F401
    _check_fault_registry, _check_fault_sites, _is_fault_point_call)
from spark_rapids_tpu.lint.rules.io_write import (  # noqa: F401
    _WRITE_COMMIT_EXEMPT, _WRITE_ONE, _check_write_commit,
    _open_mode_writes)
from spark_rapids_tpu.lint.rules.obs_passive import (  # noqa: F401
    _OBS_PASSIVE_ALLOWLIST, _OBS_PASSIVE_MODULE, _check_obs_passive)
from spark_rapids_tpu.lint.rules.streaming_epoch import (  # noqa: F401
    _MV_EPOCH_ALLOWED_IMPORTS, _check_mv_epoch)
from spark_rapids_tpu.lint.rules.thread_shared import (  # noqa: F401
    _THREAD_SHARED_ALLOWLIST, _THREAD_SHARED_DIRS, _check_thread_shared,
    _is_lock_guard, _is_mutable_container)


def lint_repo(repo_root: Optional[str] = None) -> List[Diagnostic]:
    root = _repo_root(repo_root)
    from spark_rapids_tpu.lint.registry_audit import _import_full_package
    _import_full_package()
    from spark_rapids_tpu import conf as C
    ctx = LintContext(declared=set(C.registry()))
    diags: List[Diagnostic] = []
    for path in _iter_source_files(root):
        rel = _rel(root, path)
        if rel.startswith("spark_rapids_tpu/lint/"):
            continue  # the lint's own rule tables name forbidden patterns
        with open(path) as f:
            src = f.read()
        tree = ast.parse(src, filename=rel)  # unparseable repo = hard error
        ctx.trees[rel] = tree
        for rule in REGISTRY:
            if rule.file_check is not None:
                rule.file_check(ctx, rel, tree, diags)
    for rule in REGISTRY:
        if rule.finalizer is not None:
            rule.finalizer(ctx, diags)
    return diags
