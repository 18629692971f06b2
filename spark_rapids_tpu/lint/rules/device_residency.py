"""Device-residency rules: the TPU-first contract (dispatch.py header)
that NOTHING transfers host<->device on a warm query outside the
sanctioned sites.

* RL-HOST-SYNC — no host synchronization (``jax.device_get``,
  ``.block_until_ready()``) inside execs/ or ops/ hot paths except via
  the sanctioned ``dispatch.host_fetch`` helper.
* RL-JNP-SCOPE — ``jax.numpy`` imports only in the device layers.
* RL-MESH-HOST — mesh-native execution keeps shards device-resident
  BETWEEN exchanges: inside ``parallel/`` and the shard-dispatch
  placement layer, host materialization may appear only at sanctioned
  gather points (``_MESH_HOST_ALLOWLIST``, each entry justified).
* RL-MEM-ACCOUNT — device landings in execs//ops/ must route through
  arbiter-accounted paths (``DeviceTable.from_host``); a raw
  ``jax.device_put`` lands bytes the MemoryArbiter never sees.
"""

from __future__ import annotations

import ast
from typing import List, Optional

from spark_rapids_tpu.lint.diagnostics import Diagnostic, make
from spark_rapids_tpu.lint.rules.common import (_attr_chain,
                                                _host_sync_call,
                                                _is_device_expr)

#: directories (under spark_rapids_tpu/) whose modules are device layers
#: and may import jax.numpy
_DEVICE_DIRS = ("execs", "ops", "columnar", "parallel", "runtime",
                "shuffle", "shims", "models")
#: top-level device-layer files
_DEVICE_FILES = ("dispatch.py", "udf.py")


def _check_host_sync(rel: str, tree: ast.AST, diags: List[Diagnostic]):
    in_hot_path = rel.startswith(("spark_rapids_tpu/execs/",
                                  "spark_rapids_tpu/ops/"))
    if not in_hot_path:
        return
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            # `from jax import device_get` would make the call below
            # invisible to the chain matcher — ban the import form too
            for a in node.names:
                if a.name in ("device_get", "block_until_ready"):
                    diags.append(make(
                        "RL-HOST-SYNC", f"{rel}:{node.lineno}",
                        f"importing jax.{a.name} into a hot path; route "
                        "through dispatch.host_fetch so syncs are "
                        "counted and reviewable"))
            continue
        if not isinstance(node, ast.Call):
            continue
        chain = _attr_chain(node.func)
        if chain.endswith(".block_until_ready"):
            diags.append(make(
                "RL-HOST-SYNC", f"{rel}:{node.lineno}",
                "block_until_ready() stalls the dispatch pipeline; use "
                "dispatch.host_fetch at a sanctioned sync point"))
        elif chain == "jax.device_get" or chain.endswith(".device_get") \
                or chain == "device_get":
            diags.append(make(
                "RL-HOST-SYNC", f"{rel}:{node.lineno}",
                "raw jax.device_get in a hot path (a host sync "
                "each); route through dispatch.host_fetch so syncs are "
                "counted and reviewable"))
        elif chain in ("np.asarray", "numpy.asarray", "float", "int") \
                and node.args and _is_device_expr(node.args[0]):
            # the statically-decidable slice of "np.asarray/float/int on
            # device values": the argument is itself a jnp./jax. call,
            # so the conversion provably forces a device sync (general
            # deviceness needs dataflow a lint can't do)
            diags.append(make(
                "RL-HOST-SYNC", f"{rel}:{node.lineno}",
                f"{chain}() over a jax expression synchronizes the "
                "device; route through dispatch.host_fetch"))


def _check_jnp_scope(rel: str, tree: ast.AST, diags: List[Diagnostic]):
    parts = rel.split("/")
    allowed = False
    if parts[0] != "spark_rapids_tpu":
        allowed = False  # scale_test.py is a host driver
    elif len(parts) == 2:
        allowed = parts[1] in _DEVICE_FILES
    else:
        allowed = parts[1] in _DEVICE_DIRS
    if allowed:
        return
    for node in ast.walk(tree):
        hit = None
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "jax.numpy":
                    hit = f"{a.name} imported"
        elif isinstance(node, ast.ImportFrom):
            if node.module == "jax.numpy" or (
                    node.module == "jax"
                    and any(a.name == "numpy" for a in node.names)):
                hit = "jax.numpy imported"
        elif isinstance(node, ast.Attribute):
            # `import jax; jax.numpy.foo(...)` bypasses the import
            # check — catch the attribute access form too (exact match:
            # the inner `jax.numpy` node; avoids double-reporting the
            # enclosing `jax.numpy.foo` chain)
            if _attr_chain(node) == "jax.numpy":
                hit = "jax.numpy used"
        if hit:
            diags.append(make(
                "RL-JNP-SCOPE", f"{rel}:{node.lineno}",
                f"{hit} outside the device layers "
                f"({', '.join(_DEVICE_DIRS)}); host-side layers must "
                "stay device-agnostic"))


#: sanctioned mesh->host materialization points: "<rel>:<function>" ->
#: justification. The hook for new gather points — add an entry HERE
#: with a reason, never a bare suppression.
_MESH_HOST_ALLOWLIST = {
    "spark_rapids_tpu/parallel/mesh.py:mesh_gather":
        "THE sanctioned mesh->host gather point (routes through "
        "dispatch.host_fetch and counts meshGatherRows; the ICI "
        "exchange's per-shard live-count fetch comes through here)",
    "spark_rapids_tpu/parallel/mesh.py:MeshRuntime.configure":
        "np.array over a list of jax DEVICE HANDLES (building the Mesh "
        "topology array) — no device data is materialized",
    "spark_rapids_tpu/parallel/mesh.py:MeshRuntime.exchange_mesh":
        "np.array over jax device handles (submesh construction) — no "
        "device data is materialized",
}


def _check_mesh_host(rel: str, tree: ast.AST, diags: List[Diagnostic]):
    """RL-MESH-HOST: inside parallel/ and the shard-dispatch placement
    layer, host materialization of device data (np.asarray on arrays,
    jax.device_get, dispatch.host_fetch, .block_until_ready(),
    .addressable_shards reads) is forbidden outside the sanctioned
    gather points — the static guard for 'zero host round-trips
    between exchanges': shards land once at the scan and stay
    device-resident until a sanctioned gather."""
    if not (rel.startswith("spark_rapids_tpu/parallel/")
            or rel == "spark_rapids_tpu/runtime/placement.py"):
        return

    def flag(node, what: str, func: Optional[str]):
        if f"{rel}:{func}" in _MESH_HOST_ALLOWLIST:
            return
        diags.append(make(
            "RL-MESH-HOST", f"{rel}:{node.lineno}",
            f"{what} in mesh/shard-dispatch code"
            + (f" (function {func!r})" if func else " (module level)")
            + " — device shards must stay resident between exchanges; "
            "gather through parallel.mesh.mesh_gather or allowlist the "
            "function in _MESH_HOST_ALLOWLIST with a justification"))

    def walk(node, func: Optional[str]):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            # QUALIFIED name (Class.method / outer.inner): a bare-name
            # key would exempt EVERY function sharing the allowlisted
            # name anywhere in the file
            func = f"{func}.{node.name}" if func else node.name
        if isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain in ("np.asarray", "numpy.asarray", "asarray",
                         "np.array", "numpy.array"):
                # bare 'asarray' covers `from numpy import asarray`;
                # np.array() forces the same device->host copy
                flag(node, f"{chain}()", func)
            elif _host_sync_call(chain):
                flag(node, f"{chain}()", func)
        elif isinstance(node, ast.Attribute) \
                and node.attr == "addressable_shards":
            flag(node, ".addressable_shards read", func)
        for child in ast.iter_child_nodes(node):
            walk(child, func)

    walk(tree, None)


#: sanctioned raw device_put sites inside execs//ops/:
#: "<rel>:<qualified function>" -> justification. The hook for new
#: exceptions — add an entry HERE with a reason, never a bare
#: suppression. Table-sized landings are NEVER eligible: they belong
#: on the arbiter-accounted DeviceTable.from_host path.
_MEM_ACCOUNT_ALLOWLIST = {
    "spark_rapids_tpu/execs/mesh.py:reland":
        "re-lands a 4-element uint32 DIGEST scalar (gather-integrity "
        "checksum, ~16 bytes) onto device 0 — validation overhead, "
        "not a table landing; budget accounting at this size would be "
        "pure ledger noise",
}


def _check_mem_account(rel: str, tree: ast.AST,
                       diags: List[Diagnostic]):
    """RL-MEM-ACCOUNT: device landings in execs//ops/ must route
    through arbiter-accounted paths — a raw jax.device_put there lands
    bytes the MemoryArbiter never sees, and the hard budget contract
    (zero violations under scale_test --device-budget) silently
    breaks."""
    if not rel.startswith(("spark_rapids_tpu/execs/",
                           "spark_rapids_tpu/ops/")):
        return

    def flag(node, what: str, func):
        if f"{rel}:{func}" in _MEM_ACCOUNT_ALLOWLIST:
            return
        diags.append(make(
            "RL-MEM-ACCOUNT", f"{rel}:{node.lineno}",
            f"{what} in a device-landing layer"
            + (f" (function {func!r})" if func else " (module level)")
            + " — land through DeviceTable.from_host so the memory "
            "arbiter accounts the bytes, or allowlist the function in "
            "_MEM_ACCOUNT_ALLOWLIST with a justification"))

    def walk(node, func):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            func = f"{func}.{node.name}" if func else node.name
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            # `from jax import device_put` would make the call below
            # invisible to the chain matcher — ban the import form too
            for a in node.names:
                if a.name == "device_put":
                    flag(node, "importing jax.device_put", func)
        elif isinstance(node, ast.Call):
            chain = _attr_chain(node.func)
            if chain == "jax.device_put" \
                    or chain.endswith(".device_put") \
                    or chain == "device_put":
                flag(node, f"{chain}()", func)
        for child in ast.iter_child_nodes(node):
            walk(child, func)

    walk(tree, None)
