"""Shared plumbing for the per-rule lint modules.

Every rule module under ``lint/rules/`` walks the same parsed ASTs with
the same small vocabulary: repo-relative paths, dotted attribute
chains, and THE host-synchronization call set (the device-residency
rules walk different scopes but must agree on what a host sync IS — a
spelling added to one and not the other would silently diverge).
"""

from __future__ import annotations

import ast
import os
from typing import Optional


def _repo_root(repo_root: Optional[str]) -> str:
    if repo_root:
        return repo_root
    import spark_rapids_tpu
    return os.path.dirname(os.path.dirname(
        os.path.abspath(spark_rapids_tpu.__file__)))


def _iter_source_files(root: str):
    pkg = os.path.join(root, "spark_rapids_tpu")
    for dirpath, dirnames, filenames in os.walk(pkg):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for f in sorted(filenames):
            if f.endswith(".py"):
                yield os.path.join(dirpath, f)
    p = os.path.join(root, "scale_test.py")
    if os.path.exists(p):
        yield p


def _rel(root: str, path: str) -> str:
    return os.path.relpath(path, root)


def _attr_chain(node: ast.AST) -> str:
    """Dotted name of an attribute/name chain ('' when not a plain chain)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _host_sync_call(chain: str) -> bool:
    """THE host-synchronization call set of the device-residency
    rules: what a host sync IS, spelled once."""
    return ((chain.endswith("device_get") and chain.startswith(
                ("jax.", "jax")))
            or chain == "host_fetch" or chain.endswith(".host_fetch")
            or chain.endswith(".block_until_ready"))


def _is_device_expr(node: ast.AST) -> bool:
    """Is this expression PROVABLY a device value — a jnp./jax. call not
    already funneled through the sanctioned host_fetch wrapper (whose
    RESULT is host data, however device-y its argument)?"""
    if isinstance(node, ast.Call):
        chain = _attr_chain(node.func)
        if chain == "host_fetch" or chain.endswith(".host_fetch"):
            return False
        if chain.startswith(("jnp.", "jax.")):
            return True
    for child in ast.iter_child_nodes(node):
        if _is_device_expr(child):
            return True
    return False
