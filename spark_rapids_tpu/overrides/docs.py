"""Supported-operators documentation generator (reference:
TypeChecks.scala's supported_ops.md generation — `TypeChecks.main` emits
the per-operator type-support matrix the reference docs ship; SURVEY.md
§2.2 #5). The matrix is derived from the SAME registries the tagging
layer consults (_EXPR_SIGS / _EXEC_RULES), so docs cannot drift from the
actual fallback behavior."""

from __future__ import annotations

from typing import List

from spark_rapids_tpu import types as T
from spark_rapids_tpu.io.common import FileScanNode

#: probe instance per doc column — a sig supports the column iff it
#: supports this representative type
_TYPE_COLUMNS = [
    ("BOOLEAN", T.BOOLEAN),
    ("BYTE", T.BYTE),
    ("SHORT", T.SHORT),
    ("INT", T.INT),
    ("LONG", T.LONG),
    ("FLOAT", T.FLOAT),
    ("DOUBLE", T.DOUBLE),
    ("DATE", T.DATE),
    ("TIMESTAMP", T.TIMESTAMP),
    ("STRING", T.STRING),
    ("DECIMAL", T.DecimalType(18, 2)),
    ("DECIMAL128", T.DecimalType(38, 2)),
    ("ARRAY", T.ArrayType(T.LONG)),
    ("MAP", T.MapType(key_type=T.LONG, value_type=T.DOUBLE)),
    ("STRUCT", T.StructType([T.StructField("f", T.LONG)])),
]

#: exec node -> TypeSig used by its tag function (kept in sync with the
#: _tag_* implementations in rules.py; scan/project accept nested)
_EXEC_SIGS = {}


def register_exec_sig(node_cls, sig) -> None:
    _EXEC_SIGS[node_cls] = sig


def _matrix_row(name: str, sig, notes: str = "") -> str:
    cells = []
    for _, probe in _TYPE_COLUMNS:
        cells.append("S" if sig.supports(probe) else "NS")
    return "| " + name + " | " + " | ".join(cells) + " | " + notes + " |"


def generate_supported_ops() -> str:
    """supported_ops.md content: one row per exec and per expression with
    an S/NS cell per type column."""
    import importlib

    from spark_rapids_tpu.overrides import rules as R
    from spark_rapids_tpu.overrides.typesig import COMMON_128

    # file-format / Delta scan rules register at THEIR package's import
    # time (register_file_scan) so the core engine never hard-requires
    # pyarrow; pull them in here so the matrix is complete and identical
    # no matter what the process imported first
    for _mod in ("spark_rapids_tpu.io", "spark_rapids_tpu.delta",
                 "spark_rapids_tpu.iceberg"):
        try:
            importlib.import_module(_mod)
        except ImportError:
            pass
    R._build_expr_sigs()

    header = ("| Operator | " +
              " | ".join(n for n, _ in _TYPE_COLUMNS) + " | Notes |")
    sep = "|" + "---|" * (len(_TYPE_COLUMNS) + 2)

    lines: List[str] = [
        "# Supported operators and types",
        "",
        "Generated from the overrides registries "
        "(`spark_rapids_tpu.overrides.docs.generate_supported_ops`) — the "
        "same `TypeSig` objects drive tag-time CPU fallback, so this "
        "matrix cannot drift from runtime behavior. `S` = runs on TPU for "
        "that type; `NS` = the operator (or the column of that type) "
        "falls back to the CPU path. Every operator also has a kill "
        "switch conf `spark.rapids.sql.exec.<Name>` / "
        "`spark.rapids.sql.expression.<Name>` (see CONFIGS.md).",
        "",
        "## Execs",
        "",
        header,
        sep,
    ]
    for node_cls, rule in sorted(R._EXEC_RULES.items(),
                                 key=lambda kv: kv[0].__name__):
        # unregistered execs doc as COMMON_128: the _check_output_schema
        # default their tag functions apply (storage-level DECIMAL128
        # flows through; per-construct carve-outs — e.g. avg over a
        # dec128 input — still tag fallback at the expression level)
        sig = _EXEC_SIGS.get(node_cls, COMMON_128)
        # a file scan (io/common.py FileScanNode) is narrowed by column
        # pruning to what the plan reads
        note = ("decodes and uploads the referenced columns only"
                if issubclass(node_cls, FileScanNode) else "")
        lines.append(_matrix_row(node_cls.__name__, sig, note))
    lines += [
        "",
        "## Expressions",
        "",
        header,
        sep,
    ]
    for cls, sig in sorted(R._EXPR_SIGS.items(),
                           key=lambda kv: kv[0].__name__):
        note = ""
        if getattr(cls, "device_supported", True) is False:
            note = "CPU-path expression (no device kernel)"
        # per-PARAM rows where input checks exist (ExprChecks analog —
        # `Acos / param 0 / STRING` reads NS even though the result row
        # is always DOUBLE)
        from spark_rapids_tpu.overrides.typesig import lookup_mro
        checks = lookup_mro(R._EXPR_CHECKS, cls)
        if checks is None:
            lines.append(_matrix_row(cls.__name__, sig, note))
            continue
        lines.append(_matrix_row(f"{cls.__name__} / result", sig, note))
        for label, psig in checks.doc_param_rows():
            lines.append(_matrix_row(f"{cls.__name__} / {label}", psig))
    lines.append("")
    return "\n".join(lines)
