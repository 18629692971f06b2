"""Dispatch + compile: what compiled inside the measured window. Queries
whose event record reports `compileMs > 0`, plus the executables JAX built
or loaded from its persistent cache there (its
`backend_compile_duration` events). 0 expected."""


def read(run):
    records = [q["record"] for q in run["queries"] if "record" in q]
    return (sum(1 for r in records if (r.get("compileMs") or 0) > 0)
            + run["backend_compiles_in_window"])
