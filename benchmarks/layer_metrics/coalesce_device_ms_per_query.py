"""Kernels: device time of the coalesce exec's own program, `jit_coalesce`
(`tpu_jit(..., name="coalesce")` in columnar/table.py `concat_device`),
per query traced, in milliseconds. 0 where it never ran: every coalesce
passed its batches on, or the program has no program of that name."""

from benchmarks import costs_coalesce

PROGRAM = "jit_coalesce"


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"]:
        return None
    return 1e3 * costs_coalesce.program_seconds(trace, PROGRAM) \
        / trace["queries"]
