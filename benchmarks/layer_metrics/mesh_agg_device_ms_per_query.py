"""Kernels: device time of the aggregate that runs on the resident
shards, `jit_agg_fast_mesh` (`tpu_jit(..., name="agg_fast_mesh")` in
execs/aggregate.py), per chip and per query traced, in milliseconds
(`trace_reduce` divides an operation's time by the chips it found). 0
where it never ran: the batches were gathered to one chip first, or the
program has no program of that name."""

from benchmarks import costs_coalesce

PROGRAM = "jit_agg_fast_mesh"


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"]:
        return None
    return 1e3 * costs_coalesce.program_seconds(trace, PROGRAM) \
        / trace["queries"]
