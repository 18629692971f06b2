"""Kernels: device time of the join execs' own programs, the ones named
`jit_join_*` (`tpu_jit(..., name="join_<what>")` in execs/join.py: the
build side's statistics and direct-address table, the probes, the
gathers, or the sorted body's rank, expand and gather), per query
traced, in milliseconds. 0 where no such program ran in the traced
queries (a program without a join, or one that names them otherwise)."""

from benchmarks import costs_join


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"]:
        return None
    return 1e3 * costs_join.programs_seconds(
        trace, costs_join.JOIN_PROGRAMS) / trace["queries"]
