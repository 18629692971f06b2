"""Kernels: device time of the ICI exchange's programs, the ones named
`jit_mesh_exchange*` (`tpu_jit(..., name="mesh_exchange")` in
parallel/exchange.py: each chip's murmur3 partition of its rows and the
all-to-all that delivers them), per chip and per query traced, in
milliseconds (`trace_reduce` divides an operation's time by the chips it
found). 0 where no such program ran in the traced queries."""

from benchmarks import costs_join

PROGRAM = "jit_mesh_exchange"


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"]:
        return None
    return 1e3 * costs_join.programs_seconds(trace, PROGRAM) \
        / trace["queries"]
