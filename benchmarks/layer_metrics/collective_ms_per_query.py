"""Mesh: device time of the operations that move data between chips
(costs_mesh.COLLECTIVE_OPCODES: all-gather, all-reduce, all-to-all,
collective-permute, reduce-scatter and their -start/-done halves), self
time per chip and per query traced, in milliseconds. 0.0 where none ran:
one chip, or a plan that exchanges nothing."""

from benchmarks import costs_mesh


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"]:
        return None
    return 1e3 * costs_mesh.collective_seconds(trace) / trace["queries"]
