"""Kernels: device time of the programs that group and order a join's
output — the sorted-path aggregate `jit_agg_sorted` (execs/aggregate.py)
and the sorts `jit_sort_run` and `jit_sort_topk` (execs/sort.py: the
ORDER BY ... LIMIT's) — per query traced, in milliseconds. 0 where none
of them ran in the traced queries (a few-group aggregate takes
`jit_agg_fast*` and is read by `device_busy_ms_per_query` alone)."""

from benchmarks import costs_join


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"]:
        return None
    return 1e3 * costs_join.programs_seconds(
        trace, costs_join.SORT_AGG_PROGRAMS) / trace["queries"]
