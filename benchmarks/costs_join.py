"""Bytes a join statement has to read, from the shapes alone, and the
device time of the programs that do its work: the yardsticks of
`join_roofline`, `join_device_ms_per_query` and
`sort_agg_device_ms_per_query`. The same whatever implements the join.

The least a chip can do for a statement that joins its tables is to read
every row of the columns the statement names once (`costs.scan_bytes`:
rows x landed width of every table the text names; validity is not
counted). Build tables, row-id tables, gathered copies and sort passes
are work beyond the least, not part of it."""

from __future__ import annotations

#: every program of execs/join.py is named `join_<what>` by
#: `tpu_jit(fn, name=...)`, so `jit_join_<what>` on the device timeline
JOIN_PROGRAMS = "jit_join_"
#: the grouped (sorted-path) aggregate of execs/aggregate.py and the two
#: sorts of execs/sort.py (a whole sort, and TakeOrderedAndProject's)
SORT_AGG_PROGRAMS = ("jit_agg_sorted", "jit_sort_run", "jit_sort_topk")


def join_bytes_of(run: dict, query_id: str) -> int:
    """The least bytes a statement of a run reads: `run.py` keeps
    `costs.scan_bytes` of every statement it sent."""
    return run["scan_bytes_per_query"][query_id]


def programs_seconds(trace: dict, programs) -> float:
    """Device seconds, in a reduced trace, of the programs whose name is
    one of `programs` or, for a string, starts with it: the self times of
    their operations (`trace_reduce` names each `<program> <op>`)."""
    if isinstance(programs, str):
        mine = lambda name: name.startswith(programs)
    else:
        mine = lambda name: name in programs
    return sum(seconds for name, seconds in trace["device_ops"]
               if mine(name.split(" ", 1)[0]))
