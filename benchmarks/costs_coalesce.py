"""Bytes the coalesce's copy has to move, from the shapes alone: the
yardstick of `coalesce_roofline`. The same whatever implements the copy.

A coalesce that honours its goal reads every row of the columns the
statement names once and writes it once: twice `costs.scan_bytes`
(rows x landed width; validity is not counted there either). Padding,
validity bytes and unread columns a coalesce may also carry are work
beyond the least, not part of it."""

from __future__ import annotations

from benchmarks import costs

#: each byte is read once and written once
PASSES = 2


def copy_bytes(tables: dict, text: str) -> int:
    """The least bytes a copy of what `text` reads of `tables` moves."""
    return PASSES * costs.scan_bytes(tables, text)


def copy_bytes_of(run: dict, query_id: str) -> int:
    """The same for a statement of a run: `run.py` keeps
    `costs.scan_bytes` of every statement it sent."""
    return PASSES * run["scan_bytes_per_query"][query_id]


def program_seconds(trace: dict, program: str) -> float:
    """Device seconds of one named program in a reduced trace: the self
    times of its operations (`trace_reduce` names each `<program> <op>`)."""
    return sum(seconds for name, seconds in trace["device_ops"]
               if name.split(" ", 1)[0] == program)
