"""Execs (execs/join.py `TpuJoinExec`): the median over the window of the
host seconds a query spent inside its join execs — making each build
side ready (range `srt.join.build`: the build child's batches, their
coalesce, the reads of the key range and uniqueness) and joining each
probe batch (range `srt.join.batch`: the enqueues and the read of the
output counts) — `phasesS.joinS` of the event record, in milliseconds.
A nested join's time lies inside its parent's build range and is counted
again there. A program that records no such phase gives nothing."""

import statistics


def read(run):
    values = [q["record"]["phasesS"].get("joinS") for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    values = [v for v in values if v is not None]
    return statistics.median(values) * 1e3 if values else None
