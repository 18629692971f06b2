"""Execs (execs/basic.py `TpuCoalesceExec`): the median over the window
of the host seconds a query spent inside its coalesce execs' multi-batch
flushes — the dictionary checks and the enqueue of the copy, the range
`srt.coalesce.flush` (`phasesS.coalesceS` of the event record) — in
milliseconds. 0 where every coalesce passed its batches on; a program
that records no such phase gives nothing."""

import statistics


def read(run):
    values = [q["record"]["phasesS"].get("coalesceS") for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    values = [v for v in values if v is not None]
    return statistics.median(values) * 1e3 if values else None
