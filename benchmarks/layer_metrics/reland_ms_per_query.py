"""Mesh (execs/mesh.py `TpuMeshRelandExec`): the median over the window
of the host seconds a query spent re-landing sharded batches on one chip
— the gather's enqueue, its two digest programs and the fetch that
compares them, the range `srt.mesh.reland` (`phasesS.relandS` of the
event record) — in milliseconds. 0 where no batch was gathered: every
consumer ran on the resident shards. A program that records no such
phase gives nothing."""

import statistics


def read(run):
    values = [q["record"]["phasesS"].get("relandS") for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    values = [v for v in values if v is not None]
    return statistics.median(values) * 1e3 if values else None
