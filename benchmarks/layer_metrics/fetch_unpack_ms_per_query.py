"""Columnar / transfer: the median over the window of the host seconds a
query spent unpacking and decoding its fetched result buffer
(`phasesS.fetchUnpackS` of the event record), in milliseconds."""

import statistics


def read(run):
    values = [q["record"]["phasesS"].get("fetchUnpackS") for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    values = [v for v in values if v is not None]
    return statistics.median(values) * 1e3 if values else None
