"""Execs: the median over the window of the host seconds a query spent
blocked in mid-pipeline device-to-host syncs (`dispatch.host_fetch`:
`phasesS.syncWaitS` of the event record), in milliseconds."""

import statistics


def read(run):
    values = [q["record"]["phasesS"].get("syncWaitS") for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    values = [v for v in values if v is not None]
    return statistics.median(values) * 1e3 if values else None
