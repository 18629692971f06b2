"""Columnar / transfer: the median over the window of the seconds a query
blocked for its root result's packed buffer (`PendingHostTable.resolve`,
the device-to-host round trip that also waits for the last program:
`phasesS.fetchWaitS` of the event record), in milliseconds."""

import statistics


def read(run):
    values = [q["record"]["phasesS"].get("fetchWaitS") for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    values = [v for v in values if v is not None]
    return statistics.median(values) * 1e3 if values else None
