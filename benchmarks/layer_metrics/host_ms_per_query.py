"""Execs: the engine's host time a query did not spend waiting for the
device — its wall less the blocking syncs and the blocking result fetch
(`wallS - phasesS.syncWaitS - phasesS.fetchWaitS` of the event record),
the median over the window in milliseconds. It bounds the device idle the
engine's host code can cause."""

import statistics


def read(run):
    values = []
    for q in run["queries"]:
        record = q.get("record") or {}
        phases = record.get("phasesS") or {}
        if record.get("wallS") is None or "syncWaitS" not in phases \
                or "fetchWaitS" not in phases:
            continue
        values.append(record["wallS"] - phases["syncWaitS"]
                      - phases["fetchWaitS"])
    return statistics.median(values) * 1e3 if values else None
