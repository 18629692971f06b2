"""Front end (session.py, sql/, plan/): the median over the window of the
engine's own plan phase per query (`phasesS.planS` of its event record,
host clock inside the program), in milliseconds."""

import statistics


def read(run):
    plans = [q["record"]["phasesS"].get("planS") for q in run["queries"]
             if "record" in q and q["record"].get("phasesS")]
    plans = [p for p in plans if p is not None]
    return statistics.median(plans) * 1e3 if plans else None
