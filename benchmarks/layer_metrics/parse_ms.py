"""Front end (session.py `sql()`, sql/): the median over the window of the
seconds the engine took to lower a statement, parser to logical plan
(`phasesS.parseS` of its event record, host clock inside the program, taken
before the query's wall starts), in milliseconds."""

import statistics


def read(run):
    values = [q["record"]["phasesS"].get("parseS") for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    values = [v for v in values if v is not None]
    return statistics.median(values) * 1e3 if values else None
