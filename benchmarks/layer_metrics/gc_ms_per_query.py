"""Runtime (Python host): the mean over the window of the host seconds a
query's thread spent in Python's collector (`phasesS.gcS` of the event
record, by obs/spans.py's `gc.callbacks` hook), in milliseconds. The mean,
so that one generation-2 pause in a window shows."""


def read(run):
    values = [q["record"]["phasesS"].get("gcS") for q in run["queries"]
              if "record" in q and q["record"].get("phasesS")]
    if not values or None in values:
        return None
    return 1e3 * sum(values) / len(values)
