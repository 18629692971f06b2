"""Execs: device dispatches per query, the mean over the window (the
engine's own count, `dispatches` of its event record)."""


def read(run):
    counts = [q["record"]["dispatches"] for q in run["queries"]
              if "record" in q and q["record"].get("dispatches") is not None]
    return sum(counts) / len(counts) if counts else None
