"""Overrides / placement: operators that fell back to the CPU plus
operators demoted, summed over the window's queries (0 expected)."""


def read(run):
    records = [q["record"] for q in run["queries"] if "record" in q]
    if not records:
        return None
    return sum(len(r.get("fallbacks") or []) + len(r.get("demotions") or {})
               for r in records)
