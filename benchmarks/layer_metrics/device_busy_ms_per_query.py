"""Device: the union of the device's operation intervals in the traced
part of the window, per query traced, in milliseconds."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["queries"] or not trace["busy_s"]:
        return None
    return 1e3 * trace["busy_s"] / trace["queries"]
