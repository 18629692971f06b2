"""Memory runtime: the device's peak of bytes in use read when the tables
have landed, before any query: the resident tables plus the landing's own
staging."""


def read(run):
    peak = run["setup"].get("landing_peak_bytes")
    return peak / 1e9 if peak else None
