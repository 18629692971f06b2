"""Dispatch + compile: the client-side time of the first execution in the
process (trace, compile or load from the persistent cache, first sizing)."""


def read(run):
    return run["setup"].get("warmup_query_s")
