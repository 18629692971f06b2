"""Columnar / transfer: host tables to device-resident, timed by the
harness from the call to `block_until_ready` (encoding of strings, the
split of doubles, padding to the capacity bucket and the upload)."""


def read(run):
    return run["setup"].get("landing_s")
