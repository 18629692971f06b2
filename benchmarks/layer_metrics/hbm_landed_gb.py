"""Columnar / transfer: the device's bytes in use when the tables have
landed and nothing runs: what the resident tables hold of the chip."""


def read(run):
    in_use = run["setup"].get("landed_in_use_bytes")
    return in_use / 1e9 if in_use else None
