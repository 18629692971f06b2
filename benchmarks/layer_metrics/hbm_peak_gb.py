"""Memory runtime: the fullest chip's peak of bytes in use once the window
has closed (`memory_stats()["peak_bytes_in_use"]`, which never falls): the
resident tables plus the most a landing or a query took beside them.
Where it is above `hbm_landing_peak_gb`, the window set it, and it less
`hbm_landed_gb` is what a query's temporaries take."""


def read(run):
    return run["memory_peak_bytes"] / 1e9
