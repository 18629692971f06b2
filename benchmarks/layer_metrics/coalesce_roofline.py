"""Kernels: the share of the HBM roofline the coalesce's copy reaches.
The least time the chip could take is the bytes a copy of what the
statement reads has to move (costs_coalesce.copy_bytes: each byte read
once and written once) over the chip's peak HBM bandwidth, summed over
the traced queries (the engine keeps no coalesced batch from query to
query, so the copy runs in each); it is divided by the device time of
`jit_coalesce` in them. Bound by bytes. It passes 100% only if the copy
is skipped; 0 where the program never ran in the traced queries."""

from benchmarks import costs_coalesce
from benchmarks.layer_metrics.coalesce_device_ms_per_query import PROGRAM


def read(run):
    trace = run["trace"]
    traced = [q for q in run["queries"] if q.get("traced")]
    if not trace or not traced:
        return None
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    device_s = costs_coalesce.program_seconds(trace, PROGRAM)
    if not device_s:
        return 0.0
    needed = sum(costs_coalesce.copy_bytes_of(run, q["id"]) for q in traced)
    least_s = needed / run["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
