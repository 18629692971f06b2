"""Kernels: the share of the HBM roofline the join programs reach. The
least time the chip could take is the bytes the statement has to read
(costs_join.join_bytes_of: every row of the columns it names, once) over
the chip's peak HBM bandwidth, summed over the traced queries; it is
divided by the device time of the `jit_join_*` programs in them. Bound by
bytes. The filters, the grouped aggregate and the sort run in other
programs, so the share reads high against the whole statement: it passes
100% only if the joins read less than the tables' named columns.
0 where no join program ran in the traced queries."""

from benchmarks import costs_join


def read(run):
    trace = run["trace"]
    traced = [q for q in run["queries"] if q.get("traced")]
    if not trace or not traced:
        return None
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    device_s = costs_join.programs_seconds(trace, costs_join.JOIN_PROGRAMS)
    if not device_s:
        return 0.0
    needed = sum(costs_join.join_bytes_of(run, q["id"]) for q in traced)
    least_s = needed / run["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s
