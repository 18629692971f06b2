"""Kernels (XLA programs): the share of the HBM roofline the
queries' device time reaches. The least time the chip could take is the
bytes the query must read (costs.scan_bytes: rows x landed width of the
columns its text names) over the chip's peak HBM bandwidth; it is divided
by the device-busy time per traced query. Bound by bytes: these queries
do a few operations per byte read."""


def read(run):
    trace = run["trace"]
    if not trace or not trace["busy_s"]:
        return None
    kind = run["device"]["kind"]
    if kind not in run["peaks"]:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    traced = [q for q in run["queries"] if q.get("traced")]
    if not traced:
        return None
    needed = sum(run["scan_bytes_per_query"][q["id"]] for q in traced)
    least_s = needed / run["peaks"][kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
