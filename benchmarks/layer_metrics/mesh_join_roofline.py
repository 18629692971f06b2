"""Kernels: the share of the MESH's HBM roofline the exchange and join
programs reach together. The least time the chips could take is the
bytes the traced queries must read (costs.scan_bytes: every row of the
columns the statement names, once) over chips x one chip's peak HBM
bandwidth (costs_mesh.mesh_least_seconds); it is divided by the device
time, per chip, of the `jit_mesh_exchange*` and `jit_join_*` programs in
them. Bound by bytes, from the shapes alone: the same whatever
implements the exchange. The filters, the grouped aggregate and the
top-k run in other programs, so it passes 100% only if the exchange and
the joins read less than the tables' named columns. 0 where none of
those programs ran in the traced queries."""

from benchmarks import costs_join, costs_mesh
from benchmarks.layer_metrics.exchange_device_ms_per_query import PROGRAM


def read(run):
    trace = run["trace"]
    traced = [q for q in run["queries"] if q.get("traced")]
    if not trace or not traced:
        return None
    device_s = costs_join.programs_seconds(trace, PROGRAM) \
        + costs_join.programs_seconds(trace, costs_join.JOIN_PROGRAMS)
    if not device_s:
        return 0.0
    least_s = costs_mesh.mesh_least_seconds(run, [q["id"] for q in traced])
    return 100.0 * least_s / device_s
