"""Kernels: the share of the MESH's HBM roofline the sharded aggregate
reaches. The least time the chips could take is the bytes the traced
queries must read (costs.scan_bytes) over chips x one chip's peak HBM
bandwidth (costs_mesh.mesh_least_seconds); it is divided by the device
time of `jit_agg_fast_mesh` in them, per chip. Bound by bytes. Unlike
`scan_roofline`, which divides by one chip's bandwidth whatever the cell
holds, this reads against all the chips the trace found. 0 where the
program never ran in the traced queries."""

from benchmarks import costs_coalesce, costs_mesh
from benchmarks.layer_metrics.mesh_agg_device_ms_per_query import PROGRAM


def read(run):
    trace = run["trace"]
    traced = [q for q in run["queries"] if q.get("traced")]
    if not trace or not traced:
        return None
    least_s = costs_mesh.mesh_least_seconds(run, [q["id"] for q in traced])
    device_s = costs_coalesce.program_seconds(trace, PROGRAM)
    if not device_s:
        return 0.0
    return 100.0 * least_s / device_s
