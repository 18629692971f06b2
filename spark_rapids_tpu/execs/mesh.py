"""Mesh re-land boundary: where sharded residency ends inside a plan.

Mesh-native execution (parallel/mesh.py) lands scan shards per-device
and lets a consumer run on the resident shards where that keeps THE
CONTRACT: against the same plan on one chip, integers, counts,
decimals, strings, dates, min/max, the number of rows and their order
are bit-identical; a DOUBLE sum is merged from the partial sums of
(batch, shard, slice) in that fixed order, so one mesh shape and one
batch cut give the same bits run after run, and it is judged as every
DOUBLE sum of this engine is, against a float64 reference under a
stated limit (the single-chip answer already depends on how the rows
are cut into batches and slices). tests/test_mesh_aggregate.py pins
both halves on the CPU test mesh, and the benchmark's cell `q1-mesh4`
holds the four-chip host to them on every run.

Three kinds of consumer take sharded input. The narrow pipeline
(filter / project / masked ops) and the ICI shuffle exchange are
elementwise or pure data movement: GSPMD partitions them and their
results are bitwise independent of the layout. The hash aggregate runs
its fast kernel on each chip's own rows and exchanges only the shards'
partial groups (execs/aggregate.py ``_shards_of`` says which batches:
a fast layout with a small group domain, no position-dependent
expression, no nested column); a batch it refuses it re-lands itself,
through :func:`reland`. The coalesce hands a sharded batch on where it
concatenates nothing and re-lands what it concatenates. Every other
wide consumer (sort, join, window, ...) is NOT layout-independent and
has no merge decomposition here, so it takes its input through a
:class:`TpuMeshRelandExec` boundary inserted at conversion time: one
device-side gather (ICI on a real pod — the host is never touched,
pinned by RL-MESH-HOST and the meshHostUploads counter) that re-lands
the shards into the single-device layout the wide kernel compiles
against.

Post-exchange inputs are already per-device (the all-to-all emits each
partition on its owner device), so the boundary is a no-op there — the
distributed path through scan -> narrow ops -> ICI exchange ->
per-partition wide ops pays zero re-lands and zero host transfers.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from spark_rapids_tpu.columnar import DeviceTable
from spark_rapids_tpu.dispatch import tpu_jit
from spark_rapids_tpu.execs.base import (
    DeviceToHost,
    HostToDevice,
    InputAdapter,
    TpuExec,
)


def _table_digest(table: DeviceTable):
    """Device-side (row count + checksum) of one table — the TPAK-v2
    validation pair for the re-land gather: an order-independent uint32
    word-sum over every column's data and validity words, the live
    mask, and the row-count scalar. The gather (DeviceTable.unsharded)
    is pure data movement, so the digest of the landed copy must equal
    the digest of the sharded source EXACTLY; integer summation makes
    the GSPMD-partitioned evaluation bitwise equal to the single-device
    one, so one cached kernel (epoch-guarded in parallel/exchange.py —
    a device-loss reinit mid-build must not re-seed the cleared cache)
    serves both sides."""
    from spark_rapids_tpu.parallel.exchange import digest_kernel
    from spark_rapids_tpu.parallel.mesh import wordsum_u32

    key = ("reland-digest", table.schema_key()[0], table.capacity,
           table.live is not None)

    def build():
        def digest(datas, valids, live, nrows):
            acc = nrows.astype(jnp.uint32)
            for d in datas:
                acc = acc + wordsum_u32(d)
            for v in valids:
                acc = acc + wordsum_u32(v)
            if live is not None:
                acc = acc + wordsum_u32(live)
            return acc
        return tpu_jit(digest, name="reland_digest")

    fn = digest_kernel(key, build)
    return fn(tuple(c.data for c in table.columns),
              tuple(c.validity for c in table.columns),
              table.live, table.nrows_dev)


def _taint_landed(table: DeviceTable) -> DeviceTable:
    """Damage the LANDED copy the way an in-flight gather corruption
    would (validity of slot 0 flips: a row silently becomes null/non-
    null — exactly the class of wrong-results bug the digest exists to
    catch). Driven by the ``mesh.gather`` corrupt kind through a
    sentinel byte: the sharded source is untouched, so the bounded
    re-gather converges."""
    c0 = table.columns[0]
    flipped = c0.with_arrays(
        c0.data, c0.validity.at[0].set(~c0.validity[0]))
    out = DeviceTable(table.names, (flipped,) + tuple(table.columns[1:]),
                      table.nrows_dev, table.capacity, live=table.live)
    out._nrows_host = table._nrows_host
    return out


class TpuMeshRelandExec(TpuExec):
    """Schema-preserving residency boundary: re-lands physically
    sharded batches into the single-device layout (DeviceTable.
    unsharded) so the parent's kernels bitwise-match single-chip
    execution. Transparent to both batch protocols — masked batches
    stay masked (their live mask re-lands with the columns)."""

    def __init__(self, child: TpuExec):
        super().__init__()
        self.children = (child,)
        # mirror the child's protocol so mask-aware parents keep
        # consuming masked batches through the boundary
        self.produces_masked = bool(getattr(child, "produces_masked",
                                            False))

    def output_schema(self):
        return self.children[0].output_schema()

    def execute(self):
        for b in self.children[0].execute():
            yield reland(self, b)

    def execute_masked(self):
        for b in self.children[0].execute_masked():
            yield reland(self, b)

    def describe(self):
        return "MeshReland"


def reland(node: TpuExec, table: DeviceTable) -> DeviceTable:
    """``table`` in the single-device layout: the verified gather of a
    physically sharded table, its rows and re-gathers counted on
    ``node``. THE re-land of every consumer: the boundary's own
    batches, the aggregate's batches that ``_shards_of`` refuses, the
    coalesce's batches that it concatenates. The gather is the range
    ``srt.mesh.reland`` and the query's ``phasesS.relandS``."""
    # only PHYSICAL gathers count: unsharded() also returns a new
    # object when it merely drops a shard_spec descriptor from
    # single-device buffers (1-device mesh) — no data moved there
    if not (table.columns and table.physically_sharded()):
        return table if table.shard_spec is None else table.unsharded()
    from spark_rapids_tpu.dispatch import phase_span
    with phase_span("relandS", "reland", "mesh"):
        from spark_rapids_tpu.runtime.faults import fault_point
        from spark_rapids_tpu.parallel import mesh as PM
        from spark_rapids_tpu.parallel.mesh import MESH_SCOPE, mesh_gather
        node.add_metric("meshRelandRows", table.capacity)
        MESH_SCOPE.add("meshRelandRows", table.capacity)
        # crash / device_lost / slow fire here, BEFORE the gather (the
        # ladder's mesh.gather injection site); corrupt is consumed by
        # the sentinel inside the verified loop below
        fault_point("mesh.gather")
        if not PM.GATHER_VERIFY:
            return table.unsharded()
        # TPAK-v2 gather integrity: (row count + checksum) of the
        # sharded source vs the landed copy, compared in ONE tiny host
        # fetch through the sanctioned gather point. A mismatch is a
        # corrupted shard CAUGHT — re-land from the still-intact
        # sharded source instead of feeding the wide kernel above this
        # boundary silently wrong buffers.
        from spark_rapids_tpu.errors import MeshGatherError
        # the source digest evaluates GSPMD-partitioned on the shards
        # (replicated output); re-land the scalar once so the compare
        # pair below shares one committed device — device-to-device,
        # like the gather it validates
        pre = jax.device_put(_table_digest(table), jax.devices()[0])
        retries = 0
        while True:
            out = table.unsharded()
            if fault_point("mesh.gather", data=b"\x00") != b"\x00":
                out = _taint_landed(out)  # injected in-flight corruption
            post = _table_digest(out)
            # rows=0: a digest-pair compare is validation overhead,
            # not gathered table data — meshGatherRows must keep
            # meaning 'elements gathered'
            pair = mesh_gather(jax.lax.bitcast_convert_type(
                jnp.stack([pre, post]), jnp.int32), rows=0)
            if int(pair[0]) == int(pair[1]):
                return out
            MESH_SCOPE.add("gatherChecksFailed", 1)
            node.add_metric("gatherChecksFailed", 1)
            if retries >= PM.MAX_SHARD_RETRIES:
                raise MeshGatherError(
                    f"mesh re-land gather failed its row-count/checksum "
                    f"validation {retries + 1} times (source digest "
                    f"{int(pair[0])} vs landed {int(pair[1])})")
            retries += 1
            MESH_SCOPE.add("shardRetries", 1)
            node.add_metric("shardRetries", 1)


#: consumers that accept physically sharded input: elementwise /
#: data-movement execs whose results are bitwise layout-independent
#: (GSPMD partitions them across the resident shards), the ICI
#: exchange (it re-shards explicitly via shard_put), the hash aggregate
#: and the coalesce (each re-lands itself what it cannot take sharded:
#: reland), and the re-land boundary itself. Everything else sees the
#: single-device layout.
def _shard_safe_consumers() -> tuple:
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.execs.basic import (
        TpuCoalesceExec,
        TpuFilterExec,
        TpuProjectExec,
    )
    from spark_rapids_tpu.execs.exchange import TpuShuffleExchangeExec
    return (TpuFilterExec, TpuProjectExec, TpuShuffleExchangeExec,
            TpuHashAggregateExec, TpuCoalesceExec, TpuMeshRelandExec)


def insert_mesh_relands(executable):
    """Conversion-time pass (applied by apply_overrides when mesh-
    native execution is on): wrap the TpuExec children of every
    non-shard-safe consumer in a re-land boundary, and stamp every scan
    with the mesh generation the boundaries were planned against
    (``_mesh_scan_gen`` — execs/basic._scan_sharding). Sharded
    placement is therefore BOUND to the converted tree: an unstamped
    tree (converted with the mesh off) never lands sharded batches even
    if a concurrent session flips the process mesh on mid-query — it
    has no boundaries, so sharded input would let GSPMD repartition a
    wide float kernel and break bit-identity. The boundary is a no-op
    on unsharded batches, so liberal insertion is correct — the
    whitelist only determines where sharded residency may FLOW, and
    default-deny means a new exec is bit-identical by construction
    until it is proven layout-independent."""
    from spark_rapids_tpu.execs.basic import TpuFileScanExec, TpuScanExec
    from spark_rapids_tpu.parallel.mesh import MESH

    safe = _shard_safe_consumers()
    gen = MESH.generation()

    def rec(node):
        if isinstance(node, (TpuScanExec, TpuFileScanExec)):
            node._mesh_scan_gen = gen
        if isinstance(node, DeviceToHost):
            # the root/mid-plan transition gathers to host anyway (the
            # sanctioned materialization point) — sharded input is fine
            rec(node.tpu_exec)
            return
        if isinstance(node, HostToDevice):
            rec(node.cpu_node)
            return
        if isinstance(node, InputAdapter):
            rec(node.source)
            return
        scan_node = getattr(node, "scan_node", None)
        if scan_node is not None:
            rec(scan_node)
        children = tuple(getattr(node, "children", ()) or ())
        if not children:
            return
        if isinstance(node, TpuExec) and not isinstance(node, safe):
            node.children = tuple(
                TpuMeshRelandExec(c)
                if isinstance(c, TpuExec)
                and not isinstance(c, TpuMeshRelandExec) else c
                for c in node.children)
            children = node.children
        for c in children:
            rec(c)

    rec(executable)
    return executable
