"""TPU hash aggregate (reference: GpuHashAggregateExec / GpuMergeAggregate-
Iterator, GpuAggregateExec.scala — SURVEY.md §2.3).

TPU-first design, two device strategies (neither is a hash table —
pointer-chasing is hostile to the VPU):

FAST PATH (dictionary-code grouping, no sort): when every grouping key is a
dictionary-encoded string or a boolean, the key domain is known on the host
(dict sizes), so each row's group id is a mixed-radix combination of its
codes — ``gid = sum(code_i * stride_i)`` with one extra slot per key for
null. Aggregation is then direct ``segment_*`` reductions with
``num_segments = padded domain product`` (small!), group compaction is a
cumsum scatter, and the live group count stays on device — no sort, no
host sync, no capacity-sized outputs. f64 sums run through the exact-
decomposition blocked f32 path, and the per-group row counts through a
one-hot contraction as well, up to segsum.MATMUL_MAX_SEGMENTS groups
(ops/segsum.py).

SORT-SEGMENT PATH (general keys): lexicographic multi-operand ``lax.sort``
over (live, key-validity, key-data...) with a row-index payload; segment
boundaries -> dense group ids via cumsum; ``jax.ops.segment_*`` reductions.

Input fusion: Project/Filter chains feeding the aggregate are substituted
into the kernel (execs/fuse.py) — predicates become weight masks evaluated
in the same XLA program, so a filter+project+aggregate pipeline is ONE
device dispatch with no intermediate materialization.

Multi-batch inputs STREAM (GpuMergeAggregateIterator analog): each batch
aggregates to a spillable partial as it arrives, the host running ahead of
the device by no more input than the memory arbiter's budget has room for
(_bound_run_ahead), and a merge aggregation + finalize projection combines
the partials (see _merge_plan).

A batch of several AGG_SLICE-row slices (what a coalesce under the default
batchSizeBytes builds) is such a stream INSIDE one program: the fast
kernel compiled at one slice runs in a loop over the batch's rows and the
slices' partial tables merge like any other partials (_slices_of,
_over_slices).

Consecutive batches that lay RESIDENT when pulled (a cached table's,
passing their coalesce) are a stream whose members wait for nothing: up to
AGG_GROUP of them with one trace key and equal key dictionaries are taken
by ONE program, the fast kernel once a member on that member's own
operands (no batch is stacked or copied), their partial groups compacted
into one partial table: what an enqueue costs the host is the program and
its results, not its operands. A batch the pull built (an upload, a
coalesce flush, an upstream exec's output), a sliced batch and the sorted
path are groups of one: the batch's own program, at once (_pulled,
_member, _over_members).

A batch that lies row-sharded over the device mesh (a mesh-native scan's)
is the same stream ACROSS chips: a row shard is a slice that lives on
another chip. The fast kernel runs under ``shard_map`` on each chip's own
rows, and only the shards' partial groups cross between chips
(_shards_of, _over_shards); a group of resident sharded batches is one
such program, every chip running the kernel over its shard of each
member. A batch this does not admit is re-landed on one chip first
(execs/mesh.py)."""

from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceColumn, DeviceTable
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.ops import aggregates as agg
from spark_rapids_tpu.ops.expr import (
    BoundReference,
    DevVal,
    EvalCtx,
    Expression,
    NodePrep,
    PrepCtx,
    _prep_trace_key,
    _walk_eval,
    _walk_prep,
)
from spark_rapids_tpu.ops.segsum import (
    batched_segment_sum_f64,
    segment_counts,
    segment_sum_f64,
)

DEVICE_SUPPORTED_AGGS = (agg.Sum, agg.Min, agg.Max, agg.Count, agg.Average,
                         agg.First, agg.Last, agg.StddevPop, agg.StddevSamp,
                         agg.VariancePop, agg.VarianceSamp,
                         agg.CollectList, agg.CollectSet, agg.Percentile)

#: aggregates needing the SORT-SEGMENT path (contiguous groups / per-group
#: value order) and a single coalesced input (no streaming merge decomposition)
SORT_ONLY_AGGS = (agg.CollectList, agg.CollectSet, agg.Percentile)

#: rows of one slice of the fast aggregate. The kernel's elementwise
#: fusions cost 2 to 3 times as much a row at 2^24 rows as at 2^21, where
#: an f32[rows] intermediate still stays near the cores between fusions
#: (PERF.md, PR 30), so a larger batch is walked in slices of this many
#: rows by the kernel compiled at this capacity. Not an option: what is
#: observed is the batch's capacity.
AGG_SLICE = 1 << 21

#: batches one program of the streaming aggregate takes at most. What an
#: enqueue costs the host on a v5e is its RESULTS (about 0.05 ms each on
#: one chip, 0.08 over four), hardly its operands: one compacted partial
#: of 27 results costs 1.6, 1.7, 1.8 and 2.0 ms for 1, 4, 8 and 16 bodies
#: on one chip and 3.0 ms for 1 to 8 over four (PERF.md section 6, PR
#: 32), so every body after the first is nearly free to the host. Four,
#: because the device starts only when the first group is enqueued (16
#: batches take 30.8 ms of wall at 4 bodies, 31.4 at 8, 31.7 at 16; over
#: four chips 2, 4 and 8 read alike) and the unrolled program's cold
#: compile grows with its bodies (7.7 / 13.0 / 28.6 s at 4 / 8 / 16 on
#: one chip's host, 10.2 / 15.2 s at 4 / 8 over four, where one body
#: takes 6.8 and the four-chip cell's first run has 34 s to spare). Not
#: an option: what is observed is where the batches lie.
AGG_GROUP = 4


_M32 = 0xFFFFFFFF
_TOP64 = -0x8000000000000000


def _dec_limb_words(sd):
    """Decompose decimal storage into four 32-bit words per row such that
    value = w0 + w1*2^32 + w2*2^64 + w3*2^96 with w0..w2 in [0, 2^32)
    and w3 carrying the sign. Accepts (n, 2) two-limb dec128 columns and
    plain (n,) int64 decimal64 columns (hi = sign extension)."""
    if getattr(sd, "ndim", 1) == 2:
        hi, lo = sd[:, 0], sd[:, 1]
    else:
        lo = sd.astype(jnp.int64)
        hi = lo >> 63  # 0 / -1 sign extension
    return (lo & _M32, (lo >> 32) & _M32, hi & _M32, hi >> 32)


def _dec_wide_sum_segments(sd, sv, gid, nseg):
    """EXACT 128-bit segment sum of unscaled decimal storage: per-word i64
    segment sums (each word < 2^32 and row counts < 2^31, so partials are
    exact), carry-normalized back to (hi, lo) two's-complement limbs.
    Returns (hi, lo, t3) where t3 holds bits >=96 of the TRUE sum for
    overflow detection. Works for decimal64 AND dec128 inputs."""
    words = _dec_limb_words(sd)
    sums = [jax.ops.segment_sum(jnp.where(sv, w, 0), gid,
                                num_segments=nseg) for w in words]
    t0 = sums[0]
    r0, c = t0 & _M32, t0 >> 32
    t1 = sums[1] + c
    r1, c = t1 & _M32, t1 >> 32
    t2 = sums[2] + c
    r2, c = t2 & _M32, t2 >> 32
    t3 = sums[3] + c
    hi = (t3 << 32) | r2
    lo = (r1 << 32) | r0
    return hi, lo, t3


def _dec_wide_to_f64(hi, lo):
    """(hi, lo) i128 -> f64 via sign-magnitude (a direct hi*2^64 + lo
    combine cancels catastrophically for small negatives)."""
    from spark_rapids_tpu.ops.decimal import i128_abs
    ahi, alo, neg = i128_abs(hi, lo.astype(jnp.uint64))
    mag = (ahi.astype(jnp.float64) * float(2.0 ** 64)
           + alo.astype(jnp.float64))
    return jnp.where(neg, -mag, mag)


def _dec_sum_segments(out_type, sd, sv, gid, nseg, has_any):
    """EXACT decimal segment sum (Spark sums decimals exactly; an f64
    ride would round beyond 2^53): 128-bit word sums, overflow -> NULL
    (non-ANSI CheckOverflow semantics)."""
    from spark_rapids_tpu.ops.decimal import i128_abs_fits_pow10
    hi, lo, t3 = _dec_wide_sum_segments(sd, sv, gid, nseg)
    # t3 holds bits >=96 of the TRUE sum (no i64 overflow possible at
    # <2^31 rows), so a t3 outside i32 range means 128-bit overflow
    ovf = (t3 > 0x7FFFFFFF) | (t3 < -0x80000000)
    fits = i128_abs_fits_pow10(hi, lo, out_type.precision)
    valid = has_any & ~ovf & fits
    if out_type.precision > T.DecimalType.MAX_LONG_DIGITS:
        return (jnp.stack([hi, lo], axis=1), valid)
    # result precision fits int64: the low limb IS the two's-complement
    # value when in range
    return (jnp.where(valid, lo, 0), valid)


def _dec128_minmax_segments(is_min, sd, sv, gid, nseg, has_any):
    """Two-limb lexicographic segment min/max: high limbs reduce first
    (signed); rows tying on the winning high limb break on the low limb
    compared as UNSIGNED via a top-bit flip."""
    seg_red = jax.ops.segment_min if is_min else jax.ops.segment_max
    hi, lo = sd[:, 0], sd[:, 1]
    info = jnp.iinfo(jnp.int64)
    ident = info.max if is_min else info.min
    hi_m = seg_red(jnp.where(sv, hi, ident), gid, num_segments=nseg)
    cand = sv & (hi == hi_m[gid])
    lob = lo ^ _TOP64  # unsigned order as signed
    lo_m = seg_red(jnp.where(cand, lob, ident), gid,
                   num_segments=nseg) ^ _TOP64
    data = jnp.stack([jnp.where(has_any, hi_m, 0),
                      jnp.where(has_any, lo_m, 0)], axis=1)
    return (data, has_any)


def _compact_parts(outs, ngroups, gpad: int, scope: str):
    """The partial groups of several parts (slices, shards, a group's
    members) compacted to one prefix, in part order: ``outs`` holds each
    output column as (data, validity) of ``[parts..., gpad]`` rows,
    ``ngroups`` each part's group count (a part's groups are a prefix of
    its gpad rows). Returns what a fast kernel returns, at ``parts *
    gpad`` rows."""
    from spark_rapids_tpu.ops.scatter32 import compact_pairs
    total = ngroups.size * gpad
    exists = (jnp.arange(gpad, dtype=jnp.int32)
              < ngroups.reshape(-1)[:, None]).reshape(-1)
    with jax.named_scope(scope):
        outs, count = compact_pairs(
            [d.reshape((total,) + d.shape[v.ndim:]) for d, v in outs],
            [v.reshape(-1) for _, v in outs], exists, total)
    return list(outs), count


def _over_slices(kernel, slices: int, rows: int, gpad: int):
    """A fast kernel built at ``rows`` rows, run over a batch of
    ``slices * rows`` rows inside one program: a scan over the slices'
    first rows, each step the kernel itself over that slice of every
    column, with the slice's own row count and its part of the live
    mask. Returns what the kernel returns, at ``slices * gpad`` rows:
    every slice's partial groups compacted to one prefix, and their
    count."""
    def sliced(cols, aux, nrows, sizes, strides, bases, live_in):
        def step(_, start):
            # read in place: scanning over the columns reshaped to
            # [slices, rows] copies them first (the chip tiles a 2-D
            # array otherwise) and ran 3 x slower (PERF.md, PR 30)
            c, live = jax.tree.map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, start, rows),
                (cols, live_in))
            return None, kernel(c, aux, jnp.clip(nrows - start, 0, rows),
                                sizes, strides, bases, live)

        _, (outs, ngroups) = jax.lax.scan(
            step, None, jnp.arange(slices, dtype=jnp.int32) * rows)
        return _compact_parts(outs, ngroups, gpad, "compact_slices")

    return sliced


def _one_at_a_time(kernel, batches):
    """``kernel`` over each of a group's members in turn, one body at a
    time: a member's operands wait behind the partial before it (an
    ``optimization_barrier``: no copy, no operation), so the compiler
    schedules the bodies one after another and a body's temporaries
    stay its own. Left free it interleaves the bodies' phases and holds
    every body's temporaries at once (0.79 GB for 0.32 at 4 bodies of
    Q1's, and 3.6% more device time at 16: PERF.md, PR 32). Returns the
    members' partials stacked: each output column as (data, validity)
    of ``[members, gpad]`` rows, and the members' group counts."""
    parts = []
    for b in batches:
        if parts:
            b, parts[-1] = jax.lax.optimization_barrier((b, parts[-1]))
        parts.append(kernel(*b))
    outs = [(jnp.stack([outs[i][0] for outs, _ in parts]),
             jnp.stack([outs[i][1] for outs, _ in parts]))
            for i in range(len(parts[0][0]))]
    return outs, jnp.stack([n for _, n in parts])


def _over_members(kernel, gpad: int):
    """A fast kernel run over each of a group's batches inside one
    program, each on its own operands where they lie (a member is the
    kernel's argument tuple: no batch is stacked, sliced or copied).
    Returns what the kernel returns, at ``members * gpad`` rows: every
    member's partial groups compacted to one prefix in member order,
    and their count: one set of results a program, because what an
    enqueue costs the host is its results (PERF.md, PR 32)."""
    def grouped(*batches):
        outs, ngroups = _one_at_a_time(kernel, batches)
        return _compact_parts(outs, ngroups, gpad, "compact_members")

    return grouped


def _over_shards(kernel, mesh, row_axes, shards: int, rows: int, gpad: int,
                 members: int = 1):
    """A fast kernel built at ``rows`` rows (or ``_over_slices`` of one:
    ``gpad`` is then the slices' total), run on each of ``shards`` row
    shards where it lies: under ``shard_map`` over the mesh's row axes
    every chip runs the kernel over its own rows of every column, with
    the shard's own row count (the batch's, less the rows of the shards
    before it) and its part of the live mask. Only the partial groups
    cross chips: an ``all_gather`` of ``gpad`` rows a shard, in shard
    order, compacted to one prefix as ``_over_slices`` compacts its
    slices. Returns what the kernel returns, at ``shards * gpad`` rows,
    the same on every chip. With ``members`` > 1 the program takes that
    many batches (``_over_members``): every chip runs the kernel over
    its shard of each, one body at a time, the members' partial groups
    cross in one exchange and come back at ``members * shards * gpad``
    rows in (member, shard) order."""
    from jax.sharding import PartitionSpec as P
    by_row, same = P(row_axes), P()
    member_specs = (by_row, same, same, same, same, same, by_row)

    def shard_rows(nrows):
        return jnp.clip(nrows - jax.lax.axis_index(row_axes) * rows, 0, rows)

    def on_shard(cols, aux, nrows, sizes, strides, bases, live_in):
        outs, ngroups = kernel(cols, aux, shard_rows(nrows),
                               sizes, strides, bases, live_in)
        with jax.named_scope("exchange_partials"):
            outs, ngroups = jax.lax.all_gather((list(outs), ngroups),
                                               row_axes)
        return _compact_parts(outs, ngroups, gpad, "compact_shards")

    def on_shard_group(*batches):
        outs, ngroups = _one_at_a_time(kernel, [
            (cols, aux, shard_rows(nrows), sizes, strides, bases, live_in)
            for cols, aux, nrows, sizes, strides, bases, live_in in batches])
        # the members' partials cross in one exchange and lie [shards,
        # members, gpad] on every chip
        with jax.named_scope("exchange_partials"):
            outs, ngroups = jax.lax.all_gather((outs, ngroups), row_axes)
        return _compact_parts(
            [(jnp.swapaxes(d, 0, 1), jnp.swapaxes(v, 0, 1)) for d, v in outs],
            jnp.swapaxes(ngroups, 0, 1), gpad, "compact_shards")

    if members == 1:
        return jax.shard_map(
            on_shard, mesh=mesh, in_specs=member_specs,
            out_specs=same, check_vma=False)
    return jax.shard_map(
        on_shard_group, mesh=mesh, in_specs=(member_specs,) * members,
        out_specs=same, check_vma=False)


def _preps(exprs, pctx: PrepCtx) -> List[List[NodePrep]]:
    """The host prep pass of each expression: one NodePrep list apiece,
    in the order given (aux slots are handed out in that order)."""
    out = []
    for e in exprs:
        preps: List[NodePrep] = []
        _walk_prep(e, pctx, preps)
        out.append(preps)
    return out


def _sortable(data, validity):
    """Transform (data, validity) into sort operands grouping nulls
    together: (invalid_first_flag, *native-width key operands). The
    ordering decomposition canonicalizes floats (-0.0 == 0.0, one NaN
    pattern — Spark NormalizeFloatingNumbers groups NaNs together) and
    keeps every compare at <=32 bits (ops/ordering.py)."""
    from spark_rapids_tpu.ops.ordering import comparable_operands, zero_invalid
    return ([(~validity).astype(jnp.int32)]
            + comparable_operands(zero_invalid(data, validity)))


class _Member(NamedTuple):
    """One batch as the aggregate's program takes it (_member): the
    program's operands, the keys its program is found under, and what
    the output's columns are built from."""

    table: DeviceTable
    args: tuple
    schema: tuple
    tkey: tuple
    fast: Optional[tuple]
    filter_preps: list
    key_preps: list
    val_preps: list
    slices: int
    shards: int
    body_rows: int

    def _output_dictionaries(self):
        """What the output's columns carry beside the program's results:
        each key's dictionary and domain, each string result's
        dictionary."""
        for preps in self.key_preps:
            yield preps[-1].out_dict, preps[-1].out_domain
        for per_child in self.val_preps:
            if per_child:
                yield per_child[-1][-1].out_dict, None

    def joins(self, head: "_Member") -> bool:
        """May one program aggregate this batch and ``head``, into one
        partial table? The same program (schema and trace key), and the
        same codes in every dictionary-coded output column, as the
        coalesce asks before it concatenates without a union."""
        from spark_rapids_tpu.columnar.table import _same_dictionary
        return (self.schema == head.schema and self.tkey == head.tkey
                and all(_same_dictionary(d0, d) and domain0 == domain
                        for (d0, domain0), (d, domain) in zip(
                            head._output_dictionaries(),
                            self._output_dictionaries())))


class TpuHashAggregateExec(TpuExec):
    def __init__(self, child: TpuExec, grouping: Sequence[Expression],
                 agg_specs: Sequence[Tuple[str, agg.AggregateFunction]],
                 grouping_names: Sequence[str],
                 filters: Sequence[Expression] = (),
                 use_split: bool = False,
                 max_dict_groups: int = 1 << 16,
                 max_domain_groups: int = 1 << 21):
        super().__init__()
        self.children = (child,)
        self.grouping = list(grouping)
        self.agg_specs = list(agg_specs)
        self.grouping_names = list(grouping_names)
        self.filters = list(filters)
        self.use_split = use_split
        self.max_dict_groups = max_dict_groups
        self.max_domain_groups = max_domain_groups

    def output_schema(self):
        out = [(n, g.data_type) for n, g in zip(self.grouping_names, self.grouping)]
        out += [(n, fn.data_type) for n, fn in self.agg_specs]
        return out

    def execute(self):
        from collections import deque
        from itertools import chain
        from spark_rapids_tpu.runtime.retry import retry_block
        from spark_rapids_tpu.runtime.spill import BufferCatalog, SpillableBatch

        from spark_rapids_tpu.parallel.mesh import MESH_SCOPE
        it = self._pulled(iter(self.children[0].execute_masked()))
        # the node's record carries both counts of the streaming loop,
        # 0 where it read no partial's count and never waited
        self.add_metric("partialCountReads", 0)
        self.add_metric("runAheadWaits", 0)
        # and both counts of the sliced aggregate: batches walked in
        # slices inside one program, and the slices they held
        self.add_metric("slicedAggBatches", 0)
        self.add_metric("aggSlices", 0)
        # and of the aggregate on a mesh's resident shards: batches
        # aggregated where they lay, and the shards they held
        self.add_metric("meshAggBatches", 0)
        self.add_metric("meshAggShards", 0)
        # and of the grouped aggregate: programs enqueued for two or
        # more resident batches, and the batches they held
        self.add_metric("groupedAggPrograms", 0)
        self.add_metric("groupedAggBatches", 0)
        first = next(it, None)
        if first is None:
            return
        second = next(it, None)
        # every batch is placed once: on its shards, or on one chip
        head = self._placed(*first)
        if second is None and head[1:3] == (1, 1):
            # single batch in one body: aggregate directly
            # (spill-and-replay on OOM)
            yield retry_block(lambda: self._aggregate(
                head[0], self.grouping, self.agg_specs,
                self.grouping_names, self.filters))
            return

        # STREAMING multi-batch path (GpuMergeAggregateIterator analog,
        # GpuAggregateExec.scala:718-950): each input batch aggregates
        # immediately to a per-batch PARTIAL table, partials are
        # spillable, and one merge aggregation re-groups the concatenated
        # partials with merge semantics (sum-of-sums, min-of-mins,
        # Chan-style moment combination), followed by a finalize
        # projection (avg = s/n, ...). A single batch of several slices
        # is the same stream: its slices' partials merge below.
        # Consecutive batches that lay resident when pulled aggregate
        # several to a program (AGG_GROUP): they wait for nothing, so
        # nothing is lost by enqueueing them together, and what a
        # program's enqueue costs the host does not grow with them.
        plan = self._merge_plan()
        catalog = BufferCatalog.get()
        partials = []
        #: capacities of the partials kept with their count on the device
        kept_capacity = 0
        #: (count, input bytes) of the partials enqueued and not yet
        #: known complete, oldest first (_bound_run_ahead)
        ahead = deque()
        #: the open group: resident batches that one program will take
        group: List[_Member] = []
        may_group = not self._reads_positions(
            self.grouping, plan.partial_specs, self.filters)

        def aggregate(members):
            return self._aggregate(
                members[0].table, self.grouping, plan.partial_specs,
                self.grouping_names, self.filters,
                shards=members[0].shards, members=members)

        def enqueued(members):
            """(partial, the batches it holds) of ``members`` in one
            program; where its OOM persists after the replay, of each
            member alone."""
            from spark_rapids_tpu.runtime.retry import FatalDeviceOOM
            try:
                return [(retry_block(lambda: aggregate(members)),
                         [m.table for m in members])]
            except FatalDeviceOOM:
                if len(members) == 1:
                    raise
            return [(retry_block(lambda m=m: aggregate([m])), [m.table])
                    for m in members]

        def keep(pt, batches):
            # A partial's row count stays a device scalar
            # (concat_device and the merge take it as one): reading
            # it stalls the host until the batch's kernel has run,
            # and nothing further is enqueued meanwhile. It is read
            # only where the read can free something. THE CAPACITY
            # RULE: the merge concat below buckets the SUM of
            # partial capacities, so a chunked scan's N
            # full-capacity partials would concat into an N-fold
            # over-capacity table, exactly the over-budget resident
            # the out-of-core contract forbids. A partial is kept
            # as it is only while it is smaller than its input (the
            # fast path's domain-sized output, not the sorted
            # path's input-sized one), under EMBED_NROWS_CAP rows
            # (where the sorted path's speculative capacities
            # start: such a partial's batch costs far more than a
            # sync, and so would a sorted merge over it), and the
            # kept capacities together stay within one input
            # batch's, so the merge costs at most one more batch.
            # Any other partial is shrunk to its live-group bucket
            # at the price of one row-count sync (shrink() reads
            # nothing where no smaller bucket exists).
            nonlocal kept_capacity
            if not pt.num_rows_known:
                if (pt.capacity < sum(b.capacity for b in batches)
                        and pt.capacity < DeviceTable.EMBED_NROWS_CAP
                        and kept_capacity + pt.capacity
                        <= batches[0].capacity):
                    kept_capacity += pt.capacity
                else:
                    pt = pt.shrink()
            if pt.num_rows_known:
                # read here, or by _aggregate's own shrink (the
                # sorted path): the device is done up to this one
                self.add_metric("partialCountReads", 1)
                ahead.clear()
            else:
                # one entry a program: its batches are done together
                ahead.append((pt.nrows_dev,
                              sum(b.device_nbytes() for b in batches)))
            partials.append(SpillableBatch(pt, catalog))
            self.add_metric("partialAggBatches", len(batches))

        def flush():
            for pt, batches in enqueued(group):
                keep(pt, batches)
            group.clear()

        try:
            rest = it if second is None else chain([second], it)
            for batch, shards, slices, fast, resident in chain(
                    [head], (self._placed(*pulled) for pulled in rest)):
                if shards > 1:
                    self.add_metric("meshAggBatches", 1)
                    self.add_metric("meshAggShards", shards)
                    MESH_SCOPE.add("meshAggBatches", 1)
                    MESH_SCOPE.add("meshAggShards", shards)
                if slices > 1:
                    self.add_metric("slicedAggBatches", 1)
                    self.add_metric("aggSlices", shards * slices)
                # what may wait for its neighbours: a batch the pull
                # found resident (nothing was built for it, so nothing
                # is held for it), in one body, on the fast layout
                member = None
                if resident and slices == 1 and may_group:
                    member = self._member(
                        batch, self.grouping, plan.partial_specs,
                        self.filters, 1, shards, fast)
                    if not member.fast:
                        member = None
                if group and (member is None or not member.joins(group[0])):
                    flush()
                if member is None:
                    pt = retry_block(
                        lambda b=batch, n=slices, m=shards, f=fast:
                        self._aggregate(
                            b, self.grouping, plan.partial_specs,
                            self.grouping_names, self.filters, slices=n,
                            shards=m, fast=f))
                    keep(pt, [batch])
                else:
                    group.append(member)
                    if len(group) == AGG_GROUP:
                        flush()
                self._bound_run_ahead(ahead)
            if group:
                flush()

            from spark_rapids_tpu.columnar.table import concat_device

            def merge():
                merged = concat_device([p.get() for p in partials])
                return self._aggregate(
                    merged, plan.merge_grouping, plan.merge_specs,
                    self.grouping_names, [])

            mt = retry_block(merge)
        finally:
            for p in partials:
                p.release()

        from spark_rapids_tpu.ops.expr import bind, compile_project
        bound = [bind(e, mt.schema()) for e in plan.final_exprs]
        out_cols = compile_project(bound, mt)
        out_names = self.grouping_names + [n for n, _ in self.agg_specs]
        yield DeviceTable(out_names, out_cols, mt.nrows_dev, mt.capacity)

    def _bound_run_ahead(self, ahead) -> None:
        """Back-pressure for the streaming loop, called before it pulls
        the next batch. The per-partial row-count sync used to be the
        only one: without it the host can enqueue the whole stream, and
        an input the pull BUILT (a coalesce flush, a scan chunk's
        upload, an upstream exec's output) stays in HBM until its
        partial's kernel has run, while the arbiter releases a table
        when its Python reference dies, i.e. at enqueue. ``ahead``
        holds those inputs' bytes; while they would not fit the
        arbiter's budget beside what it accounts, wait for the oldest
        partial (its count: a counted, timed, ranged host_fetch) and
        drop what that confirms."""
        from spark_rapids_tpu.dispatch import host_fetch
        from spark_rapids_tpu.runtime.memory import MEMORY
        while ahead and (MEMORY.occupancy()
                         + sum(nbytes for _, nbytes in ahead)
                         > MEMORY.budget_bytes()):
            count, _ = ahead.popleft()
            host_fetch(count)
            self.add_metric("runAheadWaits", 1)

    # -- streaming merge plan ----------------------------------------------
    def _merge_plan(self):
        """Decompose each aggregate into (partial specs, merge specs, final
        projection) so multi-batch inputs stream:

          Count  -> partial Count          ; merge Sum          ; identity
          Sum    -> partial Sum            ; merge Sum          ; identity
          Min/Max-> partial Min/Max        ; merge Min/Max      ; identity
          First/ -> partial First/Last     ; merge First/Last   ; identity
           Last     (concat preserves batch order, and a group row exists
                     in a partial iff the batch had rows for it)
          Avg    -> partial Sum+Count      ; merge Sum each     ; s / n
          Var*/  -> partial Count+Sum+VarPop; merge N=Σn plus the stable
          Stddev*   Chan combination m2 = Σm2_i + Σn_i(mean_i - mean_tot)²
                    via the internal MergeMoments aggregate (the naive
                    M + Q - S²/N form cancels catastrophically when
                    |mean| >> stddev); finalize m2/denom (+ sqrt)
        """
        from types import SimpleNamespace
        from spark_rapids_tpu.ops.cast import Cast
        from spark_rapids_tpu.ops.expr import Literal, col, lit
        from spark_rapids_tpu.ops.math import Sqrt

        pschema = [(n, g.data_type)
                   for n, g in zip(self.grouping_names, self.grouping)]
        partial_specs: List[Tuple[str, agg.AggregateFunction]] = []
        merge_specs: List[Tuple[str, agg.AggregateFunction]] = []
        final_exprs: List[Expression] = [col(n) for n in self.grouping_names]

        def add_partial(pname, pfn):
            partial_specs.append((pname, pfn))
            pschema.append((pname, pfn.data_type))
            return len(pschema) - 1

        def pref(idx):
            name, dt = pschema[idx]
            return BoundReference(idx, dt, name_hint=name)

        for j, (name, fn) in enumerate(self.agg_specs):
            t = type(fn)
            if isinstance(fn, agg.Count):
                i = add_partial(f"__p{j}c", agg.Count(fn.child))
                merge_specs.append((name, agg.Sum(pref(i))))
                final_exprs.append(col(name))
            elif isinstance(fn, agg.Sum):
                if isinstance(fn.data_type, T.DecimalType):
                    # a PARTIAL whose rows overflowed emits NULL; a plain
                    # sum-of-partials would silently skip it (dropping
                    # that batch's rows from a non-null final). Track it:
                    # rows present + null partial sum == overflow, which
                    # must null the FINAL (Spark non-ANSI CheckOverflow)
                    from spark_rapids_tpu.ops.conditional import If
                    from spark_rapids_tpu.ops.predicates import IsNull
                    si = add_partial(f"__p{j}s", agg.Sum(fn.child))
                    ci = add_partial(f"__p{j}n", agg.Count(fn.child))
                    merge_specs.append((f"__m{j}s", agg.Sum(pref(si))))
                    merge_specs.append((f"__m{j}o", agg.Sum(
                        If(IsNull(pref(si)) & (pref(ci) > lit(0)),
                           lit(1), lit(0)))))
                    final_exprs.append(
                        If(col(f"__m{j}o") > lit(0),
                           Literal(None, fn.data_type),
                           col(f"__m{j}s")).alias(name))
                else:
                    i = add_partial(f"__p{j}s", agg.Sum(fn.child))
                    merge_specs.append((name, agg.Sum(pref(i))))
                    final_exprs.append(col(name))
            elif isinstance(fn, (agg.Min, agg.Max)):
                i = add_partial(f"__p{j}m", t(fn.child))
                merge_specs.append((name, t(pref(i))))
                final_exprs.append(col(name))
            elif isinstance(fn, (agg.First, agg.Last)):
                i = add_partial(f"__p{j}f", t(fn.child, fn.ignore_nulls))
                merge_specs.append((name, t(pref(i), fn.ignore_nulls)))
                final_exprs.append(col(name))
            elif isinstance(fn, agg.Average):
                si = add_partial(f"__p{j}s", agg.Sum(fn.child))
                ci = add_partial(f"__p{j}n", agg.Count(fn.child))
                merge_specs.append((f"__m{j}s", agg.Sum(pref(si))))
                merge_specs.append((f"__m{j}n", agg.Sum(pref(ci))))
                final_exprs.append(
                    (col(f"__m{j}s").cast(T.DOUBLE)
                     / col(f"__m{j}n").cast(T.DOUBLE)).alias(name))
            elif isinstance(fn, (agg.StddevPop, agg.StddevSamp,
                                 agg.VariancePop, agg.VarianceSamp)):
                ni = add_partial(f"__p{j}n", agg.Count(fn.child))
                si = add_partial(f"__p{j}s",
                                 agg.Sum(Cast(fn.child, T.DOUBLE)))
                vi = add_partial(f"__p{j}v", agg.VariancePop(fn.child))
                n_d = Cast(pref(ni), T.DOUBLE)
                merge_specs.append((f"__m{j}n", agg.Sum(pref(ni))))
                merge_specs.append((f"__m{j}m", agg.MergeMoments(
                    pref(ni), pref(si), pref(vi) * n_d)))
                N = col(f"__m{j}n").cast(T.DOUBLE)
                m2 = col(f"__m{j}m")
                if isinstance(fn, (agg.StddevPop, agg.VariancePop)):
                    var = m2 / N
                else:
                    var = m2 / (N - lit(1.0))
                out = Sqrt(var) if isinstance(
                    fn, (agg.StddevPop, agg.StddevSamp)) else var
                final_exprs.append(out.alias(name))
            else:
                raise ColumnarProcessingError(
                    f"no merge decomposition for {t.__name__}")

        merge_grouping = [
            BoundReference(i, g.data_type, name_hint=n)
            for i, (g, n) in enumerate(zip(self.grouping, self.grouping_names))]
        return SimpleNamespace(partial_specs=partial_specs,
                               merge_specs=merge_specs,
                               merge_grouping=merge_grouping,
                               final_exprs=final_exprs)

    # -- core ---------------------------------------------------------------
    def _prep_all(self, table: DeviceTable, grouping, agg_specs, filters):
        pctx = PrepCtx(table)
        filter_preps = _preps(filters, pctx)
        key_preps = _preps(grouping, pctx)
        # per spec: one prep list PER CHILD expression (Count() has none,
        # most aggs have one, MergeMoments has three)
        val_preps = [_preps(fn.children, pctx) for _, fn in agg_specs]
        return pctx, filter_preps, key_preps, val_preps

    def _fast_layout(self, grouping, key_preps, capacity) -> Optional[tuple]:
        """No-sort layout if every key has a small known domain:
        (kinds, sizes, strides, padded_num_segments, bases).

        Three key kinds aggregate by direct segment reduction (no sort):
        dictionary-encoded strings, booleans, and — via upload-time column
        statistics (DeviceColumn.domain) — integer-family keys whose value
        domain is bounded. gid = sum_i (code_i * stride_i) where an int
        key's code is ``value - base_i`` (bases ride as device operands so
        one trace serves any same-shaped domain)."""
        if self.max_dict_groups <= 0:
            return None
        if any(isinstance(fn, SORT_ONLY_AGGS) for _, fn in self.agg_specs):
            return None  # collect/percentile need contiguous sorted groups
        if not grouping:
            # ungrouped aggregate: ONE segment (padded to 8) — the batched
            # one-hot pass beats _agg_one's capacity-segment scatter by ~8x
            # wall on a 1M-row q2-style global sum
            return (), (), (), 8, ()
        kinds: List[str] = []
        sizes: List[int] = []
        bases: List[int] = []
        has_int = False
        for g, preps in zip(grouping, key_preps):
            dt = g.data_type
            root = preps[-1]
            if isinstance(dt, T.StringType) and root.out_dict is not None:
                kinds.append("str")
                sizes.append(len(root.out_dict) + 1)  # +1: null slot
                bases.append(0)
            elif isinstance(dt, T.BooleanType):
                kinds.append("bool")
                sizes.append(3)  # False, True, null
                bases.append(0)
            elif (isinstance(dt, (T.ByteType, T.ShortType, T.IntegerType,
                                  T.LongType, T.DateType, T.TimestampType))
                  and root.out_domain is not None
                  and self.max_domain_groups > 0):
                lo, hi = root.out_domain
                kinds.append("int")
                sizes.append(hi - lo + 2)  # values + null slot
                bases.append(lo)
                has_int = True
            else:
                return None
        total = 1
        for s in sizes:
            total *= max(s, 1)
        cap = self.max_dict_groups
        if has_int:
            # int domains are data-dependent, not cardinality-bounded like
            # a string dictionary: allow larger segment counts (scatter
            # segment ops are O(n + gpad)) but never a domain so sparse it
            # dwarfs the batch itself
            cap = max(cap, min(self.max_domain_groups, 16 * capacity))
        if total > cap:
            return None
        strides = [1] * len(sizes)
        for i in range(len(sizes) - 2, -1, -1):
            strides[i] = strides[i + 1] * sizes[i + 1]
        # tight power-of-two segment count (NOT the 128-row table bucket):
        # one-hot einsum traffic scales with it (the sums' and, up to
        # segsum.MATMUL_MAX_SEGMENTS, the counts'), and a q1-style 12-slot
        # domain must pad to 16, not 128
        gpad = max(8, 1 << (max(total - 1, 1)).bit_length())
        return tuple(kinds), sizes, strides, gpad, bases

    def _slices_of(self, table: DeviceTable) -> tuple:
        """(how many AGG_SLICE-row slices the fast kernel walks ``table``
        in inside one program, the fast layout at a slice's rows);
        (1, None) = the whole-capacity body, whose layout _aggregate
        works out. More than
        one where the capacity is a whole multiple of the slice, every
        column is one row-shaped array (its rows can be sliced), no
        expression depends on a row's position in the batch, and the
        fast layout applies with the small segment count whose one-hot
        contractions are the body that was measured (a larger domain's
        partial per slice would outgrow what slicing saves). Every
        aggregate the fast layout admits has a merge decomposition
        (_merge_plan), which is what combines the slices."""
        slices, rest = divmod(table.capacity, AGG_SLICE)
        if slices >= 2 and not rest:
            fast = self._part_layout(table, AGG_SLICE)
            if fast is not None:
                return slices, fast
        return 1, None

    def _part_layout(self, table: DeviceTable, rows: int) -> Optional[tuple]:
        """The fast layout at ``rows`` rows where ``table`` may be
        aggregated in parts of that many rows (slices in one program,
        shards on their chips) whose partial groups merge: _slices_of's
        conditions but the capacity's. None where it may not."""
        from spark_rapids_tpu.ops import segsum as _ss
        if any(c.is_nested for c in table.columns) or self._reads_positions(
                self.grouping, self.agg_specs, self.filters):
            return None
        fast = self._fast_layout(
            self.grouping, _preps(self.grouping, PrepCtx(table)), rows)
        if fast is None or not _ss.takes_contraction(fast[3], rows):
            return None
        return fast

    def _shards_of(self, table: DeviceTable) -> tuple:
        """(row shards, slices a shard, the fast layout at the body's
        rows) the fast kernel aggregates ``table`` in where it lies, or
        (0, 0, None): the batch is then re-landed on one chip first
        (execs/mesh.py). Admitted is a
        batch physically sharded over its mesh's row axes, under
        _slices_of's own conditions at the shard's rows: a shard of at
        most one AGG_SLICE is one body, a larger one a whole number of
        slices (_over_slices on each chip). Nothing here is an option:
        what decides is where the batch lies and what the expressions
        are."""
        spec = table.shard_spec
        if spec is None or not table.columns \
                or not table.physically_sharded():
            return 0, 0, None
        shards = int(spec.mesh.devices.size)
        rows, rest = divmod(table.capacity, shards)
        if shards < 2 or rest:
            return 0, 0, None
        slices = 1
        if rows > AGG_SLICE:
            slices, rest = divmod(rows, AGG_SLICE)
            if rest:
                return 0, 0, None
        fast = self._part_layout(table, rows // slices)
        if fast is None:
            return 0, 0, None
        return shards, slices, fast

    @staticmethod
    def _pulled(batches):
        """(batch, whether it lay resident when pulled) of each batch of
        ``batches``, a repartition's same-split views mask-unioned back
        into one batch (columnar/table.merge_split_views: aggregation
        is partition-structure-blind, and no data moves). Resident: the
        pull enqueued no program and the arbiter granted no landing, so
        nothing was uploaded, copied or computed for the batch (a
        cached scan's image passing through). What the pull BUILT is
        not: it is aggregated at once."""
        from spark_rapids_tpu.columnar.table import (
            mergeable_views,
            union_views,
        )
        from spark_rapids_tpu.dispatch import dispatch_count
        from spark_rapids_tpu.runtime.memory import MEMORY
        held = None
        while True:
            before = dispatch_count(), MEMORY.landings()
            batch = next(batches, None)
            if batch is None:
                break
            resident = (dispatch_count(), MEMORY.landings()) == before
            if held is not None and mergeable_views(held[0], batch):
                held = union_views(held[0], batch), False
                continue
            if held is not None:
                yield held
            held = batch, resident
        if held is not None:
            yield held

    def _placed(self, batch: DeviceTable, resident: bool = False) -> tuple:
        """(``batch`` where the fast kernel takes it, its row shards,
        the slices a shard, the parts' fast layout, whether it lies
        where the pull found it resident): on its shards where
        _shards_of admits it, else gathered to one chip
        (execs/mesh.reland), where _slices_of counts."""
        from spark_rapids_tpu.execs.mesh import reland
        shards, slices, fast = self._shards_of(batch)
        if not shards:
            landed, shards = reland(self, batch), 1
            resident = resident and landed is batch
            batch = landed
            slices, fast = self._slices_of(batch)
        return batch, shards, slices, fast, resident

    def _member(self, table: DeviceTable, grouping, agg_specs, filters,
                slices: int = 1, shards: int = 1, fast=None) -> "_Member":
        """The host's part of one aggregation of ``table``: the prep
        pass, the small operands where the program takes them, the
        layout and the program's trace key. Enqueues nothing (but a
        masked batch's compaction where an expression reads a row's
        position)."""
        if table.live is not None and self._reads_positions(
                grouping, agg_specs, filters):
            table = table.compacted()  # slot ids must match prefix form
        pctx, filter_preps, key_preps, val_preps = self._prep_all(
            table, grouping, agg_specs, filters)
        from spark_rapids_tpu.dispatch import (
            device_const,
            device_scalar,
            prep_aux,
        )
        cols = tuple(DevVal(c.data, c.validity) for c in table.columns)
        #: where the small operands lie: a program over sharded columns
        #: takes them replicated over the batch's mesh (interned so, once
        #: per constant: dispatch.device_const)
        everywhere = None
        if shards > 1:
            from jax.sharding import NamedSharding, PartitionSpec
            everywhere = NamedSharding(table.shard_spec.mesh,
                                       PartitionSpec())
        aux = prep_aux(pctx, everywhere)
        capacity = table.capacity
        #: rows the kernel body is built at: the batch, or one slice
        #: (of one shard)
        body_rows = capacity // (slices * shards)

        if fast is None:
            fast = self._fast_layout(grouping, key_preps, body_rows)

        from spark_rapids_tpu.ops import segsum as _ss
        mode_key = ("fast", fast[0], fast[3]) if fast else ("sorted",)
        if slices > 1:
            mode_key = ("fast_sliced", fast[0], fast[3], slices)
        if shards > 1:
            from spark_rapids_tpu.parallel.mesh import mesh_token
            mode_key = ("fast_mesh", fast[0], fast[3], slices, shards,
                        mesh_token(table.shard_spec.mesh))
        tkey = (capacity, self.use_split, _ss.trace_key(),
                mode_key, table.live is not None,
                tuple(_prep_trace_key(p) for p in filter_preps),
                tuple(_prep_trace_key(p) for p in key_preps),
                tuple(tuple(_prep_trace_key(p) for p in per_child)
                      for per_child in val_preps))
        if not fast:
            args = (cols, aux, table.nrows_dev, table.live)
        else:
            _, sizes, strides, _, bases = fast
            nrows = table.nrows_dev
            if shards > 1:
                # a count the host knows is interned replicated; one
                # only the device knows is copied to every chip
                nrows = device_scalar(table.num_rows, sharding=everywhere) \
                    if table.num_rows_known \
                    else device_const(nrows, everywhere)
            args = (
                cols, aux, nrows,
                device_const(np.asarray(sizes, dtype=np.int32), everywhere),
                device_const(np.asarray(strides, dtype=np.int32), everywhere),
                device_const(np.asarray(bases, dtype=np.int64), everywhere),
                table.live)
        return _Member(table, args, table.schema_key()[0], tkey, fast,
                       filter_preps, key_preps, val_preps, slices, shards,
                       body_rows)

    @staticmethod
    def _reads_positions(grouping, agg_specs, filters) -> bool:
        from spark_rapids_tpu.ops.expr import has_position_dependent
        return any(has_position_dependent(e) for e in (
            list(grouping) + list(filters)
            + [c for _, fn in agg_specs for c in fn.children]))

    def _aggregate(self, table: DeviceTable, grouping, agg_specs,
                   grouping_names, filters, slices: int = 1,
                   shards: int = 1, fast=None, members=None) -> DeviceTable:
        """One aggregation of ``table``. ``slices`` > 1 (_slices_of) or
        ``shards`` > 1 (_shards_of, ``slices`` then counts a shard's;
        the caller merges what comes back): the fast kernel runs once a
        slice of every shard and the output holds every part's partial
        groups, on one device. ``fast`` is the parts' layout where
        _placed found one already. ``members``: the group that
        ``table`` is the first batch of, each member as _member gives
        it, with equal keys and key dictionaries (_Member.joins): ONE
        program aggregates them all and the output holds every
        member's partial groups, in member order."""
        if members is None:
            members = [self._member(table, grouping, agg_specs, filters,
                                    slices, shards, fast)]
        head, count = members[0], len(members)
        table, fast = head.table, head.fast
        shards, slices, body_rows = head.shards, head.slices, head.body_rows
        capacity = table.capacity
        key_preps, val_preps = head.key_preps, head.val_preps

        from spark_rapids_tpu.ops.expr import shared_traces
        self._traces = shared_traces(
            ("agg",
             tuple(g.key() for g in grouping),
             tuple(fn.key() for _, fn in agg_specs),
             tuple(f.key() for f in filters),
             head.schema))
        from spark_rapids_tpu.ops import segsum as _ss
        # a group of one is the batch's own program under its own key
        tkey = head.tkey if count == 1 else head.tkey + (count,)
        fn = self._traces.get(tkey)
        if fn is None:
            if fast:
                kernel = self._build_fast_kernel(
                    body_rows, fast[0], fast[3], head.filter_preps,
                    key_preps, val_preps, grouping, agg_specs, filters)
                if slices > 1:
                    kernel = _over_slices(kernel, slices, body_rows, fast[3])
                if shards > 1:
                    # one name whatever the member count: the same work,
                    # and the benchmark's readers match it exactly
                    fn = tpu_jit(_over_shards(
                        kernel, table.shard_spec.mesh,
                        table.shard_spec.spec[0], shards,
                        slices * body_rows, slices * fast[3],
                        members=count), name="agg_fast_mesh")
                elif count > 1:
                    fn = tpu_jit(_over_members(kernel, fast[3]),
                                 name="agg_fast_group")
                elif slices > 1:
                    fn = tpu_jit(kernel, name="agg_fast_sliced")
                else:
                    fn = tpu_jit(kernel, name="agg_fast")
            else:
                fn = tpu_jit(self._build_kernel(
                    capacity, head.filter_preps, key_preps, val_preps,
                    grouping, agg_specs, filters), name="agg_sorted")
            self._traces[tkey] = fn

        if fast:
            gpad = fast[3]
            if _ss.takes_contraction(gpad, body_rows):
                self.add_metric("countsByContraction", 1)
            out_arrays, ngroups = fn(*head.args) if count == 1 \
                else fn(*[m.args for m in members])
            if count > 1:
                self.add_metric("groupedAggPrograms", 1)
                self.add_metric("groupedAggBatches", count)
            out_capacity = count * shards * slices * gpad
            if shards > 1:
                # every chip holds the gathered partials: what follows
                # takes the first device's, where it runs on one chip
                from spark_rapids_tpu.columnar.table import (
                    replica_on_first_device,
                )
                out_arrays, ngroups = replica_on_first_device(
                    (out_arrays, ngroups))
        else:
            out_arrays, ngroups = fn(*head.args)
            out_capacity = capacity

        out_cols: List[DeviceColumn] = []
        names: List[str] = []
        for i, (g, name) in enumerate(zip(grouping, grouping_names)):
            data, validity = out_arrays[i]
            root = key_preps[i][-1]
            out_cols.append(DeviceColumn(g.data_type, data, validity,
                                         dictionary=root.out_dict,
                                         dict_sorted=root.dict_sorted,
                                         domain=root.out_domain))
            names.append(name)
        for j, (name, fnagg) in enumerate(agg_specs):
            data, validity = out_arrays[len(grouping) + j]
            dictionary = None
            dict_sorted = True
            if isinstance(fnagg.data_type, T.StringType) and val_preps[j]:
                dictionary = val_preps[j][-1][-1].out_dict
                dict_sorted = val_preps[j][-1][-1].dict_sorted
            out_cols.append(DeviceColumn(fnagg.data_type, data, validity,
                                         dictionary=dictionary, dict_sorted=dict_sorted))
            names.append(name)
        out = DeviceTable(names, out_cols, ngroups, out_capacity)
        if fast:
            # outputs are already domain-sized; the group count stays a
            # device scalar: no host sync here, and the streaming loop
            # reads it only under its capacity rule (execute)
            return out
        from spark_rapids_tpu.columnar import bucket_for
        from spark_rapids_tpu.runtime import speculation as spec
        if out_capacity <= DeviceTable.EMBED_NROWS_CAP:
            # small outputs embed their row count in the collect fetch and
            # cost downstream ops little — under async mode shrinking
            # would only add a sync
            return out if spec.current() is not None else out.shrink()
        site = self._spec_site_key() + ":shrink"
        ctx = spec.allowed(site)
        if ctx is None:
            if spec.current() is not None:
                # blocklisted site under async mode: keep the padded
                # capacity rather than paying the sync mid-plan
                return out
            return out.shrink()
        # SPECULATIVE shrink (ADVICE r3): large sorted-path outputs used to
        # keep the INPUT capacity (inflating every downstream kernel) to
        # avoid shrink()'s ~0.1s row-count sync. Speculate that the group
        # count fits a quarter-capacity bucket; the flag rides the collect
        # fetch and a miss replays this site on the exact path.
        spec_cap = max(bucket_for(max(out_capacity // 4, 1)),
                       DeviceTable.EMBED_NROWS_CAP)
        if spec_cap >= out_capacity:
            return out
        flag_key = ("shrinkflag", out_capacity, spec_cap)
        flag_fn = self._traces.get(flag_key)
        if flag_fn is None:
            flag_fn = tpu_jit(
                lambda n: n > jnp.asarray(spec_cap, jnp.int32),
                name="agg_flag")
            self._traces[flag_key] = flag_fn
        ctx.add_flag(site, flag_fn(out.nrows_dev))
        cols = [c.sliced_rows(spec_cap) for c in out.columns]
        return DeviceTable(names, cols, out.nrows_dev, spec_cap)

    def _spec_site_key(self) -> str:
        return "agg:{}:{}:op{}".format(
            tuple(g.key() for g in self.grouping),
            tuple(fn.key() for _, fn in self.agg_specs),
            getattr(self, "_lore_id", 0))

    def _eval_live(self, filters, capacity, cols, aux, nrows, filter_preps,
                   live_in=None):
        """Row-liveness mask: in-bounds (or the input's deferred-compaction
        mask) AND every fused predicate true."""
        if live_in is not None:
            live = live_in
        else:
            live = jnp.arange(capacity, dtype=jnp.int32) < nrows
        for f, preps in zip(filters, filter_preps):
            ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in)
            ctx._prep_iter = iter(preps)
            pred = _walk_eval(f, ctx)
            live = live & pred.data & pred.validity
        return live

    # -- fast path: dictionary-code grouping, no sort -----------------------
    def _build_fast_kernel(self, capacity: int, kinds, gpad: int,
                           filter_preps, key_preps, val_preps,
                           grouping, agg_specs, filters):
        value_exprs = [list(fn.children) for _, fn in agg_specs]
        use_split = self.use_split

        # the named scopes are op metadata: they tell one fusion of
        # jit_agg_fast from another on the device timeline, and cost
        # nothing at run time
        def kernel(cols, aux, nrows, sizes, strides, bases, live_in):
            with jax.named_scope("live_mask"):
                live = self._eval_live(filters, capacity, cols, aux,
                                       nrows, filter_preps, live_in)

            with jax.named_scope("group_ids"):
                gid = jnp.zeros(capacity, dtype=jnp.int32)
                for i, (g, preps, kind) in enumerate(zip(grouping, key_preps, kinds)):
                    ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in)
                    ctx._prep_iter = iter(preps)
                    kv = _walk_eval(g, ctx)
                    if kind == "int":
                        # domain-coded integer key: value - base. The where
                        # runs BEFORE the int32 narrowing — invalid/padding
                        # slots hold arbitrary data, valid ones are inside the
                        # stats bound by the domain superset contract.
                        delta = kv.data.astype(jnp.int64) - bases[i]
                        code = jnp.where(kv.validity, delta,
                                         (sizes[i] - 1).astype(jnp.int64))
                        code = code.astype(jnp.int32)
                    else:
                        code = (kv.data.astype(jnp.int32)
                                if kind == "bool" else kv.data)
                        code = jnp.where(kv.validity, code, sizes[i] - 1)
                    gid = gid + code * strides[i]

            # ---- batched value aggregation ------------------------------
            # All sum-class f64 reductions (Sum/Average/Stddev/Variance)
            # ride ONE batched device pass (ops/segsum.py); group existence
            # and every spec's validity count ride one more
            # (segsum.segment_counts: a one-hot contraction too at small
            # gpad, a scatter above). Min/Max/First/Last and i64 sums stay
            # per-spec (_agg_one).
            with jax.named_scope("agg_values"):
                vvs = []
                for ves, per_child in zip(value_exprs, val_preps):
                    vals = []
                    for ve, preps in zip(ves, per_child):
                        ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in)
                        ctx._prep_iter = iter(preps)
                        vals.append(_walk_eval(ve, ctx))
                    vvs.append(vals)
                svs = [(vv[0].validity & live) if vv else None for vv in vvs]

            # one pass for the live count + every DISTINCT nonnull mask:
            # specs over one input column (sum(x), avg(x), count(x)) share
            # ``validity & live``, so the mask is counted once and its
            # column of mcnt fans back out through mix. Only bare column
            # references are matched: key() is no identity of a computed
            # child (it leaves string literals' values and rand's draws
            # out: pivot's when(p = 'x', v) and when(p = 'y', v) share one)
            with jax.named_scope("valid_counts"):
                masks = [live]
                mix = {}
                seen = {}
                for j, sv in enumerate(svs):
                    if sv is None:
                        continue
                    child = value_exprs[j][0]
                    ckey = (("col", child.ordinal)
                            if isinstance(child, BoundReference)
                            else ("spec", j))
                    if ckey not in seen:
                        seen[ckey] = len(masks)
                        masks.append(sv)
                    mix[j] = seen[ckey]
                mcnt = segment_counts(masks, gid, gpad, capacity)
                nonnulls = {j: mcnt[:, i] for j, i in mix.items()}

            exists = mcnt[:, 0] > 0
            if not grouping:
                # global aggregate: exactly one output row even when the
                # input is empty (count=0, sums NULL — Spark semantics)
                exists = jnp.arange(gpad, dtype=jnp.int32) == 0
            ngroups = jnp.sum(exists.astype(jnp.int32))

            # every output column compacts slot -> dense rank through
            # ONE shared call
            pairs = []
            slot_ix = jnp.arange(gpad, dtype=jnp.int32)
            for i, kind in enumerate(kinds):
                slot = (slot_ix // strides[i]) % sizes[i]
                kvalid = slot != (sizes[i] - 1)
                if kind == "bool":
                    kdata = slot == 1
                elif kind == "int":
                    kdata = (slot.astype(jnp.int64) + bases[i]).astype(
                        grouping[i].data_type.np_dtype)
                else:
                    kdata = slot
                pairs.append((kdata, kvalid))

            fplan = []  # (spec index, kind) riding a batched f64 pass
            for j, (_, fnagg) in enumerate(agg_specs):
                if isinstance(fnagg, (agg.StddevPop, agg.StddevSamp,
                                      agg.VariancePop, agg.VarianceSamp)):
                    fplan.append((j, "var"))
                elif isinstance(fnagg, agg.Average):
                    # decimal averages sum EXACTLY in i64 unscaled space
                    # (_agg_one; Spark computes avg(decimal) from an exact
                    # decimal sum — the split guard's 1e-6 tolerance is not
                    # decimal semantics), so they skip the f64 ride
                    if not isinstance(fnagg.child.data_type, T.DecimalType):
                        fplan.append((j, "avg"))
                elif isinstance(fnagg, agg.Sum) and not isinstance(
                        fnagg.data_type, (T.LongType, T.DecimalType)):
                    # decimal sums are EXACT limb sums (_agg_one), never
                    # the f64 ride
                    fplan.append((j, "sum"))
            # sum/avg ride the split pass; variance means must be EXACT —
            # a mean error d inflates the centered pass by n*d^2 (quadratic
            # amplification the split guard cannot bound)
            splan = [(j, kind) for j, kind in fplan if kind != "var"]
            vplan_j = [j for j, kind in fplan if kind == "var"]
            fcols = [jnp.where(svs[j], vvs[j][0].data.astype(jnp.float64), 0.0)
                     for j, _ in splan]
            # nonnull counts are already scattered (mcnt) — the split
            # guard reuses them instead of scattering its own
            scnt = (jnp.stack([nonnulls[j] for j, _ in splan], axis=1)
                    if splan else None)
            fsums_s = batched_segment_sum_f64(fcols, gid, gpad, capacity,
                                              use_split, counts=scnt)
            def _vdata(j):
                # decimal variance inputs are UNSCALED ints; moments are
                # VALUE-unit doubles (same scaling contract as cpu_agg)
                d = vvs[j][0].data.astype(jnp.float64)
                cdt = agg_specs[j][1].child.data_type
                if isinstance(cdt, T.DecimalType):
                    d = d / jnp.float64(10 ** cdt.scale)
                return d

            vcols = [jnp.where(svs[j], _vdata(j), 0.0) for j in vplan_j]
            fsums_v = batched_segment_sum_f64(vcols, gid, gpad, capacity,
                                              use_split=False)
            fsums = {}
            for i, (j, _) in enumerate(splan):
                fsums[j] = fsums_s[:, i]
            for i, j in enumerate(vplan_j):
                fsums[j] = fsums_v[:, i]

            # second batched pass: centered moments (positive values, so the
            # split path's relative-error guard applies cleanly)
            ccols = []
            for j in vplan_j:
                mean = fsums[j] / jnp.maximum(nonnulls[j], 1)
                ccols.append(jnp.where(
                    svs[j], (_vdata(j) - mean[gid]) ** 2, 0.0))
            csums = batched_segment_sum_f64(ccols, gid, gpad, capacity,
                                            use_split)
            m2s = {j: csums[:, i2] for i2, j in enumerate(vplan_j)}

            fres = {}
            for j, kind in fplan:
                fnagg = agg_specs[j][1]
                nonnull = nonnulls[j]
                has_any = (nonnull > 0) & exists
                s = fsums[j]
                if kind == "sum":
                    fres[j] = (jnp.where(has_any, s, 0.0), has_any)
                elif kind == "avg":
                    fres[j] = (jnp.where(has_any, s / jnp.maximum(nonnull, 1), 0.0),
                               has_any)
                else:
                    if isinstance(fnagg, (agg.StddevPop, agg.VariancePop)):
                        denom = jnp.maximum(nonnull, 1)
                        validity = has_any
                    else:
                        denom = jnp.maximum(nonnull - 1, 1)
                        validity = (nonnull > 1) & exists
                    var = m2s[j] / denom
                    out = jnp.sqrt(var) if isinstance(
                        fnagg, (agg.StddevPop, agg.StddevSamp)) else var
                    fres[j] = (jnp.where(validity, out, 0.0), validity)

            for j, (_, fnagg) in enumerate(agg_specs):
                if j in fres:
                    data, validity = fres[j]
                elif isinstance(fnagg, agg.Count):
                    w = mcnt[:, 0] if fnagg.child is None else nonnulls[j]
                    data, validity = w.astype(jnp.int64), exists
                elif isinstance(fnagg, agg.MergeMoments):
                    data, validity = self._merge_moments(
                        vvs[j], live, gid, gpad, exists)
                else:
                    sd = vvs[j][0].data if vvs[j] else None
                    data, validity = self._agg_one(
                        fnagg, sd, svs[j], live, gid, gpad, exists,
                        capacity, use_split)
                pairs.append((data, validity))
            with jax.named_scope("compact_groups"):
                from spark_rapids_tpu.ops.scatter32 import compact_pairs
                outs, _ = compact_pairs([d for d, _ in pairs],
                                        [v for _, v in pairs], exists, gpad)
            return list(outs), ngroups

        return kernel

    # -- general path: sort-segment -----------------------------------------
    def _build_kernel(self, capacity: int, filter_preps, key_preps, val_preps,
                      grouping, agg_specs, filters):
        value_exprs = [list(fn.children) for _, fn in agg_specs]
        use_split = self.use_split

        def kernel(cols, aux, nrows, live_in):
            with jax.named_scope("live_mask"):
                live = self._eval_live(filters, capacity, cols, aux,
                                       nrows, filter_preps, live_in)

            with jax.named_scope("agg_values"):
                key_vals: List[DevVal] = []
                for g, preps in zip(grouping, key_preps):
                    ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in)
                    ctx._prep_iter = iter(preps)
                    key_vals.append(_walk_eval(g, ctx))
                val_vals = []
                for ves, per_child in zip(value_exprs, val_preps):
                    vals = []
                    for ve, preps in zip(ves, per_child):
                        ctx = EvalCtx(cols, aux, nrows, capacity, live=live_in)
                        ctx._prep_iter = iter(preps)
                        vals.append(_walk_eval(ve, ctx))
                    val_vals.append(vals)

            with jax.named_scope("group_ids"):
                # normalize float keys so grouping matches the CPU oracle
                norm = []
                for kv in key_vals:
                    d = kv.data
                    if jnp.issubdtype(d.dtype, jnp.floating):
                        d = jnp.where(d == 0.0, jnp.zeros_like(d), d)
                    norm.append(DevVal(d, kv.validity))
                key_vals = norm

                if grouping:
                    operands = [(~live).astype(jnp.int32)]  # dead rows last
                    for kv in key_vals:
                        operands.extend(_sortable(kv.data, kv.validity))
                    from spark_rapids_tpu.ops.ordering import lex_sort
                    payload = jnp.arange(capacity, dtype=jnp.int32)
                    sorted_all = lex_sort(operands, payload)
                    perm = sorted_all[-1]
                    s_live = live[perm]
                    s_keys = [DevVal(kv.data[perm], kv.validity[perm])
                              for kv in key_vals]
                    s_vals = [[DevVal(x.data[perm], x.validity[perm])
                               for x in vv] for vv in val_vals]

                    # group boundaries on the CANONICAL operands (raw float
                    # compares would split NaN groups: NaN != NaN); the sort
                    # already emitted every operand in sorted order — compare
                    # those directly instead of re-gathering by perm
                    first = jnp.arange(capacity) == 0
                    changed = jnp.zeros(capacity, dtype=jnp.bool_)
                    for so in sorted_all[1:-1]:
                        changed = changed | (so != jnp.roll(so, 1))
                    new_group = (first | changed) & s_live
                    gid = jnp.cumsum(new_group.astype(jnp.int32)) - 1
                    gid = jnp.where(s_live, gid, capacity - 1)  # park dead rows
                    ngroups = jnp.sum(new_group.astype(jnp.int32))
                else:
                    s_live = live
                    s_keys = []
                    s_vals = val_vals
                    gid = jnp.zeros(capacity, dtype=jnp.int32)
                    ngroups = jnp.asarray(1, dtype=jnp.int32)

            group_live = jnp.arange(capacity, dtype=jnp.int32) < ngroups

            outs = []
            # key columns: scatter first-occurrence values to gid slots
            from spark_rapids_tpu.ops.scatter32 import scatter_pair
            for kv in s_keys:
                tgt = jnp.where(s_live, gid, capacity)
                kd, kvv = scatter_pair(capacity, tgt, kv.data, kv.validity)
                outs.append((kd, kvv & group_live))

            for (name, fnagg), vv in zip(agg_specs, s_vals):
                if isinstance(fnagg, agg.MergeMoments):
                    outs.append(self._merge_moments(vv, s_live, gid,
                                                    capacity, group_live))
                    continue
                sd = vv[0].data if vv else None
                sv = (vv[0].validity & s_live) if vv else None
                outs.append(self._agg_one(fnagg, sd, sv, s_live, gid, capacity,
                                          group_live, capacity, use_split))
            return outs, ngroups

        return kernel

    @staticmethod
    def _merge_moments(vv3, live, gid, nseg, group_live):
        """Numerically stable merge of per-batch moment partials
        (n_i, s_i, m2_i) -> total m2, via Chan's combination
        m2 = sum(m2_i) + sum(n_i * (mean_i - mean_total)^2). All sums run
        exact emulated f64 — the merge table is partials-sized, tiny."""
        nvv, svv, mvv = vv3
        sv = nvv.validity & svv.validity & mvv.validity & live
        n = jnp.where(sv, nvv.data.astype(jnp.float64), 0.0)
        s = jnp.where(sv, svv.data.astype(jnp.float64), 0.0)
        m2 = jnp.where(sv, mvv.data.astype(jnp.float64), 0.0)
        N = jax.ops.segment_sum(n, gid, num_segments=nseg)
        S = jax.ops.segment_sum(s, gid, num_segments=nseg)
        mean_tot = S / jnp.maximum(N, 1.0)
        mean_i = s / jnp.maximum(n, 1.0)
        c = jnp.where(sv, m2 + n * (mean_i - mean_tot[gid]) ** 2, 0.0)
        M2 = jax.ops.segment_sum(c, gid, num_segments=nseg)
        has = (jax.ops.segment_sum(sv.astype(jnp.int32), gid,
                                   num_segments=nseg) > 0) & group_live
        return (jnp.where(has, M2, 0.0), has)

    @staticmethod
    def _agg_one(fnagg, sd, sv, live, gid, nseg, group_live, capacity, use_split):
        """One aggregate over segment ids. ``sd``/``sv``: value data and
        validity aligned with ``gid`` (``sv`` already excludes dead rows);
        ``live``: row liveness (COUNT(*)); ``nseg``: number of segments;
        ``group_live``: which segment slots are real groups."""
        seg = jax.ops
        if isinstance(fnagg, agg.Count):
            w = live if fnagg.child is None else sv
            # capacity < 2^31 always (power-of-two row buckets), so count
            # accumulates natively in i32 and widens to Spark's LONG after
            cnt = seg.segment_sum(w.astype(jnp.int32), gid,
                                  num_segments=nseg).astype(jnp.int64)
            return (cnt, group_live)

        nonnull = seg.segment_sum(sv.astype(jnp.int32), gid, num_segments=nseg)
        has_any = (nonnull > 0) & group_live

        if isinstance(fnagg, agg.Sum):
            if isinstance(fnagg.data_type, T.LongType):
                v = jnp.where(sv, sd.astype(jnp.int64), 0)
                s = seg.segment_sum(v, gid, num_segments=nseg)
                return (s, has_any)
            if isinstance(fnagg.data_type, T.DecimalType):
                return _dec_sum_segments(fnagg.data_type, sd, sv, gid,
                                         nseg, has_any)
            v = jnp.where(sv, sd.astype(jnp.float64), 0.0)
            s = segment_sum_f64(v, gid, nseg, capacity, use_split,
                                counts=nonnull)
            return (jnp.where(has_any, s, 0.0), has_any)

        if isinstance(fnagg, agg.Average):
            if isinstance(fnagg.child.data_type, T.DecimalType):
                # EXACT 128-bit unscaled sum (Spark computes avg(decimal)
                # from an exact decimal sum; riding the f64 split pass
                # would accumulate error per row), ONE sign-magnitude
                # rounding at the final f64 convert + divide. A 128-bit
                # overflow (t3 outside i32) nulls the result, mirroring
                # the Sum path's non-ANSI CheckOverflow semantics.
                hi128, lo128, t3 = _dec_wide_sum_segments(sd, sv, gid, nseg)
                ovf = (t3 > 0x7FFFFFFF) | (t3 < -0x80000000)
                tot = _dec_wide_to_f64(hi128, lo128)
                valid = has_any & ~ovf
                # unscaled exact sum -> VALUE-unit double result (one
                # rounding), matching Cast(decimal->double) semantics
                dscale = jnp.float64(10 ** fnagg.child.data_type.scale)
                return (jnp.where(
                    valid, tot / (jnp.maximum(nonnull, 1) * dscale),
                    0.0), valid)
            v = jnp.where(sv, sd.astype(jnp.float64), 0.0)
            s = segment_sum_f64(v, gid, nseg, capacity, use_split)
            return (jnp.where(has_any, s / jnp.maximum(nonnull, 1), 0.0), has_any)

        if isinstance(fnagg, (agg.StddevPop, agg.StddevSamp, agg.VariancePop, agg.VarianceSamp)):
            sdf = sd.astype(jnp.float64)
            cdt = fnagg.child.data_type
            if isinstance(cdt, T.DecimalType):
                # unscaled decimal ints -> VALUE-unit moments (same
                # scaling contract as cpu_agg / the batched f64 ride)
                sdf = sdf / jnp.float64(10 ** cdt.scale)
            v = jnp.where(sv, sdf, 0.0)
            # EXACT mean: a split-sum mean error d would inflate the
            # centered pass by n*d^2 (quadratic amplification)
            s = segment_sum_f64(v, gid, nseg, capacity, use_split=False)
            mean = s / jnp.maximum(nonnull, 1)
            centered = jnp.where(sv, (sdf - mean[gid]) ** 2, 0.0)
            m2 = segment_sum_f64(centered, gid, nseg, capacity, use_split)
            if isinstance(fnagg, (agg.StddevPop, agg.VariancePop)):
                denom = jnp.maximum(nonnull, 1)
                validity = has_any
            else:
                denom = jnp.maximum(nonnull - 1, 1)
                validity = (nonnull > 1) & group_live
            var = m2 / denom
            out = jnp.sqrt(var) if isinstance(fnagg, (agg.StddevPop, agg.StddevSamp)) else var
            return (jnp.where(validity, out, 0.0), validity)

        if isinstance(fnagg, (agg.Min, agg.Max)) \
                and getattr(sd, "ndim", 1) == 2:
            return _dec128_minmax_segments(
                isinstance(fnagg, agg.Min), sd, sv, gid, nseg, has_any)

        if isinstance(fnagg, (agg.Min, agg.Max)) \
                and use_split and sd.dtype in (jnp.float64, jnp.int64):
            # native-32-bit two-pass limb reduction (ops/segsum.py) — the
            # emulated-64 scatter compare-select it replaces dominates
            # whole queries at large segment counts
            from spark_rapids_tpu.ops.segsum import segment_minmax_64
            r = segment_minmax_64(isinstance(fnagg, agg.Min), sd, sv, gid, nseg)
            return (jnp.where(has_any, r, jnp.zeros_like(r)), has_any)

        if isinstance(fnagg, (agg.Min, agg.Max)):
            dt = sd.dtype
            if jnp.issubdtype(dt, jnp.floating):
                ident = jnp.asarray(jnp.inf if isinstance(fnagg, agg.Min) else -jnp.inf, dtype=dt)
            elif dt == jnp.bool_:
                sd = sd.astype(jnp.int32)
                dt = jnp.int32
                ident = jnp.asarray(1 if isinstance(fnagg, agg.Min) else 0, dtype=dt)
            else:
                info = jnp.iinfo(dt)
                ident = jnp.asarray(info.max if isinstance(fnagg, agg.Min) else info.min, dtype=dt)
            v = jnp.where(sv, sd, ident)
            if isinstance(fnagg, agg.Min):
                r = seg.segment_min(v, gid, num_segments=nseg)
            else:
                r = seg.segment_max(v, gid, num_segments=nseg)
            if isinstance(fnagg.data_type, T.BooleanType):
                r = r.astype(jnp.bool_)
            zero = jnp.zeros_like(r)
            return (jnp.where(has_any, r, zero), has_any)

        if isinstance(fnagg, (agg.CollectList, agg.CollectSet)):
            from spark_rapids_tpu.ops.ordering import comparable_operands
            keep = sv
            sdv = sd
            gidv = gid
            if isinstance(fnagg, agg.CollectSet):
                from spark_rapids_tpu.ops.ordering import lex_sort
                # distinct: re-sort by (gid, value) and keep group-local
                # first occurrences
                ops = comparable_operands(
                    jnp.where(sv, sd, jnp.zeros_like(sd)))
                res = lex_sort(
                    [gid, (~sv).astype(jnp.int32)] + ops,
                    jnp.arange(capacity, dtype=jnp.int32))
                gidv = res[0]
                sflag = res[1] == 0
                perm2 = res[-1]
                sdv = sd[perm2]
                same = gidv == jnp.roll(gidv, 1)
                for o in res[2:-1]:
                    same = same & (o == jnp.roll(o, 1))
                first = jnp.arange(capacity) == 0
                keep = sflag & (first | ~same)
            from spark_rapids_tpu.ops.scatter32 import scatter_set
            cpos = jnp.cumsum(keep.astype(jnp.int32)) - 1
            etgt = jnp.where(keep, cpos, capacity)
            elements = scatter_set(capacity, etgt, sdv, mode="drop")
            evalid = jnp.zeros(capacity, dtype=jnp.bool_).at[etgt].set(
                True, mode="drop")
            counts = seg.segment_sum(keep.astype(jnp.int32), gidv,
                                     num_segments=nseg)
            offsets = jnp.concatenate(
                [jnp.zeros(1, dtype=jnp.int32),
                 jnp.cumsum(counts).astype(jnp.int32)])
            # empty array (not null) for groups whose values were all null
            return ((offsets, elements, evalid), group_live)

        if isinstance(fnagg, agg.Percentile):
            from spark_rapids_tpu.ops.ordering import comparable_operands, lex_sort
            ops = comparable_operands(jnp.where(sv, sd, jnp.zeros_like(sd)))
            res = lex_sort(
                [gid, (~sv).astype(jnp.int32)] + ops,
                jnp.arange(capacity, dtype=jnp.int32))
            gidv = res[0]
            perm2 = res[-1]
            sdv = sd[perm2].astype(jnp.float64)
            svv = sv[perm2]
            nn2 = seg.segment_sum(svv.astype(jnp.int32), gidv,
                                  num_segments=nseg)
            start = seg.segment_min(jnp.arange(capacity, dtype=jnp.int32),
                                    gidv, num_segments=nseg)
            k = (nn2 - 1).astype(jnp.float64) * fnagg.percentage
            klo = jnp.floor(k).astype(jnp.int32)
            khi = jnp.ceil(k).astype(jnp.int32)
            safe_s = jnp.clip(start, 0, capacity - 1)
            vlo = sdv[jnp.clip(safe_s + klo, 0, capacity - 1)]
            vhi = sdv[jnp.clip(safe_s + khi, 0, capacity - 1)]
            out = vlo + (vhi - vlo) * (k - klo)
            validity = (nn2 > 0) & group_live
            return (jnp.where(validity, out, 0.0), validity)

        if isinstance(fnagg, (agg.First, agg.Last)):
            idx = jnp.arange(capacity, dtype=jnp.int32)
            pick_mask = sv if fnagg.ignore_nulls else live
            sentinel = capacity if isinstance(fnagg, agg.First) else -1
            pos = jnp.where(pick_mask, idx, sentinel)
            if isinstance(fnagg, agg.First):
                chosen = seg.segment_min(pos, gid, num_segments=nseg)
            else:
                chosen = seg.segment_max(pos, gid, num_segments=nseg)
            got = (chosen >= 0) & (chosen < capacity) & group_live
            safe = jnp.clip(chosen, 0, capacity - 1)
            data = sd[safe]
            # chosen rows are live by construction, so sv at them equals the
            # raw value validity — right for both ignore_nulls modes
            validity = got & sv[safe]
            return (jnp.where(validity, data, jnp.zeros_like(data)), validity)

        raise ColumnarProcessingError(f"device aggregate {type(fnagg).__name__}")

    def describe(self):
        fused = f", fusedFilters={len(self.filters)}" if self.filters else ""
        return (f"TpuHashAggregate[keys={self.grouping_names}, "
                f"aggs={[n for n, _ in self.agg_specs]}{fused}]")
