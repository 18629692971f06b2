"""Device partitioners + contiguous split.

Reference (SURVEY.md §2.6): GpuPartitioning.sliceInternalOnGpuAndClose
(GpuPartitioning.scala:64 — device split into per-partition contiguous
tables), GpuHashPartitioningBase (murmur3-compatible, pmod), GpuRange-
Partitioner (sampled bounds, CPU-row-order compatible), GpuRoundRobin-
Partitioning, GpuSinglePartitioning.

TPU design: a jitted kernel computes each row's partition id, sorts rows by
(pid) with a payload permutation — one lax.sort = the contiguous_split —
and segment-counts give the partition boundaries. The host then slices the
sorted columns per partition (zero-copy views after one D2H)."""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
from spark_rapids_tpu.dispatch import tpu_jit
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceTable, HostColumn, HostTable
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.ops.expr import Expression, compile_project
from spark_rapids_tpu.shuffle.hashing import (
    SPARK_SEED,
    murmur3_hash_device,
    string_dict_bytes,
)


class Partitioner:
    num_partitions: int

    def partition_ids(self, table: DeviceTable):
        """Return an int32 device array of partition ids for [0, capacity)
        (padding rows get id 0; they are dropped by the split)."""
        raise NotImplementedError


class HashPartitioner(Partitioner):
    """Spark-compatible: pmod(murmur3(keys, seed=42), n)."""

    def __init__(self, keys: Sequence[Expression], num_partitions: int):
        self.keys = list(keys)
        self.num_partitions = num_partitions

    def partition_ids(self, table: DeviceTable):
        from spark_rapids_tpu.ops.expr import cached_kernel
        key_cols = compile_project(self.keys, table)
        string_bytes = {}
        datas, valids, dts = [], [], []
        for i, c in enumerate(key_cols):
            datas.append(c.data)
            valids.append(c.validity)
            dts.append(c.dtype)
            if isinstance(c.dtype, T.StringType):
                mat, lens = string_dict_bytes(c.dictionary)
                string_bytes[i] = (jnp.asarray(mat), jnp.asarray(lens))

        n = self.num_partitions
        # PROCESS-WIDE kernel cache keyed by structure: partitioner
        # instances are per-plan, and a per-instance trace dict made
        # every fresh conversion of a repeated template re-trace the
        # partition-id kernel (the VERDICT r1 per-instance-jit bug
        # class, surfaced by the executable cache's racing misses)
        tkey = ("hashpart", table.capacity,
                tuple(str(d) for d in dts),
                tuple((i, sb[0].shape) for i, sb in string_bytes.items()),
                n)
        dts_c = list(dts)

        def build():
            def run(datas, valids, sbytes):
                cols = [(d, v, dt) for d, v, dt in zip(datas, valids, dts_c)]
                h = murmur3_hash_device(cols, SPARK_SEED, sbytes)
                # Spark pmod: ((h % n) + n) % n
                m = h % jnp.int32(n)
                return jnp.where(m < 0, m + n, m)
            return tpu_jit(run, name="partition_ids")

        fn = cached_kernel(tkey, build)
        return fn(tuple(datas), tuple(valids), string_bytes)


class RoundRobinPartitioner(Partitioner):
    def __init__(self, num_partitions: int, start: int = 0):
        self.num_partitions = num_partitions
        self.start = start

    def partition_ids(self, table: DeviceTable):
        n = self.num_partitions
        return ((jnp.arange(table.capacity, dtype=jnp.int32) + self.start) % n)


class SinglePartitioner(Partitioner):
    num_partitions = 1

    def partition_ids(self, table: DeviceTable):
        return jnp.zeros(table.capacity, dtype=jnp.int32)


class RangePartitioner(Partitioner):
    """Sampled-bounds range partitioning. Bounds come from a host sample of
    the SAME key projection (order matches the CPU sort order); rows map to
    partitions by lexicographic comparison against the bounds on device.
    String keys compare by order-preserving dictionary code."""

    def __init__(self, keys: Sequence[Expression], num_partitions: int,
                 ascending: Optional[Sequence[bool]] = None,
                 samples_per_partition: int = 100):
        self.keys = list(keys)
        self.num_partitions = num_partitions
        self.ascending = list(ascending) if ascending else [True] * len(self.keys)
        self.samples_per_partition = samples_per_partition
        self._bounds: Optional[List[HostColumn]] = None

    def compute_bounds_multi(self, tables: Sequence[DeviceTable]):
        """Sample key rows across ALL input batches (Spark samples the whole
        input, not the first batch) -> num_partitions-1 bounds."""
        per_batch: List[List[HostColumn]] = []
        for t in tables:
            if t.num_rows == 0:
                continue
            key_cols = compile_project(self.keys, t)
            per_batch.append([c.to_host(t.num_rows) for c in key_cols])
        if not per_batch:
            self._bounds = []
            return
        merged = [
            HostColumn(per_batch[0][i].dtype,
                       np.concatenate([b[i].data for b in per_batch]),
                       np.concatenate([b[i].validity for b in per_batch]))
            for i in range(len(per_batch[0]))]
        self._compute_bounds_host(merged)

    def compute_bounds(self, table: DeviceTable):
        """Single-batch bounds (multi-batch callers use compute_bounds_multi)."""
        if table.num_rows == 0 or self.num_partitions <= 1:
            self._bounds = []
            return
        key_cols = compile_project(self.keys, table)
        self._compute_bounds_host([c.to_host(table.num_rows) for c in key_cols])

    def _compute_bounds_host(self, host_cols: List[HostColumn]):
        n = len(host_cols[0].data)
        if n == 0 or self.num_partitions <= 1:
            self._bounds = []
            return
        rng = np.random.default_rng(42)
        k = min(n, self.samples_per_partition * self.num_partitions)
        idx = np.sort(rng.choice(n, size=k, replace=False))
        sampled = [HostColumn(c.dtype, c.data[idx], c.validity[idx])
                   for c in host_cols]
        from spark_rapids_tpu.plan.nodes import SortOrder, _stable_sort_indices
        orders = [SortOrder(kexpr, asc)
                  for kexpr, asc in zip(self.keys, self.ascending)]
        perm = _stable_sort_indices(sampled, orders, k)
        bound_pos = [int(k * (i + 1) / self.num_partitions)
                     for i in range(self.num_partitions - 1)]
        bound_pos = [min(p, k - 1) for p in bound_pos]
        sel = perm[bound_pos]
        self._bounds = [HostColumn(c.dtype, c.data[sel], c.validity[sel])
                        for c in sampled]

    def partition_ids(self, table: DeviceTable):
        if self._bounds is None:
            self.compute_bounds(table)
        if not self._bounds or self.num_partitions <= 1:
            return jnp.zeros(table.capacity, dtype=jnp.int32)
        key_cols = compile_project(self.keys, table)
        nb = len(self._bounds[0].data)

        # per key: device data + bound values in comparable integer space
        pid = jnp.zeros(table.capacity, dtype=jnp.int32)
        # lexicographic: row > bound_j  <=>  exists first k where differs and
        # row_k > bound_jk (per direction). Compute (cap, nb) "row after
        # bound" matrix iteratively from last key to first.
        after = None  # row strictly after bound (in sort order)
        for c, bcol, asc in zip(reversed(key_cols),
                                list(reversed(self._bounds)),
                                list(reversed(self.ascending))):
            d, v = self._comparable(c)
            bd, bv, bexact = self._comparable_bounds(bcol, c)
            dd = d[:, None]
            vv = v[:, None]
            # Spark null ordering in range partitioning: nulls first (asc).
            # Inexact bounds (absent from this batch's dictionary) sit just
            # BELOW the entry whose code they borrowed: >= means after.
            cmp_gt = jnp.where(bexact, dd > bd, dd >= bd)
            gt = jnp.where(vv & bv, cmp_gt, vv & ~bv)
            lt = jnp.where(vv & bv, dd < bd, ~vv & bv)
            if not asc:
                gt, lt = lt, gt
            eq = ~gt & ~lt
            after = gt if after is None else (gt | (eq & after))
        pid = jnp.sum(after.astype(jnp.int32), axis=1)
        return pid

    @staticmethod
    def _comparable(c):
        d = c.data
        if jnp.issubdtype(d.dtype, jnp.floating):
            d = jnp.where(d == 0.0, jnp.zeros_like(d), d)
        if d.dtype == jnp.bool_:
            d = d.astype(jnp.int32)
        return d, c.validity

    def _comparable_bounds(self, bcol: HostColumn, dev_col):
        """Bounds as device row-vectors (values, validity, is_exact);
        strings map into the column's dictionary code space. A bound value
        ABSENT from this batch's dictionary takes the code of the next
        larger entry with is_exact=False: rows carrying that code are
        strictly greater than the bound, and the comparison kernel treats
        code >= bound_code as 'after' — without the flag, equal-to-next-
        entry rows would land in different partitions across batches
        (ADVICE r1: breaks the range-partition ordering invariant)."""
        if isinstance(bcol.dtype, T.StringType):
            dictionary = dev_col.dictionary
            if dictionary is None or len(dictionary) == 0:
                codes = np.zeros(len(bcol.data), dtype=np.int32)
                exact = np.zeros(len(bcol.data), dtype=np.bool_)
            else:
                codes = np.searchsorted(dictionary, bcol.data.astype(object),
                                        side="left").astype(np.int32)
                safe = np.minimum(codes, len(dictionary) - 1)
                exact = (codes < len(dictionary)) & (
                    dictionary[safe] == bcol.data.astype(object))
                # codes == len(dictionary) stays UN-clamped: the bound is
                # above every entry of this batch, so no row may compare
                # 'after' it (clamping to the last entry would push rows
                # equal to that entry across the bound)
            return (jnp.asarray(codes)[None, :],
                    jnp.asarray(bcol.validity)[None, :],
                    jnp.asarray(exact)[None, :])
        vals = bcol.data
        if np.issubdtype(vals.dtype, np.floating):
            vals = np.where(vals == 0.0, 0.0, vals)
        if vals.dtype == np.bool_:
            vals = vals.astype(np.int32)
        return (jnp.asarray(vals)[None, :],
                jnp.asarray(bcol.validity)[None, :],
                jnp.ones((1, len(bcol.data)), dtype=jnp.bool_))


class _SplitKernel:
    """pid -> (sorted columns, per-partition counts); one lax.sort."""

    _traces = {}

    @classmethod
    def run(cls, table: DeviceTable, pids, num_partitions: int):
        key = (table.capacity, num_partitions, table.schema_key()[0])
        fn = cls._traces.get(key)
        if fn is None:
            cap = table.capacity
            nparts = num_partitions

            def split(datas, valids, pids, nrows):
                live = jnp.arange(cap, dtype=jnp.int32) < nrows
                sort_pid = jnp.where(live, pids, nparts)  # padding last
                operands = [sort_pid, jnp.arange(cap, dtype=jnp.int32)]
                _, perm = jax.lax.sort(operands, num_keys=1, is_stable=True)
                counts = jax.ops.segment_sum(
                    jnp.where(live, 1, 0), jnp.clip(sort_pid, 0, nparts),
                    num_segments=nparts + 1)[:nparts]
                outs = [(d[perm], v[perm]) for d, v in zip(datas, valids)]
                return outs, counts

            fn = tpu_jit(split, name="partition_split")
            cls._traces[key] = fn
        datas = tuple(c.data for c in table.columns)
        valids = tuple(c.validity for c in table.columns)
        return fn(datas, valids, pids, table.nrows_dev)


def split_by_partition(table: DeviceTable, partitioner: Partitioner
                       ) -> List[HostTable]:
    """Contiguous split: one device sort by pid, one D2H, then zero-copy
    host slices per partition (sliceInternalOnGpuAndClose analog; the host
    tables feed the shuffle serializer)."""
    pids = partitioner.partition_ids(table)
    outs, counts = _SplitKernel.run(table, pids, partitioner.num_partitions)
    counts = np.asarray(jax.device_get(counts))
    # live rows sort to the front: transfer only the live bucket, not padding
    from spark_rapids_tpu.columnar import bucket_for
    k = bucket_for(max(int(counts.sum()), 1))
    k = min(k, table.capacity)
    host_datas = [np.asarray(jax.device_get(d[:k])) for d, _ in outs]
    host_valids = [np.asarray(jax.device_get(v[:k])) for _, v in outs]

    results: List[HostTable] = []
    start = 0
    for p in range(partitioner.num_partitions):
        cnt = int(counts[p])
        cols = []
        for c, d, v in zip(table.columns, host_datas, host_valids):
            dd = d[start:start + cnt]
            vv = np.ascontiguousarray(v[start:start + cnt])
            # decode_host rebuilds the LOGICAL host column (string
            # dictionary decode, dec128 limb recombination)
            cols.append(c.decode_host(dd, vv))
        results.append(HostTable(table.names, cols))
        start += cnt
    return results
