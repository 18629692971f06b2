"""Bloom-filter runtime join filtering (reference: SURVEY.md §2.9 JNI
BloomFilter; Spark's InjectRuntimeFilter plans BloomFilterAggregate on the
build side and BloomFilterMightContain on the probe side of selective
joins — sql-plugin shims GpuBloomFilterAggregate / GpuBloomFilterMightContain).

TPU-first representation: the filter is a device BOOL array of ``num_bits``
slots (XLA scatters/gathers booleans natively; a packed word layout would
only add emulated shift chains). k bit indexes derive from one xxhash64
per value via Spark's h1 + i*h2 double-hashing over the 64-bit hash's
halves. Building is one scatter-max over the build keys; membership is k
gathers ANDed — both fuse into surrounding programs.

Surface: ``build_bloom_filter(df, column)`` aggregates a DataFrame's
column into a BloomFilter handle (the BloomFilterAggregate analog), and
``F.might_contain(bloom, expr)`` is the probe-side expression. Note on
profitability: with static-shape kernels a bloom pre-filter does not
shrink per-operator compute (buckets stay capacity-sized); it pays where
row COUNTS matter — before a shuffle exchange or to cut matched output
rows — which is why it is an explicit tool, not an unconditional rewrite."""

from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.ops.expr import (
    DevVal,
    EvalCtx,
    Expression,
    NodePrep,
    PrepCtx,
)

DEFAULT_NUM_BITS = 1 << 20
DEFAULT_NUM_HASHES = 3


def _hash_split(h):
    h = h.astype(jnp.uint64)
    h1 = (h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    h2 = (h >> jnp.uint64(32)).astype(jnp.uint32)
    return h1, h2


def _bit_indexes_dev(data, num_bits: int, k: int) -> List[jax.Array]:
    from spark_rapids_tpu.ops.hashfns import xxhash64_device
    h = xxhash64_device([(data.astype(jnp.int64),
                          jnp.ones(data.shape[0], jnp.bool_), T.LONG)])
    h1, h2 = _hash_split(h)
    nb = jnp.uint32(num_bits)
    return [((h1 + jnp.uint32(i) * h2) % nb).astype(jnp.int32)
            for i in range(k)]


class BloomFilter:
    """Device-resident filter handle (the materialized
    BloomFilterAggregate result). ``host_bits`` backs the might_contain
    expression's aux input so compiled traces are SHARED across filters
    of the same shape (the device copy is content-interned by
    dispatch.device_const)."""

    def __init__(self, bits: jax.Array, num_hashes: int):
        from spark_rapids_tpu.dispatch import host_fetch
        self.bits = bits
        self.num_bits = int(bits.shape[0])
        self.num_hashes = int(num_hashes)
        self.host_bits = np.asarray(host_fetch(bits))

    def approx_set_bits(self) -> int:
        from spark_rapids_tpu.dispatch import host_fetch
        return int(host_fetch(jnp.sum(self.bits.astype(jnp.int32))))


_BUILD_CACHE = {}


def _build_kernel(num_bits: int, k: int, cap: int):
    key = (num_bits, k, cap)
    fn = _BUILD_CACHE.get(key)
    if fn is None:
        from spark_rapids_tpu.dispatch import tpu_jit

        def build(data, valid):
            bits = jnp.zeros(num_bits, jnp.bool_)
            for idx in _bit_indexes_dev(data, num_bits, k):
                tgt = jnp.where(valid, idx, num_bits)
                bits = bits.at[tgt].max(True, mode="drop")
            return bits

        fn = tpu_jit(build, name="bloom_build")
        _BUILD_CACHE[key] = fn
    return fn


def build_bloom_filter(df, column: str,
                       num_bits: int = None,
                       num_hashes: int = None) -> BloomFilter:
    """Aggregate ``df[column]`` (integral type) into a BloomFilter — the
    engine's bloom_filter_agg. Executes the DataFrame's plan on device and
    folds every batch into one bit array."""
    if num_bits is None or num_hashes is None:
        from spark_rapids_tpu.conf import (
            BLOOM_DEFAULT_NUM_BITS,
            BLOOM_DEFAULT_NUM_HASHES,
        )
        conf = getattr(df.session, "conf", None)
        if num_bits is None:
            num_bits = (conf.get_entry(BLOOM_DEFAULT_NUM_BITS)
                        if conf else DEFAULT_NUM_BITS)
        if num_hashes is None:
            num_hashes = (conf.get_entry(BLOOM_DEFAULT_NUM_HASHES)
                          if conf else DEFAULT_NUM_HASHES)
    schema = dict(df.select(column).plan.output_schema())
    if not isinstance(schema[column], T.IntegralType):
        raise ColumnarProcessingError(
            f"bloom filter column {column} must be integral, got "
            f"{schema[column].simple_string()}")
    cols, _nrows = df.select(column).to_device_arrays()
    data, valid = cols[column][0], cols[column][1]
    fn = _build_kernel(num_bits, num_hashes, int(data.shape[0]))
    return BloomFilter(fn(data, valid), num_hashes)


class BloomFilterMightContain(Expression):
    """might_contain(bloom, e) — True when e MAY be in the build set (no
    false negatives), null for null input. The bit array rides as a
    device-resident constant captured per plan (the reference ships the
    serialized bloom as a GpuLiteral into the probe-side expression)."""

    def __init__(self, bloom: BloomFilter, child: Expression):
        self.bloom = bloom
        self.children = (child,)

    @property
    def data_type(self):
        return T.BOOLEAN

    def key(self):
        # content-independent: the bit array rides as an aux input, so
        # every bloom of the same shape shares one compiled trace
        return ("mightcontain", self.bloom.num_bits,
                self.bloom.num_hashes, self.children[0].key())

    def prep(self, pctx, child_preps):
        return NodePrep(
            aux_slots=(pctx.add_aux(self.bloom.host_bits),))

    def with_children(self, children):
        return BloomFilterMightContain(self.bloom, children[0])

    @property
    def device_supported(self):
        return isinstance(self.children[0].data_type, T.IntegralType)

    def eval_cpu(self, table: HostTable) -> HostColumn:
        c = self.children[0].eval_cpu(table)
        # the host copy is cached at filter build; re-fetching the full
        # bits array per batch would stall the pipeline ~0.1s each
        bits = self.bloom.host_bits
        from spark_rapids_tpu.ops.hashfns import xxhash64_host
        n = len(c)
        out = np.zeros(n, dtype=np.bool_)
        for i in range(n):
            if not c.validity[i]:
                continue
            h = xxhash64_host(
                [(int(c.data[i]), True, T.LONG)]) & 0xFFFFFFFFFFFFFFFF
            h1 = h & 0xFFFFFFFF
            h2 = h >> 32
            hit = True
            for j in range(self.bloom.num_hashes):
                ix = ((h1 + j * h2) & 0xFFFFFFFF) % self.bloom.num_bits
                if not bits[ix]:
                    hit = False
                    break
            out[i] = hit
        return HostColumn(T.BOOLEAN, out, c.validity.copy())

    def eval_dev(self, ctx: EvalCtx, child_vals, prep) -> DevVal:
        (c,) = child_vals
        bits = ctx.aux[prep.aux_slots[0]]
        hit = jnp.ones(ctx.capacity, jnp.bool_)
        for idx in _bit_indexes_dev(c.data, self.bloom.num_bits,
                                    self.bloom.num_hashes):
            hit = hit & bits[idx]
        return DevVal(hit, c.validity)
