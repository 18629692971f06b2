"""Host and device tables (batches of columns).

Reference surface: ai.rapids.cudf Table + Spark ColumnarBatch. A DeviceTable
is the unit that flows between TPU execs; a HostTable is the CPU-fallback /
transition representation (GpuRowToColumnarExec / GpuColumnarToRowExec analog
lives in overrides/transitions.py).
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
from spark_rapids_tpu.dispatch import (
    count_host_sync,
    count_pad_waste,
    host_fetch,
    phase_span,
    tpu_jit,
)
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import (
    DeviceColumn,
    HostColumn,
    bucket_for,
    stage_upload,
)
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.obs.spans import span

#: jitted per-(recipe, capacity) H2D assemble kernels (see stage_upload):
#: one device program rebuilds every column's logical dtype + validity from
#: the fast-transferring staged arrays in a single dispatch.
_ASSEMBLE_CACHE: Dict[tuple, object] = {}


def _get_assemble(recipes: tuple, cap: int):
    key = (recipes, cap)
    fn = _ASSEMBLE_CACHE.get(key)
    if fn is None:
        from spark_rapids_tpu.ops.limbs import f64_bits_hi_lo

        def assemble(arrays, nrows):
            row_mask = jnp.arange(cap, dtype=jnp.int32) < nrows
            outs = []
            i = 0
            for kind, vkind, _ in recipes:
                if kind == "f64bits":
                    hi, lo = f64_bits_hi_lo(arrays[i])
                    h64 = hi.astype(jnp.float64)
                    l64 = lo.astype(jnp.float64)
                    # emulated f64 add flushes -0.0 + -0.0 to +0.0; take hi
                    # directly for zeros so the signed zero survives
                    data = jnp.where((h64 == 0.0) & (l64 == 0.0), h64,
                                     h64 + l64)
                    i += 1
                elif kind == "dec128":
                    data = jnp.stack([arrays[i], arrays[i + 1]], axis=1)
                    i += 2
                elif kind in ("u32", "u8codes", "u16codes"):
                    data = arrays[i].astype(jnp.int32)
                    i += 1
                elif kind == "bool8":
                    data = arrays[i] != 0
                    i += 1
                else:
                    data = arrays[i]
                    i += 1
                if vkind == "ones":
                    validity = row_mask
                else:
                    validity = arrays[i] != 0
                    i += 1
                outs.append((data, validity))
            return outs

        fn = tpu_jit(assemble, name="assemble")
        _ASSEMBLE_CACHE[key] = fn
    return fn


def split_f64_on_device() -> bool:
    """Whether a staged landing hands a DOUBLE over as its raw 64-bit
    words for the assemble program to split into the device's (f32,
    f32) pair (recipe ``f64bits``): every backend but the CPU, whose
    f64 is exact and lands as it is."""
    return jax.default_backend() != "cpu"


def _has_nested(host: HostTable) -> bool:
    """A nested column sends a whole batch past the staged landing."""
    return any(isinstance(c.dtype, (T.ArrayType, T.StructType, T.MapType))
               for c in host.columns)


def f64_bits_columns(host: HostTable) -> int:
    """DOUBLE columns of ``host`` that DeviceTable.from_host lands as
    ``f64bits`` (split on the device): none on the CPU backend, nor in a
    batch with a nested column (it lands column by column)."""
    if not split_f64_on_device() or _has_nested(host):
        return 0
    return sum(1 for c in host.columns if isinstance(c.dtype, T.DoubleType))


#: jitted pack kernels for DeviceTable.to_host, keyed by (kinds, k, cap)
_PACK_CACHE: Dict[tuple, object] = {}

#: host tables holding a device-resident cache (weak: dropping the table
#: drops its device image); evicted under memory pressure (runtime/retry.py)
_CACHED_TABLES = None  # lazy weakref.WeakSet


#: tables that are capacity-sharing VIEWS of one source (a local shuffle
#: split's per-partition masks): concatenating them only multiplies
#: capacity, so coalesce streams them (weak: dropping the table drops it)
_SHARED_VIEWS = None


def mark_shared_view(table: "DeviceTable", group=None) -> None:
    """``group`` identifies ONE split execution: views carrying the same
    non-None group have DISJOINT masks by construction and may merge."""
    global _SHARED_VIEWS
    if _SHARED_VIEWS is None:
        import weakref
        _SHARED_VIEWS = weakref.WeakKeyDictionary()
    _SHARED_VIEWS[table] = group


def is_shared_view(table: "DeviceTable") -> bool:
    return _SHARED_VIEWS is not None and table in _SHARED_VIEWS


def view_group(table: "DeviceTable"):
    return _SHARED_VIEWS.get(table) if _SHARED_VIEWS is not None else None


def mergeable_views(a: "DeviceTable", b: "DeviceTable") -> bool:
    """May two masked views merge by mask union? Requires the SAME device
    buffers AND the same split-execution group — same buffers alone is
    not enough (two filters of one scan share buffers with OVERLAPPING
    masks; OR-ing those would dedupe rows)."""
    ga = view_group(a)
    return (ga is not None and ga is view_group(b)
            and a.live is not None and b.live is not None
            and a.capacity == b.capacity
            and len(a.columns) == len(b.columns)
            and all(x.data is y.data and x.validity is y.validity
                    for x, y in zip(a.columns, b.columns)))


def union_views(a: "DeviceTable", b: "DeviceTable") -> "DeviceTable":
    """Merge two same-split masked views by OR-ing liveness — zero data
    movement, one downstream kernel instead of two. Masks are disjoint
    (split partitions), so row counts add."""
    out = DeviceTable(a.names, a.columns, a.nrows_dev + b.nrows_dev,
                      a.capacity, live=a.live | b.live)
    mark_shared_view(out, view_group(a))
    return out


def merge_split_views(batches):
    """Generator: mask-union consecutive same-split views. For consumers
    that are partition-structure-blind (aggregate re-groups everything
    anyway), a repartition's k per-partition views collapse back into ONE
    masked batch — one downstream kernel instead of k full-capacity ones
    (q7-style repartition->agg was paying 8x)."""
    cur = None
    for b in batches:
        if cur is not None and mergeable_views(cur, b):
            cur = union_views(cur, b)
        else:
            if cur is not None:
                yield cur
            cur = b
    if cur is not None:
        yield cur


def register_device_cache(host: "HostTable") -> None:
    global _CACHED_TABLES
    if _CACHED_TABLES is None:
        import weakref
        _CACHED_TABLES = weakref.WeakSet()
    _CACHED_TABLES.add(host)


def evict_device_caches() -> int:
    """Drop every cached device image (called on device OOM before spill
    replay — cached scans are the lowest-priority device residents)."""
    if _CACHED_TABLES is None:
        return 0
    n = 0
    for t in list(_CACHED_TABLES):
        if t._cache.pop("device", None) is not None:
            n += 1
    return n


def _pack_kind(c: DeviceColumn) -> str:
    dt = c.data.dtype
    if getattr(c.data, "ndim", 1) == 2:
        if dt == jnp.int64:
            return "dec128"
        raise ColumnarProcessingError(f"unpackable 2-D device dtype {dt}")
    for kind, want in (("f64", jnp.float64), ("i64", jnp.int64),
                       ("i32", jnp.int32), ("f32", jnp.float32),
                       ("i16", jnp.int16), ("i8", jnp.int8),
                       ("bool", jnp.bool_)):
        if dt == want:
            return kind
    raise ColumnarProcessingError(f"unpackable device dtype {dt}")


def _u32_units(kind: str) -> int:
    return {"f64": 2, "i64": 2, "dec128": 4, "i32": 1, "f32": 1}.get(kind, 0)


def _get_pack(kinds: tuple, k: int, cap: int, n_extra: int = 0):
    """One jitted program bitcasting every column (data + validity) into a
    single u32 buffer: f64 as an exact hi/lo f32 split on TPU (f64 storage
    IS an f32 pair there; CPU bitcasts natively), i64 as hi/lo words, small
    ints and validities byte-packed 4-per-u32 at the tail.

    ``n_extra`` i32 scalars (the live row count + pending speculation
    flags — runtime/speculation.py) prepend as a header so the whole
    result, its size, and its validity arrive in ONE device fetch."""
    cpu = jax.default_backend() == "cpu"
    key = (kinds, k, cap, cpu, n_extra)
    fn = _PACK_CACHE.get(key)
    if fn is None:
        def pack(cols, extras):
            u32s, u8s = [], []
            for (data, _), kind in zip(cols, kinds):
                d = data[:k]
                if kind == "f64":
                    if cpu:
                        u32s.append(jax.lax.bitcast_convert_type(
                            d, jnp.uint32).reshape(-1))
                    else:
                        from spark_rapids_tpu.ops.segsum import split_f64_hi_lo
                        hi, lo = split_f64_hi_lo(d)
                        u32s.append(jax.lax.bitcast_convert_type(hi, jnp.uint32))
                        u32s.append(jax.lax.bitcast_convert_type(lo, jnp.uint32))
                elif kind == "i64":
                    hi = (d >> 32).astype(jnp.int32)
                    lo = (d & 0xFFFFFFFF).astype(jnp.uint32)
                    u32s.append(jax.lax.bitcast_convert_type(hi, jnp.uint32))
                    u32s.append(lo)
                elif kind == "dec128":
                    for limb in (d[:, 0], d[:, 1]):
                        u32s.append(jax.lax.bitcast_convert_type(
                            (limb >> 32).astype(jnp.int32), jnp.uint32))
                        u32s.append((limb & 0xFFFFFFFF).astype(jnp.uint32))
                elif kind in ("i32", "f32"):
                    u32s.append(jax.lax.bitcast_convert_type(d, jnp.uint32))
                elif kind == "i16":
                    u8s.append(jax.lax.bitcast_convert_type(
                        d, jnp.uint8).reshape(-1))
                elif kind == "i8":
                    u8s.append(jax.lax.bitcast_convert_type(d, jnp.uint8))
                else:  # bool
                    u8s.append(d.astype(jnp.uint8))
            for (_, validity), _kind in zip(cols, kinds):
                u8s.append(validity[:k].astype(jnp.uint8))
            u8cat = jnp.concatenate(u8s)
            padlen = (-u8cat.shape[0]) % 4
            if padlen:
                u8cat = jnp.concatenate(
                    [u8cat, jnp.zeros(padlen, dtype=jnp.uint8)])
            tail = jax.lax.bitcast_convert_type(
                u8cat.reshape(-1, 4), jnp.uint32)
            parts = [a for a in u32s] + [tail]
            if n_extra:
                head = jax.lax.bitcast_convert_type(
                    extras.astype(jnp.int32), jnp.uint32)
                parts = [head] + parts
            return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

        fn = tpu_jit(pack, name="d2h_pack")
        _PACK_CACHE[key] = fn
    return fn


def _unpack_host(buf: np.ndarray, kinds: tuple, k: int, n_extra: int = 0):
    cpu = jax.default_backend() == "cpu"
    extras = buf[:n_extra].view(np.int32)
    buf = buf[n_extra:]
    nu32 = sum(_u32_units(kd) for kd in kinds) * k
    u32part = buf[:nu32]
    bytes_part = buf.view(np.uint8)[4 * nu32:]
    datas = []
    o32 = 0
    o8 = 0
    for kind in kinds:
        if kind == "f64":
            if cpu:
                data = u32part[o32:o32 + 2 * k].view(np.float64)
                o32 += 2 * k
            else:
                hi = u32part[o32:o32 + k].view(np.float32).astype(np.float64)
                o32 += k
                lo = u32part[o32:o32 + k].view(np.float32).astype(np.float64)
                o32 += k
                data = hi + lo
        elif kind == "i64":
            hi = u32part[o32:o32 + k].view(np.int32).astype(np.int64)
            o32 += k
            lo = u32part[o32:o32 + k].astype(np.int64)
            o32 += k
            data = (hi << 32) | lo
        elif kind == "dec128":
            limbs = []
            for _limb in range(2):
                hi = u32part[o32:o32 + k].view(np.int32).astype(np.int64)
                o32 += k
                lo = u32part[o32:o32 + k].astype(np.int64)
                o32 += k
                limbs.append((hi << 32) | lo)
            data = np.stack(limbs, axis=1)
        elif kind == "i32":
            data = u32part[o32:o32 + k].view(np.int32)
            o32 += k
        elif kind == "f32":
            data = u32part[o32:o32 + k].view(np.float32)
            o32 += k
        elif kind == "i16":
            data = bytes_part[o8:o8 + 2 * k].view(np.int16)
            o8 += 2 * k
        elif kind == "i8":
            data = bytes_part[o8:o8 + k].view(np.int8)
            o8 += k
        else:  # bool
            data = bytes_part[o8:o8 + k] != 0
            o8 += k
        datas.append(data)
    valids = []
    for _ in kinds:
        valids.append(bytes_part[o8:o8 + k] != 0)
        o8 += k
    return extras, datas, valids


def _multi_device(a) -> bool:
    """True when ``a`` is a jax.Array physically laid out across more
    than one device — the buffer-level predicate behind
    ``DeviceTable.physically_sharded``/``unsharded``. Non-arrays (None,
    host scalars) and single-device arrays are False."""
    return isinstance(a, jax.Array) and len(a.sharding.device_set) > 1


def replica_on_first_device(arrays):
    """The copy on the engine's first device of arrays a mesh program
    returned REPLICATED (a pytree): every chip holds the whole value, so
    taking that chip's own moves nothing. Where the mesh does not hold
    the first device (a degraded mesh) the first replica is copied
    there, device to device: the place ``unsharded()`` re-lands on, so
    what follows runs where it runs on one chip."""
    leaves, tree = jax.tree.flatten(arrays)
    parts = [a.addressable_data(0) for a in leaves]
    home = jax.devices()[0]
    # one program's results lie on one set of devices: ask the first
    if parts and parts[0].devices() != {home}:
        parts = jax.device_put(parts, home)
    return jax.tree.unflatten(tree, parts)


#: jitted concat kernels keyed by (program, schema kinds, input caps, out
#: cap, which inputs are masked)
_CONCAT_CACHE: Dict[tuple, object] = {}


def _same_dictionary(d0, d) -> bool:
    """Do two dictionaries hold the same strings in the same order (so
    that their codes agree)? Identity first, then lengths, then contents:
    one linear pass of ``==`` over the entries, where the union it saves
    sorts them all (n log n string compares), so no size is excluded."""
    if d is d0:
        return True
    if d is None or d0 is None or len(d) != len(d0):
        return False
    return bool(np.array_equal(d, d0))


def _build_concat(ncols: int, out_cap: int):
    """The concat program's body for ``ncols`` columns into ``out_cap``
    rows (module level, so that it can be lowered at any shapes)."""
    def concat(cols_per_table, remap_per_table, nrows_list, lives):
        from spark_rapids_tpu.ops.scatter32 import scatter_pair
        outs = []
        for ci in range(ncols):
            od = ov = None
            offset = jnp.asarray(0, dtype=jnp.int32)
            for ti in range(len(cols_per_table)):
                data, valid = cols_per_table[ti][ci]
                rm = remap_per_table[ti][ci]
                if rm is not None:
                    data = rm[jnp.clip(data, 0, rm.shape[0] - 1)]
                n = nrows_list[ti]
                if lives[ti] is None:
                    # the copy: the input whole, the next input's head
                    # landing on its dead tail. Only the tail's validity
                    # is cleared: a dead slot's data is never read (as
                    # in a masked table), and clearing it too would be
                    # one more pass over every 64-bit half
                    valid = valid & (
                        jnp.arange(data.shape[0], dtype=jnp.int32) < n)
                    if od is None:
                        pad = out_cap - data.shape[0]
                        od = jnp.pad(
                            data, [(0, pad)] + [(0, 0)] * (data.ndim - 1))
                        ov = jnp.pad(valid, (0, pad))
                    else:
                        od = jax.lax.dynamic_update_slice(
                            od, data,
                            (offset,) + (jnp.zeros_like(offset),)
                            * (data.ndim - 1))
                        ov = jax.lax.dynamic_update_slice(
                            ov, valid, (offset,))
                else:
                    # masked input: its deferred compaction fuses into
                    # this scatter (slot -> rank among live rows). It
                    # takes everything from the offset on: what lies
                    # there may be a copied input's dead tail, whose
                    # data is whatever its producer left (a literal, a
                    # projection over padding), so nothing is added in
                    lv = lives[ti]
                    pos = jnp.cumsum(lv.astype(jnp.int32)) - 1
                    tgt = jnp.where(lv, pos + offset, out_cap)
                    pd, pv = scatter_pair(out_cap, tgt, data, valid)
                    if od is None:
                        od, ov = pd, pv
                    else:
                        past = jnp.arange(out_cap, dtype=jnp.int32) >= offset
                        od = jnp.where(
                            past.reshape((out_cap,) + (1,) * (od.ndim - 1)),
                            pd, od)
                        ov = jnp.where(past, pv, ov)
                offset = offset + n
            outs.append((od, ov))
        total = jnp.asarray(0, dtype=jnp.int32)
        for n in nrows_list:
            total = total + n
        return outs, total

    return concat


def concat_device(tables: Sequence["DeviceTable"], *, coalesce: bool = False,
                  stats: Optional[dict] = None) -> "DeviceTable":
    """Concatenate device tables ON DEVICE (no host round trip).

    Row counts stay device scalars, so no host sync happens. An input
    without a ``live`` mask is COPIED: each column is written whole at
    the running device offset (the sum of its predecessors' nrows_dev)
    by a ``dynamic_update_slice`` in input order, so the next input's
    head overwrites its dead tail and the last tail lies beyond the total
    (its validity cleared; dead data is never read). The offset plus an input's capacity never passes
    the output's (an offset is at most the capacities before it), so no
    start index is ever clamped. A MASKED input keeps its deferred
    compaction fused in: its live rows scatter to their ranks past the
    offset, and the scatter's result REPLACES the output from the offset
    on (a copied predecessor's dead tail may hold anything: a literal
    fills its whole capacity). Output capacity is the bucket of the
    capacity sum — a static upper bound that avoids syncing the live
    counts.

    String columns whose inputs carry the same dictionary — the same
    object, or equal contents — keep the first input's dictionary and
    their codes (a sorted one stays flagged sorted); unequal ones are
    remapped into the union dictionary (host work O(dict log dict) —
    ``stats['dictUnions']`` counts those columns — and one device gather
    per input).

    ``coalesce`` only names the program: ``jit_coalesce`` for the
    coalesce exec's flush, ``jit_concat`` for everything else (the
    streaming merge's partials, sorts, joins), so a device trace tells
    the two apart."""
    if not tables:
        raise ColumnarProcessingError("concat of zero tables")
    if len(tables) == 1:
        return tables[0]
    names = tables[0].names
    ncols = len(tables[0].columns)
    caps = tuple(t.capacity for t in tables)
    out_cap = bucket_for(sum(caps))

    # unify string dictionaries; build per-(table, col) remap aux arrays
    out_dicts: List[Optional[np.ndarray]] = []
    out_sorted: dict = {}  # ci -> dict_sorted of a reused shared dict
    remaps: List[List[Optional[np.ndarray]]] = [[None] * ncols
                                                for _ in tables]
    for ci in range(ncols):
        col0 = tables[0].columns[ci]
        if not isinstance(col0.dtype, T.StringType):
            out_dicts.append(None)
            continue
        if all(_same_dictionary(col0.dictionary, t.columns[ci].dictionary)
               for t in tables[1:]):
            # one dictionary on every input (masked splits of one table,
            # re-coalesced scan batches, a low-cardinality column encoded
            # batch by batch): codes already agree — skip the union (a
            # 1M-entry object dict costs ~seconds to re-sort). The shared
            # dictionary may be UNSORTED (concat_ws outputs): sortedness
            # is a property of the contents, so any input's flag proves it
            out_sorted[ci] = any(t.columns[ci].dict_sorted for t in tables)
            out_dicts.append(col0.dictionary)
            continue
        if stats is not None:
            stats["dictUnions"] = stats.get("dictUnions", 0) + 1
        dicts = [(t.columns[ci].dictionary if t.columns[ci].dictionary
                  is not None else np.array([], dtype=object))
                 for t in tables]
        union = np.unique(np.concatenate([d.astype(object) for d in dicts])) \
            if any(len(d) for d in dicts) else np.array([], dtype=object)
        for ti, d in enumerate(dicts):
            m = np.searchsorted(union, d).astype(np.int32) if len(d) else \
                np.zeros(1, np.int32)
            remaps[ti][ci] = m
        out_dicts.append(union)

    kinds = tuple((str(c.dtype), c.dictionary is not None)
                  for c in tables[0].columns)
    masked = tuple(t.live is not None for t in tables)
    key = (coalesce, kinds, caps, out_cap, masked)
    fn = _CONCAT_CACHE.get(key)
    if fn is None:
        concat = _build_concat(ncols, out_cap)
        fn = (tpu_jit(concat, name="coalesce") if coalesce
              else tpu_jit(concat, name="concat"))
        _CONCAT_CACHE[key] = fn

    cols_per_table = tuple(
        tuple((c.data, c.validity) for c in t.columns) for t in tables)
    from spark_rapids_tpu.dispatch import device_const
    remap_per_table = tuple(
        tuple(device_const(m) if m is not None else None for m in row)
        for row in remaps)
    nrows_list = tuple(t.nrows_dev for t in tables)
    lives = tuple(t.live for t in tables)
    outs, total = fn(cols_per_table, remap_per_table, nrows_list, lives)
    def _union_domain(ci):
        doms = [t.columns[ci].domain for t in tables]
        if any(d is None for d in doms):
            return None
        return (min(d[0] for d in doms), max(d[1] for d in doms))

    out_cols = [
        DeviceColumn(c.dtype, d, v, dictionary=out_dicts[ci],
                     dict_sorted=out_sorted.get(
                         ci, True if out_dicts[ci] is not None
                         else c.dict_sorted),
                     domain=_union_domain(ci))
        for ci, (c, (d, v)) in enumerate(zip(tables[0].columns, outs))]
    return DeviceTable(names, out_cols, total, out_cap)


class HostTable:
    """Named host columns with a shared row count.

    ``_cache`` holds derived artifacts — notably the device-resident image
    of the table (see DeviceTable.from_host cache wiring in
    execs/basic.TpuScanExec), the GpuInMemoryTableScanExec analog."""

    __slots__ = ("names", "columns", "_cache", "__weakref__")

    def __init__(self, names: Sequence[str], columns: Sequence[HostColumn]):
        self.names: Tuple[str, ...] = tuple(names)
        self.columns: Tuple[HostColumn, ...] = tuple(columns)
        self._cache = {}
        if len(self.names) != len(self.columns):
            raise ColumnarProcessingError("names/columns mismatch")
        lens = {len(c) for c in self.columns}
        if len(lens) > 1:
            raise ColumnarProcessingError(f"ragged columns: {lens}")

    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def schema(self) -> List[Tuple[str, T.DataType]]:
        return [(n, c.dtype) for n, c in zip(self.names, self.columns)]

    def column(self, name: str) -> HostColumn:
        return self.columns[self.names.index(name)]

    @staticmethod
    def from_pydict(data: Dict[str, list], dtypes: Optional[Dict[str, T.DataType]] = None) -> "HostTable":
        names, cols = [], []
        for name, values in data.items():
            dt = (dtypes or {}).get(name)
            names.append(name)
            cols.append(HostColumn.from_pylist(values, dt))
        return HostTable(names, cols)

    def to_pydict(self) -> Dict[str, list]:
        return {n: c.to_pylist() for n, c in zip(self.names, self.columns)}

    @staticmethod
    def from_pandas(df) -> "HostTable":
        names, cols = [], []
        for name in df.columns:
            s = df[name]
            if s.dtype == object or str(s.dtype) in ("string", "str"):
                cols.append(HostColumn.from_pylist(
                    [None if v is None or (isinstance(v, float) and np.isnan(v)) else str(v)
                     for v in s.tolist()], T.STRING))
            else:
                validity = ~s.isna().to_numpy()
                vals = s.to_numpy()
                if vals.dtype == np.float64 and not validity.all():
                    vals = np.where(validity, vals, 0.0)
                cols.append(HostColumn.from_numpy(np.ascontiguousarray(vals), validity))
            names.append(name)
        return HostTable(names, cols)

    def to_pandas(self):
        import pandas as pd
        return pd.DataFrame({n: c.to_pylist() for n, c in zip(self.names, self.columns)})

    def slice(self, start: int, length: int) -> "HostTable":
        return HostTable(self.names, [c.slice(start, length) for c in self.columns])

    @staticmethod
    def concat(tables: Sequence["HostTable"]) -> "HostTable":
        if not tables:
            raise ColumnarProcessingError("concat of zero tables")
        names = tables[0].names
        cols = []
        for i in range(len(names)):
            dtype = tables[0].columns[i].dtype
            datas = [t.columns[i].data for t in tables]
            vals = [t.columns[i].validity for t in tables]
            cols.append(HostColumn(dtype, np.concatenate(datas), np.concatenate(vals)))
        return HostTable(names, cols)

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns)


class PendingHostTable:
    """An ENQUEUED packed download: the d2h kernel is already in flight
    (enqueued under the device semaphore), ``resolve()`` blocks for the
    buffer, validates any speculation flags riding the header, and
    decodes the HostTable. Splitting enqueue from fetch lets the
    session release the device semaphore before paying the device
    round trip (async result fetch) — the next admitted query's
    kernels dispatch while this one's bytes cross the wire.

    ``resolve()`` may raise SpeculationFailed exactly like the
    synchronous path; callers must therefore resolve INSIDE the
    speculation attempt that produced the batch."""

    __slots__ = ("_table", "_buf", "_kinds", "_k", "_n_extra", "_pend")

    def __init__(self, table: "DeviceTable", buf_dev, kinds: tuple,
                 k: int, n_extra: int, pend):
        self._table = table
        self._buf = buf_dev
        self._kinds = kinds
        self._k = k
        self._n_extra = n_extra
        self._pend = pend

    def resolve(self) -> HostTable:
        from spark_rapids_tpu.runtime import speculation as spec
        with span("resolve", "fetch"):
            count_host_sync()
            with phase_span("fetchWaitS", "wait", "fetch"):
                buf = np.asarray(self._buf)  # blocks: the one d2h round trip
            with phase_span("fetchUnpackS", "unpack", "fetch"):
                extras, datas, valids = _unpack_host(
                    buf, self._kinds, self._k, self._n_extra)
                if self._pend:
                    spec.check_flag_values([s for s, _ in self._pend],
                                           extras[1:])
                t = self._table
                n = int(extras[0])
                if t._nrows_host is None:
                    t._nrows_host = n
                n = min(n, self._k)
                cols = []
                for c, data, validity in zip(t.columns, datas, valids):
                    cols.append(c.decode_host(
                        data[:n], np.ascontiguousarray(validity[:n])))
                return HostTable(t.names, cols)


class DeviceTable:
    """Named device columns padded to a common capacity bucket.

    ``num_rows`` is tracked both as a device int32 scalar (``nrows_dev``,
    usable inside jitted kernels without host sync) and, lazily, as a host
    int (``num_rows`` property — blocks on the device the first time it is
    read after a data-dependent op such as filter).

    ``live`` (optional device bool[capacity]) marks MASKED tables: live rows
    sit at their original slots instead of a compacted prefix. Row
    compaction is a scatter per column word — 64-bit columns split into
    2-3 scatters plus emulated recombine chains, the single most expensive
    per-row operation on TPU (PERF.md: ~0.15-0.25s per 8-column 1M-row
    compaction). Filters and dense-key joins therefore emit masked tables
    and downstream mask-aware execs (filter, project, join probe,
    aggregate, sort) consume liveness from ``row_mask()`` — the scatter is
    paid only at a boundary that truly needs the prefix invariant
    (``compacted()``: collects, spill demotion, splits, unlearned execs).
    The reference has no analog: cuDF compaction is bandwidth-priced, so
    GpuFilterExec compacts eagerly (basicPhysicalOperators.scala)."""

    __slots__ = ("names", "columns", "nrows_dev", "_nrows_host", "capacity",
                 "live", "shard_spec", "__weakref__")

    def __init__(self, names: Sequence[str], columns: Sequence[DeviceColumn],
                 nrows, capacity: Optional[int] = None, live=None,
                 shard_spec=None):
        self.names: Tuple[str, ...] = tuple(names)
        self.columns: Tuple[DeviceColumn, ...] = tuple(columns)
        self.live = live
        #: plan-carried sharding descriptor (jax.sharding.NamedSharding
        #: over the row axis, or None for single-device tables): set
        #: when a mesh-native scan lands shards per device; narrow
        #: kernels preserve the layout through GSPMD propagation and
        #: exchanges re-shard explicitly (parallel/mesh.py)
        self.shard_spec = shard_spec
        if self.columns:
            caps = {c.capacity for c in self.columns}
            if len(caps) != 1:
                raise ColumnarProcessingError(f"ragged capacities {caps}")
            self.capacity = caps.pop()
        else:
            self.capacity = int(capacity or 0)
        if isinstance(nrows, (int, np.integer)):
            from spark_rapids_tpu.dispatch import device_scalar
            self._nrows_host: Optional[int] = int(nrows)
            self.nrows_dev = device_scalar(int(nrows))
        else:
            self._nrows_host = None
            self.nrows_dev = nrows

    @property
    def num_rows(self) -> int:
        if self._nrows_host is None:
            # a blocking read of a device scalar: through host_fetch, so
            # it is counted (hostSyncs), timed (syncWaitS) and ranged
            self._nrows_host = int(host_fetch(self.nrows_dev))
        return self._nrows_host

    @property
    def num_rows_known(self) -> bool:
        """True when ``num_rows`` costs no device read (the count came
        from the host, or a read has already fetched it)."""
        return self._nrows_host is not None

    @property
    def num_columns(self) -> int:
        return len(self.columns)

    def schema(self) -> List[Tuple[str, T.DataType]]:
        return [(n, c.dtype) for n, c in zip(self.names, self.columns)]

    def schema_key(self) -> tuple:
        """Structural key for the compile cache: column dtypes + capacity +
        which columns are dictionary-encoded."""
        return (
            tuple((str(c.dtype), c.dictionary is not None) for c in self.columns),
            self.capacity,
        )

    def column(self, name: str) -> DeviceColumn:
        return self.columns[self.names.index(name)]

    def device_nbytes(self) -> int:
        return sum(c.device_nbytes() for c in self.columns)

    def select_columns(self, ordinals: Sequence[int]) -> "DeviceTable":
        """This table with only the columns at ``ordinals``, in that
        order: the same device arrays under a narrower table (no data
        moves), its row count (the host's copy too), mask, sharding and
        shared-view group kept."""
        nrows = (self._nrows_host if self._nrows_host is not None
                 else self.nrows_dev)
        out = DeviceTable([self.names[i] for i in ordinals],
                          [self.columns[i] for i in ordinals],
                          nrows, self.capacity, live=self.live,
                          shard_spec=self.shard_spec)
        if is_shared_view(self):
            mark_shared_view(out, view_group(self))
        return out

    @staticmethod
    def from_host(host: HostTable, capacity: Optional[int] = None,
                  sharding=None) -> "DeviceTable":
        """Upload ``host`` as one staged transfer. With ``sharding`` (a
        NamedSharding over the row axis — mesh-native scans), every
        staged column lands SPLIT across the mesh devices by
        ``jax.device_put``: each device receives only its row shard, no
        single-device concat ever materializes, and the assemble
        kernel's outputs inherit the sharded layout (GSPMD)."""
        cap = capacity or bucket_for(host.num_rows)
        if sharding is not None:
            # even per-device shards: round the capacity up to a mesh
            # multiple (pow2 buckets >= 128 already divide pow2 meshes)
            ndev = len(sharding.mesh.devices.flat)
            cap = -(-cap // ndev) * ndev
        # bucket pad waste: dead tail rows this upload carries so the
        # kernel set stays bounded (`compile` scope, padWasteRows)
        count_pad_waste(cap - host.num_rows)
        # the device memory arbiter (runtime/memory.py): every landing
        # reserves its estimated device bytes against the hard budget
        # FIRST — an over-budget reservation spills idle spillables and,
        # when spilling cannot make room, raises RetryOOM into the
        # retry framework — then accounts the landed table at its
        # actual bytes for as long as the object lives
        from spark_rapids_tpu.runtime.memory import (
            MEMORY,
            estimate_device_nbytes,
        )
        reservation = MEMORY.reserve(
            estimate_device_nbytes(host, cap), label="from_host")
        try:
            if not host.columns:
                return MEMORY.account(
                    DeviceTable(host.names, [], host.num_rows, cap),
                    reservation)
            if _has_nested(host):
                # nested columns bypass the staged fast path (per-column
                # upload) and stay single-device — the exchange layer
                # excludes them from collectives for the same reason
                cols = [DeviceColumn.from_host(c, cap)
                        for c in host.columns]
                return MEMORY.account(
                    DeviceTable(host.names, cols, host.num_rows, cap),
                    reservation)
            return MEMORY.account(
                DeviceTable._from_host_staged(host, cap, sharding),
                reservation)
        finally:
            # a failed upload returns the grant; a successful account()
            # already consumed it (release is idempotent)
            reservation.release()

    @staticmethod
    def _from_host_staged(host: HostTable, cap: int,
                          sharding) -> "DeviceTable":
        """The staged fast-path upload body of :meth:`from_host` (all
        budget accounting happens in the caller)."""
        split_f64 = split_f64_on_device()
        recipes, staged, dicts = [], [], []
        # the landing's host side, one range each: srt.transfer.stage
        # (padding every column to the bucket; a string column's
        # srt.transfer.encode nests in it), srt.transfer.upload, then
        # the assemble program's srt.dispatch.assemble (a DOUBLE's f32
        # pair split from its 64-bit words, the dtypes rebuilt)
        with span("stage", "transfer", columns=len(host.columns)):
            for c in host.columns:
                recipe, arrays, dictionary = stage_upload(c, cap, split_f64)
                recipes.append(recipe)
                staged.extend(arrays)
                dicts.append(dictionary)
        with span("upload", "transfer", arrays=len(staged)):
            if sharding is None:
                dev_arrays = tuple(jnp.asarray(a) for a in staged)
            else:
                # the shard-landing fault site (the second registered
                # mesh.shard.put call site — parallel/mesh.shard_put
                # covers the exchange reshards): one evaluation per
                # sharded batch, before any per-device transfer starts
                from spark_rapids_tpu.runtime.faults import fault_point
                fault_point("mesh.shard.put")
                dev_arrays = tuple(jax.device_put(a, sharding)
                                   for a in staged)
        fn = _get_assemble(tuple(recipes), cap)
        outs = fn(dev_arrays, jnp.asarray(np.int32(host.num_rows)))
        cols = [
            DeviceColumn(c.dtype, data, validity, dictionary=d,
                         domain=c.int_domain())
            for c, (data, validity), d in zip(host.columns, outs, dicts)
        ]
        return DeviceTable(host.names, cols, host.num_rows, cap,
                           shard_spec=sharding)

    #: capacity up to which an unknown row count is fetched by embedding it
    #: in the packed buffer (fetching the padded bucket) instead of paying a
    #: separate row-count sync first
    EMBED_NROWS_CAP = 1 << 16

    #: ...but only while the padded transfer stays under this many bytes —
    #: a wide schema at 64k rows can be tens of MB of padding over the d2h
    #: link, costing more than the row-count sync it avoids
    EMBED_MAX_BYTES = 4 << 20

    def _packed_row_bytes(self) -> int:
        """Bytes per row of the packed d2h buffer (data words + validity)."""
        total = 0
        for c in self.columns:
            total += 4 * _u32_units(_pack_kind(c)) or 2  # small ints ~1-2B
            total += 1  # validity byte
        return max(total, 1)

    def to_host(self) -> HostTable:
        """Download as one packed transfer.

        Every d2h fetch is a host sync, so per-column (data + validity)
        fetches multiply it by the schema width. A jitted pack kernel bitcasts
        every column into one u32 buffer (f64/i64 as exact hi/lo splits —
        TPU f64 storage is an f32 pair; small ints byte-packed 4-per-u32)
        sliced to the live bucket, fetched with ONE device_get, and the host
        unpacks by numpy views.

        The packed buffer carries an i32 header: the live row count plus any
        pending speculation flags (runtime/speculation.py), so a warm query
        whose output bucket is small performs exactly ONE round trip —
        no separate row-count sync, no separate flag validation fetch."""
        out = self.to_host_pending()
        return out.resolve() if isinstance(out, PendingHostTable) else out

    def to_host_pending(self):
        """ENQUEUE the packed-download kernel and return a
        :class:`PendingHostTable` whose ``resolve()`` completes the d2h
        round trip — the async-result-fetch split: kernels are enqueued
        while the caller still holds the device semaphore, the fetch
        itself happens after it is released. Paths that cannot
        defer (no columns, nested columns) return a plain HostTable."""
        if not self.columns:
            return HostTable(self.names, [])
        if self.live is not None:
            return self.compacted().to_host_pending()
        if any(c.is_nested for c in self.columns):
            return self.to_host_per_column()
        from spark_rapids_tpu.runtime import speculation as spec
        ctx = spec.current()
        if (self._nrows_host is None
                and self.capacity <= self.EMBED_NROWS_CAP
                and self.capacity * self._packed_row_bytes()
                <= self.EMBED_MAX_BYTES):
            k = self.capacity  # fetch the padded bucket; n rides the header
        else:
            k = min(bucket_for(max(self.num_rows, 1)), self.capacity)
        pend = ctx.take_pending() if ctx is not None else []
        n_extra = 1 + len(pend)
        kinds = tuple(_pack_kind(c) for c in self.columns)
        fn = _get_pack(kinds, k, self.capacity, n_extra)
        extras_dev = jnp.concatenate(
            [jnp.reshape(self.nrows_dev.astype(jnp.int32), (1,))]
            + [jnp.reshape(f.astype(jnp.int32), (1,)) for _, f in pend])
        buf_dev = fn(
            tuple((c.data, c.validity) for c in self.columns), extras_dev)
        return PendingHostTable(self, buf_dev, kinds, k, n_extra, pend)

    def to_host_per_column(self) -> HostTable:
        """Low-allocation download: transfer each column's existing buffers
        (no pack kernel, no table-sized staging allocation). Used by spill
        demotion during OOM recovery, where allocating on the exhausted
        device would fail (the packed path is for collects)."""
        if self.live is not None:
            # OOM demotion path: the device is exhausted, so the deferred
            # compaction must NOT allocate there — fetch the padded
            # columns plus the mask and compact with numpy on host
            mask = np.asarray(jax.device_get(self.live))
            idx = np.nonzero(mask)[0]
            cols = []
            for c in self.columns:
                full = c.to_host(self.capacity)
                cols.append(type(full)(full.dtype, full.data[idx],
                                       full.validity[idx]))
            if self._nrows_host is None:
                self._nrows_host = int(len(idx))
            return HostTable(self.names, cols)
        n = self.num_rows
        return HostTable(self.names, [c.to_host(n) for c in self.columns])

    def row_mask(self):
        """Bool mask of live rows — usable inside jit (no host sync)."""
        if self.live is not None:
            return self.live
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.nrows_dev

    def compacted(self) -> "DeviceTable":
        """Prefix form: live rows scattered to [0, nrows) in original
        order. No-op for prefix tables; masked tables pay the one scatter
        per column word this representation exists to defer."""
        if self.live is None:
            return self
        key = ("tablecompact", self.capacity, self.schema_key()[0])
        fn = _PACK_CACHE.get(key)
        if fn is None:
            cap = self.capacity

            def compact(datas, valids, keep):
                from spark_rapids_tpu.ops.scatter32 import compact_pairs
                outs, _ = compact_pairs(datas, valids, keep, cap)
                return outs

            fn = tpu_jit(compact, name="compact_live")
            _PACK_CACHE[key] = fn
        outs = fn(tuple(c.data for c in self.columns),
                  tuple(c.validity for c in self.columns), self.live)
        cols = [c.with_arrays(d, v) for c, (d, v) in zip(self.columns, outs)]
        out = DeviceTable(self.names, cols, self.nrows_dev, self.capacity,
                          shard_spec=self.shard_spec)
        out._nrows_host = self._nrows_host
        return out

    def physically_sharded(self) -> bool:
        """True when any buffer is laid out across more than one device
        — the predicate ``unsharded()`` gathers on. A bare shard_spec
        descriptor over single-device buffers (e.g. a 1-device mesh)
        does not count: dropping it moves no data."""
        return bool(_multi_device(self.live)
                    or _multi_device(self.nrows_dev)
                    or any(_multi_device(c.data)
                           or _multi_device(c.validity)
                           for c in self.columns))

    def unsharded(self) -> "DeviceTable":
        """Re-land a row-sharded table into the single-device layout —
        the merge-boundary gather of mesh-native execution. Wide kernels
        (aggregate/sort/join/window) must see exactly the layout the
        single-chip path computes on: a GSPMD-partitioned reduction over
        mesh shards changes float accumulation order, breaking the
        bit-identity contract. The move is DEVICE-to-device (ICI on a
        real pod) — data never round-trips through the host, so the
        RL-MESH-HOST zero-host-transfer invariant holds; no-op for
        tables that are not physically sharded."""
        # one traversal: per-buffer verdicts drive both the early-out
        # and the selective re-land below
        live_m = _multi_device(self.live)
        nrows_m = _multi_device(self.nrows_dev)
        col_m = [(_multi_device(c.data), _multi_device(c.validity))
                 for c in self.columns]
        if not (live_m or nrows_m or any(d or v for d, v in col_m)):
            if self.shard_spec is None:
                return self
            out = DeviceTable(self.names, self.columns, self.nrows_dev,
                              self.capacity, live=self.live)
            out._nrows_host = self._nrows_host
            return out
        dev = jax.devices()[0]

        def _land(a, multi):
            return jax.device_put(a, dev) if multi else a

        cols = [c.with_arrays(_land(c.data, d), _land(c.validity, v))
                for c, (d, v) in zip(self.columns, col_m)]
        # the row-count scalar rides replicated across the mesh on
        # sharded tables — re-land it with the columns or a downstream
        # jit sees mixed committed devices
        out = DeviceTable(self.names, cols, _land(self.nrows_dev, nrows_m),
                          self.capacity, live=_land(self.live, live_m))
        out._nrows_host = self._nrows_host
        return out

    def shrink(self) -> "DeviceTable":
        """Re-bucket to the smallest capacity holding the live rows. Syncs
        the row count (host round-trip) only when a smaller bucket exists:
        a table at or under the smallest bucket is returned as it is, its
        count unread. Worth the sync after cardinality-collapsing ops
        (aggregate output of a few groups must not drag the input's
        multi-million-row bucket through downstream sorts/uploads)."""
        if self.live is not None:
            return self.compacted().shrink()
        if bucket_for(1) >= self.capacity:
            return self
        n = self.num_rows
        k = bucket_for(max(n, 1))
        if k >= self.capacity:
            return self
        cols = [c.sliced_rows(k) for c in self.columns]
        return DeviceTable(self.names, cols, n, k)
