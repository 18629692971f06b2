"""Host and device column representations."""

from __future__ import annotations

from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.errors import ColumnarProcessingError

_MISSING = object()

# Lane width on TPU is 128; keep every device buffer a multiple of it so XLA
# tiles cleanly onto the VPU/MXU.
MIN_BUCKET = 128


class BucketPolicy:
    """A BOUNDED, declared set of capacity buckets.

    Every device buffer's leading dimension is drawn from this set, so
    the number of distinct compiled programs per (schema, expression)
    is bounded by the set's size — the XLA analog of cuDF's precompiled
    kernels (SURVEY.md §7 hard parts). ``spark.rapids.sql.shapeBuckets``
    picks the policy:

    * ``pow2`` — powers of two from ``minBucket`` (the historical
      default: log2(max_rows) buckets);
    * ``pow4`` — powers of four from ``minBucket``: half the compiled
      shapes for at most 4x pad waste (mask-aware execs never touch the
      dead tail rows, they only cost bandwidth);
    * an explicit ascending comma-separated list (``'1024,16384,...'``)
      — the exact bucket set, continuing pow2 above its largest entry
      (a capacity must always exist for any row count).

    Buckets must be multiples of 128 (the TPU lane width) and strictly
    ascending; a bad spec raises at conf-apply time, never mid-kernel.
    """

    __slots__ = ("spec", "min_bucket", "_explicit", "_ratio")

    def __init__(self, spec: str = "pow2", min_bucket: int = MIN_BUCKET):
        self.spec = str(spec).strip().lower() or "pow2"
        self.min_bucket = int(min_bucket)
        if self.min_bucket < 1 or self.min_bucket % MIN_BUCKET:
            raise ColumnarProcessingError(
                f"spark.rapids.sql.shapeBuckets.minBucket must be a "
                f"positive multiple of {MIN_BUCKET}, got {min_bucket}")
        self._explicit = None
        if self.spec == "pow2":
            self._ratio = 2
        elif self.spec == "pow4":
            self._ratio = 4
        else:
            self._ratio = 2
            try:
                buckets = tuple(int(b) for b in self.spec.split(","))
            except ValueError:
                raise ColumnarProcessingError(
                    f"spark.rapids.sql.shapeBuckets must be 'pow2', "
                    f"'pow4' or an ascending comma-separated int list, "
                    f"got {spec!r}")
            if not buckets or any(b < 1 or b % self.min_bucket
                                  for b in buckets):
                # multiples of minBucket (itself a lane-width multiple):
                # the operator's minBucket contract applies to explicit
                # lists too, not just the geometric policies
                raise ColumnarProcessingError(
                    f"spark.rapids.sql.shapeBuckets entries must be "
                    f"positive multiples of "
                    f"spark.rapids.sql.shapeBuckets.minBucket "
                    f"({self.min_bucket}), got {spec!r}")
            if any(a >= b for a, b in zip(buckets, buckets[1:])):
                raise ColumnarProcessingError(
                    f"spark.rapids.sql.shapeBuckets entries must be "
                    f"strictly ascending, got {spec!r}")
            self._explicit = buckets

    def bucket_for(self, n: int) -> int:
        """Smallest declared bucket >= n (and >= the min bucket)."""
        if self._explicit is not None:
            for b in self._explicit:
                if b >= n:
                    return b
            b = self._explicit[-1]
        else:
            b = self.min_bucket
        while b < n:
            b *= self._ratio if self._explicit is None else 2
        return b

    def buckets_up_to(self, cap: int) -> tuple:
        """The declared bucket set covering capacities <= ``cap`` —
        the bound on distinct compiled shapes for a workload whose
        largest batch fits ``cap``."""
        out = []
        if self._explicit is not None:
            out.extend(b for b in self._explicit if b <= cap)
            b = self._explicit[-1] * 2
        else:
            b = self.min_bucket
        while b <= cap:
            out.append(b)
            b *= self._ratio if self._explicit is None else 2
        if not out or out[-1] < cap:
            out.append(self.bucket_for(cap))
        return tuple(sorted(set(out)))


_POLICY = BucketPolicy()
_POLICY_KEY = ("pow2", MIN_BUCKET)


def set_bucket_policy(spec: str, min_bucket: int = MIN_BUCKET) -> None:
    """Install the process-wide bucket policy (pushed from the session's
    conf per query, the DeviceTable.EMBED_* tuning pattern). No-op when
    unchanged; validates eagerly so a typo'd spec fails the query at
    plan time."""
    global _POLICY, _POLICY_KEY
    key = (str(spec).strip().lower() or "pow2", int(min_bucket))
    if key == _POLICY_KEY:
        return
    _POLICY = BucketPolicy(spec, min_bucket)
    _POLICY_KEY = key


def bucket_policy() -> BucketPolicy:
    return _POLICY


def bucket_for(n: int) -> int:
    """Smallest declared capacity bucket >= n (see BucketPolicy)."""
    return _POLICY.bucket_for(n)


class HostColumn:
    """A column on the host: numpy values + validity mask.

    For STRING, ``data`` is a numpy object array of Python str (None allowed
    at invalid slots). For everything else ``data`` is the Spark internal
    representation (see types.py).

    ``_cache`` memoizes derived per-column artifacts (dictionary encoding,
    all-valid flag) so repeated uploads of the same host column — re-collects,
    multi-query reuse of an in-memory table — don't redo O(n) host work. A
    reader may supply the ``encode`` memo itself: a STRING column decoded
    from Arrow (io/arrow_convert.py) comes with its (codes, sorted
    dictionary), so its upload encodes nothing. The memo holds because
    ``data`` is never mutated once built; slice and concat build new
    columns, which encode from their objects."""

    __slots__ = ("dtype", "data", "validity", "_cache")

    def __init__(self, dtype: T.DataType, data: np.ndarray, validity: Optional[np.ndarray] = None):
        self.dtype = dtype
        self.data = data
        if validity is None:
            validity = np.ones(len(data), dtype=np.bool_)
        self.validity = validity
        self._cache = {}
        if len(data) != len(validity):
            raise ColumnarProcessingError("data/validity length mismatch")

    @property
    def all_valid(self) -> bool:
        got = self._cache.get("all_valid")
        if got is None:
            got = bool(self.validity.all())
            self._cache["all_valid"] = got
        return got

    def __len__(self) -> int:
        return len(self.data)

    @property
    def null_count(self) -> int:
        return int(len(self.validity) - self.validity.sum())

    def int_domain(self) -> Optional[Tuple[int, int]]:
        """(min, max) over VALID rows for integer-family columns, else None.

        Cheap host-side column statistics (one numpy min/max per upload,
        cached) in the spirit of the reference's use of parquet/ORC
        column statistics — consumed by the aggregation fast path, which
        turns a group-by on a bounded-domain integer key into a direct
        segment reduction with no sort (see TpuHashAggregateExec
        _fast_layout). The result is a conservative SUPERSET contract:
        every valid value lies in [min, max]."""
        got = self._cache.get("int_domain", _MISSING)
        if got is not _MISSING:
            return got
        dom = None
        if (isinstance(self.dtype, (T.ByteType, T.ShortType, T.IntegerType,
                                    T.LongType, T.DateType, T.TimestampType))
                and isinstance(self.data, np.ndarray)
                and self.data.dtype.kind in "iu"):
            vals = self.data[self.validity] if not self.all_valid else self.data
            if len(vals):
                dom = (int(vals.min()), int(vals.max()))
        self._cache["int_domain"] = dom
        return dom

    @staticmethod
    def from_pylist(values, dtype: Optional[T.DataType] = None) -> "HostColumn":
        import datetime as _dt
        if dtype is None:
            sample = next((v for v in values if v is not None), None)
            dtype = T.python_to_spark_type(sample) if sample is not None else T.NULL
        validity = np.array([v is not None for v in values], dtype=np.bool_)
        if isinstance(dtype, (T.StructType, T.MapType)):
            data = np.empty(len(values), dtype=object)
            data[:] = list(values)
        elif isinstance(dtype, T.ArrayType):
            ec = HostColumn._element_conv(dtype.element_type)
            data = np.empty(len(values), dtype=object)
            data[:] = [[ec(x) if x is not None else None for x in v]
                       if v is not None else None for v in values]
        elif isinstance(dtype, T.StringType):
            data = np.empty(len(values), dtype=object)
            data[:] = [v if v is not None else None for v in values]
        elif T.is_dec128(dtype):
            # unscaled values beyond int64: python-int object storage
            data = np.empty(len(values), dtype=object)
            data[:] = [int(v) if v is not None else 0 for v in values]
        else:
            np_dtype = dtype.np_dtype
            fill = np.zeros((), dtype=np_dtype).item()
            conv = lambda v: v  # noqa: E731
            if isinstance(dtype, T.DateType):
                epoch = _dt.date(1970, 1, 1)

                def conv(v):
                    if isinstance(v, _dt.datetime):  # datetime subclasses date
                        v = v.date()
                    return (v - epoch).days if isinstance(v, _dt.date) else v
            elif isinstance(dtype, T.TimestampType):
                epoch_ts = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)

                def conv(v):  # noqa: E731
                    if isinstance(v, _dt.datetime):
                        if v.tzinfo is None:
                            v = v.replace(tzinfo=_dt.timezone.utc)
                        delta = v - epoch_ts
                        return delta.days * 86_400_000_000 + delta.seconds * 1_000_000 + delta.microseconds
                    return v
            data = np.array([conv(v) if v is not None else fill for v in values],
                            dtype=np_dtype)
        return HostColumn(dtype, data, validity)

    @staticmethod
    def _element_conv(dtype: T.DataType):
        """Python value -> internal representation for ARRAY elements
        (dates to epoch days, timestamps to epoch micros)."""
        import datetime as _dt
        if isinstance(dtype, T.DateType):
            epoch = _dt.date(1970, 1, 1)

            def conv(v):
                if isinstance(v, _dt.datetime):
                    v = v.date()
                return (v - epoch).days if isinstance(v, _dt.date) else v
            return conv
        if isinstance(dtype, T.TimestampType):
            epoch_ts = _dt.datetime(1970, 1, 1, tzinfo=_dt.timezone.utc)

            def conv(v):
                if isinstance(v, _dt.datetime):
                    if v.tzinfo is None:
                        v = v.replace(tzinfo=_dt.timezone.utc)
                    d = v - epoch_ts
                    return (d.days * 86_400_000_000 + d.seconds * 1_000_000
                            + d.microseconds)
                return v
            return conv
        return lambda v: v

    @staticmethod
    def from_numpy(values: np.ndarray, validity: Optional[np.ndarray] = None,
                   dtype: Optional[T.DataType] = None) -> "HostColumn":
        if dtype is None:
            dtype = T.from_numpy(values.dtype)
        return HostColumn(dtype, values, validity)

    def to_pylist(self):
        import datetime as _dt
        conv = None
        if isinstance(self.dtype, T.ArrayType):
            edt = self.dtype.element_type
            if isinstance(edt, T.DateType):
                epoch = _dt.date(1970, 1, 1)
                conv = lambda lst: [  # noqa: E731
                    epoch + _dt.timedelta(days=int(x)) if x is not None
                    else None for x in lst]
            elif isinstance(edt, T.TimestampType):
                epoch_ts = _dt.datetime(1970, 1, 1)
                conv = lambda lst: [  # noqa: E731
                    epoch_ts + _dt.timedelta(microseconds=int(x))
                    if x is not None else None for x in lst]
        elif isinstance(self.dtype, T.DateType):
            epoch = _dt.date(1970, 1, 1)
            conv = lambda v: epoch + _dt.timedelta(days=int(v))  # noqa: E731
        elif isinstance(self.dtype, T.TimestampType):
            epoch_ts = _dt.datetime(1970, 1, 1)
            conv = lambda v: epoch_ts + _dt.timedelta(microseconds=int(v))  # noqa: E731
        out = []
        for i in range(len(self)):
            if not self.validity[i]:
                out.append(None)
            else:
                v = self.data[i]
                if conv is not None:
                    out.append(conv(v))
                else:
                    out.append(v.item() if isinstance(v, np.generic) else v)
        return out

    def slice(self, start: int, length: int) -> "HostColumn":
        return HostColumn(self.dtype, self.data[start:start + length],
                          self.validity[start:start + length])

    def nbytes(self) -> int:
        if isinstance(self.dtype, T.StringType):
            return int(sum(len(s.encode("utf-8")) for s, v in zip(self.data, self.validity) if v)) + len(self)
        if isinstance(self.dtype, T.ArrayType):
            elem = np.dtype(self.dtype.element_type.np_dtype).itemsize
            total = sum(len(x) for x, v in zip(self.data, self.validity) if v)
            return int(total * elem + 4 * (len(self) + 1) + len(self))
        return int(self.data.nbytes + self.validity.nbytes)


class DeviceColumn:
    """A column resident on device as XLA buffers.

    ``data``     : jnp array of length ``capacity`` (padded bucket)
    ``validity`` : jnp bool array, True = valid; padding region is False at
                   upload time; operators maintain correctness on [0, n).
    ``dictionary``: for STRING columns, host numpy object array such that the
                   logical value of row i is dictionary[data[i]]. When
                   ``dict_sorted`` is True the dictionary is sorted+unique so
                   code order == Spark UTF-8 byte order (order-preserving).
    """

    __slots__ = ("dtype", "data", "validity", "dictionary", "dict_sorted",
                 "domain")

    def __init__(self, dtype: T.DataType, data, validity,
                 dictionary: Optional[np.ndarray] = None, dict_sorted: bool = True,
                 domain: Optional[Tuple[int, int]] = None):
        self.dtype = dtype
        self.data = data
        self.validity = validity
        self.dictionary = dictionary
        self.dict_sorted = dict_sorted
        #: host-known (min, max) bound on VALID values of integer-family
        #: columns (None = unknown). Contract: a conservative SUPERSET —
        #: set at upload from column stats, carried only through
        #: structural ops (with_arrays: gather/slice/permute/pad, same
        #: logical value space as the dictionary it already carries).
        #: Consumed by the aggregation no-sort fast path.
        self.domain = domain

    @property
    def is_array(self) -> bool:
        return isinstance(self.data, tuple)

    @property
    def is_struct(self) -> bool:
        from spark_rapids_tpu.columnar.nested import StructData
        return isinstance(self.data, StructData)

    @property
    def is_map(self) -> bool:
        from spark_rapids_tpu.columnar.nested import MapData
        return isinstance(self.data, MapData)

    @property
    def is_nested(self) -> bool:
        return self.is_array or self.is_struct or self.is_map

    @property
    def capacity(self) -> int:
        # array columns store data as (offsets, elem_data, elem_validity);
        # row capacity always equals the validity length
        return int(self.validity.shape[0])

    def device_nbytes(self) -> int:
        if self.is_array:
            off, ed, ev = self.data
            return int(off.size * 4 + ed.size * ed.dtype.itemsize
                       + ev.size + self.validity.size)
        if self.is_struct or self.is_map:
            from spark_rapids_tpu.columnar.nested import nested_nbytes
            return nested_nbytes(self.data) + int(self.validity.size)
        return int(self.data.size * self.data.dtype.itemsize + self.validity.size)

    @staticmethod
    def _encode_strings(host: HostColumn) -> Tuple[np.ndarray, np.ndarray]:
        """Order-preserving dictionary encode. Returns (codes int32, dict).

        Python str comparison is by code point, which equals UTF-8 byte order
        — the order Spark's UTF8String.compareTo uses — so a sorted-unique
        dictionary makes code comparisons match Spark string comparisons."""
        got = host._cache.get("encode")
        if got is not None:
            return got
        vals = np.where(host.validity, host.data, "")
        # hash-dedupe + (native UTF-32 sort | numpy argsort) — 5-6x the old
        # np.unique-over-objects; order is code-point order == UTF-8 byte
        # order either way (spark_rapids_tpu/native.py)
        from spark_rapids_tpu.native import encode_sorted_dict
        from spark_rapids_tpu.obs.spans import span
        with span("encode", "transfer", rows=len(vals)):
            got = encode_sorted_dict(np.asarray(vals, dtype=object))
        host._cache["encode"] = got
        return got

    @staticmethod
    def _array_parts(host: HostColumn, cap: int):
        """Flatten host lists to (offsets[cap+1] i32, elem_data, elem_valid);
        null/padding rows get ZERO length (the engine invariant: only live
        valid rows own elements)."""
        n = len(host)
        lengths = np.zeros(cap + 1, dtype=np.int64)
        for i in range(n):
            if host.validity[i]:
                lengths[i + 1] = len(host.data[i])
        offsets = np.cumsum(lengths).astype(np.int32)
        total = int(offsets[cap])
        ecap = bucket_for(max(total, 1))
        edt = host.dtype.element_type.np_dtype
        elems = np.zeros(ecap, dtype=edt)
        evalid = np.zeros(ecap, dtype=np.bool_)
        pos = 0
        for i in range(n):
            if host.validity[i]:
                for v in host.data[i]:
                    if v is not None:
                        elems[pos] = v
                        evalid[pos] = True
                    pos += 1
        return offsets, elems, evalid

    @staticmethod
    def from_host(host: HostColumn, capacity: Optional[int] = None) -> "DeviceColumn":
        n = len(host)
        cap = capacity or bucket_for(n)
        if cap < n:
            raise ColumnarProcessingError(f"capacity {cap} < rows {n}")
        if isinstance(host.dtype, T.StructType):
            from spark_rapids_tpu.columnar.nested import struct_from_host
            sd, validity = struct_from_host(host, cap)
            return DeviceColumn(host.dtype, sd, validity)
        if isinstance(host.dtype, T.MapType):
            from spark_rapids_tpu.columnar.nested import map_from_host
            md, validity = map_from_host(host, cap)
            return DeviceColumn(host.dtype, md, validity)
        if isinstance(host.dtype, T.ArrayType):
            offsets, elems, evalid = DeviceColumn._array_parts(host, cap)
            validity = np.zeros(cap, dtype=np.bool_)
            validity[:n] = host.validity
            return DeviceColumn(host.dtype,
                                (jnp.asarray(offsets), jnp.asarray(elems),
                                 jnp.asarray(evalid)),
                                jnp.asarray(validity))
        validity = np.zeros(cap, dtype=np.bool_)
        validity[:n] = host.validity
        if T.is_dec128(host.dtype):
            limbs = dec128_limbs(host.data, host.validity, cap)
            return DeviceColumn(host.dtype, jnp.asarray(limbs),
                                jnp.asarray(validity))
        if isinstance(host.dtype, T.StringType):
            codes, dictionary = DeviceColumn._encode_strings(host)
            data = np.zeros(cap, dtype=np.int32)
            data[:n] = codes
            return DeviceColumn(host.dtype, jnp.asarray(data), jnp.asarray(validity),
                                dictionary=dictionary, dict_sorted=True)
        np_dtype = host.dtype.np_dtype
        data = np.zeros(cap, dtype=np_dtype)
        data[:n] = host.data
        return DeviceColumn(host.dtype, jnp.asarray(data), jnp.asarray(validity),
                            domain=host.int_domain())

    def to_host(self, num_rows: int) -> HostColumn:
        if self.is_array:
            return self._array_to_host(num_rows)
        if self.is_struct:
            from spark_rapids_tpu.columnar.nested import struct_to_host
            return struct_to_host(self.dtype, self.data, self.validity,
                                  num_rows)
        if self.is_map:
            from spark_rapids_tpu.columnar.nested import map_to_host
            return map_to_host(self.dtype, self.data, self.validity,
                               num_rows)
        # device-slice down to the live bucket BEFORE the transfer: results
        # are often tiny (an aggregate's groups) while capacity is the input
        # bucket — never ship padding over the d2h link.
        k = bucket_for(max(num_rows, 1))
        dev_data = self.data[:k] if k < self.capacity else self.data
        dev_valid = self.validity[:k] if k < self.capacity else self.validity
        data = np.asarray(dev_data)[:num_rows]
        validity = np.ascontiguousarray(np.asarray(dev_valid)[:num_rows])
        return self.decode_host(data, validity)

    def _array_to_host(self, num_rows: int) -> HostColumn:
        off = np.asarray(self.data[0])
        elems = np.asarray(self.data[1])
        evalid = np.asarray(self.data[2])
        validity = np.ascontiguousarray(np.asarray(self.validity)[:num_rows])
        out = np.empty(num_rows, dtype=object)
        for i in range(num_rows):
            if validity[i]:
                s, e = int(off[i]), int(off[i + 1])
                out[i] = [elems[j].item() if evalid[j] else None
                          for j in range(s, e)]
        return HostColumn(self.dtype, out, validity)

    def decode_host(self, data: np.ndarray, validity: np.ndarray) -> HostColumn:
        """Build the logical HostColumn from downloaded raw arrays (shared
        by the per-column path above and DeviceTable's packed to_host)."""
        if T.is_dec128(self.dtype):
            return HostColumn(self.dtype,
                              dec128_unscaled(np.asarray(data), validity),
                              validity)
        if isinstance(self.dtype, T.StringType):
            if self.dictionary is None:
                raise ColumnarProcessingError("string column missing dictionary")
            # Clip: padding/invalid slots may hold arbitrary codes.
            codes = np.clip(data, 0, max(len(self.dictionary) - 1, 0))
            vals = np.empty(len(data), dtype=object)
            if len(self.dictionary):
                vals[:] = self.dictionary[codes]
            vals[~validity] = None
            return HostColumn(self.dtype, vals, validity)
        arr = np.ascontiguousarray(data)
        if arr.dtype != self.dtype.np_dtype:
            arr = arr.astype(self.dtype.np_dtype)
        return HostColumn(self.dtype, arr, validity)

    def with_arrays(self, data, validity) -> "DeviceColumn":
        return DeviceColumn(self.dtype, data, validity, self.dictionary,
                            self.dict_sorted, domain=self.domain)

    def sliced_rows(self, k: int) -> "DeviceColumn":
        """First k row slots (array/map columns keep their element buffers
        and slice only the offsets — the shape every row-slicer must use)."""
        if self.is_array:
            off, ed, ev = self.data
            return self.with_arrays((off[:k + 1], ed, ev), self.validity[:k])
        if self.is_struct:
            from spark_rapids_tpu.columnar.nested import StructData
            sd = StructData(tuple((d[:k], v[:k])
                                  for d, v in self.data.fields))
            return self.with_arrays(sd, self.validity[:k])
        if self.is_map:
            from spark_rapids_tpu.columnar.nested import MapData
            md = self.data
            return self.with_arrays(
                MapData(md.offsets[:k + 1], md.kdata, md.kvalid,
                        md.vdata, md.vvalid), self.validity[:k])
        return self.with_arrays(self.data[:k], self.validity[:k])


_MASK64 = (1 << 64) - 1


def dec128_limbs(values, validity, cap: int) -> np.ndarray:
    """Python-int unscaled values -> (cap, 2) int64 two-limb storage:
    [:, 0] = signed high 64 bits, [:, 1] = unsigned low 64 bits
    reinterpreted as int64 (the DECIMAL128 device layout). Vectorized
    over object arrays — this runs per upload AND per shuffle batch."""
    n = len(values)
    out = np.zeros((cap, 2), dtype=np.int64)
    if n == 0:
        return out
    v = np.where(np.asarray(validity[:n], dtype=bool),
                 np.asarray(values[:n], dtype=object), 0)
    lo = v & _MASK64
    lo = np.where(lo >= (1 << 63), lo - (1 << 64), lo)
    out[:n, 0] = (v >> 64).astype(np.int64)
    out[:n, 1] = lo.astype(np.int64)
    return out


def dec128_unscaled(limbs: np.ndarray, validity) -> np.ndarray:
    """(n, 2) int64 limbs -> python-int unscaled object array."""
    n = len(limbs)
    out = np.empty(n, dtype=object)
    if n == 0:
        return out
    vals = ((limbs[:, 0].astype(object) << 64)
            | (limbs[:, 1].astype(object) & _MASK64))
    out[:] = np.where(np.asarray(validity[:n], dtype=bool), vals, 0)
    return out


def null_data_array(dt: T.DataType, capacity: int):
    """All-null device data of the right SHAPE for ``dt`` — dec128
    columns are (capacity, 2) limb matrices (outer-join null sides)."""
    if T.is_dec128(dt):
        return jnp.zeros((capacity, 2), dtype=jnp.int64)
    return jnp.zeros(capacity, dtype=dt.np_dtype)


def _pad(values, cap: int, dtype) -> np.ndarray:
    """``values`` copied into a ``cap``-slot array of ``dtype``: one pass
    over the rows, and only the tail past them zeroed."""
    n = len(values)
    out = np.empty(cap, dtype=dtype)
    out[:n] = values
    out[n:] = 0
    return out


def stage_upload(host: HostColumn, cap: int, split_f64: bool):
    """Host side of the fast H2D path: turn one column into (recipe, staged
    numpy arrays, dictionary). Every column is staged as a dtype that
    transfers as it is, padded to the bucket, and the jitted assemble
    program (table.py) rebuilds the logical dtype on device:

      f64   -> with ``split_f64`` (every non-CPU backend, where a device
               f64 is an (f32, f32) pair) its raw 64-bit words as int64
               (``f64bits``): the program splits them into the pair by
               integer operations (ops/limbs.f64_bits_hi_lo), the same
               bits a host split f32(x), f32(x - f32(x)) gives, and adds
               the halves as before (on the tpu backend bit-identical to
               a native f64 transfer except in the f32-denormal range,
               where the native transfer keeps a denormal high limb and
               the sum flushes it to zero); exact f64 rides unchanged on
               CPU backends (split_f64=False there);
      i32   -> u32 view (astype back is value-exact mod 2^32 = bit-exact);
      bool  -> i8 (compare != 0 on device);
      rest  -> direct (i8/i16/i64/f32 transfer fast natively);
      validity -> omitted when all-valid (device row mask), else i8.
    """
    if isinstance(host.dtype, T.StringType):
        codes, dictionary = DeviceColumn._encode_strings(host)
        # narrow the code transfer to the dictionary's width: low-cardinality
        # string columns (the common case) ship 1 byte/row instead of 4
        if len(dictionary) <= 0xFF:
            kind, arrays = "u8codes", [_pad(codes, cap, np.uint8)]
        elif len(dictionary) <= 0xFFFF:
            kind, arrays = "u16codes", [_pad(codes, cap, np.uint16)]
        else:
            kind, arrays = "u32", [_pad(codes, cap, np.int32)
                                   .view(np.uint32)]
    elif T.is_dec128(host.dtype):
        limbs = dec128_limbs(host.data, host.validity, cap)
        dictionary = None
        kind, arrays = "dec128", [np.ascontiguousarray(limbs[:, 0]),
                                  np.ascontiguousarray(limbs[:, 1])]
    else:
        np_dtype = host.dtype.np_dtype
        dictionary = None
        padded = _pad(host.data, cap, np_dtype)
        if np_dtype == np.float64 and split_f64:
            kind, arrays = "f64bits", [padded.view(np.int64)]
        elif np_dtype == np.int32:
            kind, arrays = "u32", [padded.view(np.uint32)]
        elif np_dtype == np.bool_:
            kind, arrays = "bool8", [padded.astype(np.int8)]
        else:
            kind, arrays = "direct", [padded]
    if host.all_valid:
        vkind = "ones"
    else:
        vkind = "i8"
        arrays.append(_pad(host.validity, cap, np.int8))
    recipe = (kind, vkind, str(host.dtype))
    return recipe, arrays, dictionary
