"""Arrow <-> HostTable conversion.

Arrow is the host interchange format (SURVEY.md §7: "Columnar batches live in
HBM as XLA buffers; Arrow is the host format"). Spark internal representations
are preserved: DATE as int32 days, TIMESTAMP as int64 micros UTC, DECIMAL(p<=18)
as int64 unscaled, STRING as Python-str object arrays.

A STRING column is dictionary-encoded here, by Arrow's own kernels, into the
order-preserving encoding the device upload needs (codes into a dictionary
sorted in UTF-8 byte order): the column carries it in its ``encode`` memo, so
``DeviceColumn._encode_strings`` finds it and walks no object a row. Only the
distinct values become Python objects; the rows of ``data`` share them."""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.errors import ColumnarProcessingError


def arrow_type_to_spark(at: pa.DataType) -> T.DataType:
    if pa.types.is_boolean(at):
        return T.BOOLEAN
    if pa.types.is_int8(at):
        return T.BYTE
    if pa.types.is_int16(at):
        return T.SHORT
    if pa.types.is_int32(at):
        return T.INT
    if pa.types.is_int64(at):
        return T.LONG
    if pa.types.is_float32(at):
        return T.FLOAT
    if pa.types.is_float64(at):
        return T.DOUBLE
    if pa.types.is_string(at) or pa.types.is_large_string(at):
        return T.STRING
    if pa.types.is_date32(at):
        return T.DATE
    if pa.types.is_timestamp(at):
        return T.TIMESTAMP
    if pa.types.is_decimal(at):
        return T.DecimalType(at.precision, at.scale)
    if pa.types.is_null(at):
        return T.NULL
    if pa.types.is_dictionary(at):
        return arrow_type_to_spark(at.value_type)
    raise ColumnarProcessingError(f"unsupported Arrow type {at}")


def spark_type_to_arrow(dt: T.DataType) -> pa.DataType:
    if isinstance(dt, T.BooleanType):
        return pa.bool_()
    if isinstance(dt, T.ByteType):
        return pa.int8()
    if isinstance(dt, T.ShortType):
        return pa.int16()
    if isinstance(dt, T.IntegerType):
        return pa.int32()
    if isinstance(dt, T.LongType):
        return pa.int64()
    if isinstance(dt, T.FloatType):
        return pa.float32()
    if isinstance(dt, T.DoubleType):
        return pa.float64()
    if isinstance(dt, T.StringType):
        return pa.string()
    if isinstance(dt, T.DateType):
        return pa.date32()
    if isinstance(dt, T.TimestampType):
        return pa.timestamp("us", tz="UTC")
    if isinstance(dt, T.DecimalType):
        return pa.decimal128(dt.precision, dt.scale)
    if isinstance(dt, T.NullType):
        return pa.null()
    raise ColumnarProcessingError(f"no Arrow type for {dt}")


def arrow_schema_to_spark(schema: pa.Schema) -> List[Tuple[str, T.DataType]]:
    return [(f.name, arrow_type_to_spark(f.type)) for f in schema]


def _chunked_to_array(col: pa.ChunkedArray) -> pa.Array:
    return col.combine_chunks() if col.num_chunks != 1 else col.chunk(0)


def _string_host_column(arr: pa.Array, dt: T.DataType,
                        validity: np.ndarray) -> HostColumn:
    """A STRING column with its upload encoding already made: the same
    (codes int32, sorted dictionary) that ``native.encode_sorted_dict``
    gives for ``np.where(validity, data, "")``, with nulls as ``""`` as
    there. Arrow sorts strings by their bytes, which for UTF-8 is
    code-point order: Python's, and Spark's UTF8String order."""
    if not (pa.types.is_string(arr.type) or pa.types.is_large_string(arr.type)):
        arr = arr.cast(pa.string())  # an all-null column typed null
    if arr.null_count:
        arr = arr.fill_null("")
    enc = arr.dictionary_encode()
    order = pc.sort_indices(enc.dictionary)
    k = len(enc.dictionary)
    rank = np.empty(k, dtype=np.int32)
    rank[order.to_numpy()] = np.arange(k, dtype=np.int32)
    codes = rank[enc.indices.to_numpy()]
    dictionary = enc.dictionary.take(order).to_numpy(zero_copy_only=False)
    data = dictionary[codes]
    if not validity.all():
        data[~validity] = None
    col = HostColumn(dt, data, validity)
    col._cache["encode"] = (codes, dictionary)
    return col


def arrow_array_to_host_column(arr, dt: T.DataType) -> HostColumn:
    if isinstance(arr, pa.ChunkedArray):
        arr = _chunked_to_array(arr)
    if pa.types.is_dictionary(arr.type):
        arr = arr.cast(arr.type.value_type)
    n = len(arr)
    validity = np.ones(n, dtype=np.bool_)
    if arr.null_count:
        validity = ~np.asarray(arr.is_null())

    if isinstance(dt, T.StringType):
        return _string_host_column(arr, dt, validity)
    if isinstance(dt, T.TimestampType):
        micros = arr.cast(pa.timestamp("us"))
        vals = np.asarray(micros.fill_null(0)).astype("datetime64[us]").astype(np.int64)
        return HostColumn(dt, vals, validity)
    if isinstance(dt, T.DateType):
        vals = np.asarray(arr.fill_null(0)).astype("datetime64[D]").astype(np.int32)
        return HostColumn(dt, vals, validity)
    if isinstance(dt, T.DecimalType):
        import decimal as _dec
        # default context precision (28) silently ROUNDS 38-digit
        # decimals; widen it for the exact unscaled conversion
        ctx = _dec.Context(prec=T.DecimalType.MAX_PRECISION + 10)
        scaled = [int(v.scaleb(dt.scale, context=ctx)) if v is not None
                  else 0 for v in arr.to_pylist()]
        if T.is_dec128(dt):
            # unscaled beyond int64: python-int object storage (two-limb
            # device columns — columnar/column.py dec128_limbs)
            data = np.empty(n, dtype=object)
            data[:] = scaled
            return HostColumn(dt, data, validity)
        # int64 unscaled value, exact for p<=18
        return HostColumn(dt, np.array(scaled, dtype=np.int64), validity)
    if isinstance(dt, T.NullType):
        return HostColumn(dt, np.zeros(n, dtype=np.int8), np.zeros(n, dtype=np.bool_))
    # fixed-width numerics/bool: zero-fill nulls then view as numpy
    if arr.null_count:
        arr = arr.fill_null(False if pa.types.is_boolean(arr.type) else 0)
    vals = np.asarray(arr)
    np_dtype = dt.np_dtype
    if vals.dtype != np_dtype:
        vals = vals.astype(np_dtype)
    return HostColumn(dt, np.ascontiguousarray(vals), validity)


def arrow_to_host_table(table: pa.Table,
                        schema: Optional[Sequence[Tuple[str, T.DataType]]] = None
                        ) -> HostTable:
    if schema is None:
        schema = arrow_schema_to_spark(table.schema)
    names, cols = [], []
    for (name, dt) in schema:
        arr = table.column(name)
        names.append(name)
        cols.append(arrow_array_to_host_column(arr, dt))
    return HostTable(names, cols)


def decode_to_schema(table: pa.Table, schema: Sequence[Tuple[str, T.DataType]]
                     ) -> HostTable:
    """Select the schema's columns present in ``table`` and SAFELY cast each
    to the expected Arrow type before conversion. This pins multi-file reads
    to the scan schema: a file whose inferred types drift (e.g. int column
    that parses as double in file 2) either casts losslessly or raises,
    instead of silently truncating at the numpy layer."""
    present = set(table.schema.names)
    use = [(n, dt) for n, dt in schema if n in present]
    names, cols = [], []
    for name, dt in use:
        arr = table.column(name)
        if isinstance(arr, pa.ChunkedArray):
            arr = _chunked_to_array(arr)
        target = spark_type_to_arrow(dt)
        if not pa.types.is_dictionary(arr.type) and arr.type != target \
                and not isinstance(dt, T.NullType):
            arr = arr.cast(target)  # safe cast: raises on lossy conversion
        names.append(name)
        cols.append(arrow_array_to_host_column(arr, dt))
    return HostTable(names, cols)


def host_column_to_arrow(col: HostColumn) -> pa.Array:
    dt = col.dtype
    mask = None if bool(col.validity.all()) else ~col.validity
    if isinstance(dt, T.StringType):
        vals = [v if ok else None for v, ok in zip(col.data, col.validity)]
        return pa.array(vals, type=pa.string())
    if isinstance(dt, T.TimestampType):
        return pa.array(col.data.astype("datetime64[us]"), mask=mask,
                        type=pa.timestamp("us", tz="UTC"))
    if isinstance(dt, T.DateType):
        return pa.array(col.data.astype("datetime64[D]"), mask=mask, type=pa.date32())
    if isinstance(dt, T.DecimalType):
        import decimal
        ctx = decimal.Context(prec=T.DecimalType.MAX_PRECISION + 10)
        q = decimal.Decimal(1).scaleb(-dt.scale)
        vals = [decimal.Decimal(int(v)).scaleb(-dt.scale, context=ctx)
                .quantize(q, context=ctx) if ok else None
                for v, ok in zip(col.data, col.validity)]
        return pa.array(vals, type=pa.decimal128(dt.precision, dt.scale))
    if isinstance(dt, T.NullType):
        return pa.nulls(len(col))
    return pa.array(col.data, mask=mask, type=spark_type_to_arrow(dt))


def host_table_to_arrow(table: HostTable) -> pa.Table:
    arrays = [host_column_to_arrow(c) for c in table.columns]
    return pa.table(dict(zip(table.names, arrays)))
