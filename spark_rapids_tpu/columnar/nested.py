"""Device representation of STRUCT and MAP columns.

Reference surface: cuDF STRUCT/LIST columns consumed by the plugin's
complexTypeCreator.scala / collectionOperations.scala expression families
(SURVEY.md §2.3 #26). The TPU mapping keeps everything as flat padded
buffers XLA can fuse over:

* STRUCT — a bundle of per-field (data, validity) pairs sharing the parent
  row capacity, plus a struct-level validity. No row data moves to form or
  project a struct: creation bundles existing arrays, field access is a
  tuple pick (both free under XLA).
* MAP — the array layout with TWO element streams: row offsets[cap+1] into
  parallel key/value buffers (keys non-null by construction, values carry
  their own validity). Spark's map<k,v> IS array<struct<k,v>> semantically;
  splitting the streams keeps every buffer fixed-width so lookups and
  lambda transforms are plain gathers/segment ops.

Host form: structs are python tuples (collect() rows are tuples), maps are
python dicts.

Device maps/structs restrict element/field types to the fixed-width set
(is_fixed_array's element rule); anything else tags the op for CPU
fallback through the TypeSig layer (overrides/typesig.py) — the same
per-op nested-type gating the reference encodes in TypeChecks.scala."""

from __future__ import annotations

from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.errors import ColumnarProcessingError


class StructData:
    """Device payload of a struct column/value: one (data, validity) pair
    per field. Field data may itself be nested."""

    __slots__ = ("fields",)

    def __init__(self, fields: Tuple[tuple, ...]):
        self.fields = tuple(fields)


class MapData:
    """Device payload of a map column/value."""

    __slots__ = ("offsets", "kdata", "kvalid", "vdata", "vvalid")

    def __init__(self, offsets, kdata, kvalid, vdata, vvalid):
        self.offsets = offsets
        self.kdata = kdata
        self.kvalid = kvalid
        self.vdata = vdata
        self.vvalid = vvalid


# nested payloads cross jit boundaries as ordinary pytrees
jax.tree_util.register_pytree_node(
    StructData,
    lambda sd: (sd.fields, None),
    lambda _, fields: StructData(tuple(fields)))
jax.tree_util.register_pytree_node(
    MapData,
    lambda md: ((md.offsets, md.kdata, md.kvalid, md.vdata, md.vvalid),
                None),
    lambda _, ch: MapData(*ch))


def fixed_np_dtype(dt: T.DataType):
    """np dtype for a device-supported nested element/field type, or None."""
    if isinstance(dt, (T.StringType, T.ArrayType, T.StructType, T.MapType,
                       T.NullType)):
        return None
    try:
        return dt.np_dtype
    except Exception:
        return None


def struct_device_supported(dt: T.StructType) -> bool:
    return all(fixed_np_dtype(f.data_type) is not None for f in dt.fields)


def map_device_supported(dt: T.MapType) -> bool:
    return (fixed_np_dtype(dt.key_type) is not None
            and fixed_np_dtype(dt.value_type) is not None)


def struct_from_host(host, cap: int):
    """(StructData, validity) from a host object-array of tuples/dicts."""
    dt: T.StructType = host.dtype
    n = len(host)
    validity = np.zeros(cap, dtype=np.bool_)
    validity[:n] = host.validity
    fields = []
    for fi, f in enumerate(dt.fields):
        npdt = fixed_np_dtype(f.data_type)
        if npdt is None:
            raise ColumnarProcessingError(
                f"struct field {f.name} type {f.data_type.simple_string()} "
                "not device-representable")
        fd = np.zeros(cap, dtype=npdt)
        fv = np.zeros(cap, dtype=np.bool_)
        for i in range(n):
            if not host.validity[i]:
                continue
            row = host.data[i]
            v = row.get(f.name) if isinstance(row, dict) else row[fi]
            if v is not None:
                fd[i] = v
                fv[i] = True
        fields.append((jnp.asarray(fd), jnp.asarray(fv)))
    return StructData(tuple(fields)), jnp.asarray(validity)


def struct_to_host(dtype: T.StructType, sd: StructData, validity,
                   num_rows: int):
    from spark_rapids_tpu.columnar.column import HostColumn
    validity = np.ascontiguousarray(np.asarray(validity)[:num_rows])
    fds = [np.asarray(d)[:num_rows] for d, _ in sd.fields]
    fvs = [np.asarray(v)[:num_rows] for _, v in sd.fields]
    out = np.empty(num_rows, dtype=object)
    for i in range(num_rows):
        if validity[i]:
            out[i] = tuple(
                fds[fi][i].item() if fvs[fi][i] else None
                for fi in range(len(sd.fields)))
    return HostColumn(dtype, out, validity)


def map_from_host(host, cap: int):
    """(MapData, validity) from a host object-array of dicts (or
    (key, value) pair lists)."""
    dt: T.MapType = host.dtype
    kdt, vdt = fixed_np_dtype(dt.key_type), fixed_np_dtype(dt.value_type)
    if kdt is None or vdt is None:
        raise ColumnarProcessingError(
            f"map type {dt.simple_string()} not device-representable")
    from spark_rapids_tpu.columnar.column import bucket_for
    n = len(host)
    lengths = np.zeros(cap + 1, dtype=np.int64)
    for i in range(n):
        if host.validity[i]:
            lengths[i + 1] = len(host.data[i])
    offsets = np.cumsum(lengths).astype(np.int32)
    ecap = bucket_for(max(int(offsets[cap]), 1))
    kd = np.zeros(ecap, dtype=kdt)
    kv = np.zeros(ecap, dtype=np.bool_)
    vd = np.zeros(ecap, dtype=vdt)
    vv = np.zeros(ecap, dtype=np.bool_)
    pos = 0
    for i in range(n):
        if not host.validity[i]:
            continue
        items = (host.data[i].items() if isinstance(host.data[i], dict)
                 else host.data[i])
        for k, v in items:
            kd[pos] = k
            kv[pos] = True
            if v is not None:
                vd[pos] = v
                vv[pos] = True
            pos += 1
    validity = np.zeros(cap, dtype=np.bool_)
    validity[:n] = host.validity
    return (MapData(jnp.asarray(offsets), jnp.asarray(kd), jnp.asarray(kv),
                    jnp.asarray(vd), jnp.asarray(vv)),
            jnp.asarray(validity))


def map_to_host(dtype: T.MapType, md: MapData, validity, num_rows: int):
    from spark_rapids_tpu.columnar.column import HostColumn
    validity = np.ascontiguousarray(np.asarray(validity)[:num_rows])
    off = np.asarray(md.offsets)
    kd, kv = np.asarray(md.kdata), np.asarray(md.kvalid)
    vd, vv = np.asarray(md.vdata), np.asarray(md.vvalid)
    out = np.empty(num_rows, dtype=object)
    for i in range(num_rows):
        if validity[i]:
            s, e = int(off[i]), int(off[i + 1])
            if not kv[s:e].all():
                # a null key expression result reached a map entry — Spark
                # raises at evaluation; the device kernel cannot, so the
                # error surfaces at collect instead of as a bogus zero key
                raise ColumnarProcessingError("Cannot use null as map key")
            out[i] = {kd[j].item(): (vd[j].item() if vv[j] else None)
                      for j in range(s, e)}
    return HostColumn(dtype, out, validity)


def nested_nbytes(data) -> int:
    if isinstance(data, StructData):
        # fields are fixed-width by construction (struct_device_supported)
        return int(sum(d.size * d.dtype.itemsize + v.size
                       for d, v in data.fields))
    if isinstance(data, MapData):
        return int(data.offsets.size * 4
                   + data.kdata.size * data.kdata.dtype.itemsize
                   + data.kvalid.size
                   + data.vdata.size * data.vdata.dtype.itemsize
                   + data.vvalid.size)
    return 0
