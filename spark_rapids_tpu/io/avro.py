"""Avro object-container-file scan.

Reference (SURVEY.md §2.4): ``GpuAvroScan.scala`` / ``AvroDataFileReader
.scala`` (~1,500 LoC) — header/schema parse on the CPU in Scala, block
decode on the GPU, with the shared three reader modes. The TPU build
decodes on host (pure-Python binary decoder — no Avro library is baked
into the image) into columnar numpy and uploads through the standard scan
machinery; PERFILE/COALESCING/MULTITHREADED prefetch semantics come from
FileScanNode (io/common.py), exactly as the reference inherits them from
GpuMultiFileReader.

Supported schema surface (mirrors the engine's device types, with the
reference's tag-or-reject contract): records of null/boolean/int/long/
float/double/string, nullable unions ``["null", T]``, and the logical
types date (int), timestamp-millis/micros (long). Unsupported branches
(bytes/fixed/enum/map/nested records/arrays, multi-branch unions) raise
with a reason instead of decoding wrongly. Codecs: null, deflate, zstd
(when the zstandard module is present); snappy is rejected."""

from __future__ import annotations

import json
import struct
import zlib
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import HostColumn, HostTable
from spark_rapids_tpu.conf import RapidsConf, str_conf
from spark_rapids_tpu.errors import ColumnarProcessingError
from spark_rapids_tpu.io.common import FileScanNode, row_carrier_table
from spark_rapids_tpu.plan.nodes import Schema

AVRO_READER_TYPE = str_conf(
    "spark.rapids.sql.format.avro.reader.type", "AUTO",
    "PERFILE, COALESCING, MULTITHREADED or AUTO.")

MAGIC = b"Obj\x01"

_F32 = struct.Struct("<f")
_F64 = struct.Struct("<d")


class ByteReader:
    __slots__ = ("buf", "pos")

    def __init__(self, buf: bytes, pos: int = 0):
        self.buf = buf
        self.pos = pos

    def read(self, n: int) -> bytes:
        b = self.buf[self.pos:self.pos + n]
        if len(b) != n:
            raise ColumnarProcessingError("truncated avro data")
        self.pos += n
        return b

    def read_long(self) -> int:
        """Zigzag varint (avro int and long share the encoding)."""
        buf, pos = self.buf, self.pos
        shift = 0
        acc = 0
        while True:
            if pos >= len(buf):
                raise ColumnarProcessingError("truncated avro varint")
            b = buf[pos]
            pos += 1
            acc |= (b & 0x7F) << shift
            if not b & 0x80:
                break
            shift += 7
        self.pos = pos
        return (acc >> 1) ^ -(acc & 1)

    def read_bytes(self) -> bytes:
        return self.read(self.read_long())

    def at_end(self) -> bool:
        return self.pos >= len(self.buf)


# -- schema mapping ----------------------------------------------------------

def _spark_type_of(field_schema: Any) -> Tuple[T.DataType, bool]:
    """(spark type, nullable) for one avro field schema; raises on
    unsupported shapes (the reference's willNotWorkOnGpu analog)."""
    if isinstance(field_schema, list):  # union
        branches = [b for b in field_schema if b != "null"]
        if len(branches) != 1 or len(field_schema) > 2:
            raise ColumnarProcessingError(
                f"unsupported avro union {field_schema} (only "
                "[\"null\", T] unions are supported)")
        dt, _ = _spark_type_of(branches[0])
        return dt, True
    if isinstance(field_schema, dict):
        logical = field_schema.get("logicalType")
        base = field_schema.get("type")
        if logical == "date" and base == "int":
            return T.DATE, False
        if logical == "timestamp-micros" and base == "long":
            return T.TIMESTAMP, False
        if logical == "timestamp-millis" and base == "long":
            return T.TIMESTAMP, False
        if logical is None and isinstance(base, str):
            return _spark_type_of(base)
        raise ColumnarProcessingError(
            f"unsupported avro logical type {field_schema}")
    mapping = {"boolean": T.BOOLEAN, "int": T.INT, "long": T.LONG,
               "float": T.FLOAT, "double": T.DOUBLE, "string": T.STRING}
    if field_schema in mapping:
        return mapping[field_schema], False
    raise ColumnarProcessingError(
        f"unsupported avro type {field_schema!r} (bytes/fixed/enum/map/"
        "array/nested records are not supported)")


def _decoder_of(field_schema: Any) -> Callable[[ByteReader], Any]:
    """Value decoder for one (non-null-branch) schema; None return means
    the null branch was taken."""
    if isinstance(field_schema, list):
        branches = list(field_schema)
        inner = _decoder_of([b for b in branches if b != "null"][0])
        null_index = branches.index("null")

        def dec_union(r: ByteReader):
            idx = r.read_long()
            if idx == null_index:
                return None
            return inner(r)
        return dec_union
    if isinstance(field_schema, dict):
        logical = field_schema.get("logicalType")
        if logical == "timestamp-millis":
            return lambda r: r.read_long() * 1000  # -> micros
        return _decoder_of(field_schema["type"])
    if field_schema in ("int", "long"):
        return ByteReader.read_long
    if field_schema == "boolean":
        return lambda r: r.read(1) == b"\x01"
    if field_schema == "float":
        return lambda r: _F32.unpack(r.read(4))[0]
    if field_schema == "double":
        return lambda r: _F64.unpack(r.read(8))[0]
    if field_schema == "string":
        return lambda r: r.read_bytes().decode("utf-8")
    raise ColumnarProcessingError(f"unsupported avro type {field_schema!r}")


# -- container file ----------------------------------------------------------

class AvroFileInfo:
    def __init__(self, schema_json: dict, codec: str, sync: bytes,
                 blocks_offset: int):
        self.schema_json = schema_json
        self.codec = codec
        self.sync = sync
        self.blocks_offset = blocks_offset


def read_header(buf: bytes) -> AvroFileInfo:
    """Parse the container header: magic, metadata map, sync marker
    (AvroDataFileReader header parse analog)."""
    if buf[:4] != MAGIC:
        raise ColumnarProcessingError("not an avro object container file")
    r = ByteReader(buf, 4)
    meta: Dict[str, bytes] = {}
    while True:
        n = r.read_long()
        if n == 0:
            break
        if n < 0:  # negative count: abs count + byte size follows
            n = -n
            r.read_long()
        for _ in range(n):
            key = r.read_bytes().decode("utf-8")
            meta[key] = r.read_bytes()
    sync = r.read(16)
    schema_json = json.loads(meta["avro.schema"].decode("utf-8"))
    codec = meta.get("avro.codec", b"null").decode("utf-8")
    return AvroFileInfo(schema_json, codec, sync, r.pos)


def _decompress_block(codec: str, data: bytes) -> bytes:
    if codec == "null":
        return data
    if codec == "deflate":
        return zlib.decompress(data, wbits=-15)  # raw DEFLATE per spec
    if codec == "zstandard":
        try:
            import zstandard
        except ImportError:
            raise ColumnarProcessingError(
                "avro zstandard codec needs the zstandard module")
        return zstandard.ZstdDecompressor().decompress(data)
    raise ColumnarProcessingError(f"unsupported avro codec {codec!r}")


def decode_file(buf: bytes, keep: Optional[Sequence[str]] = None
                ) -> HostTable:
    """Decode a whole container file to a HostTable. With ``keep``, only
    the named fields become columns (in the file's order): the others'
    bytes are still walked, a row-wise container has no other way past
    them, but no value of theirs is held or converted. ``keep`` empty
    gives the row-count carrier."""
    info = read_header(buf)
    schema = info.schema_json
    if schema.get("type") != "record":
        raise ColumnarProcessingError("avro top-level schema must be a record")
    fields = schema["fields"]
    kept = [keep is None or f["name"] in keep for f in fields]
    decoders = [_decoder_of(f["type"]) for f in fields]
    values: List[List[Any]] = [[] for _ in fields]
    # a skipped field's values go to a sink nobody reads
    sinks = [(dec, out.append if k else _discard)
             for dec, out, k in zip(decoders, values, kept)]

    nrows = 0
    r = ByteReader(buf, info.blocks_offset)
    while not r.at_end():
        count = r.read_long()
        size = r.read_long()
        block = ByteReader(_decompress_block(info.codec, r.read(size)))
        if r.read(16) != info.sync:
            raise ColumnarProcessingError("avro sync marker mismatch")
        for _ in range(count):
            for dec, sink in sinks:
                sink(dec(block))
        nrows += count

    names, cols = [], []
    for f, vals, k in zip(fields, values, kept):
        if not k:
            continue
        dt, _nullable = _spark_type_of(f["type"])
        validity = np.array([v is not None for v in vals], dtype=np.bool_)
        if isinstance(dt, T.StringType):
            data = np.array(vals, dtype=object)
        else:
            fill = [v if v is not None else 0 for v in vals]
            data = np.asarray(fill, dtype=dt.np_dtype)
        names.append(f["name"])
        cols.append(HostColumn(dt, data, validity))
    if not cols:
        return row_carrier_table(nrows)
    return HostTable(names, cols)


def _discard(_value) -> None:
    pass


class AvroScanNode(FileScanNode):
    format_name = "avro"

    def _conf_reader_type(self) -> str:
        return self.conf.get_entry(AVRO_READER_TYPE)

    def file_schema(self, path: str) -> Schema:
        with open(path, "rb") as f:
            head = f.read(1 << 16)
        try:
            info = read_header(head)
        except ColumnarProcessingError:
            with open(path, "rb") as f:  # header larger than probe window
                info = read_header(f.read())
        return [(f["name"], _spark_type_of(f["type"])[0])
                for f in info.schema_json["fields"]]

    def read_file(self, path: str) -> HostTable:
        with open(path, "rb") as f:
            buf = f.read()
        if self.columns is None:
            return decode_file(buf)
        return decode_file(buf, keep={n for n, _ in self.data_schema})


# -- generic (nested) record decoding ----------------------------------------
# The COLUMNAR decode above intentionally stays flat (device types); this
# generic decoder handles full Avro recursion (nested records, arrays,
# maps, enums, fixed, multi-branch unions) into Python dicts — what the
# Iceberg connector needs for manifest-list/manifest files
# (AvroDataFileReader's generic datum path).

def _generic_decoder(schema: Any, named: Optional[dict] = None):
    named = {} if named is None else named
    if isinstance(schema, str):
        prim = {"null": lambda r: None,
                "boolean": lambda r: r.read(1) == b"\x01",
                "int": ByteReader.read_long,
                "long": ByteReader.read_long,
                "float": lambda r: _F32.unpack(r.read(4))[0],
                "double": lambda r: _F64.unpack(r.read(8))[0],
                "bytes": ByteReader.read_bytes,
                "string": lambda r: r.read_bytes().decode("utf-8")}
        if schema in prim:
            return prim[schema]
        if schema in named:
            return lambda r: named[schema](r)
        raise ColumnarProcessingError(f"unknown avro type {schema!r}")
    if isinstance(schema, list):
        branches = [_generic_decoder(b, named) for b in schema]

        def dec_union(r: ByteReader):
            return branches[r.read_long()](r)
        return dec_union
    t = schema["type"]
    if t == "record":
        field_decs = []
        names = []
        placeholder = [None]
        if "name" in schema:
            named[schema["name"]] = lambda r: placeholder[0](r)
        for f in schema["fields"]:
            names.append(f["name"])
            field_decs.append(_generic_decoder(f["type"], named))

        def dec_record(r: ByteReader):
            return {n: d(r) for n, d in zip(names, field_decs)}
        placeholder[0] = dec_record
        return dec_record
    if t == "array":
        item = _generic_decoder(schema["items"], named)

        def dec_array(r: ByteReader):
            out = []
            while True:
                n = r.read_long()
                if n == 0:
                    return out
                if n < 0:
                    n = -n
                    r.read_long()  # block byte size
                for _ in range(n):
                    out.append(item(r))
        return dec_array
    if t == "map":
        val = _generic_decoder(schema["values"], named)

        def dec_map(r: ByteReader):
            out = {}
            while True:
                n = r.read_long()
                if n == 0:
                    return out
                if n < 0:
                    n = -n
                    r.read_long()
                for _ in range(n):
                    k = r.read_bytes().decode("utf-8")
                    out[k] = val(r)
        return dec_map
    if t == "enum":
        symbols = schema["symbols"]
        return lambda r: symbols[r.read_long()]
    if t == "fixed":
        size = schema["size"]
        return lambda r: r.read(size)
    # logical types / wrapped primitives
    return _generic_decoder(t, named)


def decode_records(buf: bytes) -> List[dict]:
    """Decode a container file of arbitrary (possibly nested) records to a
    list of Python dicts."""
    info = read_header(buf)
    dec = _generic_decoder(info.schema_json)
    out: List[dict] = []
    r = ByteReader(buf, info.blocks_offset)
    while not r.at_end():
        count = r.read_long()
        size = r.read_long()
        block = ByteReader(_decompress_block(info.codec, r.read(size)))
        if r.read(16) != info.sync:
            raise ColumnarProcessingError("avro sync marker mismatch")
        for _ in range(count):
            out.append(dec(block))
    return out
