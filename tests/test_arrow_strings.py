"""A file scan's string columns are dictionary-encoded by Arrow in the reader
(io/arrow_convert.py ``_string_host_column``): the decoded column carries the
(codes, sorted dictionary) its upload needs, so no Python object a row is made
between the file and the device. Held here: the values and the encoding equal,
element for element and in dtype, what the object path gives
(``to_pylist`` + ``native.encode_sorted_dict``) on every kind of input; a
Q1-shaped statement over Parquet in each reader mode, CSV and ORC answers as
the same rows through ``create_dataframe`` do; ``scanStringsPreEncoded``
counts the columns whose encoding came with the batch; and a second query
decodes and encodes again."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.csv as pcsv
import pyarrow.orc as po
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.execs.basic import TpuFileScanExec
from spark_rapids_tpu.io import arrow_convert
from spark_rapids_tpu.io.arrow_convert import (
    arrow_array_to_host_column,
    decode_to_schema,
)
from spark_rapids_tpu.native import encode_sorted_dict
from spark_rapids_tpu.ops.expr import col


def _text(n, seed=0):
    rng = np.random.default_rng(seed)
    words = ["carefully", "final", "deposits", "sleep", "quickly", "ironic"]
    return [" ".join(rng.choice(words, 3)) + f" {j}" for j in range(n)]


def _dict_with_unused():
    indices = pa.array([0, 2, 2, None, 0], type=pa.int32())
    dictionary = pa.array(["zeta", "unused", "alpha"])
    return pa.DictionaryArray.from_arrays(indices, dictionary)


CASES = {
    "one_char_flags": lambda: pa.array(
        list("ANRNNAFRO" * 50)),
    "high_cardinality_text": lambda: pa.array(_text(5000)),
    "non_bmp_and_combining": lambda: pa.array(
        ["\U0001F600", "é", "é", "\uffff", "\U00010000", "z",
         "e", "é", "\U0001F600a", None]),
    "nul_suffix": lambda: pa.array(["a\x00", "a", "a\x00b", "a", "\x00"]),
    "empty_with_nulls": lambda: pa.array(["", None, "x", "", None]),
    "empty_alone": lambda: pa.array(["", "", ""]),
    "nulls_alone": lambda: pa.array([None, None, None], type=pa.string()),
    "all_null_typed_null": lambda: pa.nulls(4),
    "zero_rows": lambda: pa.array([], type=pa.string()),
    "multi_chunk": lambda: pa.chunked_array(
        [pa.array(["b", "a", None]), pa.array(["c", "a"]),
         pa.array([], type=pa.string()), pa.array(["b"])]),
    "large_string": lambda: pa.array(
        ["beta", None, "alpha", "beta", ""], type=pa.large_string()),
    "dictionary_unused_entry": _dict_with_unused,
}


def _object_path(arr):
    """What the engine built before the reader encoded: one Python object a
    row, then the upload's encode of it."""
    values = arr.to_pylist()
    data = np.empty(len(values), dtype=object)
    for i, v in enumerate(values):
        data[i] = v
    validity = np.array([v is not None for v in values], dtype=np.bool_)
    return values, encode_sorted_dict(
        np.asarray(np.where(validity, data, ""), dtype=object))


@pytest.mark.parametrize("case", sorted(CASES))
def test_encoding_equals_the_object_path(case):
    arr = CASES[case]()
    values, (want_codes, want_dict) = _object_path(arr)
    got = arrow_array_to_host_column(arr, T.STRING)
    assert got.data.dtype == object
    assert list(got.data) == values
    assert [type(v) for v in got.data] == [type(v) for v in values]
    assert list(got.validity) == [v is not None for v in values]
    codes, dictionary = got._cache["encode"]
    assert codes.dtype == want_codes.dtype == np.int32
    assert dictionary.dtype == want_dict.dtype == object
    assert np.array_equal(codes, want_codes)
    assert list(dictionary) == list(want_dict)
    assert [type(v) for v in dictionary] == [str] * len(dictionary)


@pytest.mark.parametrize("case", sorted(CASES))
def test_upload_takes_the_readers_encoding(case):
    """The upload's encode returns the reader's memo as it stands."""
    from spark_rapids_tpu.columnar.column import DeviceColumn
    got = arrow_array_to_host_column(CASES[case](), T.STRING)
    memo = got._cache["encode"]
    assert DeviceColumn._encode_strings(got) is memo


def test_rows_share_the_distinct_values():
    """Only the k distinct values are Python objects; rows point at them."""
    got = arrow_array_to_host_column(pa.array(list("ANR" * 1000)), T.STRING)
    assert len({id(v) for v in got.data}) == 3


def test_decode_to_schema_casts_large_string_and_dictionary():
    t = pa.table({"a": pa.array(["y", "x", None, "", "x"],
                                type=pa.large_string()),
                  "b": _dict_with_unused()})
    host = decode_to_schema(t, [("a", T.STRING), ("b", T.STRING)])
    for name, column in zip(host.names, host.columns):
        values, (codes, dictionary) = _object_path(t.column(name))
        assert list(column.data) == values
        assert np.array_equal(column._cache["encode"][0], codes)
        assert list(column._cache["encode"][1]) == list(dictionary)


# --------------------------------------------------------------------------
# Readers: a Q1-shaped statement over files
# --------------------------------------------------------------------------

FILES = 3
ROWS = 400
STRINGS = 2  # the flag columns the statement groups by


def _rows(f, nulls):
    rng = np.random.default_rng(f)
    flag = list(rng.choice(list("ANR"), ROWS))
    if nulls:
        for j in range(0, ROWS, 37):
            flag[j] = None
    return {
        "l_returnflag": flag,
        "l_linestatus": list(rng.choice(list("FO"), ROWS)),
        "l_quantity": np.round(rng.random(ROWS) * 50, 2),
        "l_orderkey": rng.integers(0, 1 << 20, ROWS).astype(np.int64),
    }


def _write(fmt, directory, nulls):
    os.makedirs(directory, exist_ok=True)
    parts = []
    for f in range(FILES):
        rows = _rows(f, nulls)
        parts.append(rows)
        t = pa.table({k: pa.array(v) for k, v in rows.items()})
        path = os.path.join(directory, f"part-{f}.{fmt}")
        if fmt == "parquet":
            pq.write_table(t, path, row_group_size=ROWS // 2)
        elif fmt == "orc":
            po.write_table(t, path)
        else:
            pcsv.write_csv(t, path)
    return {k: [v for p in parts for v in list(p[k])] for k in parts[0]}


def _q1(df):
    return (df.filter(col("l_quantity") > 1.0)
            .group_by("l_returnflag", "l_linestatus")
            .agg(F.sum(col("l_quantity")).alias("sum_qty"),
                 F.count().alias("count_order")))


def _sorted(rows):
    return sorted(rows, key=repr)


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(_sorted(got), _sorted(want)):
        assert g[:2] == w[:2] and g[3] == w[3]
        assert g[2] == pytest.approx(w[2], rel=1e-9)


def _scan(session):
    stack, found = [session._last_executable], []
    while stack:
        e = stack.pop()
        if isinstance(e, TpuFileScanExec):
            found.append(e)
        stack.extend(getattr(e, "children", ()))
        for attr in ("source", "tpu_exec"):
            nxt = getattr(e, attr, None)
            if nxt is not None:
                stack.append(nxt)
    assert len(found) == 1
    return found[0]


def _expected(session, rows):
    dtypes = {"l_returnflag": T.STRING, "l_linestatus": T.STRING,
              "l_quantity": T.DOUBLE, "l_orderkey": T.LONG}
    data = {k: list(v) for k, v in rows.items()}
    data["l_quantity"] = [float(x) for x in data["l_quantity"]]
    data["l_orderkey"] = [int(x) for x in data["l_orderkey"]]
    return _q1(session.create_dataframe(data, dtypes)).collect()


READERS = [
    ("parquet", "PERFILE", FILES * STRINGS),
    ("parquet", "MULTITHREADED", FILES * STRINGS),
    ("parquet", "AUTO", FILES * STRINGS),
    ("parquet", "COALESCING", 0),
    ("orc", "PERFILE", FILES * STRINGS),
    ("orc", "MULTITHREADED", FILES * STRINGS),
    ("orc", "COALESCING", 0),
    ("csv", "PERFILE", FILES * STRINGS),
    ("csv", "MULTITHREADED", FILES * STRINGS),
]


@pytest.mark.parametrize("fmt,mode,pre_encoded", READERS,
                         ids=[f"{f}-{m}" for f, m, _ in READERS])
def test_q1_over_files_answers_as_create_dataframe(tmp_path, session, fmt,
                                                   mode, pre_encoded):
    nulls = fmt != "csv"  # a CSV field reads back "" where Arrow wrote null
    rows = _write(fmt, str(tmp_path / fmt), nulls)
    df = getattr(session, f"read_{fmt}")(str(tmp_path / fmt),
                                         reader_type=mode)
    got = _q1(df).collect()
    scan = _scan(session)
    assert scan.metrics["scanStringsPreEncoded"] == pre_encoded
    assert scan.metrics["scanRows"] == FILES * ROWS
    _same(got, _expected(session, rows))


def test_a_second_query_decodes_and_encodes_again(tmp_path, session,
                                                  monkeypatch):
    """Nothing decoded or encoded outlives its query: the same statement
    over the same files runs the reader's encode again, and the counter
    reads one query's columns, not a sum over queries."""
    rows = _write("parquet", str(tmp_path / "p"), nulls=True)
    calls = []
    real = arrow_convert._string_host_column

    def counting(arr, dt, validity):
        out = real(arr, dt, validity)
        calls.append(dt)
        return out

    monkeypatch.setattr(arrow_convert, "_string_host_column", counting)
    df = session.read_parquet(str(tmp_path / "p"))
    want = _expected(session, rows)
    for query in range(2):
        _same(_q1(df).collect(), want)
        assert _scan(session).metrics["scanStringsPreEncoded"] == \
            FILES * STRINGS
        assert len(calls) == (query + 1) * FILES * STRINGS
