"""TPC-H customer, orders and lineitem to dbgen's rules (spec rev. 3.0.1,
section 4.2.3), made from a seed with numpy. Independent of the program
under test: it imports nothing of `spark_rapids_tpu`.

lineitem is `benchmarks.datagen.tpch`'s, to the bit: the same makers over
the same random streams, so a seed and a scale give the lineitem that the
Q1 cells read. This module adds the two tables lineitem hangs from.

What follows dbgen, per column (section 4.2.3):
  customer  150,000 x SF rows; c_custkey 1..n; c_name "Customer#" + the
            key zero-padded to 9 digits; c_address 10..40 random
            characters; c_nationkey uniform 0..24; c_phone
            "<nationkey+10>-ddd-ddd-dddd" (100..999, 100..999,
            1000..9999); c_acctbal uniform -999.99..9999.99;
            c_mktsegment one of five; c_comment 29..116 characters of
            the grammar's text.
  orders    1,500,000 x SF rows; o_orderkey sparse (the first 8 of every
            32 keys); o_custkey uniform over the customer keys that are
            not divisible by 3 (a third of the customers have no order);
            o_orderstatus F when all its lines are F, O when all are O,
            else P; o_totalprice the sum over its lines of
            l_extendedprice x (1 + l_tax) x (1 - l_discount);
            o_orderdate uniform in [1992-01-01, 1998-08-02];
            o_orderpriority one of five; o_clerk "Clerk#" + a value of
            1..1000 x SF zero-padded to 9 digits; o_shippriority 0;
            o_comment 19..78 characters of the grammar's text.
A formatted key (c_name, c_phone) is a text column over a pool of its own,
the characters computed as digits: no Python object a row is made here.
What departs from dbgen is listed in the configuration file's `assumed`.
"""

from __future__ import annotations

import numpy as np

from benchmarks.datagen import tpch
from benchmarks.datagen.tpch import (
    Column,
    POOL_BYTES,
    _Lazy,
    _lineitem_makers,
    _orders_base,
    _stream,
    _uniform,
    text_pool,
)

SEGMENTS = np.array(sorted(["AUTOMOBILE", "BUILDING", "FURNITURE",
                            "HOUSEHOLD", "MACHINERY"]), dtype=object)
PRIORITIES = np.array(sorted(["1-URGENT", "2-HIGH", "3-MEDIUM",
                              "4-NOT SPECIFIED", "5-LOW"]), dtype=object)
ORDERSTATUS = np.array(["F", "O", "P"], dtype=object)

COLUMN_TYPES = {
    "customer": {"c_custkey": "long", "c_name": "text", "c_address": "text",
                 "c_nationkey": "int", "c_phone": "text",
                 "c_acctbal": "double", "c_mktsegment": "string",
                 "c_comment": "text"},
    "orders": {"o_orderkey": "long", "o_custkey": "long",
               "o_orderstatus": "string", "o_totalprice": "double",
               "o_orderdate": "date", "o_orderpriority": "string",
               "o_clerk": "string", "o_shippriority": "int",
               "o_comment": "text"},
    "lineitem": tpch.COLUMN_TYPES["lineitem"],
}

_ADDRESS_ALPHABET = np.frombuffer(
    b"0123456789abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ ,",
    np.uint8)
_ADDRESS_SEED = 19920102  # one pool of characters for every run
_address_made = None


def address_pool() -> str:
    """`POOL_BYTES` random characters: c_address is cut from it."""
    global _address_made
    if _address_made is None:
        rng = np.random.default_rng(_ADDRESS_SEED)
        picks = rng.integers(0, len(_ADDRESS_ALPHABET), size=POOL_BYTES,
                             dtype=np.uint8)
        _address_made = _ADDRESS_ALPHABET[picks].tobytes().decode("ascii")
    return _address_made


def _digits(values: np.ndarray, width: int) -> np.ndarray:
    """`values` zero-padded to `width` decimal digits, as a uint8 matrix
    of ASCII codes, one row a value."""
    powers = 10 ** np.arange(width - 1, -1, -1, dtype=np.int64)
    return ((values.astype(np.int64)[:, None] // powers) % 10 + 48) \
        .astype(np.uint8)


def _fixed_text(rows: np.ndarray) -> Column:
    """A text column whose pool is its own rows, all of one width."""
    n, width = rows.shape
    return Column("text", np.arange(n, dtype=np.int32) * width,
                  lengths=np.full(n, width, dtype=np.int32),
                  pool=rows.tobytes().decode("ascii"))


def _cut_text(seed, table, column, low, high, n, pool: str) -> Column:
    lengths = _uniform(seed, table, f"{column}.length", low, high, n)
    starts = _uniform(seed, table, f"{column}.offset", 0,
                      POOL_BYTES - high - 1, n)
    return Column("text", starts, lengths=lengths, pool=pool)


def _customer_makers(seed: int, sf: float):
    n = int(round(150_000 * sf))
    memo = _Lazy()

    def custkey():
        return np.arange(1, n + 1, dtype=np.int64)

    def nationkey():
        return memo.get("c_nationkey", lambda: _uniform(
            seed, "customer", "c_nationkey", 0, 24, n))

    def name():
        prefix = np.frombuffer(b"Customer#", np.uint8)
        return _fixed_text(np.concatenate(
            [np.broadcast_to(prefix, (n, len(prefix))),
             _digits(custkey(), 9)], axis=1))

    def phone():
        dash = np.full((n, 1), ord("-"), np.uint8)
        part = lambda name, low, high: _uniform(
            seed, "customer", f"c_phone.{name}", low, high, n)
        return _fixed_text(np.concatenate(
            [_digits(nationkey() + 10, 2), dash,
             _digits(part("a", 100, 999), 3), dash,
             _digits(part("b", 100, 999), 3), dash,
             _digits(part("c", 1000, 9999), 4)], axis=1))

    return n, {
        "c_custkey": lambda: Column("long", custkey()),
        "c_name": name,
        "c_address": lambda: _cut_text(seed, "customer", "c_address", 10,
                                       40, n, address_pool()),
        "c_nationkey": lambda: Column("int", nationkey()),
        "c_phone": phone,
        "c_acctbal": lambda: Column("double", _uniform(
            seed, "customer", "c_acctbal", -99999, 999999, n) / 100.0),
        "c_mktsegment": lambda: Column("string", _uniform(
            seed, "customer", "c_mktsegment", 0, len(SEGMENTS) - 1, n),
            SEGMENTS),
        "c_comment": lambda: _cut_text(seed, "customer", "c_comment", 29,
                                       116, n, text_pool()),
    }


def _orders_makers(seed: int, sf: float, orders, line):
    """`orders` is `_orders_base`'s (keys, dates, line counts); `line`
    the lineitem makers, whose rows lie order by order."""
    counts = orders["linecount"]()
    n = len(counts)
    first = np.cumsum(counts) - counts  # each order's first line
    customers = int(round(150_000 * sf))

    def custkey():
        # the j-th customer key that 3 does not divide: 1, 2, 4, 5, 7, ...
        with_orders = customers - customers // 3
        j = _stream(seed, "orders", "o_custkey").integers(
            0, with_orders, size=n, dtype=np.int64)
        return 3 * (j // 2) + j % 2 + 1

    def orderstatus():
        open_lines = np.add.reduceat(line["l_linestatus"](), first)
        # codes into ORDERSTATUS: F=0, O=1, P=2
        return np.where(open_lines == 0, 0,
                        np.where(open_lines == counts, 1, 2)) \
            .astype(np.int32)

    def totalprice():
        charge = (line["l_extendedprice"]() * (1.0 + line["l_tax"]())
                  * (1.0 - line["l_discount"]()))
        return np.add.reduceat(charge, first)

    def clerk():
        clerks = int(round(1000 * sf))
        prefix = np.frombuffer(b"Clerk#", np.uint8)
        rows = np.concatenate(
            [np.broadcast_to(prefix, (clerks, len(prefix))),
             _digits(np.arange(1, clerks + 1), 9)], axis=1)
        # the few clerks' names as a sorted dictionary (zero-padded, so
        # key order is string order)
        text, width = rows.tobytes().decode("ascii"), rows.shape[1]
        dictionary = np.array([text[i * width:(i + 1) * width]
                               for i in range(clerks)], dtype=object)
        return Column("string", _uniform(seed, "orders", "o_clerk", 0,
                                         clerks - 1, n), dictionary)

    return n, {
        "o_orderkey": lambda: Column("long", orders["o_orderkey"]()),
        "o_custkey": lambda: Column("long", custkey()),
        "o_orderstatus": lambda: Column("string", orderstatus(),
                                        ORDERSTATUS),
        "o_totalprice": lambda: Column("double", totalprice()),
        "o_orderdate": lambda: Column("date", orders["o_orderdate"]()),
        "o_orderpriority": lambda: Column("string", _uniform(
            seed, "orders", "o_orderpriority", 0, len(PRIORITIES) - 1, n),
            PRIORITIES),
        "o_clerk": clerk,
        "o_shippriority": lambda: Column("int", np.zeros(n, np.int32)),
        "o_comment": lambda: _cut_text(seed, "orders", "o_comment", 19, 78,
                                       n, text_pool()),
    }


def generate(config: dict, seed: int) -> dict:
    """The tables `config["tables"]` lists, each with the columns listed
    there, at `config["scale_factor"]`."""
    sf = float(config["scale_factor"])
    wanted = config["tables"]
    unknown = [f"{t}.{c}" for t, cols in wanted.items() for c in cols
               if c not in COLUMN_TYPES.get(t, {})]
    if unknown:
        raise ValueError(f"the tpch_tables generator makes no column "
                         f"{unknown}")
    orders = _orders_base(seed, sf, _Lazy())
    n_lines, line = _lineitem_makers(seed, sf, orders, _Lazy())
    out = {}
    for table, names in wanted.items():
        if table == "lineitem":
            # as `tpch.generate` makes it, call for call
            columns = {}
            for name in names:
                kind = COLUMN_TYPES["lineitem"][name]
                if kind == "text":
                    starts, lengths = line[name]()
                    columns[name] = Column(kind, starts, lengths=lengths,
                                           pool=text_pool())
                else:
                    columns[name] = Column(kind, line[name](),
                                           tpch._DICTIONARIES.get(name))
            out[table] = {"num_rows": n_lines, "columns": columns}
            continue
        n, make = (_customer_makers(seed, sf) if table == "customer"
                   else _orders_makers(seed, sf, orders, line))
        out[table] = {"num_rows": n,
                      "columns": {name: make[name]() for name in names}}
    return out
