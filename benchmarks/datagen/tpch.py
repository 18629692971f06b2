"""TPC-H lineitem to dbgen's rules (spec rev. 3.0.1, section 4.2.3), made
from a seed with numpy. Independent of the program under test: it imports
nothing of `spark_rapids_tpu`, and the references read what it makes.

A table is `{"num_rows": n, "columns": {name: Column}}`. A string column
is its int32 codes plus the sorted dictionary they index, so that 60M
flags never become 60M Python objects here. A text column (l_comment) is
a start and a length per row into one pool of grammar text, as dbgen
makes it; `Column.strings()` cuts the rows' strings out of the pool for a
system that wants them one by one.

What follows dbgen, per column (section 4.2.3):
  orders    (made only as far as lineitem needs it) 1,500,000 x SF rows;
            o_orderkey sparse (the first 8 of every 32 keys); o_orderdate
            uniform in [1992-01-01, 1998-08-02].
  lineitem  1 to 7 rows per order, l_linenumber counting them; l_partkey
            uniform in [1, 200,000 x SF]; l_suppkey = (partkey + i x (S/4
            + (partkey-1)/S)) mod S + 1 with S = 10,000 x SF and i in
            0..3; l_quantity 1..50; l_extendedprice = l_quantity x the
            part's retail price, (90000 + (partkey/10 mod 20001) + 100 x
            (partkey mod 1000)) cents; l_discount 0.00..0.10; l_tax
            0.00..0.08; l_shipdate = o_orderdate + 1..121; l_commitdate =
            o_orderdate + 30..90; l_receiptdate = l_shipdate + 1..30;
            l_returnflag R or A when l_receiptdate <= 1995-06-17 else N;
            l_linestatus O when l_shipdate > 1995-06-17 else F;
            l_shipinstruct one of four, l_shipmode one of seven;
            l_comment a substring of 10..43 characters, at a random
            offset, of a pool of text made by the grammar of 4.2.2.14.
What departs from dbgen is listed in each configuration file's `assumed`:
the random streams are numpy's PCG64 keyed by (seed, table, column), not
dbgen's per-column generators, so the rows are these rules' rows and not
dbgen's; the text pool is 16 MiB, not 300 MB; only the columns a
configuration lists are made.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

EPOCH = np.datetime64("1970-01-01", "D")


def days(date: str) -> int:
    """A calendar date as days since 1970-01-01 (the DATE representation)."""
    return int((np.datetime64(date, "D") - EPOCH).astype(np.int64))


START_DATE = days("1992-01-01")
LAST_ORDER_DATE = days("1998-08-02")  # ENDDATE (1998-12-31) - 151 days
CURRENT_DATE = days("1995-06-17")

RETURNFLAGS = np.array(["A", "N", "R"], dtype=object)
LINESTATUS = np.array(["F", "O"], dtype=object)
INSTRUCTIONS = np.array(sorted(["DELIVER IN PERSON", "COLLECT COD", "NONE",
                                "TAKE BACK RETURN"]), dtype=object)
MODES = np.array(sorted(["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL",
                         "FOB"]), dtype=object)

#: the type of every column this generator can make (spec 1.4: identifiers
#: as 64-bit integers, the decimal columns as DOUBLE — see the
#: configurations' `source`)
COLUMN_TYPES = {
    "lineitem": {"l_orderkey": "long", "l_partkey": "long",
                 "l_suppkey": "long", "l_linenumber": "int",
                 "l_quantity": "double", "l_extendedprice": "double",
                 "l_discount": "double", "l_tax": "double",
                 "l_returnflag": "string", "l_linestatus": "string",
                 "l_shipdate": "date", "l_commitdate": "date",
                 "l_receiptdate": "date", "l_shipinstruct": "string",
                 "l_shipmode": "string", "l_comment": "text"},
}

_DICTIONARIES = {"l_returnflag": RETURNFLAGS, "l_linestatus": LINESTATUS,
                 "l_shipinstruct": INSTRUCTIONS, "l_shipmode": MODES}


@dataclass
class Column:
    type: str                  # long | int | double | date | string | text
    values: np.ndarray         # string: int32 codes; text: int32 starts
    dictionary: Optional[np.ndarray] = None  # sorted, for a string column
    lengths: Optional[np.ndarray] = None     # for a text column
    pool: Optional[str] = None               # for a text column

    def strings(self) -> np.ndarray:
        """The rows as an object array of str. A string column's rows
        point at the dictionary's few objects; a text column's are cut
        out of the pool, one new object a row."""
        if self.type == "string":
            return self.dictionary[self.values]
        out = np.empty(len(self.values), dtype=object)
        step = 1 << 20  # a few million rows at once take four times as long
        for i in range(0, len(out), step):
            starts = self.values[i:i + step]
            ends = (starts + self.lengths[i:i + step]).tolist()
            out[i:i + step] = list(map(self.pool.__getitem__,
                                       map(slice, starts.tolist(), ends)))
        return out


def _stream(seed: int, table: str, column: str) -> np.random.Generator:
    """One independent random stream per (seed, table, column): a column
    holds the same values whichever other columns are asked for."""
    key = [int(seed)] + [ord(c) for c in f"{table}.{column}"]
    return np.random.default_rng(key)


def _uniform(seed, table, column, low, high, n, dtype=np.int32):
    """n integers uniform in [low, high], both ends included."""
    return _stream(seed, table, column).integers(
        low, high + 1, size=n, dtype=dtype)


# -- the text pool (spec 4.2.2.14) ----------------------------------------

_NOUNS = ("foxes ideas theodolites pinto_beans instructions dependencies "
          "excuses platelets asymptotes courts dolphins multipliers "
          "sauternes warthogs frets dinos attainments somas Tiresias' "
          "patterns forges braids hockey_players frays warhorses dugouts "
          "notornis epitaphs pearls tithes waters orbits gifts sheaves "
          "depths sentiments decoys realms pains grouches escapades")
_VERBS = ("sleep wake are cajole haggle nag use boost affix detect integrate "
          "maintain nod was lose sublate solve thrash promise engage hinder "
          "print x-ray breach eat grow impress mold poach serve run dazzle "
          "snooze doze unwind kindle play hang believe doubt")
_ADJECTIVES = ("furious sly careful blithe quick fluffy slow quiet ruthless "
               "thin close dogged daring brave stealthy permanent enticing "
               "idle busy regular final ironic even bold silent")
_ADVERBS = ("sometimes always never furiously slyly carefully blithely "
            "quickly fluffily slowly quietly ruthlessly thinly closely "
            "doggedly daringly bravely stealthily permanently enticingly "
            "idly busily regularly finally ironically evenly boldly silently")
_PREPOSITIONS = ("about above according_to across after against along "
                 "alongside_of among around at atop before behind beneath "
                 "beside besides between beyond by despite during except "
                 "for from in_place_of inside instead_of into near of on "
                 "outside over past since through throughout to toward "
                 "under until up upon without with within")
_AUXILIARIES = ("do may might shall will would can could should ought_to "
                "must will_have_to shall_have_to could_have_to "
                "should_have_to must_have_to need_to try_to")
_TERMINATORS = (".", ";", ":", "?", "!", "--")

POOL_BYTES = 16 << 20
_POOL_SEED = 19920101  # the pool is one text for every run, as dbgen's is
_pool_made: Optional[str] = None


def text_pool() -> str:
    """`POOL_BYTES` characters of the grammar's sentences: sentence = NP VP
    T | NP VP PP T | NP VP NP T | NP PP VP T | NP PP VP PP T; NP = N | J N
    | J, J N | D J N; VP = V | X V | V D | X V D; PP = P the NP."""
    global _pool_made
    if _pool_made is not None:
        return _pool_made
    words = {k: [w.replace("_", " ") for w in v.split()] for k, v in (
        ("N", _NOUNS), ("V", _VERBS), ("J", _ADJECTIVES), ("D", _ADVERBS),
        ("P", _PREPOSITIONS), ("X", _AUXILIARIES))}
    noun_phrases = ("N", "J N", "J, J N", "D J N")
    verb_phrases = ("V", "X V", "V D", "X V D")
    sentences = ("NP VP T", "NP VP PP T", "NP VP NP T", "NP PP VP T",
                 "NP PP VP PP T")
    rng = np.random.default_rng(_POOL_SEED)
    draws = iter(rng.integers(0, 1 << 30, size=POOL_BYTES // 3).tolist())

    def pick(options):
        return options[next(draws) % len(options)]

    def phrase(form):
        out = []
        for slot in form.split():
            comma = slot.endswith(",")
            word = pick(words[slot.rstrip(",")])
            out.append(word + "," if comma else word)
        return " ".join(out)

    parts, size = [], 0
    while size < POOL_BYTES:
        out = []
        for slot in pick(sentences).split():
            if slot == "NP":
                out.append(phrase(pick(noun_phrases)))
            elif slot == "VP":
                out.append(phrase(pick(verb_phrases)))
            elif slot == "PP":
                out.append(pick(words["P"]) + " the "
                           + phrase(pick(noun_phrases)))
            else:
                out[-1] += pick(_TERMINATORS)
        sentence = " ".join(out) + " "
        parts.append(sentence)
        size += len(sentence)
    _pool_made = "".join(parts)[:POOL_BYTES]
    return _pool_made


# -- the tables ------------------------------------------------------------

class _Lazy:
    """Memo of the base arrays one table needs, made on first use."""

    def __init__(self):
        self._made = {}

    def get(self, name, make):
        if name not in self._made:
            self._made[name] = make()
        return self._made[name]


def _orders_base(seed: int, sf: float, memo: _Lazy):
    n = int(round(1_500_000 * sf))

    def orderkey():
        i = np.arange(1, n + 1, dtype=np.int64)
        return ((i >> 3) << 5) | (i & 7)

    def orderdate():
        return _uniform(seed, "orders", "o_orderdate",
                        START_DATE, LAST_ORDER_DATE, n)

    def linecount():
        return _uniform(seed, "orders", "linecount", 1, 7, n)

    return {
        "o_orderkey": lambda: memo.get("o_orderkey", orderkey),
        "o_orderdate": lambda: memo.get("o_orderdate", orderdate),
        "linecount": lambda: memo.get("linecount", linecount),
    }


def _lineitem_makers(seed: int, sf: float, orders, memo: _Lazy):
    counts = orders["linecount"]()
    n = int(counts.sum())

    def per_line(name):
        return np.repeat(orders[name](), counts)

    def linenumber():
        first = np.cumsum(counts) - counts  # each order's first row
        return (np.arange(n, dtype=np.int32)
                - np.repeat(first, counts).astype(np.int32) + 1)

    def partkey():
        return _uniform(seed, "lineitem", "l_partkey", 1,
                        int(round(200_000 * sf)), n, np.int64)

    def suppkey():
        # int32 holds every term (partkey <= 200,000 x SF) at a third of
        # the cost of 64-bit division
        pk = memo.get("l_partkey", partkey).astype(np.int32)
        s = int(round(10_000 * sf))
        i = _uniform(seed, "lineitem", "l_suppkey", 0, 3, n)
        return ((pk + i * (s // 4 + (pk - 1) // s)) % s + 1).astype(np.int64)

    def retail_cents(pk):
        return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)

    def quantity():
        return _uniform(seed, "lineitem", "l_quantity", 1, 50, n)

    def extendedprice():
        # in cents, as dbgen; int32 holds 50 x 209,999 and halves the work
        pk = memo.get("l_partkey", partkey).astype(np.int32)
        cents = memo.get("l_quantity", quantity) * retail_cents(pk)
        return cents / 100.0

    def shipdate():
        return (per_line("o_orderdate")
                + _uniform(seed, "lineitem", "l_shipdate", 1, 121, n))

    def commitdate():
        return (per_line("o_orderdate")
                + _uniform(seed, "lineitem", "l_commitdate", 30, 90, n))

    def receiptdate():
        return (memo.get("l_shipdate", shipdate)
                + _uniform(seed, "lineitem", "l_receiptdate", 1, 30, n))

    def returnflag():
        returned = memo.get("l_receiptdate", receiptdate) <= CURRENT_DATE
        r_or_a = _uniform(seed, "lineitem", "l_returnflag", 0, 1, n)
        # codes into RETURNFLAGS: A=0, N=1, R=2
        return np.where(returned, r_or_a * 2, 1).astype(np.int32)

    def linestatus():
        return (memo.get("l_shipdate", shipdate)
                > CURRENT_DATE).astype(np.int32)

    def cents(column, high):
        return _uniform(seed, "lineitem", column, 0, high, n) / 100.0

    def code(column, dictionary):
        return _uniform(seed, "lineitem", column, 0, len(dictionary) - 1, n)

    def comment():
        lengths = _uniform(seed, "lineitem", "l_comment.length", 10, 43, n)
        starts = _uniform(seed, "lineitem", "l_comment.offset", 0,
                          POOL_BYTES - 44, n)
        return starts, lengths

    return n, {
        "l_orderkey": lambda: per_line("o_orderkey"),
        "l_partkey": lambda: memo.get("l_partkey", partkey),
        "l_suppkey": suppkey,
        "l_linenumber": linenumber,
        "l_quantity": lambda: memo.get("l_quantity", quantity).astype(
            np.float64),
        "l_extendedprice": extendedprice,
        "l_discount": lambda: cents("l_discount", 10),
        "l_tax": lambda: cents("l_tax", 8),
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": lambda: memo.get("l_shipdate", shipdate),
        "l_commitdate": commitdate,
        "l_receiptdate": lambda: memo.get("l_receiptdate", receiptdate),
        "l_shipinstruct": lambda: code("l_shipinstruct", INSTRUCTIONS),
        "l_shipmode": lambda: code("l_shipmode", MODES),
        "l_comment": comment,
    }


def generate(config: dict, seed: int) -> dict:
    """The tables `config["tables"]` lists, each with the columns listed
    there, at `config["scale_factor"]`."""
    sf = float(config["scale_factor"])
    wanted = config["tables"]
    unknown = [f"{t}.{c}" for t, cols in wanted.items() for c in cols
               if c not in COLUMN_TYPES.get(t, {})]
    if unknown:
        raise ValueError(f"the tpch generator makes no column {unknown}")
    orders = _orders_base(seed, sf, _Lazy())
    n, make = _lineitem_makers(seed, sf, orders, _Lazy())
    columns = {}
    for name in wanted["lineitem"]:
        kind = COLUMN_TYPES["lineitem"][name]
        if kind == "text":
            starts, lengths = make[name]()
            columns[name] = Column(kind, starts, lengths=lengths,
                                   pool=text_pool())
        else:
            columns[name] = Column(kind, make[name](),
                                   _DICTIONARIES.get(name))
    return {"lineitem": {"num_rows": n, "columns": columns}}
