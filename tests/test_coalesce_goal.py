"""The coalesce exec under a TargetSize goal (execs/basic.py
TpuCoalesceExec, columnar/table.py concat_device): what it carries, how
it copies, when it flushes, and TPC-H Q1 through it under the shipped
settings.

The reference of the copy is plain numpy, written here: the rows of the
carried columns, live ones only, in input order."""

import json
import os
import sys

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import DeviceTable, HostColumn, HostTable
from spark_rapids_tpu.conf import BATCH_SIZE_BYTES, RapidsConf
from spark_rapids_tpu.execs.base import TpuExec
from spark_rapids_tpu.execs.basic import TpuCoalesceExec

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

NAMES = ("key", "price", "flag", "note", "day")
TYPES = (T.LONG, T.DOUBLE, T.STRING, T.STRING, T.DATE)


class Batches(TpuExec):
    """A child exec that yields the given device tables."""

    def __init__(self, tables, schema):
        super().__init__()
        self.tables = tables
        self.schema = schema

    def output_schema(self):
        return list(self.schema)

    def execute(self):
        yield from self.tables


def host_batch(rng, rows, nulls, flags, notes):
    """One batch as plain lists (None = NULL): the reference's input."""
    def maybe(values):
        if not nulls:
            return values
        return [None if rng.random() < 0.2 else v for v in values]
    return {
        "key": maybe([int(v) for v in rng.integers(-10**12, 10**12, rows)]),
        "price": maybe([float(v) for v in rng.random(rows) * 1e5]),
        "flag": maybe([str(v) for v in rng.choice(flags, rows)]),
        "note": maybe([str(v) for v in rng.choice(notes, rows)]),
        "day": maybe([int(v) for v in rng.integers(8000, 11000, rows)]),
    }


def device_batch(lists, live=None, capacity=None):
    """The engine's table of one batch; `live` (a bool list) makes it a
    MASKED table: rows at their slots, liveness as a device mask."""
    import jax.numpy as jnp
    columns = []
    for name, dtype in zip(NAMES, TYPES):
        values = lists[name]
        validity = np.array([v is not None for v in values])
        if dtype is T.STRING:
            data = np.array(values, dtype=object)
        else:
            fill = 0.0 if dtype is T.DOUBLE else 0
            data = np.array([fill if v is None else v for v in values],
                            dtype=dtype.np_dtype)
        columns.append(HostColumn(dtype, data, validity))
    table = DeviceTable.from_host(HostTable(NAMES, columns), capacity)
    if live is None:
        return table
    mask = np.zeros(table.capacity, dtype=bool)
    mask[:len(live)] = live
    return DeviceTable(table.names, table.columns, int(mask.sum()),
                       table.capacity, live=jnp.asarray(mask))


def reference_rows(batches, lives, carried):
    """Plain numpy/Python: rows of the carried columns, live rows only,
    concatenated in input order."""
    out = {NAMES[i]: [] for i in carried}
    for lists, live in zip(batches, lives):
        rows = len(lists[NAMES[0]])
        keep = np.flatnonzero(np.ones(rows, bool) if live is None
                              else np.asarray(live))
        for i in carried:
            out[NAMES[i]].extend(lists[NAMES[i]][r] for r in keep)
    return out


def plain(table: DeviceTable):
    """A device table's live rows as {column: list}, dates as days."""
    import datetime
    epoch = datetime.date(1970, 1, 1)
    out = {}
    for name, values in table.compacted().to_host().to_pydict().items():
        out[name] = [(v - epoch).days if isinstance(v, datetime.date) else v
                     for v in values]
    return out


def with_dead_tail_data(table: DeviceTable) -> DeviceTable:
    """The same table with every slot past its rows holding leftovers, as
    a project over a partial batch leaves them (a literal fills the whole
    capacity, `x + 1` over padding gives 1): data nobody may read."""
    import jax.numpy as jnp
    from spark_rapids_tpu.columnar.column import DeviceColumn
    dead = np.arange(table.capacity) >= table.num_rows
    columns = []
    for c in table.columns:
        left = 1 if c.dictionary is not None else 77
        mask = jnp.asarray(dead.reshape((-1,) + (1,) * (c.data.ndim - 1)))
        columns.append(DeviceColumn(
            c.dtype, jnp.where(mask, jnp.asarray(left, c.data.dtype), c.data),
            c.validity, dictionary=c.dictionary, dict_sorted=c.dict_sorted))
    return DeviceTable(table.names, columns, table.num_rows, table.capacity)


CASES = {
    # name: (rows per batch, nulls, masked? (True, False, "mixed" = every
    # second, or one bool an input), flag dicts, note dicts, carried).
    # Every unmasked partial batch carries leftovers in its dead tail
    "unmasked": ([300, 300, 300], False, False, "same", "same", None),
    "partial_last_batch": ([256, 256, 256, 37], False, False, "same", "same",
                           None),
    "nulls": ([200, 129, 77], True, False, "same", "same", None),
    "masked": ([300, 210, 150], False, True, "same", "same", None),
    "masked_nulls_carried_subset": ([300, 210, 150], True, True, "same",
                                    "same", (1, 2, 4)),
    "mixed_masked_and_not": ([128, 300, 90, 256], True, "mixed", "same",
                             "same", None),
    "copied_then_masked": ([300, 210], False, [False, True], "same", "same",
                           None),
    "two_copied_then_masked": ([300, 40, 150], True, [False, False, True],
                               "same", "same", None),
    "masked_copied_masked": ([210, 300, 150], False, [True, False, True],
                             "same", "same", None),
    "copied_then_two_masked": ([90, 300, 150], True, [False, True, True],
                               "same", "unequal", None),
    "equal_dictionaries_distinct_objects": ([300, 300, 200], False, False,
                                            "equal", "equal", (0, 2, 3)),
    "unequal_dictionaries": ([300, 300, 200], True, False, "same", "unequal",
                             None),
    "unequal_dictionaries_masked": ([300, 160], False, True, "unequal",
                                    "unequal", (2, 3)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_coalesced_stream_matches_plain_reference(case):
    sizes, nulls, masked, flag_kind, note_kind, carried = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case) + 2 ** 31)
    batches, lives, tables = [], [], []
    for b, rows in enumerate(sizes):
        flags = ["A", "N", "R"]
        notes = [f"note {i:03d}" for i in range(20)]
        if flag_kind == "unequal":
            flags = flags[:2] if b % 2 else flags[1:]
        if note_kind == "unequal":
            notes = notes[b * 3:b * 3 + 12]
        lists = host_batch(rng, rows, nulls, flags, notes)
        # every value in every batch: per-batch dictionaries then hold
        # the same strings (each batch encodes its own object)
        if flag_kind in ("same", "equal"):
            lists["flag"][:len(flags)] = flags
        if note_kind in ("same", "equal"):
            lists["note"][:len(notes)] = notes
        is_masked = masked[b] if isinstance(masked, list) else (
            masked is True or (masked == "mixed" and b % 2 == 1))
        live = [bool(v) for v in rng.random(rows) < 0.6] if is_masked \
            else None
        batches.append(lists)
        lives.append(live)
        table = device_batch(lists, live)
        tables.append(table if is_masked else with_dead_tail_data(table))
    dicts = [t.columns[2].dictionary for t in tables]
    if flag_kind in ("same", "equal") and not nulls:
        assert all(d is not dicts[0] for d in dicts[1:]), \
            "each batch carries its own dictionary object"
        assert all(np.array_equal(d, dicts[0]) for d in dicts)

    exec_ = TpuCoalesceExec(Batches(tables, list(zip(NAMES, TYPES))),
                            target_bytes=1 << 30, columns=carried)
    out = list(exec_.execute_masked())
    assert len(out) == 1
    carried = tuple(range(len(NAMES))) if carried is None else carried
    assert out[0].names == tuple(NAMES[i] for i in carried)
    assert exec_.output_schema() == [(NAMES[i], TYPES[i]) for i in carried]
    assert plain(out[0]) == reference_rows(batches, lives, carried)
    assert out[0].live is None, "a copy yields a prefix table"
    # the slots past the live rows hold nothing
    total = out[0].num_rows
    for c in out[0].columns:
        assert not np.asarray(c.validity)[total:].any()
    assert exec_.metrics["concatBatches"] == len(sizes)
    assert exec_.metrics["coalescedColumns"] == len(carried)
    assert exec_.metrics["coalescedBytes"] == out[0].device_nbytes()
    strings = [i for i in carried if TYPES[i] is T.STRING]
    unequal = sum((flag_kind if i == 2 else note_kind) == "unequal"
                  for i in strings)
    if not nulls:
        assert exec_.metrics["dictUnions"] == unequal
    for i in strings:
        col = out[0].columns[carried.index(i)]
        assert col.dict_sorted
        assert list(col.dictionary) == sorted(col.dictionary)
        if (flag_kind if i == 2 else note_kind) != "unequal" and not nulls:
            assert col.dictionary is tables[0].columns[i].dictionary


@pytest.mark.parametrize("consumer", [
    "SELECT v FROM ({u}) u ORDER BY v",
    "SELECT v, count(*) AS c FROM ({u}) u GROUP BY v ORDER BY v",
], ids=["sort", "aggregate"])
def test_literal_union_filtered_through_a_coalesce(consumer):
    """A literal over a partial batch (the whole capacity filled), then a
    filtered (masked) batch, streamed in turn into one coalesce."""
    from spark_rapids_tpu.session import TpuSession
    rng = np.random.default_rng(2 ** 31 + 5)
    data = {"a": [int(v) for v in rng.integers(1, 1000, 300)],
            "p": [bool(v) for v in rng.random(300) < 0.5]}
    union = "SELECT 5 AS v FROM t UNION ALL SELECT a AS v FROM t WHERE p"
    answers = []
    for conf in ({}, {"spark.rapids.sql.enabled": "false"}):
        session = TpuSession(conf)
        session.create_dataframe(data).create_or_replace_temp_view("t")
        answers.append(session.sql(consumer.format(u=union)).collect())
    assert answers[0] == answers[1]
    assert len(answers[0]) > 1


def _long_batches(n_batches, rows=1024):
    """Batches of one BIGINT column: 9 bytes a capacity row."""
    tables = []
    for b in range(n_batches):
        data = np.arange(b * rows, (b + 1) * rows, dtype=np.int64)
        tables.append(DeviceTable.from_host(
            HostTable(["v"], [HostColumn(T.LONG, data)])))
    assert all(t.capacity == rows and t.device_nbytes() == 9 * rows
               for t in tables)
    return tables


#: 9 batches of 1024 rows, 9 B a row: four make the 4096-row bucket
#: exactly, 36864 bytes; a fifth would make the 8192-row bucket
@pytest.mark.parametrize("target,flushes", [
    (36863, [2, 2, 2, 2, 1]),     # just under: 4096 rows would pass it
    (36864, [4, 4, 1]),           # at the boundary: the bucket is filled
    (36865, [4, 4, 1]),           # just over: the next bucket would pass
    (73727, [4, 4, 1]),           # ... up to a byte under the next bucket
    (73728, [8, 1]),              # the next bucket, filled
    (9216, [1] * 9),              # one batch: everything passes through
    (100, [1] * 9),               # a target under one batch
    (1 << 30, [9]),               # everything under the goal: one batch
], ids=lambda v: str(v))
def test_flush_rule_respects_capacity_buckets(target, flushes):
    from spark_rapids_tpu.columnar import bucket_for
    tables = _long_batches(9)
    exec_ = TpuCoalesceExec(Batches(tables, [("v", T.LONG)]),
                            target_bytes=target)
    out = list(exec_.execute_masked())
    assert [o.capacity for o in out] == [bucket_for(1024 * k)
                                         for k in flushes]
    assert [o.num_rows for o in out] == [1024 * k for k in flushes]
    got = np.concatenate([np.asarray(o.columns[0].data)[:o.num_rows]
                          for o in out])
    assert np.array_equal(got, np.arange(9 * 1024))
    for o in out:
        if o.num_rows > 1024:  # a copy honours the goal, padding included
            assert o.device_nbytes() <= target
    assert exec_.metrics.get("concatBatches", 0) == sum(
        k for k in flushes if k > 1)


def test_require_single_ignores_the_target():
    tables = _long_batches(5)
    exec_ = TpuCoalesceExec(Batches(tables, [("v", T.LONG)]),
                            target_bytes=100, require_single=True)
    out = list(exec_.execute_masked())
    assert len(out) == 1 and out[0].num_rows == 5 * 1024


def test_the_default_goal_is_one_gib_and_the_cell_states_no_conf():
    assert BATCH_SIZE_BYTES.default == 1 << 30
    assert RapidsConf().batch_size_bytes == 1 << 30
    config = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "tpch-sf5-lineitem-defaultconf.json")))
    assert "session_conf" not in config
    assert "session_conf" not in config["reduced"]


# -- TPC-H Q1 through session.sql() -----------------------------------------

Q1_COLUMNS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax",
              "l_returnflag", "l_linestatus", "l_shipdate"]


@pytest.fixture(scope="module")
def lineitem():
    from benchmarks.datagen import tpch
    config = json.load(open(os.path.join(
        ROOT, "benchmarks", "configs", "tpch-sf5-lineitem-defaultconf.json")))
    config["scale_factor"] = 0.01
    return config, tpch.generate(config, 2 ** 31 + 27)


def _engine(config, tables, session_conf):
    from benchmarks import sut
    engine = sut.Engine(dict(config, session_conf=session_conf))
    engine.register(tables)
    engine.land()
    return engine


def _q1_text():
    from benchmarks import traffic
    return traffic.statement("q1", {"DELTA": 90})


def _coalesce_below_the_aggregate(executable):
    from spark_rapids_tpu.execs.aggregate import TpuHashAggregateExec
    found = []

    def walk(e):
        if isinstance(e, TpuHashAggregateExec):
            found.append(e.children[0])
        for c in getattr(e, "children", ()):
            walk(c)
        for attr in ("tpu_exec",):
            if getattr(e, attr, None) is not None:
                walk(getattr(e, attr))
    walk(executable)
    assert len(found) == 1 and isinstance(found[0], TpuCoalesceExec)
    return found[0]


def test_q1_coalesce_carries_the_seven_columns_it_reads(lineitem):
    from spark_rapids_tpu.overrides.rules import apply_overrides
    config, tables = lineitem
    engine = _engine(config, tables, {})
    try:
        df = engine.session.sql(_q1_text())
        executable, _ = apply_overrides(df.plan, engine.session.conf)
        coalesce = _coalesce_below_the_aggregate(executable)
        carried = [n for n, _ in coalesce.output_schema()]
        assert sorted(carried) == sorted(Q1_COLUMNS)
        assert "l_comment" not in carried
        assert len(coalesce.children[0].output_schema()) == 16
        assert coalesce.target_bytes == 1 << 30
        assert not coalesce.require_single
    finally:
        engine.close()


def _metric(node, key, acc):
    if isinstance(node, dict):
        if key in (node.get("metrics") or {}):
            acc.append(node["metrics"][key]["value"])
        for v in node.values():
            _metric(v, key, acc)
    elif isinstance(node, list):
        for v in node:
            _metric(v, key, acc)
    return acc


@pytest.mark.parametrize("session_conf,flushed", [
    ({}, True),
    ({"spark.rapids.sql.batchSizeBytes": "1073741824"}, True),
    ({"spark.rapids.sql.batchSizeBytes": "134217728"}, True),
    ({"spark.rapids.sql.batchSizeBytes": "512000"}, True),
    ({"spark.rapids.sql.batchSizeBytes": "102400"}, False),
], ids=["no_conf", "1GiB", "128MiB", "500KiB", "under_one_batch"])
def test_q1_through_sql_matches_the_plain_reference(lineitem, session_conf,
                                                    flushed):
    from benchmarks import compare, sut
    from benchmarks.reference import q1 as reference
    config, tables = lineitem
    limit = json.load(open(os.path.join(
        ROOT, "benchmarks", "limits", "q1-sf5-defaultconf.json")))
    engine = _engine(config, tables, session_conf)
    try:
        engine.query(_q1_text())
        answer, record = engine.query(_q1_text())
        full = engine.session.last_event_record
    finally:
        engine.close()
    want = reference.run(tables, {"DELTA": 90})
    mismatches, gap = compare.compare_answer(answer, want)
    assert mismatches == 0
    assert gap <= limit["max_rel_err"]["q1"]
    assert record["fallbacks"] == [] and not sut.off_device_path(record)
    assert "coalesceS" in record["phasesS"]
    carried = _metric(full["plan"], "coalescedColumns", [])
    if flushed:
        assert carried == [7]
        assert _metric(full["plan"], "dictUnions", []) == [0]
        assert record["phasesS"]["coalesceS"] > 0
    else:
        assert carried == [] and record["phasesS"]["coalesceS"] == 0.0
