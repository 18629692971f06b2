"""TPC-H Q3 as the spec prints it (comma-separated FROM, joins in WHERE)
through session.sql() on the CPU backend at small scales, against the
benchmark's plain reference (benchmarks/reference/q3.py) on tables made
by the benchmark's generator (benchmarks/datagen/tpch_tables.py); the
generator's invariants; and the benchmark's own tests of the cell, run
from tier 1."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from benchmarks import compare, sut, traffic  # noqa: E402
from benchmarks.datagen import tpch, tpch_tables  # noqa: E402
from benchmarks.reference import q3  # noqa: E402

PARAMS = {"SEGMENT": "BUILDING", "DATE": "1995-03-15"}
JOIN_ON = """
select l_orderkey, sum(l_extendedprice*(1-l_discount)) as revenue,
       o_orderdate, o_shippriority
from customer join orders on c_custkey = o_custkey
     join lineitem on l_orderkey = o_orderkey
where c_mktsegment = 'BUILDING' and o_orderdate < date '1995-03-15'
  and l_shipdate > date '1995-03-15'
group by l_orderkey, o_orderdate, o_shippriority
order by revenue desc, o_orderdate limit 10"""


def _config(scale, batches=None):
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "tpch-q3-tables.json")) as f:
        config = json.load(f)
    config["scale_factor"] = scale
    if batches is not None:
        config["batches"] = batches
    return config


def _limit():
    with open(os.path.join(ROOT, "benchmarks", "limits",
                           "q3-join.json")) as f:
        return float(json.load(f)["max_rel_err"]["q3"])


def _engine(config, tables):
    engine = sut.Engine(config)
    engine.register(tables)
    return engine


@pytest.mark.parametrize("batches", [
    {"customer": 1, "orders": 1, "lineitem": 1},
    {"customer": 1, "orders": 4, "lineitem": 16}], ids=["1-1-1", "1-4-16"])
@pytest.mark.parametrize("seed", [34, 2 ** 31 + 34])
@pytest.mark.parametrize("scale", [0.01, 0.02])
def test_published_q3_equals_the_plain_reference(scale, seed, batches):
    config = _config(scale, batches)
    tables = tpch_tables.generate(config, seed)
    engine = _engine(config, tables)
    try:
        answer, record = engine.query(traffic.statement("q3", PARAMS))
    finally:
        engine.close()
    want = q3.run(tables, PARAMS)
    assert len(want["l_orderkey"]) == 10
    mismatches, gap = compare.compare_answer(answer, want)
    assert mismatches == 0, (answer, want)
    assert gap < _limit()
    assert not sut.off_device_path(record)
    # row order is no matter of rounding on these tables
    assert q3.smallest_revenue_gap(tables, PARAMS) > 1e-6


def _joins(session, text):
    """(key column names left, right, build side) of every TpuJoinExec
    the statement plans, outermost first."""
    from spark_rapids_tpu.execs.join import TpuJoinExec
    from spark_rapids_tpu.overrides.rules import apply_overrides
    executable, _ = apply_overrides(session.sql(text).plan, session.conf)
    found, stack = [], [executable.tpu_exec]
    while stack:
        node = stack.pop(0)
        if isinstance(node, TpuJoinExec):
            found.append((
                [node.left_names[k.ordinal] for k in node.left_keys],
                [node.right_names[k.ordinal] for k in node.right_keys],
                "left" if node.build_left else "right"))
        stack.extend(node.children)
    return found


def test_join_on_spelling_plans_the_same_joins():
    config = _config(0.01)
    tables = tpch_tables.generate(config, 5)
    engine = _engine(config, tables)
    try:
        comma = traffic.statement("q3", PARAMS)
        got_comma, _ = engine.query(comma)
        got_on, _ = engine.query(JOIN_ON)
        assert got_on == got_comma
        joins = _joins(engine.session, comma)
        assert joins == _joins(engine.session, JOIN_ON)
        # the smaller side is built: customer under orders, and their
        # join under lineitem, whatever side each is written on
        assert joins == [(["o_orderkey"], ["l_orderkey"], "left"),
                         (["c_custkey"], ["o_custkey"], "left")]
    finally:
        engine.close()


@pytest.fixture(scope="module")
def small():
    config = _config(0.02)
    return config, tpch_tables.generate(config, 2 ** 31 + 3)


def test_every_order_has_a_customer_and_a_third_of_them_have_none(small):
    _config_, tables = small
    customers = tables["customer"]["columns"]["c_custkey"].values
    o_custkey = tables["orders"]["columns"]["o_custkey"].values
    assert np.array_equal(customers, np.arange(1, len(customers) + 1))
    assert o_custkey.min() >= 1 and o_custkey.max() <= len(customers)
    assert not np.any(o_custkey % 3 == 0)
    with_orders = len(np.unique(o_custkey))
    assert abs(with_orders / len(customers) - 2 / 3) < 0.01


def test_order_totals_and_status_agree_with_the_lines(small):
    _config_, tables = small
    o = tables["orders"]["columns"]
    li = tables["lineitem"]["columns"]
    position = np.searchsorted(o["o_orderkey"].values,
                               li["l_orderkey"].values)
    assert np.array_equal(o["o_orderkey"].values[position],
                          li["l_orderkey"].values)
    charge = (li["l_extendedprice"].values * (1 + li["l_tax"].values)
              * (1 - li["l_discount"].values))
    assert np.allclose(np.bincount(position, weights=charge),
                       o["o_totalprice"].values, rtol=1e-12)
    lines = np.bincount(position)
    open_lines = np.bincount(position,
                             weights=li["l_linestatus"].strings() == "O")
    status = o["o_orderstatus"].strings()
    assert np.all((status == "F") == (open_lines == 0))
    assert np.all((status == "O") == (open_lines == lines))
    assert set(status.tolist()) == {"F", "O", "P"}
    assert np.all(o["o_shippriority"].values == 0)


def test_formatted_keys_and_texts_follow_the_rules(small):
    _config_, tables = small
    c = tables["customer"]["columns"]
    o = tables["orders"]["columns"]
    names = c["c_name"].strings()
    assert names[0] == "Customer#000000001" \
        and names[-1] == f"Customer#{len(names):09d}"
    phones = c["c_phone"].strings()
    nation = c["c_nationkey"].values
    assert all(len(p) == 15 and p[2] == p[6] == p[10] == "-"
               and int(p[:2]) == n + 10
               for p, n in zip(phones[:200], nation[:200]))
    assert nation.min() == 0 and nation.max() == 24
    sizes = lambda col: np.array([len(x) for x in col.strings()])
    assert sizes(c["c_address"]).min() >= 10 \
        and sizes(c["c_address"]).max() <= 40
    assert sizes(c["c_comment"]).min() >= 29 \
        and sizes(c["c_comment"]).max() <= 116
    assert sizes(o["o_comment"]).min() >= 19 \
        and sizes(o["o_comment"]).max() <= 78
    assert c["c_acctbal"].values.min() >= -999.99 \
        and c["c_acctbal"].values.max() <= 9999.99
    assert set(c["c_mktsegment"].strings().tolist()) == {
        "AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"}
    assert len(set(o["o_orderpriority"].strings().tolist())) == 5
    clerks = o["o_clerk"].strings()
    assert clerks.min() >= "Clerk#000000001" \
        and clerks.max() <= f"Clerk#{int(round(1000 * 0.02)):09d}"
    assert list(o["o_clerk"].dictionary) == sorted(o["o_clerk"].dictionary)


def test_lineitem_is_the_q1_cells_lineitem_to_the_bit(small):
    config, tables = small
    alone = tpch.generate({"scale_factor": config["scale_factor"],
                           "tables": {"lineitem":
                                      config["tables"]["lineitem"]}},
                          2 ** 31 + 3)["lineitem"]
    mine = tables["lineitem"]
    assert mine["num_rows"] == alone["num_rows"]
    assert list(mine["columns"]) == list(alone["columns"])
    for name, col in alone["columns"].items():
        got = mine["columns"][name]
        assert got.type == col.type
        assert got.values.dtype == col.values.dtype
        assert np.array_equal(got.values, col.values), name
        if col.lengths is not None:
            assert np.array_equal(got.lengths, col.lengths)
            assert got.pool is col.pool
        if col.dictionary is not None:
            assert list(got.dictionary) == list(col.dictionary)


@pytest.mark.parametrize("file", ["test_q3.py", "test_files.py"])
def test_the_benchmarks_own_tests_of_its_files_pass(file):
    """benchmarks/tests/ sits outside tier 1 (benchmarks/selfcheck.py runs
    it): the new cell's tests and the storage arm's run here too, each
    file in a process of its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    done = subprocess.run(
        [sys.executable, "-m", "pytest",
         os.path.join(ROOT, "benchmarks", "tests", file), "-q",
         "-p", "no:cacheprovider", "-p", "no:xdist", "-p", "no:randomly"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
    assert " passed" in done.stdout and " failed" not in done.stdout
