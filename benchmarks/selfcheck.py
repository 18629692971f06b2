#!/usr/bin/env python3
"""The benchmark checked against itself, on the CPU backend at a tiny
scale, with no timing claimed:

- the generator's invariants (dbgen's rules as the spec states them);
- every query's plain reference against the engine's answer, and on a
  cell whose table lies in files the four `scan_*` readers on that
  query's record;
- `trace_reduce.py` on the small TPU trace recorded in `data/`;
- `tests/` (the float32 control and the planted faults come out as not
  correct through the harness's own `run_cell`).

    JAX_PLATFORMS=cpu python3 benchmarks/selfcheck.py

It sits outside the repo's `tests/`; the tier-1 count does not change.
"""

import importlib
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import compare, traffic, trace_reduce  # noqa: E402
from benchmarks.datagen import tpch  # noqa: E402

SCALE = 0.02

#: what `trace_reduce.py` reads from the trace recorded in `data/` (two
#: Q1 queries at SF0.05 on a v5e, PR 24)
RECORDED = {"busy_s": 0.107382373, "window_s": 0.12327381,
            "top_op": "jit_kernel %fusion.1 fusion s32[16,8]",
            "top_op_s": 0.008084979}


def check(ok: bool, what: str) -> None:
    print(("ok   " if ok else "FAIL ") + what, flush=True)
    if not ok:
        check.failed += 1


check.failed = 0


def generator_invariants():
    config = {"scale_factor": SCALE, "tables": {
        name: list(cols) for name, cols in tpch.COLUMN_TYPES.items()}}
    tables = tpch.generate(config, 2 ** 31 + 3)
    again = tpch.generate(config, 2 ** 31 + 3)
    other = tpch.generate(config, 4)
    columns = tables["lineitem"]["columns"]
    li = {k: c.values for k, c in columns.items()}
    n_orders = int(round(1_500_000 * SCALE))
    check(all(np.array_equal(col.values, again["lineitem"]["columns"][k].values)
              for k, col in columns.items()),
          "the same seed gives the same tables")
    check(not np.array_equal(li["l_quantity"],
                             other["lineitem"]["columns"]["l_quantity"]
                             .values[:len(li["l_quantity"])]),
          "another seed gives other rows")
    keys, lines = np.unique(li["l_orderkey"], return_counts=True)
    check(bool(np.all(keys % 32 < 8)) and len(keys) == n_orders
          and int(keys.max()) > 3 * n_orders,
          "l_orderkey sparse: the first 8 of every 32; 1,500,000 x SF orders")
    check(lines.min() >= 1 and lines.max() <= 7
          and abs(len(li["l_orderkey"]) / n_orders - 4.0) < 0.05,
          "1 to 7 lineitems per order, 4 on average")
    first = np.flatnonzero(np.r_[True, np.diff(li["l_orderkey"]) != 0])
    check(bool(np.all(li["l_linenumber"][first] == 1))
          and bool(np.all(np.diff(li["l_linenumber"])[
              np.diff(li["l_orderkey"]) == 0] == 1)),
          "l_linenumber counts an order's lines from 1")
    check(li["l_quantity"].min() == 1 and li["l_quantity"].max() == 50
          and li["l_discount"].min() == 0.0 and li["l_discount"].max() == 0.10
          and li["l_tax"].min() == 0.0 and li["l_tax"].max() == 0.08,
          "l_quantity 1..50, l_discount 0.00..0.10, l_tax 0.00..0.08")
    pk = li["l_partkey"]
    retail = (90000 + (pk // 10) % 20001 + 100 * (pk % 1000)) / 100.0
    check(bool(np.allclose(li["l_extendedprice"], li["l_quantity"] * retail,
                           rtol=1e-12)),
          "l_extendedprice = l_quantity x the part's retail price")
    s = int(round(10_000 * SCALE))
    one_of_four = np.zeros(len(pk), dtype=bool)
    for i in range(4):
        one_of_four |= (li["l_suppkey"]
                        == (pk + i * (s // 4 + (pk - 1) // s)) % s + 1)
    check(1 <= pk.min() and pk.max() <= int(round(200_000 * SCALE))
          and bool(one_of_four.all()),
          "l_partkey within [1, 200,000 x SF]; l_suppkey one of the part's "
          "four suppliers")
    ship_lag = li["l_shipdate"] - li["l_commitdate"]  # (1..121) - (30..90)
    receipt_lag = li["l_receiptdate"] - li["l_shipdate"]
    check(ship_lag.min() >= 1 - 90 and ship_lag.max() <= 121 - 30
          and receipt_lag.min() >= 1 and receipt_lag.max() <= 30
          and li["l_shipdate"].min() > tpch.days("1992-01-01")
          and li["l_shipdate"].max() <= tpch.days("1998-08-02") + 121,
          "l_shipdate = o_orderdate + 1..121, l_commitdate = + 30..90, "
          "l_receiptdate = l_shipdate + 1..30")
    flags = columns["l_returnflag"].strings()
    status = columns["l_linestatus"].strings()
    late = li["l_receiptdate"] > tpch.CURRENT_DATE
    check(bool(np.all((flags == "N") == late))
          and bool(np.all((status == "O")
                          == (li["l_shipdate"] > tpch.CURRENT_DATE))),
          "l_returnflag N exactly when received after 1995-06-17; "
          "l_linestatus O exactly when shipped after it")
    groups = sorted(set(zip(flags.tolist(), status.tolist())))
    check(groups == [("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")],
          f"Q1 has the answer set's four groups: {groups}")
    check(len(set(columns["l_shipinstruct"].strings().tolist())) == 4
          and len(set(columns["l_shipmode"].strings().tolist())) == 7,
          "four ship instructions, seven ship modes")
    comments = columns["l_comment"].strings()
    sizes = np.array([len(c) for c in comments])
    check(sizes.min() == 10 and sizes.max() == 43
          and len(set(comments.tolist())) > 0.95 * len(comments)
          and all(c in tpch.text_pool() for c in comments[:100]),
          "l_comment 10..43 characters of the grammar's text, "
          "nearly all distinct")


def references_against_the_engine():
    """Every cell's statements, the plain reference against the engine."""
    import spark_rapids_tpu  # noqa: F401
    from benchmarks import sut
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    files = {c["name"]: c["file"] for c in bench["configs"]}
    for cell in bench["workloads"]:
        config = dict(json.load(open(os.path.join(ROOT, files[cell["config"]]))),
                      scale_factor=SCALE)
        tables = importlib.import_module(
            f"benchmarks.datagen.{config['generator']}").generate(config, 17)
        engine = sut.Engine(config)
        engine.register(tables)
        engine.land()
        for query_id, params in traffic.distinct_statements(
                traffic.load_mix(cell["traffic"])):
            answer, record = engine.query(
                traffic.statement(query_id, params))
            want = importlib.import_module(
                f"benchmarks.reference.{query_id}").run(tables, params)
            mismatches, gap = compare.compare_answer(answer, want)
            check(mismatches == 0 and gap < 1e-9
                  and not sut.off_device_path(record),
                  f"{cell['name']}: engine = reference for {query_id} "
                  f"(gap {gap:.3g}, {record['dispatches']} dispatches)")
            if config.get("storage"):
                scan_readers(cell["name"], record, sum(
                    tables[t]["num_rows"] for t in config["storage"]))
        engine.close()


def scan_readers(cell_name, record, rows):
    """The four `scan_*` readers on one query's record of a cell whose
    table lies in files."""
    got = {}
    for name in ("scan_ms_per_query", "scan_upload_ms_per_query",
                 "scan_decode_wait_ms_per_query", "scan_rows_per_query"):
        reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
        got[name] = reader.read({"queries": [{"record": record}]})
        check(reader.read({"queries": [{"record": {}}, {"error": "x"}]})
              is None, f"{name}: a record without a file scan gives nothing")
    check(all(v is not None and v > 0 for v in got.values())
          and record.get("transferS", 0) > 0,
          f"{cell_name}: every scan reader and transferS give a value {got}")
    check(got["scan_rows_per_query"] == rows,
          f"{cell_name}: scan_rows_per_query = the table's {rows} rows")
    check(abs(got["scan_upload_ms_per_query"]
              + got["scan_decode_wait_ms_per_query"]
              - got["scan_ms_per_query"]) < 1e-6,
          f"{cell_name}: scan_upload_ms + scan_decode_wait_ms = scan_ms")


def trace_reduction():
    got = trace_reduce.reduce_dir(
        os.path.join(HERE, "data", "small_trace.xplane.pb"))
    check(abs(got["busy_s"] - RECORDED["busy_s"]) < 1e-9
          and abs(got["window_s"] - RECORDED["window_s"]) < 1e-9,
          f"trace_reduce: busy {got['busy_s']:.6f} s of "
          f"{got['window_s']:.6f} s as recorded")
    name, seconds = got["device_ops"][0]
    check(name == RECORDED["top_op"]
          and abs(seconds - RECORDED["top_op_s"]) < 1e-9
          and got["idle_gaps"][0][0] == "bench.execute_fetch",
          f"trace_reduce: top operation {name!r}, longest gap in "
          f"{got['idle_gaps'][0][0]!r}")
    events = [(0, 100, "while"), (10, 40, "a"), (50, 90, "b"), (120, 130, "a")]
    check(trace_reduce.self_times(events)
          == {"while": 30e-9, "a": 40e-9, "b": 40e-9},
          "trace_reduce: a parent's self time leaves out its children's")


def main():
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        print("selfcheck runs on the CPU backend: set JAX_PLATFORMS=cpu",
              file=sys.stderr)
        sys.exit(2)
    generator_invariants()
    trace_reduction()
    references_against_the_engine()
    import pytest
    rc = pytest.main([os.path.join(HERE, "tests"), "-q", "-p",
                      "no:cacheprovider"])
    check(rc == 0, "tests/: the control and every planted fault are refused")
    print(f"{check.failed} failed")
    sys.exit(1 if check.failed else 0)


if __name__ == "__main__":
    main()
