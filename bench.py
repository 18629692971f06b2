"""Benchmark: TPC-H q1 pipeline through the full engine on the TPU vs the
pandas CPU baseline (the "Spark CPU" proxy — BASELINE.md).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
vs_baseline: speedup vs CPU divided by the 3x target from BASELINE.md
(>= 1.0 means the target is met).

The timed run measures the steady state: the table is device-resident after
the warmup collect (scan device cache — GpuInMemoryTableScanExec analog,
spark.rapids.tpu.scan.deviceCache), matching the repeated-query pattern the
reference benchmarks (NDS runs queries against loaded tables). ``detail``
also reports the cold time (fresh upload included) for honesty. See PERF.md
for the full time breakdown."""

import json
import sys
import time


def main():
    import spark_rapids_tpu  # noqa: F401
    from spark_rapids_tpu.models.tpch import (
        lineitem_table,
        q1_dataframe,
        q1_pandas,
        q1_sql,
    )
    from spark_rapids_tpu.session import TpuSession

    argv = [a for a in sys.argv[1:]]
    use_sql = "--sql" in argv
    if use_sql:
        argv.remove("--sql")
    no_eventlog = "--no-eventlog" in argv
    if no_eventlog:
        argv.remove("--no-eventlog")
    require_tpu = "--require-tpu" in argv
    if require_tpu:
        argv.remove("--require-tpu")
    # the resolved backend is recorded in the artifact AND gateable
    # (tools.require_tpu_backend: a CPU-backend number must never be
    # committed as a chip reading)
    if require_tpu:
        from spark_rapids_tpu.tools import require_tpu_backend
        backend, _device_kind = require_tpu_backend()
    else:
        import jax
        backend = jax.default_backend()
    eventlog_dir = "/tmp/rapids_tpu_eventlog/bench"
    if "--eventlog-dir" in argv:
        i = argv.index("--eventlog-dir")
        if i + 1 >= len(argv):
            sys.exit("usage: bench.py [rows] [--sql] [--no-eventlog] "
                     "[--eventlog-dir DIR]")
        eventlog_dir = argv[i + 1]
        del argv[i:i + 2]
    rows = int(argv[0]) if argv else 4_000_000
    table = lineitem_table(rows, seed=0)

    # event logs on by default: every bench run leaves a
    # machine-readable artifact `python -m spark_rapids_tpu.tools`
    # can profile/compare (disable with --no-eventlog to measure the
    # observability-off steady state)
    conf = {}
    if not no_eventlog:
        conf = {"spark.rapids.sql.eventLog.enabled": "true",
                "spark.rapids.sql.eventLog.dir": eventlog_dir}
    session = TpuSession(conf)
    q1_build = q1_sql if use_sql else q1_dataframe

    # cold: compile + upload + first run
    session.next_query_tag = "q1_cold"
    t0 = time.perf_counter()
    _ = q1_build(session, table).collect_table()
    cold_s = time.perf_counter() - t0

    # warm (steady state): compiled, table device-resident. >=3 trials
    # with min AND median so host-sync latency variance is
    # distinguishable from real regressions
    warms = []
    for _i in range(3):
        session.next_query_tag = "q1"
        t0 = time.perf_counter()
        tpu_result = q1_build(session, table).collect_table()
        warms.append(time.perf_counter() - t0)
    warms.sort()
    tpu_s = warms[0]
    tpu_med_s = warms[len(warms) // 2]

    # CPU baseline (pandas proxy for Spark CPU)
    _ = q1_pandas(table)  # warmup caches
    t0 = time.perf_counter()
    cpu_result = q1_pandas(table)
    cpu_s = time.perf_counter() - t0

    # sanity: same group count and close sums
    assert tpu_result.num_rows == len(cpu_result), \
        f"group mismatch {tpu_result.num_rows} vs {len(cpu_result)}"
    tpu_sum = sorted(tpu_result.to_pydict()["sum_qty"])
    cpu_sum = sorted(cpu_result["sum_qty"].tolist())
    for a, b in zip(tpu_sum, cpu_sum):
        assert abs(a - b) <= 1e-6 * max(1.0, abs(b)), f"sum_qty mismatch {a} vs {b}"

    # q3-style multi-join (broadcast-heavy plan shape): secondary detail
    from spark_rapids_tpu.models.tpch import q3_dataframe, q3_pandas, q3_tables
    cust, orders, li = q3_tables(rows // 4, seed=1)
    session.next_query_tag = "q3_cold"
    _ = q3_dataframe(session, cust, orders, li).collect_table()  # warm
    session.next_query_tag = "q3"
    t0 = time.perf_counter()
    q3_res = q3_dataframe(session, cust, orders, li).collect_table()
    q3_tpu_s = time.perf_counter() - t0
    q3_dispatches = getattr(session, "last_dispatches", None)
    _ = q3_pandas(cust, orders, li)
    t0 = time.perf_counter()
    q3_ref = q3_pandas(cust, orders, li)
    q3_cpu_s = time.perf_counter() - t0
    # validate before reporting a speedup from it
    got = q3_res.to_pydict()
    assert got["l_orderkey"] == [int(x) for x in q3_ref.l_orderkey], \
        "q3 key mismatch vs pandas"
    for a, b in zip(got["revenue"], q3_ref.revenue):
        assert abs(a - b) <= 1e-6 * max(1.0, abs(b)), f"q3 revenue {a} vs {b}"

    speedup = cpu_s / tpu_s if tpu_s > 0 else 0.0
    print(json.dumps({
        "metric": "tpch_q1_speedup_vs_cpu",
        "value": round(speedup, 3),
        "unit": "x",
        "vs_baseline": round(speedup / 3.0, 3),
        "backend": backend,
        "detail": {"rows": rows, "tpu_s": round(tpu_s, 4),
                   "tpu_med_s": round(tpu_med_s, 4),
                   "tpu_cold_s": round(cold_s, 4), "cpu_s": round(cpu_s, 4),
                   "q3_join_speedup": round(q3_cpu_s / max(q3_tpu_s, 1e-9), 3),
                   "q3_tpu_s": round(q3_tpu_s, 4),
                   "q3_cpu_s": round(q3_cpu_s, 4),
                   "q3_dispatches": q3_dispatches},
    }))


if __name__ == "__main__":
    main()
