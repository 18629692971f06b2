#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the engine's main path still
starts, and answers correctly, on the attached TPU.

    python3 chip_smoke.py            # the driver's form: no arguments
    python3 chip_smoke.py --rows N   # local poking at a smaller size

Drives TpuSession (DataFrame DSL and .sql()), a Parquet scan and the
QueryService over the repo's seeded TPC-H-LIKE generators at TPC-H SF1
cardinalities (spec 4.2.5: lineitem 6,001,215 / orders 1,500,000 /
customer 150,000 -> 6,000,000 / 1,500,000 / 150,000 here). The column
sets are models/tpch.py's, NOT dbgen's row widths — the published schema
is ROADMAP B1's job. Every answer is checked against the pandas
reference; every query's event record must show no fallback, demotion,
replay, reinit, recovery or OOM retry.

This parent process imports neither jax nor the package (a process that
has touched JAX holds the chip). It runs child processes one after
another, never two at once: (A) one chip, cold; (B) the same again,
which must find A's compiles in the persistent cache; (C) the mesh
phase when four chips are attached. It exits non-zero, printing no
result line, as soon as any child fails; nothing here catches a phase
failure and carries on. On success stdout holds two lines: first
{"report": {...}} (row counts, HBM, phases, cold and warm seconds, cache
hits, the f64 probe, the mesh child — smoke readings,
information, not a benchmark), and LAST the result line, which holds
exactly {"ok": true, "device": {"platform", "kind", "count"}}.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

SF1_LINEITEM_ROWS = 6_000_000
#: per child; three children must fit the driver's 1200 s
CHILD_TIMEOUT_S = 900
WARM_RUNS = 3
#: float aggregates vs pandas: the bound the verify skill documents
FLOAT_RTOL = 1e-6


def log(msg):
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


# ===========================================================================
# parent: sequences the children, touches no JAX
# ===========================================================================

def run_child(mode, rows, tmp, tag):
    out = os.path.join(tmp, f"child_{tag}.json")
    log(f"child {tag} ({mode}) starting")
    t0 = time.perf_counter()
    # the child's stdout joins stderr (only the parent's result line may
    # reach stdout) and its temp files land in the parent's directory
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", mode,
         "--rows", str(rows), "--out", out],
        stdout=sys.stderr, timeout=CHILD_TIMEOUT_S,
        env={**os.environ, "TMPDIR": tmp})
    if proc.returncode != 0:
        log(f"child {tag} exited {proc.returncode}")
        sys.exit(proc.returncode or 1)
    with open(out) as f:
        result = json.load(f)
    result["child_wall_s"] = round(time.perf_counter() - t0, 3)
    log(f"child {tag} passed in {result['child_wall_s']} s")
    return result


def parent(rows):
    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "spark_rapids_tpu")):
        log("spark_rapids_tpu/ is not next to chip_smoke.py — nothing to run")
        sys.exit(1)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        a = run_child("single", rows, tmp, "A")
        b = run_child("single", rows, tmp, "B")
        if b["persistent_cache"]["hits"] <= 0:
            log(f"child B found nothing in the persistent compile cache "
                f"({b['persistent_cache']}): the cache path is not stable")
            sys.exit(1)
        if len(b["phases"]) < len(a["phases"]):
            log("child B passed fewer phases than child A")
            sys.exit(1)
        count = a["device"]["count"]
        if count >= 4:
            mesh = run_child("mesh", rows, tmp, "C")
        else:
            mesh = f"skipped: {count} device(s)"
    # the report (smoke readings) first; the result line, which holds
    # exactly "ok" and "device", last
    print(json.dumps({"report": {
        "data": a["data"],
        "hbm": a["hbm"],
        "phases": a["phases"],
        "seconds": {"cold_process": a["seconds"],
                    "cached_process": b["seconds"],
                    "child_wall": {"A": a["child_wall_s"],
                                   "B": b["child_wall_s"]}},
        "persistent_cache": {"dir": a["persistent_cache"]["dir"],
                             "A": a["persistent_cache"],
                             "B": b["persistent_cache"]},
        "demotions": a["demotions"],
        "f64_on_device": a["f64_on_device"],
        "native_available": a["native_available"],
        "mesh": mesh,
    }}))
    device = a["device"]
    print(json.dumps({"ok": True, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}}), flush=True)


# ===========================================================================
# children: everything below runs in a process that owns the chip
# ===========================================================================

class CacheCounter:
    """Persistent-compile-cache hits and misses, from JAX's own events."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self):
        import jax
        return {"dir": jax.config.jax_compilation_cache_dir,
                "hits": self.hits, "misses": self.misses}


def check_record(rec, label):
    """What every query's event record must say on the smoke path."""
    from spark_rapids_tpu.runtime.health import HEALTH
    assert rec is not None, f"{label}: no event record (event log off?)"
    assert rec["fallbacks"] == [], \
        f"{label}: operators fell back to the CPU: {rec['fallbacks']}"
    assert rec["demotions"] == {}, f"{label}: demotions {rec['demotions']}"
    assert rec["faultReplays"] == 0, f"{label}: query was replayed"
    assert rec["deviceReinits"] == 0, f"{label}: device was reinitialized"
    assert rec["healthState"] == "HEALTHY", f"{label}: {rec['healthState']}"
    assert rec["recovery"] == {}, f"{label}: recovery {rec['recovery']}"
    assert rec["oomRetries"] == 0, f"{label}: {rec['oomRetries']} OOM retries"
    assert HEALTH.cpu_only_reason() is None, \
        f"{label}: CPU-only latch: {HEALTH.cpu_only_reason()}"


def close(a, b):
    return abs(a - b) <= FLOAT_RTOL * max(1.0, abs(b))


def check_q1(table, ref):
    got = table.to_pydict()
    assert got["l_returnflag"] == list(ref.l_returnflag), "q1 keys/order"
    assert got["l_linestatus"] == list(ref.l_linestatus), "q1 keys/order"
    assert got["count_order"] == [int(x) for x in ref.count_order], \
        "q1 counts"
    for name in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
                 "avg_qty", "avg_price", "avg_disc"):
        for a, b in zip(got[name], ref[name]):
            assert close(a, b), f"q1 {name}: {a!r} vs pandas {b!r}"


def check_q3(table, ref):
    got = table.to_pydict()
    assert got["l_orderkey"] == [int(x) for x in ref.l_orderkey], \
        "q3 keys/order"
    assert got["n"] == [int(x) for x in ref.n], "q3 counts"
    for a, b in zip(got["revenue"], ref.revenue):
        assert close(a, b), f"q3 revenue: {a!r} vs pandas {b!r}"


def timed_query(session, label, build, check, seconds, warm_runs=WARM_RUNS):
    """Cold then warm runs, each checked; returns the last table."""
    times = []
    for i in range(1 + warm_runs):
        session.next_query_tag = f"{label}_{'cold' if i == 0 else 'warm'}"
        t0 = time.perf_counter()
        table = build().collect_table()
        times.append(time.perf_counter() - t0)
        rec = session.last_event_record
        check_record(rec, f"{label} run {i}")
        check(table)
        if i == 0:
            cold_compile_s = round(rec["compileMs"] / 1000.0, 3)
    seconds[label] = {"cold": round(times[0], 4),
                      "cold_compile": cold_compile_s,
                      "warm": [round(t, 4) for t in times[1:]],
                      "warm_dispatches": rec["dispatches"]}
    log(f"{label}: cold {times[0]:.2f} s, warm {seconds[label]['warm']}")
    return table


def make_session(extra=None):
    from spark_rapids_tpu.session import TpuSession
    conf = {"spark.rapids.sql.eventLog.enabled": "true",
            "spark.rapids.sql.eventLog.dir":
                tempfile.mkdtemp(prefix="chip_smoke_events_")}
    conf.update(extra or {})
    return TpuSession(conf)


def phase_device():
    """A 'tpu' platform and a device_kind that names the part, or exit
    non-zero before any data is generated."""
    import jax

    from spark_rapids_tpu.runtime.device_manager import reported_hbm_bytes
    from spark_rapids_tpu.tools import require_tpu_backend
    platform, kind = require_tpu_backend()
    if not kind.strip():
        log("the device reports no device_kind")
        sys.exit(2)
    dev = jax.devices()[0]
    # raises on an accelerator that reports no limit: the 16 GiB stand-in
    # is the CPU backend's only
    limit = reported_hbm_bytes(dev)
    device = {"platform": platform, "kind": kind, "count": len(jax.devices())}
    hbm = {"limit_bytes": limit, "source": "memory_stats().bytes_limit",
           "default_16GiB_used": False}
    log(f"device {device}, HBM limit {limit}")
    return device, hbm


def device_bytes_in_use():
    import jax
    return int(jax.devices()[0].memory_stats()["bytes_in_use"])


# -- representation probe ----------------------------------------------------

F64_EDGES = [
    ("1+2^-52", 1.0 + 2.0 ** -52), ("1+2^-30", 1.0 + 2.0 ** -30),
    ("1/3", 1.0 / 3.0), ("pi", 3.141592653589793),
    ("2^53-1", 2.0 ** 53 - 1), ("99999.99", 99999.99),
    ("123456789.123456789", 123456789.123456789),
    ("1e300", 1e300), ("-1e300", -1e300), ("3.5e38", 3.5e38),
    ("f32 min normal", 1.17549435e-38), ("1e-40", 1e-40),
    ("f64 min normal", 2.2250738585072014e-308), ("5e-324", 5e-324),
    ("+0.0", 0.0), ("-0.0", -0.0), ("nan", float("nan")),
    ("+inf", float("inf")), ("-inf", float("-inf")),
]


def phase_representation(session):
    """64-bit edge values through create_dataframe -> filter(keep all) ->
    collect, one sort and one min/max aggregate. Integers, strings,
    dates and decimals must be exact; for doubles, report what
    survives."""
    import numpy as np

    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.ops.expr import col, lit
    from spark_rapids_tpu.session import TpuSession

    n = len(F64_EDGES)
    f64 = np.array([v for _, v in F64_EDGES], dtype=np.float64)
    i64 = np.resize(np.array(
        [2 ** 63 - 1, -(2 ** 63), 0, -1, 1, -(2 ** 31), 2 ** 31,
         2 ** 32 + 5, -(2 ** 32) - 7, 2 ** 53 + 1], dtype=np.int64), n)
    data = {
        "k": np.arange(n, dtype=np.int32),
        "g": (np.arange(n) % 3).astype(np.int32),
        "i": i64,
        "f": f64,
        "s": np.array([f"row-{j:02d}-é" for j in range(n)], dtype=object),
        "d": np.arange(n, dtype=np.int32) * 997 - 5000,
        "m": i64 // 1000,
    }
    dtypes = {"d": T.DATE, "m": T.DecimalType(18, 4)}
    oracle = TpuSession({"spark.rapids.sql.enabled": "false"})

    def run(s, shape):
        return shape(s.create_dataframe(data, dtypes)) \
            .collect_table().to_pydict()

    def checked(shape, label):
        got = run(session, shape)
        check_record(session.last_event_record, f"representation {label}")
        return got, run(oracle, shape)

    got, want = checked(lambda df: df.filter(col("k") >= lit(0)), "filter")
    for name in ("k", "g", "i", "s", "d", "m"):
        assert got[name] == want[name], f"{name} changed on the device"
    assert got["i"] == [int(x) for x in i64], "i64 changed vs the input"
    survives = {}
    for (label, x), y in zip(F64_EDGES, got["f"]):
        same = np.float64(x).view(np.uint64) == np.float64(y).view(np.uint64)
        survives[label] = "exact" if same else repr(float(y))

    got, want = checked(lambda df: df.sort("i", "k"), "sort")
    assert got["k"] == want["k"] and got["i"] == want["i"], "i64 sort order"
    got, want = checked(lambda df: df.sort("f", "k"), "sort f64")
    f64_order = [F64_EDGES[j][0] for j in got["k"]]
    f64_order_oracle = [F64_EDGES[j][0] for j in want["k"]]

    def minmax(df):
        return df.group_by("g").agg(
            F.min("i").alias("min_i"), F.max("i").alias("max_i"),
            F.min("f").alias("min_f"), F.max("f").alias("max_f"),
            F.min("m").alias("min_m"), F.max("d").alias("max_d")).sort("g")

    got, want = checked(minmax, "min/max")
    for name in ("g", "min_i", "max_i", "min_m", "max_d"):
        assert got[name] == want[name], f"{name}: {got[name]} vs {want[name]}"
    report = {"roundtrip": survives,
              "sort_order": f64_order,
              "sort_order_cpu_oracle": f64_order_oracle,
              "min_f": [repr(x) for x in got["min_f"]],
              "max_f": [repr(x) for x in got["max_f"]],
              "min_f_cpu_oracle": [repr(x) for x in want["min_f"]],
              "max_f_cpu_oracle": [repr(x) for x in want["max_f"]]}
    log(f"f64 on the device: {report}")
    return report


# -- the single-chip child ---------------------------------------------------

def child_single(rows, out_path):
    cache = CacheCounter()
    import spark_rapids_tpu  # noqa: F401  (x64, cache placement)
    device, hbm = phase_device()
    phases = ["device"]

    from spark_rapids_tpu.models import tpch
    from spark_rapids_tpu.native import native_available
    from spark_rapids_tpu.runtime.faults import CIRCUIT_BREAKER
    from spark_rapids_tpu.service import QueryService

    session = make_session()
    seconds = {}

    f64_report = phase_representation(session)
    phases.append("representation")

    t0 = time.perf_counter()
    lineitem = tpch.lineitem_table(rows, seed=0)
    cust, orders, li3 = tpch.q3_tables(rows, seed=1)
    q1_ref = tpch.q1_pandas(lineitem)
    q3_ref = tpch.q3_pandas(cust, orders, li3)
    seconds["datagen_and_pandas_reference"] = round(
        time.perf_counter() - t0, 3)
    data = {"schema": "models/tpch.py TPC-H-like columns, not dbgen widths",
            "lineitem_rows": lineitem.num_rows, "orders_rows": orders.num_rows,
            "customer_rows": cust.num_rows,
            "q3_lineitem_rows": li3.num_rows}
    log(f"data {data}")

    q1_direct = timed_query(
        session, "q1_dataframe", lambda: tpch.q1_dataframe(session, lineitem),
        lambda t: check_q1(t, q1_ref), seconds)
    phases.append("q1_dataframe")
    timed_query(
        session, "q1_sql", lambda: tpch.q1_sql(session, lineitem),
        lambda t: check_q1(t, q1_ref), seconds)
    phases.append("q1_sql")
    q3_direct = timed_query(
        session, "q3_dataframe",
        lambda: tpch.q3_dataframe(session, cust, orders, li3),
        lambda t: check_q3(t, q3_ref), seconds)
    phases.append("q3_dataframe")
    hbm["bytes_in_use_after_landing"] = device_bytes_in_use()

    # scan: lineitem written to Parquet by the engine's own writer, read
    # back through read_parquet, q1 over the view
    with tempfile.TemporaryDirectory(prefix="chip_smoke_parquet_") as pq:
        path = os.path.join(pq, "lineitem")
        t0 = time.perf_counter()
        session.create_dataframe(lineitem).write_parquet(path)
        seconds["parquet_write"] = round(time.perf_counter() - t0, 3)

        def q1_scan():
            session.read_parquet(path).create_or_replace_temp_view("lineitem")
            return session.sql(tpch.Q1_SQL)

        # every run re-reads the files: one warm run says as much as three
        timed_query(session, "q1_parquet_scan", q1_scan,
                    lambda t: check_q1(t, q1_ref), seconds, warm_runs=1)
    phases.append("scan")

    # served: the same q1 and q3 through the QueryService, two tenants,
    # concurrency 2; answers equal the direct ones
    service = QueryService(session=session, max_concurrent=2)
    try:
        t0 = time.perf_counter()
        handles = []
        for tenant in ("tenant-a", "tenant-b"):
            handles.append((service.submit(
                tpch.q1_dataframe(session, lineitem), tenant=tenant,
                tag="q1"), q1_direct))
            handles.append((service.submit(
                tpch.q3_dataframe(session, cust, orders, li3), tenant=tenant,
                tag="q3"), q3_direct))
        for handle, direct in handles:
            table = handle.result(timeout=CHILD_TIMEOUT_S)
            assert table.to_pydict() == direct.to_pydict(), \
                f"served {handle.tag} ({handle.tenant}) differs from direct"
            check_record(handle.event_record,
                         f"served {handle.tag} ({handle.tenant})")
        seconds["served_4_queries_2_tenants"] = round(
            time.perf_counter() - t0, 4)
    finally:
        service.shutdown()
    phases.append("served")

    result = {
        "device": device, "hbm": hbm, "data": data, "phases": phases,
        "seconds": seconds, "persistent_cache": cache.report(),
        "demotions": CIRCUIT_BREAKER.demoted_ops(),
        "f64_on_device": f64_report,
        "native_available": bool(native_available()),
    }
    assert result["demotions"] == {}, result["demotions"]
    with open(out_path, "w") as f:
        json.dump(result, f)


# -- the four-chip child -----------------------------------------------------

def child_mesh(rows, out_path):
    """q1 and a repartition(4, key).group_by(key) query mesh-native over
    four chips, at the same row counts: shards on four distinct devices,
    the exchange on ICI, no host shuffle, no warm host upload, answers
    equal to the single-chip session's."""
    import spark_rapids_tpu  # noqa: F401
    device, _hbm = phase_device()
    assert device["count"] >= 4, device

    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.models import tpch
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    from spark_rapids_tpu.ops.expr import col, lit

    def mesh_scope():
        return dict(scopes_snapshot().get("mesh", {}))

    def delta(before):
        after = mesh_scope()
        return {k: after.get(k, 0) - before.get(k, 0) for k in after}

    single = make_session()
    mesh = make_session({"spark.rapids.mesh.enabled": "true",
                         "spark.rapids.mesh.shape": "4"})
    lineitem = tpch.lineitem_table(rows, seed=0)
    q1_ref = tpch.q1_pandas(lineitem)
    seconds = {}

    def exchange_query(s):
        return (s.create_dataframe(lineitem)
                .filter(col("l_quantity") > lit(2.0))
                .repartition(4, "l_returnflag")
                .group_by("l_returnflag")
                .agg(F.count().alias("c"),
                     F.sum(col("l_quantity")).alias("sq"))
                .sort("l_returnflag"))

    before = mesh_scope()
    timed_query(mesh, "mesh_q1", lambda: tpch.q1_dataframe(mesh, lineitem),
                lambda t: check_q1(t, q1_ref), seconds)
    want = exchange_query(single).collect_table().to_pydict()
    check_record(single.last_event_record, "single-chip exchange query")
    timed_query(
        mesh, "mesh_repartition_groupby", lambda: exchange_query(mesh),
        lambda t: _assert_equal(t.to_pydict(), want, "mesh vs single chip"),
        seconds)
    totals = delta(before)
    assert totals.get("shardsDispatched", 0) >= 4, totals
    assert totals.get("iciExchanges", 0) >= 1, totals
    assert totals.get("hostShuffleFallbacks", 0) == 0, totals

    # warm: no host upload between the cached scan and the collective
    before = mesh_scope()
    exchange_query(mesh).collect_table()
    warm = delta(before)
    assert warm.get("iciExchanges", 0) >= 1, warm
    assert warm.get("meshHostUploads", 0) == 0, warm

    # where the landed shards sit
    arrays, _n = (mesh.create_dataframe(lineitem)
                  .filter(col("l_quantity") > lit(2.0)).to_device_arrays())
    shard_devices = sorted(
        d.id for d in arrays["l_quantity"][0].sharding.device_set)
    assert len(shard_devices) == 4, \
        f"landed arrays sit on devices {shard_devices}, not on four"

    with open(out_path, "w") as f:
        json.dump({"device": device, "phases": ["device", "mesh_q1",
                                                "mesh_repartition_groupby"],
                   "seconds": seconds, "shard_devices": shard_devices,
                   "mesh_scope": totals, "mesh_scope_warm": warm}, f)


def _assert_equal(got, want, what):
    assert got == want, f"{what}: {got} vs {want}"


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, default=SF1_LINEITEM_ROWS,
                    help="lineitem rows (default: TPC-H SF1's 6,000,000)")
    ap.add_argument("--child", choices=("single", "mesh"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--out", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child == "single":
        child_single(args.rows, args.out)
    elif args.child == "mesh":
        child_mesh(args.rows, args.out)
    else:
        parent(args.rows)


if __name__ == "__main__":
    main()
