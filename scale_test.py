"""Scale Test harness (reference: integration_tests/ScaleTest.md +
datagen scaletest — SURVEY.md §2.11/§6): a parameterized join/agg/window
query set over generated tables, emitting a JSON timing report.

Usage: python scale_test.py [--sf 0.1] [--queries q1,q5] [--cpu-baseline]
       python scale_test.py --chaos [--seed 7]
       python scale_test.py --mesh 8 [--chaos] [--seed 7]
       python scale_test.py --streaming [--chaos] [--seed 7]

``--chaos`` runs the corpus twice — fault-free, then under a
randomized-but-SEEDED fault schedule (fetch errors, transport
disconnects, corrupt frames, kernel crashes injected through
``spark.rapids.test.faults`` — runtime/faults.py) — asserting
bit-identical results and bounded recovery work, with per-query
retry/recompute/demotion counts in the JSON report. It also runs the
WRITE corpus (run_write_chaos): seeded kill-mid-write scenarios
asserting the exactly-once transactional-write contract — no torn
file ever reader-visible, rerun-after-kill bit-identical, Delta
concurrent commits converge through the rebase-and-retry loop, and
vacuum reports zero orphans afterwards.

``--streaming`` runs the micro-batch streaming + materialized-view
harness (run_streaming, STREAM_r01.json): rate, file-watch and Delta
CDF-tail streams over corpus-derived tables into exactly-once Delta
sinks, plus two incrementally-maintained MVs, asserting sink row sets
bit-identical to a fault-free twin and every MV read bit-identical to a
from-scratch recompute at the same epoch; with ``--chaos`` each stream
is killed once mid-micro-batch (after its offsets are durably logged,
before the commit) under the seeded streaming fault schedule and must
resume exactly-once from its checkpoint.

``--mesh N --chaos`` composes both modes (run_mesh_chaos): the corpus
runs MESH-NATIVE under a seeded mesh-fault schedule firing every
``mesh.*`` point — shard-put crashes, checksummed-fetch corruption,
partial device losses walking the degradation ladder down to a mesh
shrink — asserting the mesh contract against fault-free single-chip
(mesh_contract_differ: bit-identity, but for the DOUBLE columns of a
query whose aggregate ran on the resident shards), bounded
recovery counters, and the mesh back at full strength at the end
(MULTICHIP_r07.json). Unsupported flag combinations fail fast
(validate_flags) instead of silently ignoring a mode."""

from __future__ import annotations

import argparse
import json
import time


def build_queries(s, tables, paths=None):
    """q1-q22: the TPC-H-flavored golden corpus (scan/filter/agg/join/
    window mix; the lint plan verifier and test_lint run over every one
    of these in both DSL and SQL form). With ``paths`` (the --hosts
    harness), each table comes from its parquet directory through the
    file-scan path instead of an in-memory HostTable — same queries,
    but scans can partition their source files BY HOST."""
    from spark_rapids_tpu import functions as F
    from spark_rapids_tpu.ops.expr import col, lit
    from spark_rapids_tpu.plan import from_host_table

    if paths is not None:
        cust = lambda: s.read_parquet(paths["customer"])   # noqa: E731
        orders = lambda: s.read_parquet(paths["orders"])   # noqa: E731
        li = lambda: s.read_parquet(paths["lineitem"])     # noqa: E731
    else:
        cust = lambda: from_host_table(tables["customer"], s)  # noqa: E731
        orders = lambda: from_host_table(tables["orders"], s)  # noqa: E731
        li = lambda: from_host_table(tables["lineitem"], s)    # noqa: E731

    def q1():  # pricing summary (TPC-H q1 shape)
        import datetime as _dt
        cutoff = _dt.date(1970, 1, 1) + _dt.timedelta(days=10500)
        return (li().filter(col("l_shipdate") <= lit(cutoff))
                .group_by("l_returnflag", "l_linestatus")
                .agg(F.sum("l_quantity").alias("sum_qty"),
                     F.sum("l_extendedprice").alias("sum_base"),
                     F.avg("l_discount").alias("avg_disc"),
                     F.count("l_quantity").alias("cnt")))

    def q2():  # filter + project arithmetic
        return (li().filter((col("l_discount") > lit(0.05))
                            & (col("l_quantity") < lit(25)))
                .select((col("l_extendedprice") * col("l_discount"))
                        .alias("revenue"))
                .agg(F.sum("revenue").alias("total")))

    def q3():  # join orders->lineitem + agg
        oj = orders().select("o_orderkey", "o_custkey", "o_orderdate")
        j = li().join(oj.with_column("l_orderkey", col("o_orderkey")),
                      on=["l_orderkey"], how="inner")
        return (j.group_by("o_custkey")
                .agg(F.sum("l_extendedprice").alias("spend"),
                     F.count("l_quantity").alias("items")))

    def q4():  # two-level join: customer -> orders -> lineitem
        oj = orders().select("o_orderkey", "o_custkey")
        cj = cust().select("c_custkey", "c_nationkey")
        j1 = (li().select("l_orderkey", "l_extendedprice")
              .join(oj.with_column("l_orderkey", col("o_orderkey")),
                    on=["l_orderkey"], how="inner"))
        j2 = j1.with_column("c_custkey", col("o_custkey")).join(
            cj, on=["c_custkey"], how="inner")
        return (j2.group_by("c_nationkey")
                .agg(F.sum("l_extendedprice").alias("rev")))

    def q5():  # sort + limit (TakeOrderedAndProject)
        return (orders().sort("o_totalprice", ascending=False).limit(100))

    def q6():  # window: rank orders per customer by price
        from spark_rapids_tpu.functions import row_number
        from spark_rapids_tpu.ops.window import Window as W
        return orders().with_windows(
            rn=row_number().over(
                W.partition_by("o_custkey").order_by("o_totalprice")))\
            .filter(col("rn") <= lit(3))

    def q7():  # repartition + agg (shuffle exercise)
        return (li().repartition(8, "l_returnflag")
                .group_by("l_returnflag")
                .agg(F.count("l_quantity").alias("c"),
                     F.sum("l_quantity").alias("s")))

    def q8():  # distinct-ish: group by high-cardinality key
        return (orders().group_by("o_custkey")
                .agg(F.max("o_totalprice").alias("m"))
                .agg(F.count("m").alias("n_custs")))

    def q9():  # TPC-H q5-like: 2-level join + filters + group + topk
        import datetime as _dt
        cut = _dt.date(1970, 1, 1) + _dt.timedelta(days=9000)
        cj = cust().select("c_custkey", "c_nationkey")
        oj = (orders().filter(col("o_orderdate") >= lit(cut))
              .select("o_orderkey", "o_custkey"))
        j1 = (li().select("l_orderkey", "l_extendedprice", "l_discount")
              .join(oj.with_column("l_orderkey", col("o_orderkey")),
                    on=["l_orderkey"], how="inner"))
        j2 = j1.with_column("c_custkey", col("o_custkey")).join(
            cj, on=["c_custkey"], how="inner")
        return (j2.select(col("c_nationkey"),
                          (col("l_extendedprice")
                           * (lit(1.0) - col("l_discount"))).alias("rev"))
                .group_by("c_nationkey")
                .agg(F.sum("rev").alias("revenue"))
                .sort("revenue", ascending=False).limit(10))

    def q10():  # TPC-H q17-like: join against an aggregated subquery
        avg_q = (li().group_by("l_orderkey")
                 .agg(F.avg("l_quantity").alias("avg_qty")))
        j = li().select("l_orderkey", "l_quantity", "l_extendedprice")\
            .join(avg_q, on=["l_orderkey"], how="inner")
        return (j.filter(col("l_quantity").cast("double")
                         < lit(0.6) * col("avg_qty"))
                .agg(F.sum("l_extendedprice").alias("total")))

    def q11():  # TPC-H q11-like: per-nation balance totals over a floor
        agged = (cust().group_by("c_nationkey")
                 .agg(F.sum("c_acctbal").alias("total_bal"),
                      F.count("c_custkey").alias("n")))
        return (agged.filter(col("n") > lit(5))
                .sort("total_bal", ascending=False))

    def q12():  # TPC-H q12-like: date-window join + per-flag counts
        import datetime as _dt
        lo = _dt.date(1970, 1, 1) + _dt.timedelta(days=9000)
        hi = _dt.date(1970, 1, 1) + _dt.timedelta(days=10000)
        lj = (li().filter((col("l_shipdate") >= lit(lo))
                          & (col("l_shipdate") < lit(hi)))
              .select("l_orderkey", "l_returnflag"))
        oj = orders().select("o_orderkey", "o_totalprice")
        j = lj.join(oj.with_column("l_orderkey", col("o_orderkey")),
                    on=["l_orderkey"], how="inner")
        return (j.group_by("l_returnflag")
                .agg(F.count("l_orderkey").alias("n"),
                     F.avg("o_totalprice").alias("avg_price")))

    def q13():  # TPC-H q13-like: customer order-count distribution
        per_cust = (orders().group_by("o_custkey")
                    .agg(F.count("o_orderkey").alias("c_orders")))
        return (per_cust.group_by("c_orders")
                .agg(F.count("o_custkey").alias("n_custs"))
                .sort("c_orders"))

    def q14():  # TPC-H q14-like: windowed revenue ratio
        import datetime as _dt
        lo = _dt.date(1970, 1, 1) + _dt.timedelta(days=9500)
        hi = _dt.date(1970, 1, 1) + _dt.timedelta(days=9700)
        f = (li().filter((col("l_shipdate") >= lit(lo))
                         & (col("l_shipdate") < lit(hi)))
             .select((col("l_extendedprice")
                      * (lit(1.0) - col("l_discount"))).alias("rev")))
        agged = f.agg(F.sum("rev").alias("total_rev"),
                      F.count("rev").alias("n"))
        return agged.select((col("total_rev") / col("n")).alias("avg_rev"),
                            col("total_rev"))

    def q15():  # TPC-H q15-like: top revenue customers
        oj = orders().select("o_orderkey", "o_custkey")
        j = (li().select("l_orderkey", "l_extendedprice", "l_discount")
             .join(oj.with_column("l_orderkey", col("o_orderkey")),
                   on=["l_orderkey"], how="inner"))
        return (j.select(col("o_custkey"),
                         (col("l_extendedprice")
                          * (lit(1.0) - col("l_discount"))).alias("rev"))
                .group_by("o_custkey").agg(F.sum("rev").alias("revenue"))
                .sort("revenue", ascending=False).limit(5))

    def q16():  # TPC-H q16-like: active customers per nation
        oc = (orders().select("o_custkey").group_by("o_custkey")
              .agg(F.count("o_custkey").alias("x")))
        j = oc.with_column("c_custkey", col("o_custkey")).join(
            cust().select("c_custkey", "c_nationkey"),
            on=["c_custkey"], how="inner")
        return (j.group_by("c_nationkey")
                .agg(F.count("c_custkey").alias("active_custs"))
                .sort("c_nationkey"))

    def q17():  # TPC-H q17-like: below-average-quantity revenue
        avg_q = (li().group_by("l_orderkey")
                 .agg(F.avg("l_quantity").alias("aq")))
        j = (li().select("l_orderkey", "l_quantity", "l_extendedprice")
             .join(avg_q, on=["l_orderkey"], how="inner"))
        return (j.filter(col("l_quantity").cast("double")
                         < lit(0.5) * col("aq"))
                .agg(F.sum("l_extendedprice").alias("s"))
                .select((col("s") / lit(7.0)).alias("avg_yearly")))

    def q18():  # TPC-H q18-like: large-volume orders
        big = (li().group_by("l_orderkey")
               .agg(F.sum("l_quantity").alias("sum_qty"))
               .filter(col("sum_qty") > lit(150)))
        j = big.with_column("o_orderkey", col("l_orderkey")).join(
            orders().select("o_orderkey", "o_custkey", "o_totalprice"),
            on=["o_orderkey"], how="inner")
        return (j.select("l_orderkey", "sum_qty", "o_custkey",
                         "o_totalprice")
                .sort("o_totalprice", ascending=False).limit(20))

    def q19():  # TPC-H q19-like: disjunctive predicate revenue
        f = li().filter(
            ((col("l_quantity") >= lit(1)) & (col("l_quantity") <= lit(11))
             & (col("l_discount") > lit(0.02)))
            | ((col("l_quantity") >= lit(10))
               & (col("l_quantity") <= lit(20))
               & (col("l_discount") < lit(0.06)))
            | (col("l_returnflag") == lit("R00000001")))
        return (f.select((col("l_extendedprice")
                          * (lit(1.0) - col("l_discount"))).alias("rev"))
                .agg(F.sum("rev").alias("revenue")))

    def q20():  # TPC-H q20-like: customers with big orders
        per = (orders().filter(col("o_totalprice") > lit(400000.0))
               .select("o_custkey").group_by("o_custkey")
               .agg(F.count("o_custkey").alias("nbig")))
        j = per.with_column("c_custkey", col("o_custkey")).join(
            cust().select("c_custkey", "c_name", "c_acctbal"),
            on=["c_custkey"], how="inner")
        return (j.select("c_custkey", "nbig", "c_name", "c_acctbal")
                .sort("nbig", ascending=False).limit(10))

    def q21():  # TPC-H q21-like: per-nation top accounts via window rank
        from spark_rapids_tpu.functions import row_number
        from spark_rapids_tpu.ops.window import Window as W
        return (cust().with_windows(
            rn=row_number().over(
                W.partition_by("c_nationkey").order_by("c_custkey")))
            .filter(col("rn") <= lit(2))
            .select("c_nationkey", "c_custkey", "rn"))

    def q22():  # TPC-H q22-like: accounts above the global average
        avg_t = (cust().select(col("c_acctbal"))
                 .agg(F.avg("c_acctbal").alias("ab"))
                 .with_column("k", lit(1)))
        c = (cust().select("c_custkey", "c_nationkey", "c_acctbal")
             .with_column("k", lit(1)))
        j = c.join(avg_t, on=["k"], how="inner")
        return (j.filter(col("c_acctbal").cast("double") > col("ab"))
                .group_by("c_nationkey")
                .agg(F.count("c_custkey").alias("numcust"),
                     F.sum("c_acctbal").alias("totacctbal"))
                .sort("c_nationkey"))

    return {"q1": q1, "q2": q2, "q3": q3, "q4": q4, "q5": q5,
            "q6": q6, "q7": q7, "q8": q8, "q9": q9, "q10": q10,
            "q11": q11, "q12": q12, "q13": q13, "q14": q14, "q15": q15,
            "q16": q16, "q17": q17, "q18": q18, "q19": q19, "q20": q20,
            "q21": q21, "q22": q22}


def sql_texts():
    """q1-q22 re-expressed as SQL text. Each query is written so the
    analyzer lowers it onto the SAME plan shape as its build_queries DSL
    form (nested selects mirror select/with_column chains; USING joins
    mirror on=[key] joins) — test_sql_frontend.py asserts result AND
    device-dispatch-count equality between the two forms."""
    import datetime as _dt

    def _iso(days):
        return (_dt.date(1970, 1, 1) + _dt.timedelta(days=days)).isoformat()

    cutoff = _iso(10500)
    cut9 = _iso(9000)
    return {
        "q1": f"""
            SELECT l_returnflag, l_linestatus,
                   SUM(l_quantity) AS sum_qty,
                   SUM(l_extendedprice) AS sum_base,
                   AVG(l_discount) AS avg_disc,
                   COUNT(l_quantity) AS cnt
            FROM lineitem
            WHERE l_shipdate <= DATE '{cutoff}'
            GROUP BY l_returnflag, l_linestatus""",
        "q2": """
            SELECT SUM(revenue) AS total FROM (
                SELECT l_extendedprice * l_discount AS revenue
                FROM lineitem
                WHERE l_discount > 0.05 AND l_quantity < 25)""",
        "q3": """
            SELECT o_custkey, SUM(l_extendedprice) AS spend,
                   COUNT(l_quantity) AS items
            FROM lineitem
            JOIN (SELECT o_orderkey, o_custkey, o_orderdate,
                         o_orderkey AS l_orderkey
                  FROM (SELECT o_orderkey, o_custkey, o_orderdate
                        FROM orders))
              USING (l_orderkey)
            GROUP BY o_custkey""",
        "q4": """
            SELECT c_nationkey, SUM(l_extendedprice) AS rev
            FROM (SELECT *, o_custkey AS c_custkey
                  FROM (SELECT l_orderkey, l_extendedprice FROM lineitem)
                  JOIN (SELECT o_orderkey, o_custkey,
                               o_orderkey AS l_orderkey
                        FROM (SELECT o_orderkey, o_custkey FROM orders))
                    USING (l_orderkey))
            JOIN (SELECT c_custkey, c_nationkey FROM customer)
              USING (c_custkey)
            GROUP BY c_nationkey""",
        "q5": """
            SELECT * FROM orders ORDER BY o_totalprice DESC LIMIT 100""",
        "q6": """
            SELECT * FROM (
                SELECT *, ROW_NUMBER() OVER (PARTITION BY o_custkey
                                             ORDER BY o_totalprice) AS rn
                FROM orders)
            WHERE rn <= 3""",
        "q7": """
            SELECT /*+ REPARTITION(8, l_returnflag) */
                   l_returnflag, COUNT(l_quantity) AS c,
                   SUM(l_quantity) AS s
            FROM lineitem GROUP BY l_returnflag""",
        "q8": """
            SELECT COUNT(m) AS n_custs FROM (
                SELECT o_custkey, MAX(o_totalprice) AS m
                FROM orders GROUP BY o_custkey)""",
        "q9": f"""
            SELECT c_nationkey, SUM(rev) AS revenue FROM (
                SELECT c_nationkey,
                       l_extendedprice * (1.0 - l_discount) AS rev
                FROM (SELECT *, o_custkey AS c_custkey
                      FROM (SELECT l_orderkey, l_extendedprice, l_discount
                            FROM lineitem)
                      JOIN (SELECT o_orderkey, o_custkey,
                                   o_orderkey AS l_orderkey
                            FROM (SELECT o_orderkey, o_custkey FROM orders
                                  WHERE o_orderdate >= DATE '{cut9}'))
                        USING (l_orderkey))
                JOIN (SELECT c_custkey, c_nationkey FROM customer)
                  USING (c_custkey))
            GROUP BY c_nationkey
            ORDER BY revenue DESC LIMIT 10""",
        "q10": """
            SELECT SUM(l_extendedprice) AS total
            FROM (SELECT l_orderkey, l_quantity, l_extendedprice
                  FROM lineitem)
            JOIN (SELECT l_orderkey, AVG(l_quantity) AS avg_qty
                  FROM lineitem GROUP BY l_orderkey)
              USING (l_orderkey)
            WHERE CAST(l_quantity AS double) < 0.6 * avg_qty""",
        "q11": """
            SELECT * FROM (
                SELECT c_nationkey, SUM(c_acctbal) AS total_bal,
                       COUNT(c_custkey) AS n
                FROM customer GROUP BY c_nationkey)
            WHERE n > 5
            ORDER BY total_bal DESC""",
        "q12": f"""
            SELECT l_returnflag, COUNT(l_orderkey) AS n,
                   AVG(o_totalprice) AS avg_price
            FROM (SELECT l_orderkey, l_returnflag FROM lineitem
                  WHERE l_shipdate >= DATE '{_iso(9000)}'
                    AND l_shipdate < DATE '{_iso(10000)}')
            JOIN (SELECT o_orderkey, o_totalprice,
                         o_orderkey AS l_orderkey
                  FROM (SELECT o_orderkey, o_totalprice FROM orders))
              USING (l_orderkey)
            GROUP BY l_returnflag""",
        "q13": """
            SELECT c_orders, COUNT(o_custkey) AS n_custs FROM (
                SELECT o_custkey, COUNT(o_orderkey) AS c_orders
                FROM orders GROUP BY o_custkey)
            GROUP BY c_orders ORDER BY c_orders""",
        "q14": f"""
            SELECT total_rev / n AS avg_rev, total_rev FROM (
                SELECT SUM(rev) AS total_rev, COUNT(rev) AS n FROM (
                    SELECT l_extendedprice * (1.0 - l_discount) AS rev
                    FROM lineitem
                    WHERE l_shipdate >= DATE '{_iso(9500)}'
                      AND l_shipdate < DATE '{_iso(9700)}'))""",
        "q15": """
            SELECT o_custkey, SUM(rev) AS revenue FROM (
                SELECT o_custkey,
                       l_extendedprice * (1.0 - l_discount) AS rev
                FROM (SELECT l_orderkey, l_extendedprice, l_discount
                      FROM lineitem)
                JOIN (SELECT o_orderkey, o_custkey,
                             o_orderkey AS l_orderkey
                      FROM (SELECT o_orderkey, o_custkey FROM orders))
                  USING (l_orderkey))
            GROUP BY o_custkey ORDER BY revenue DESC LIMIT 5""",
        "q16": """
            SELECT c_nationkey, COUNT(c_custkey) AS active_custs
            FROM (SELECT *, o_custkey AS c_custkey FROM (
                    SELECT o_custkey, COUNT(o_custkey) AS x
                    FROM (SELECT o_custkey FROM orders)
                    GROUP BY o_custkey))
            JOIN (SELECT c_custkey, c_nationkey FROM customer)
              USING (c_custkey)
            GROUP BY c_nationkey ORDER BY c_nationkey""",
        "q17": """
            SELECT s / 7.0 AS avg_yearly FROM (
                SELECT SUM(l_extendedprice) AS s
                FROM (SELECT l_orderkey, l_quantity, l_extendedprice
                      FROM lineitem)
                JOIN (SELECT l_orderkey, AVG(l_quantity) AS aq
                      FROM lineitem GROUP BY l_orderkey)
                  USING (l_orderkey)
                WHERE CAST(l_quantity AS double) < 0.5 * aq)""",
        "q18": """
            SELECT l_orderkey, sum_qty, o_custkey, o_totalprice FROM (
                SELECT *, l_orderkey AS o_orderkey FROM (
                    SELECT l_orderkey, SUM(l_quantity) AS sum_qty
                    FROM lineitem GROUP BY l_orderkey)
                WHERE sum_qty > 150)
            JOIN (SELECT o_orderkey, o_custkey, o_totalprice FROM orders)
              USING (o_orderkey)
            ORDER BY o_totalprice DESC LIMIT 20""",
        "q19": """
            SELECT SUM(rev) AS revenue FROM (
                SELECT l_extendedprice * (1.0 - l_discount) AS rev
                FROM lineitem
                WHERE (l_quantity >= 1 AND l_quantity <= 11
                       AND l_discount > 0.02)
                   OR (l_quantity >= 10 AND l_quantity <= 20
                       AND l_discount < 0.06)
                   OR l_returnflag = 'R00000001')""",
        "q20": """
            SELECT c_custkey, nbig, c_name, c_acctbal FROM (
                SELECT *, o_custkey AS c_custkey FROM (
                    SELECT o_custkey, COUNT(o_custkey) AS nbig
                    FROM (SELECT o_custkey FROM orders
                          WHERE o_totalprice > 400000.0)
                    GROUP BY o_custkey))
            JOIN (SELECT c_custkey, c_name, c_acctbal FROM customer)
              USING (c_custkey)
            ORDER BY nbig DESC LIMIT 10""",
        "q21": """
            SELECT c_nationkey, c_custkey, rn FROM (
                SELECT *, ROW_NUMBER() OVER (PARTITION BY c_nationkey
                                             ORDER BY c_custkey) AS rn
                FROM customer)
            WHERE rn <= 2""",
        "q22": """
            SELECT c_nationkey, COUNT(c_custkey) AS numcust,
                   SUM(c_acctbal) AS totacctbal
            FROM (SELECT *, 1 AS k
                  FROM (SELECT c_custkey, c_nationkey, c_acctbal
                        FROM customer))
            JOIN (SELECT *, 1 AS k
                  FROM (SELECT AVG(c_acctbal) AS ab FROM customer))
              USING (k)
            WHERE CAST(c_acctbal AS double) > ab
            GROUP BY c_nationkey ORDER BY c_nationkey""",
    }


def build_sql_queries(s, tables, paths=None):
    """q1-q22 from SQL text via session.sql() over temp views (--sql
    mode): same queries as build_queries, entering through the parser ->
    analyzer -> plan layer instead of the DataFrame DSL. With ``paths``
    the views sit over parquet scans (the --hosts harness) instead of
    in-memory tables."""
    from spark_rapids_tpu.plan import from_host_table
    if paths is not None:
        for name, tdir in paths.items():
            s.read_parquet(tdir).create_or_replace_temp_view(name)
    else:
        for name, table in tables.items():
            from_host_table(table, s).create_or_replace_temp_view(name)
    return {name: (lambda text=text: s.sql(text))
            for name, text in sql_texts().items()}


def time_query(fn, runs=3, session=None, tag=None):
    """Cold run + `runs` warm trials; returns (cold, min, median).

    >=3 warm trials with a median bound so host-sync latency variance is
    distinguishable from real regressions (the reference ScaleTest
    harness reports per-iteration times for the same reason —
    ref: integration_tests/ScaleTest.md). With a session+tag, every run
    is tagged in the query event log (cold runs as <tag>_cold) so the
    offline tools can match runs per query across reports."""

    def _tag(suffix=""):
        if session is not None and tag is not None:
            session.next_query_tag = tag + suffix

    _tag("_cold")
    t0 = time.perf_counter()
    fn().collect_table()
    cold = time.perf_counter() - t0
    warms = []
    for _ in range(runs):
        _tag()
        t0 = time.perf_counter()
        fn().collect_table()
        warms.append(time.perf_counter() - t0)
    warms.sort()
    return cold, warms[0], warms[len(warms) // 2]


# ---------------------------------------------------------------------------
# Chaos mode
# ---------------------------------------------------------------------------


def chaos_fault_spec(seed: int) -> str:
    """The seeded fault schedule: every recoverable fault class fires
    with a small per-hit probability (deterministic per seed). Kernel
    crashes stay rare — each one costs a whole-query replay."""
    return ";".join([
        f"shuffle.fetch.metadata:fetch:0.15:{seed * 10 + 1}",
        f"shuffle.fetch.stream:fetch:0.1:{seed * 10 + 2}",
        f"shuffle.fetch.stream:corrupt:0.1:{seed * 10 + 3}",
        f"shuffle.transport.request:disconnect:0.25:{seed * 10 + 4}",
        f"exec.execute:crash:0.01:{seed * 10 + 5}",
        f"dispatch.kernel:crash:0.001:{seed * 10 + 6}",
    ])


def service_fault_spec(seed: int) -> str:
    """Service-level survivability faults (PR 7) — THE schedule both
    chaos harnesses share (tools/loadtest.py owns it; drift between the
    two would mean they test different contracts)."""
    from spark_rapids_tpu.tools.loadtest import service_chaos_spec
    return service_chaos_spec(seed)


def chaos_conf(seed: int, faults: bool, service_faults: bool = False,
               concurrency: int = 4):
    """Session conf for a chaos (or its fault-free twin) run: the P2P
    shuffle so the full client/server/transport wire path is exercised,
    fast retry backoff, and the circuit breaker armed. The twin differs
    ONLY in the fault schedule so results are comparable bit-for-bit.
    ``service_faults`` extends the schedule with the service-level
    points (worker crash / device loss / wedge) plus the shared
    survivability settings (watchdog hard limit, slots == workers,
    strike budget — loadtest.service_chaos_settings)."""
    conf = {
        "spark.rapids.shuffle.mode": "P2P",
        "spark.rapids.shuffle.localDeviceSplit.enabled": "false",
        "spark.rapids.shuffle.fetch.retryWaitMs": "1",
        "spark.rapids.shuffle.fetch.maxRetries": "3",
        "spark.rapids.sql.runtimeFallback.enabled": "true",
        # every chaos closure runs with the lock witness armed: a rank
        # inversion under fault pressure fails the run (the committed
        # artifact records the violation count in-band)
        "spark.rapids.lint.lockWitness": "true",
    }
    if faults:
        spec = chaos_fault_spec(seed)
        if service_faults:
            from spark_rapids_tpu.tools.loadtest import (
                service_chaos_settings,
            )
            spec = spec + ";" + service_fault_spec(seed)
            conf.update(service_chaos_settings(concurrency))
        conf["spark.rapids.test.faults"] = spec
    return conf


def _record_lock_witness(report: dict, failures: list) -> None:
    """Record the runtime lock witness verdict in-band in a chaos
    artifact. Every chaos closure arms ``spark.rapids.lint.lockWitness``
    in its session conf, so locks constructed for the run are
    rank-checked at every blocking acquire; a nonzero count here is a
    rank inversion OBSERVED under fault pressure — a run failure the
    committed artifact must carry as evidence, not a warning."""
    from spark_rapids_tpu import lockorder
    n = int(lockorder.witness_violations())
    report["lockWitnessViolations"] = n
    report["lockWitnessArmed"] = lockorder.witness_armed()
    if n:
        report["lockWitnessRecords"] = (
            lockorder.witness_violation_records())
        failures.append(
            f"lock witness observed {n} rank inversion(s) during the run")


def tables_differ(a, b, double_limit=None):
    """Bit-identity check between two HostTables; returns None when
    identical, else a description of the first divergence. With
    ``double_limit`` (mesh_contract_differ) the FLOAT columns' valid
    cells may differ by that share of the larger magnitude; everything
    else stays bitwise: names, row count and order, types, validity,
    every other column."""
    import numpy as np
    if list(a.names) != list(b.names):
        return f"column names differ: {a.names} vs {b.names}"
    if a.num_rows != b.num_rows:
        return f"row counts differ: {a.num_rows} vs {b.num_rows}"
    for name, ca, cb in zip(a.names, a.columns, b.columns):
        if type(ca.dtype) is not type(cb.dtype):
            return f"column {name}: dtypes differ ({ca.dtype} vs {cb.dtype})"
        va = np.asarray(ca.validity, dtype=bool)
        vb = np.asarray(cb.validity, dtype=bool)
        if not np.array_equal(va, vb):
            return f"column {name}: validity differs"
        da, db = np.asarray(ca.data), np.asarray(cb.data)
        if da.dtype == object or db.dtype == object:
            for i in range(a.num_rows):
                if va[i] and da[i] != db[i]:
                    return (f"column {name} row {i}: "
                            f"{da[i]!r} != {db[i]!r}")
        elif double_limit is not None and da.dtype.kind == "f":
            xa, xb = da[va].astype(np.float64), db[vb].astype(np.float64)
            with np.errstate(invalid="ignore"):
                off = ~((xa == xb) | (np.isnan(xa) & np.isnan(xb))
                        | (np.abs(xa - xb) <= double_limit
                           * np.maximum(np.abs(xa), np.abs(xb))))
            if off.any():
                i = int(np.flatnonzero(off)[0])
                return (f"column {name}: valid value {i} differs beyond "
                        f"{double_limit:g}: {xa[i]!r} vs {xb[i]!r}")
        else:
            # bit identity over VALID rows only: raw bytes so NaN
            # payloads and signed zeros count (float equality would mask
            # them); boolean row indexing also masks multi-dim layouts
            # (decimal128 limb pairs), whose null slots are garbage
            if da[va].tobytes() != db[vb].tobytes():
                return f"column {name}: valid values differ bitwise"
    return None


#: per-query recovery-work ceilings the chaos run asserts (a runaway
#: retry loop must fail the run, not grind through it)
CHAOS_BOUNDS = {"fetch_retries": 500, "recomputed_maps": 200,
                "query_replays": 12}


# ---------------------------------------------------------------------------
# Write chaos: the exactly-once contract under kill-mid-write
# ---------------------------------------------------------------------------


def run_write_chaos(seed: int = 7, base_dir=None) -> dict:
    """Seeded kill-mid-write corpus asserting the transactional write
    contract (io/committer.py + delta conflict retry):

    * **no torn files** — a write killed at the file write or at a
      task-commit rename leaves the destination exactly as it was
      (old data fully intact, zero new ``part-*`` visible, staging
      swept by abort);
    * **rerun converges** — re-running the SAME WriteFiles plan after
      the injected kill produces output bit-identical to a fault-free
      write;
    * **transparent replay** — with the runtime-fallback replay armed,
      a crash mid-write auto-replays and the query COMPLETES with
      exactly-once output (no doubled files);
    * **Delta concurrency** — concurrent disjoint appends from one
      snapshot both land via the rebase-and-retry loop; an injected
      ``delta.commit.race`` is absorbed with commitRetries counted;
    * **zero orphans** — after every scenario ``tools vacuum`` reports
      a clean directory (dry-run first, then delete, then dry-run
      again must be empty)."""
    import os
    import tempfile
    import threading

    from spark_rapids_tpu.io.committer import TEMP_DIR, WRITE_METRICS
    from spark_rapids_tpu.plan import nodes as P
    from spark_rapids_tpu.runtime.faults import FAULTS
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools.vacuum import run_vacuum

    base = base_dir or tempfile.mkdtemp(prefix="rapids_write_chaos_")
    failures = []
    report = {"seed": seed, "dir": base, "backend": _resolved_backend(),
              "scenarios": {}}

    def _frame(s, n=200):
        import numpy as np
        rng = np.random.default_rng(seed)
        return s.create_dataframe({
            "k": [f"k{i % 5}" for i in range(n)],
            "v": np.arange(n, dtype=np.int64),
            "x": rng.standard_normal(n)})

    def _visible(path):
        """part-* files a scan would see (what expand_paths lists)."""
        out = []
        for root, dirs, files in os.walk(path):
            dirs[:] = [d for d in dirs if not d.startswith(("_", "."))]
            out.extend(os.path.join(root, f) for f in files
                       if not f.startswith(("_", ".")))
        return sorted(out)

    def _assert_clean_vacuum(path, entry):
        rep = run_vacuum(path)
        entry["orphansAfter"] = len(rep["orphans"])
        if rep["orphans"]:
            failures.append(
                f"{entry['name']}: vacuum found orphans {rep['orphans']}")

    def _read_back(s, path, fmt):
        if fmt == "parquet":
            df = s.read_parquet(path)
        else:
            df = s.read_csv(path, header=True)
        return sorted(df.collect(), key=repr)

    # -- scenario: kill at the file write / at the commit rename, both
    # formats, partitioned and not; typed failure then rerun converges
    kill_specs = [
        ("parquet", None, "io.write.file:crash:1:%d" % (seed * 10 + 1)),
        ("parquet", ["k"], "io.write.file:crash:1:%d" % (seed * 10 + 2)),
        ("parquet", ["k"], "io.write.commit:crash:1:%d" % (seed * 10 + 3)),
        ("csv", None, "io.write.commit:crash:1:%d" % (seed * 10 + 4)),
    ]
    for i, (fmt, part_by, spec) in enumerate(kill_specs):
        name = f"kill_{fmt}_{'part' if part_by else 'flat'}_{i}"
        entry = {"name": name, "spec": spec}
        clean_dir = os.path.join(base, name, "clean")
        dest = os.path.join(base, name, "out")
        s_clean = TpuSession()
        writer = getattr(_frame(s_clean), f"write_{fmt}")
        writer(clean_dir, partition_by=part_by)
        expected = _read_back(s_clean, clean_dir, fmt)

        # v1 of the destination: old data a killed overwrite must keep
        # (written FAULT-FREE by the clean session — the kill is for
        # the overwrite attempt, not the setup)
        old = s_clean.create_dataframe({"k": ["old"], "v": [0],
                                        "x": [0.0]})
        getattr(old, f"write_{fmt}")(dest, partition_by=part_by)
        before = _visible(dest)

        s_kill = TpuSession({"spark.rapids.test.faults": spec,
                             "spark.rapids.sql.runtimeFallback.enabled":
                                 "false"})
        df = _frame(s_kill)
        node = P.WriteFiles(df.plan, fmt, dest, part_by, {})
        try:
            s_kill.execute(node)
            failures.append(f"{name}: injected kill did not fire")
        except Exception as exc:
            entry["killed"] = type(exc).__name__
        entry["oldDataIntact"] = _visible(dest) == before
        if not entry["oldDataIntact"]:
            failures.append(f"{name}: reader-visible files changed "
                            "under a killed write")
        if os.path.isdir(os.path.join(dest, TEMP_DIR)):
            failures.append(f"{name}: staging not swept by abort")
        # rerun the SAME plan: the armed count is spent, the job id is
        # the same — then vacuum drops the files the new manifest no
        # longer references (the old data's superseded partitions) and
        # the readable output must converge bit-identically
        s_kill.execute(node)
        run_vacuum(dest, delete=True)
        got = _read_back(s_kill, dest, fmt)
        entry["rerunIdentical"] = got == expected
        if got != expected:
            failures.append(f"{name}: rerun-after-kill diverged")
        _assert_clean_vacuum(dest, entry)
        report["scenarios"][name] = entry

    # -- scenario: transparent replay — crash mid-write with the
    # runtime-fallback replay armed completes exactly-once
    name = "replay_parquet_part"
    spec = "io.write.file:crash:1:%d" % (seed * 10 + 5)
    s_rep = TpuSession({"spark.rapids.test.faults": spec})
    dest = os.path.join(base, name, "out")
    clean_dir = os.path.join(base, name, "clean")
    _frame(TpuSession()).write_parquet(clean_dir, partition_by=["k"])
    stats = _frame(s_rep).write_parquet(dest, partition_by=["k"])
    # capture BEFORE the read-backs: each later execute on this
    # session overwrites the last-query mirror with its own 0
    replays = int(s_rep.last_fault_replays or 0)
    got = _read_back(s_rep, dest, "parquet")
    expected = _read_back(s_rep, clean_dir, "parquet")
    entry = {"name": name, "spec": spec, "replays": replays,
             "identical": got == expected,
             "numFiles": int(stats.to_pydict()["numFiles"][0])}
    if not entry["replays"]:
        failures.append(f"{name}: crash did not trigger a replay")
    if not entry["identical"]:
        failures.append(f"{name}: replayed write not exactly-once")
    _assert_clean_vacuum(dest, entry)
    report["scenarios"][name] = entry

    # -- scenario: Delta — injected commit race + two real concurrent
    # disjoint appends through the rebase-and-retry loop
    name = "delta_concurrent"
    from spark_rapids_tpu.delta.log import DeltaLog
    from spark_rapids_tpu.delta.table import (
        OptimisticTransaction,
        _write_data_file,
        write_delta,
    )
    table_dir = os.path.join(base, name)
    spec = "delta.commit.race:race:1:%d" % (seed * 10 + 6)
    s_d = TpuSession({"spark.rapids.test.faults": spec})
    retries0 = WRITE_METRICS["commitRetries"]
    write_delta(_frame(s_d, 50).plan, s_d, table_dir, mode="error")
    entry = {"name": name, "spec": spec,
             "raceRetries": WRITE_METRICS["commitRetries"] - retries0}
    if entry["raceRetries"] < 1:
        failures.append(f"{name}: injected race was not retried")
    log = DeltaLog(table_dir)
    snap_v = log.latest_version()
    errs = []
    barrier = threading.Barrier(2)

    def _append(tag):
        from spark_rapids_tpu.columnar import HostTable
        txn = OptimisticTransaction(log, s_d.conf, read_version=snap_v)
        txn.stage(_write_data_file(
            table_dir, HostTable.from_pydict({
                "k": [tag], "v": [999], "x": [0.0]}), {}))
        barrier.wait()
        try:
            txn.commit("WRITE (append)")
        except Exception as exc:  # noqa: BLE001 - report, don't hang
            errs.append(f"{tag}: {type(exc).__name__}: {exc}")

    ts = [threading.Thread(target=_append, args=(t,)) for t in ("a", "b")]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    entry["concurrentAppendErrors"] = errs
    if errs:
        failures.append(f"{name}: concurrent appends failed: {errs}")
    rows = s_d.read_delta(table_dir).count()
    entry["rows"] = rows
    if rows != 52:
        failures.append(f"{name}: expected 52 rows after two appends, "
                        f"got {rows}")
    _assert_clean_vacuum(table_dir, entry)
    report["scenarios"][name] = entry

    FAULTS.disarm()
    report["ok"] = not failures
    report["failures"] = failures
    return report


def run_chaos(sf: float = 0.02, seed: int = 7, queries=None,
              use_sql: bool = False, concurrency: int = 0,
              service_faults: bool = False):
    """Fault-free run, then the seeded-fault run, per query; returns the
    chaos report dict (and raises AssertionError on any divergence or
    bound violation — callers in CI want the failure loud).

    ``concurrency > 1`` runs the CHAOTIC side through a QueryService
    worker pool instead of serially — recovery (fetch retry, map
    recompute, crash replay/demotion) and the concurrent scheduler are
    then exercised TOGETHER, still asserting bit-identity against the
    fault-free serial baseline. Recovery bounds apply to the whole run
    (per-query attribution is meaningless across interleaved workers)."""
    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.runtime.faults import (
        CIRCUIT_BREAKER,
        FAULTS,
        RECOVERY,
    )
    from spark_rapids_tpu.session import TpuSession

    # argument sanity BEFORE the (expensive) datagen
    if service_faults and (not concurrency or concurrency <= 1):
        raise SystemExit(
            "--service-faults needs --concurrency > 1 (the service "
            "points live in the worker/watchdog machinery)")
    # write corpus FIRST, self-contained (own sessions, own fault
    # specs, disarms at the end): the read corpus's seeded schedule
    # must then advance uninterrupted across q1-q22
    write_report = run_write_chaos(seed)

    specs = scale_test_specs(sf)
    tables = {name: spec.generate_table(sf, seed=seed)
              for name, spec in specs.items()}
    build = build_sql_queries if use_sql else build_queries

    baseline = TpuSession(chaos_conf(seed, faults=False))
    chaotic = TpuSession(chaos_conf(seed, faults=True,
                                    service_faults=service_faults,
                                    concurrency=concurrency))
    base_queries = build(baseline, tables)
    chaos_queries = build(chaotic, tables)
    wanted = queries or list(base_queries)

    report = {"mode": "chaos", "seed": seed, "scale_factor": sf,
              "backend": _resolved_backend(),
              # the spec ACTUALLY armed (chaos_conf composed it) — not
              # a rebuilt copy that could drift from it
              "fault_spec": chaotic.conf.to_dict()[
                  "spark.rapids.test.faults"],
              "service_faults": service_faults,
              "writes": write_report,
              "queries": {}}
    failures = list(write_report["failures"])
    # ALL fault-free runs first: each execute() re-arms the registry from
    # its session's conf, and interleaving arm("")/arm(spec) would reset
    # the seeded schedule every query — the RNG must advance ACROSS the
    # corpus for the schedule to be randomized rather than cyclic
    expected_tables = {name: base_queries[name]().collect_table()
                       for name in wanted}
    if concurrency and concurrency > 1:
        return _run_chaos_concurrent(
            report, failures, wanted, expected_tables, base_queries,
            chaos_queries, chaotic, concurrency,
            service_faults=service_faults)
    for name in wanted:
        expected = expected_tables[name]
        before = RECOVERY.snapshot()
        fires_before = FAULTS.counters()
        demoted_before = set(CIRCUIT_BREAKER.demoted_ops())
        t0 = time.perf_counter()
        got = chaos_queries[name]().collect_table()
        elapsed = time.perf_counter() - t0
        recovery = {k: v - before[k] for k, v in RECOVERY.snapshot().items()}
        entry = {
            "chaos_s": round(elapsed, 4),
            "identical": None,
            **recovery,
            "demotions_total": len(CIRCUIT_BREAKER.demoted_ops()),
            "newly_demoted": sorted(
                set(CIRCUIT_BREAKER.demoted_ops()) - demoted_before),
            # per-query delta, like every other field in this entry
            "fault_fires": {
                k: v - fires_before.get(k, 0)
                for k, v in FAULTS.counters().items()
                if v - fires_before.get(k, 0)},
        }
        diff = tables_differ(expected, got)
        if diff is not None and CIRCUIT_BREAKER.demoted_ops():
            # ANY active demotion (this query's or an earlier one's) can
            # change float reduction order vs the pre-demotion device
            # baseline (conf: variableFloatAgg). The breaker is
            # process-wide, so re-collecting the BASELINE now runs it
            # through the same demoted (CPU) plan — results must be
            # bit-identical to THAT fault-free run of the same plan.
            # suspended(): the baseline session's arm("") must not reset
            # the seeded schedule mid-corpus (see the comment above).
            with FAULTS.suspended():
                redo = base_queries[name]().collect_table()
            diff = tables_differ(redo, got)
            entry["compared_vs_demoted_baseline"] = True
        entry["identical"] = diff is None
        if diff is not None:
            failures.append(f"{name}: {diff}")
        for field, bound in CHAOS_BOUNDS.items():
            if recovery.get(field, 0) > bound:
                failures.append(
                    f"{name}: {field}={recovery[field]} exceeds the "
                    f"chaos bound {bound}")
        report["queries"][name] = entry
        print(json.dumps({"query": name, **entry}))
    report["demoted_ops"] = CIRCUIT_BREAKER.demoted_ops()
    _record_lock_witness(report, failures)
    report["ok"] = not failures
    report["failures"] = failures
    FAULTS.disarm()
    if failures:
        raise AssertionError("chaos run failed:\n" + "\n".join(failures))
    return report


def _run_chaos_concurrent(report, failures, wanted, expected_tables,
                          base_queries, chaos_queries, chaotic_session,
                          concurrency, service_faults=False):
    """Concurrent half of run_chaos: submit the chaotic corpus to a
    QueryService at the requested concurrency across two simulated
    tenants, then verify each result bit-identical to the fault-free
    serial baseline (re-collected through the demoted plan when the
    circuit breaker fired mid-run, exactly like the serial path).

    With ``service_faults`` the schedule also kills workers, loses the
    device, and wedges a dispatch: the bar becomes the survivability
    contract — every submission terminal (no hangs), FINISHED results
    still bit-identical, non-FINISHED outcomes typed, recovery bounded,
    and the service back at HEALTHY."""
    from contextlib import ExitStack

    from spark_rapids_tpu.runtime.faults import (
        CIRCUIT_BREAKER,
        FAULTS,
        RECOVERY,
    )
    from spark_rapids_tpu.runtime.health import HEALTH, QUARANTINE
    from spark_rapids_tpu.service import QueryService
    from spark_rapids_tpu.tools.loadtest import (
        _CHAOS_TYPED_ERRORS as typed_ok,
        drive_health_probes,
        wedge_stall_env,
    )

    report["concurrency"] = concurrency
    before = RECOVERY.snapshot()
    fires_before = FAULTS.counters()
    health_before = HEALTH.snapshot()
    chaos_env = ExitStack()
    if service_faults:
        # stall longer than the hard limit so the watchdog provably
        # fires; the abandoned thread exits on its own afterwards
        chaos_env.enter_context(wedge_stall_env())
    svc = QueryService(session=chaotic_session,
                       max_concurrent=concurrency,
                       queue_depth=max(len(wanted), 64))
    t0 = time.perf_counter()
    handles = {}
    health_probes = 0
    svc_health = None
    try:
        with svc:
            hung = False
            for i, name in enumerate(wanted):
                handles[name] = svc.submit(chaos_queries[name](),
                                           tenant=f"t{i % 2}", tag=name)
            for name, h in handles.items():
                if not h.wait(timeout=600):
                    hung = True
                    failures.append(f"{name}: still {h.state} after 600s")
            # a hung run already failed — waiting out probe timeouts
            # would only delay the verdict (loadtest guards likewise)
            if service_faults and not hung:
                health_probes = drive_health_probes(
                    svc, chaos_queries[wanted[0]], timeout_s=600)
            svc_health = svc.health()
    finally:
        chaos_env.close()
    report["wall_s"] = round(time.perf_counter() - t0, 4)
    recovery = {k: v - before[k] for k, v in RECOVERY.snapshot().items()}
    report["recovery"] = recovery
    report["fault_fires"] = {
        k: v - fires_before.get(k, 0) for k, v in FAULTS.counters().items()
        if v - fires_before.get(k, 0)}
    report["service"] = svc.stats()
    for name, h in handles.items():
        got = h.result_table
        if got is None:
            if (service_faults
                    and type(h.error).__name__ in typed_ok):
                # survivable typed outcome under service faults: the
                # contract is TERMINAL + typed, not all-finished
                report["queries"][name] = {
                    "state": h.state, "identical": None,
                    "typed_error": f"{type(h.error).__name__}: "
                                   f"{h.error}",
                    "requeues": h.requeues}
                continue
            failures.append(f"{name}: no result ({h.state}: {h.error})")
            report["queries"][name] = {"state": h.state,
                                       "identical": False}
            continue
        diff = tables_differ(expected_tables[name], got)
        if diff is not None and CIRCUIT_BREAKER.demoted_ops():
            with FAULTS.suspended():
                redo = base_queries[name]().collect_table()
            diff = tables_differ(redo, got)
        entry = {"state": h.state, "identical": diff is None,
                 "latency_s": round(h.latency_s or 0.0, 4),
                 "queue_wait_s": round(h.queue_wait_s or 0.0, 4),
                 "requeues": h.requeues}
        if diff is not None:
            failures.append(f"{name}: {diff}")
        if h.state != "FINISHED":
            failures.append(f"{name}: unexpected terminal state "
                            f"{h.state} ({h.error})")
        report["queries"][name] = entry
    # whole-run recovery bounds: the per-query ceilings summed
    for field, bound in CHAOS_BOUNDS.items():
        total_bound = bound * len(wanted)
        if recovery.get(field, 0) > total_bound:
            failures.append(f"{field}={recovery[field]} exceeds the "
                            f"whole-run chaos bound {total_bound}")
    stats = report["service"]
    if service_faults:
        health_after = HEALTH.snapshot()
        if svc_health is None:
            svc_health = svc.health()
        report["survivability"] = {
            "deviceReinits": health_after["deviceReinits"]
            - health_before["deviceReinits"],
            "workersLost": stats["workersLost"],
            "workersRespawned": stats["workersRespawned"],
            "requeued": stats["requeued"],
            "hardTimeouts": stats["hardTimeouts"],
            "quarantine": QUARANTINE.snapshot(),
            "healthAtEnd": svc_health,
            "healthProbes": health_probes,
        }
        if svc_health["state"] != "HEALTHY":
            failures.append(
                f"service did not return to HEALTHY: {svc_health}")
        # the watchdog's hard timeouts are EXPECTED under the wedge
        # fault; cancellations and rejections still are not
        if stats["cancelled"] or stats["rejected"]:
            failures.append(f"spurious lifecycle events: {stats}")
    elif stats["cancelled"] or stats["timed_out"] or stats["rejected"]:
        failures.append(f"spurious lifecycle events: {stats}")
    report["demoted_ops"] = CIRCUIT_BREAKER.demoted_ops()
    _record_lock_witness(report, failures)
    report["ok"] = not failures
    report["failures"] = failures
    FAULTS.disarm()
    if failures:
        raise AssertionError("concurrent chaos run failed:\n"
                             + "\n".join(failures))
    return report


# ---------------------------------------------------------------------------
# Mesh chaos: the distributed path under a seeded mesh-fault schedule
# ---------------------------------------------------------------------------


def memory_chaos_fault_spec(seed: int) -> str:
    """The seeded memory-fault schedule: every ``mem.*`` point fires at
    least once (asserted by run_memory_chaos) — a budget squeeze
    mid-query (the retry framework spills and replays), a spill
    FAILURE (the demotion path dies; circuit-breaker/replay recovers),
    and an unspill CORRUPTION (the disk frame's CRC footer trips;
    typed SpillCorruptionError re-lands from the scan cache via query
    replay). COUNT-based entries only, so the schedule is
    deterministic and the post-corpus phases run fault-free."""
    return ";".join([
        f"mem.reserve:oom:2:{seed * 10 + 1}",
        f"mem.spill:crash:1:{seed * 10 + 2}",
        f"mem.unspill:corrupt:1:{seed * 10 + 3}",
    ])


#: whole-run recovery-work ceilings for the memory chaos closure (a
#: runaway spill/retry loop must fail the run, not grind through it)
MEMORY_CHAOS_BOUNDS = {"query_replays": 30, "oomRetries": 4000,
                       "splitRetries": 200, "spillCorruptions": 4,
                       "budgetRaises": 2000}


def tables_differ_unordered(a, b):
    """Bitwise row-MULTISET comparison: chunked/budgeted execution
    legitimately changes the ROW ORDER of unsorted output (group-by
    emission order follows batching), but every row must still exist
    bitwise-identically on both sides. repr() round-trips floats
    exactly (and distinguishes -0.0), so sorting the repr'd rows
    compares value bits, not approximations."""
    if a.names != b.names:
        return f"column names differ: {a.names} vs {b.names}"
    if a.num_rows != b.num_rows:
        return f"row counts differ: {a.num_rows} vs {b.num_rows}"
    rows_a = sorted(map(repr, zip(*[c.to_pylist() for c in a.columns])))
    rows_b = sorted(map(repr, zip(*[c.to_pylist() for c in b.columns])))
    if rows_a != rows_b:
        for i, (ra, rb) in enumerate(zip(rows_a, rows_b)):
            if ra != rb:
                return f"row multiset differs (first at sorted #{i}: " \
                       f"{ra} vs {rb})"
    return None


def tables_close(a, b, rtol=1e-9):
    """Order-insensitive SEMANTIC comparison: non-float values exact,
    floats within rtol. Used to pin that chunked execution computes
    the same ANSWER as unchunked — f64 partial merges over different
    batch structures legitimately differ in final ulps (addition is
    not associative), which is exactly why the bitwise contract runs
    against the same-shape baseline instead."""
    if a.names != b.names:
        return f"column names differ: {a.names} vs {b.names}"
    if a.num_rows != b.num_rows:
        return f"row counts differ: {a.num_rows} vs {b.num_rows}"

    def key(row):
        return tuple(f"{v:.6g}" if isinstance(v, float) else repr(v)
                     for v in row)

    rows_a = sorted(zip(*[c.to_pylist() for c in a.columns]), key=key)
    rows_b = sorted(zip(*[c.to_pylist() for c in b.columns]), key=key)
    for i, (ra, rb) in enumerate(zip(rows_a, rows_b)):
        for va, vb in zip(ra, rb):
            if isinstance(va, float) and isinstance(vb, float):
                if va != vb and not (
                        abs(va - vb) <= rtol * max(abs(va), abs(vb))):
                    return f"row {i}: {va!r} !~ {vb!r}"
            elif va != vb:
                return f"row {i}: {va!r} != {vb!r}"
    return None


def run_memory_chaos(sf: float, seed: int, budget: int, queries=None,
                     use_sql: bool = False, chaos: bool = True):
    """``--device-budget BYTES [--chaos]``: q1-q22 under a hard device
    budget well below the working set — every landing accounted by the
    MemoryArbiter, scans chunked, intermediates spilled through the
    device->host->disk tiers (host tier squeezed so the DISK tier and
    its CRC footers see traffic) — asserting every query bit-identical
    to unbudgeted execution, spillBytes > 0, zero budget violations,
    recovery within MEMORY_CHAOS_BOUNDS and (with --chaos) every
    ``mem.*`` fault point fired, a full memory-ladder walk with one
    incident bundle per action, and a QueryService ending HEALTHY.
    This is the OOC_r01 acceptance harness — ROADMAP item 2's
    out-of-core half exercised end to end."""
    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    from spark_rapids_tpu.runtime.faults import (
        CIRCUIT_BREAKER,
        FAULTS,
        RECOVERY,
    )
    from spark_rapids_tpu.runtime.health import HEALTH
    from spark_rapids_tpu.runtime.memory import MEMORY
    from spark_rapids_tpu.runtime.spill import BufferCatalog
    from spark_rapids_tpu.session import TpuSession

    specs = scale_test_specs(sf)
    tables = {name: spec.generate_table(sf, seed=seed)
              for name, spec in specs.items()}
    build = build_sql_queries if use_sql else build_queries

    # 16KB host tier: device spills overflow to DISK almost instantly,
    # so the CRC-footed frames and the mem.unspill point see traffic
    BufferCatalog.reset(host_limit_bytes=16 * 1024)
    MEMORY.reset()

    import os
    import tempfile
    flight_dir = tempfile.mkdtemp(prefix="rapids_mem_flightrec_")
    spec = memory_chaos_fault_spec(seed) if chaos else ""
    plain = TpuSession()
    # chunk share at a TENTH of the budget: the join/agg pipeline's
    # irreducible live set (current probe chunk + join output + build
    # + coalesce pending) is a few chunk shares wide — keeping it well
    # under the budget is what makes ZERO violations achievable while
    # spill pressure still builds across the query
    chunk_fraction = 0.1
    budgeted = TpuSession({
        "spark.rapids.memory.device.budgetBytes": str(int(budget)),
        "spark.rapids.memory.device.scanChunkFraction":
            str(chunk_fraction),
        "spark.rapids.sql.runtimeFallback.enabled": "true",
        "spark.rapids.lint.lockWitness": "true",
        "spark.rapids.test.faults": spec,
        "spark.rapids.obs.telemetry.enabled": "true",
        "spark.rapids.obs.telemetry.intervalMs": "200",
        "spark.rapids.obs.flightRecorder.dir": flight_dir,
    })
    plain_queries = build(plain, tables)
    budget_queries = build(budgeted, tables)
    wanted = queries or list(plain_queries)

    report = {"mode": "memory-chaos", "backend": _resolved_backend(),
              "scale_factor": sf, "seed": seed, "sql": use_sql,
              "device_budget_bytes": int(budget),
              "chaos": bool(chaos),
              "fault_spec": spec, "queries": {}}
    failures = []

    # ALL baselines first (run_mesh_chaos's discipline: the baseline
    # session's arm('') must not reset the seeded schedule). TWO
    # baselines per query:
    #
    # * UNBUDGETED (plain): measures the working set — the arbiter's
    #   peak accounted bytes over the whole corpus is what the budget
    #   must sit well below for the run to prove anything.
    # * SHAPE baseline (plain session under forced_chunking at the
    #   budget's chunk share, NO enforcement): executes the exact
    #   batching structure the budgeted run will take — chunked scans,
    #   capped coalesce flushes, sub-partitioned builds — with zero
    #   spills/retries. The budgeted run must be BITWISE IDENTICAL to
    #   it: multi-batch f64 partial merges are only reproducible
    #   against the same batch structure (the MeshReland bit-identity
    #   argument), so this is the comparison that isolates what the
    #   PR adds — enforcement, spill round trips, retries — and
    #   proves it corrupts nothing.
    from spark_rapids_tpu.runtime.memory import forced_chunking
    expected_plain = {name: plain_queries[name]().collect_table()
                      for name in wanted}
    working_set = MEMORY.snapshot()["peakBytes"]
    report["working_set_peak_bytes"] = int(working_set)
    if budget >= working_set:
        failures.append(
            f"--device-budget {budget} is not below the measured "
            f"unbudgeted working-set peak {working_set} — the run "
            "would prove nothing")
    chunk_share = max(1, int(budget * chunk_fraction))
    report["chunk_share_bytes"] = chunk_share
    expected_chunked = {}
    with forced_chunking(chunk_share):
        for name in wanted:
            expected_chunked[name] = plain_queries[name]().collect_table()
    # chunking must not change the ANSWER (row multiset, float ulps
    # aside the values are the same computation): pin the shape
    # baseline against the plain one order-insensitively before
    # trusting it as the identity reference
    for name in wanted:
        sem = tables_close(expected_plain[name], expected_chunked[name])
        if sem is not None:
            failures.append(f"{name}: chunked execution changed the "
                            f"answer vs unchunked: {sem}")
    # a fresh ledger + clean caches for the budgeted phase (the
    # baseline scans' cached unchunked device images would otherwise
    # start the budgeted run already over budget)
    from spark_rapids_tpu.columnar.table import evict_device_caches
    evict_device_caches()
    MEMORY.reset()

    def _mem():
        return dict(scopes_snapshot().get("memory", {}))

    recovery_before = RECOVERY.snapshot()
    mem_before_all = _mem()

    # -- spill round-trip closure ---------------------------------------------
    # The full demotion chain on the REAL corpus data, bitwise: every
    # lineitem chunk lands (budget-enforced, OOM-retried), registers as
    # a SpillableDeviceTable, is forced through device->host->disk
    # (the 16KB host tier overflows to CRC-footed disk frames
    # immediately), and re-lands via get() — the armed mem.unspill
    # corruption fires at the first disk read here, and the phase
    # demonstrates the documented recovery: typed SpillCorruptionError,
    # frame dropped, data re-landed from the source chunk, still
    # bitwise identical. The armed mem.reserve squeezes fire at these
    # landings too (survived by the retry framework).
    from spark_rapids_tpu.errors import (
        KernelCrashError,
        SpillCorruptionError,
    )
    from spark_rapids_tpu.runtime.memory import scan_chunks
    from spark_rapids_tpu.runtime.retry import retry_block
    from spark_rapids_tpu.runtime.spill import SpillableDeviceTable
    from spark_rapids_tpu.columnar import DeviceTable

    def _spill_all_tolerant(counter: dict) -> None:
        """One forced demotion pass, surviving the armed mem.spill
        CRASH (the spill path itself dying leaves the buffer resident
        — the documented failure mode); the immediate retry drains
        the rest of the demotion."""
        try:
            catalog.spill_all_device()
        except KernelCrashError:
            counter["spillCrashesSurvived"] = counter.get(
                "spillCrashesSurvived", 0) + 1
            catalog.spill_all_device()
    if chaos:
        FAULTS.arm(spec)
    catalog = BufferCatalog.get()
    roundtrip = {"chunks": 0, "unspillsBitIdentical": 0,
                 "corruptionsRelanded": 0}
    budgeted.set_conf("spark.rapids.memory.device.budgetBytes",
                      str(int(budget)))
    MEMORY.configure(budgeted.conf)
    with forced_chunking(chunk_share):
        li_chunks = scan_chunks(tables["lineitem"])
    sbs = []
    try:
        for ch in li_chunks:
            dt = retry_block(lambda c=ch: DeviceTable.from_host(c))
            sbs.append((ch, SpillableDeviceTable(dt, catalog)))
            del dt
        _spill_all_tolerant(roundtrip)  # host tier overflows to disk
        for ch, sb in sbs:
            roundtrip["chunks"] += 1
            try:
                got_dt = sb.get()
            except SpillCorruptionError:
                # the corrupt frame was dropped, never served: re-land
                # from the source chunk (the scan-cache re-land path)
                got_dt = retry_block(
                    lambda c=ch: DeviceTable.from_host(c))
                roundtrip["corruptionsRelanded"] += 1
            rt_diff = tables_differ(ch, got_dt.to_host())
            if rt_diff is not None:
                failures.append(
                    f"spill round trip chunk {roundtrip['chunks']} not "
                    f"bit-identical: {rt_diff}")
            else:
                roundtrip["unspillsBitIdentical"] += 1
            del got_dt
            _spill_all_tolerant(roundtrip)
    finally:
        for _, sb in sbs:
            sb.release()
    report["spill_roundtrip"] = roundtrip
    if chaos and roundtrip["corruptionsRelanded"] != 1:
        failures.append(
            f"expected exactly 1 corrupt unspill re-landed in the "
            f"round-trip phase, got {roundtrip['corruptionsRelanded']}")

    for name in wanted:
        before = _mem()
        fires_before = FAULTS.counters()
        t0 = time.perf_counter()
        got = budget_queries[name]().collect_table()
        wall = time.perf_counter() - t0
        after = _mem()
        # BITWISE identity against the same-shape baseline: the
        # budgeted run's spills/unspills/retries must not change one
        # bit of what the identical batch structure computes clean
        diff = tables_differ(expected_chunked[name], got)
        compare_mode = "bitwise"
        if diff is not None and (CIRCUIT_BREAKER.demoted_ops()
                                 or HEALTH.state() != "HEALTHY"):
            # an active demotion changes float accumulation order vs
            # the pre-demotion baseline (process-wide): re-collect the
            # baseline through the same demoted plan (run_chaos
            # pattern; suspended() keeps the schedule from resetting)
            with FAULTS.suspended(), forced_chunking(chunk_share):
                redo = plain_queries[name]().collect_table()
            diff = tables_differ(redo, got)
            compare_mode = "bitwise_vs_demoted"
        if diff is not None:
            # a mid-query split-and-retry legitimately changes the
            # batch structure (halved inputs re-accumulate): fall back
            # to the order-insensitive multiset view before declaring
            # divergence, and report which contract held
            if tables_differ_unordered(expected_chunked[name],
                                       got) is None:
                diff = None
                compare_mode = "multiset"
        entry = {
            "chaos_s": round(wall, 4),
            "identical": diff is None,
            "compare_mode": compare_mode,
            "memory": {k: int(after.get(k, 0) - before.get(k, 0))
                       for k in ("oomRetries", "splitRetries",
                                 "spillBytes", "unspills", "scanChunks",
                                 "arbiterSpills", "budgetRaises",
                                 "spillCorruptions", "budgetViolations")
                       if after.get(k, 0) != before.get(k, 0)},
            "fault_fires": {
                k: v - fires_before.get(k, 0)
                for k, v in FAULTS.counters().items()
                if v - fires_before.get(k, 0)},
            "budget_peak": MEMORY.snapshot()["peakBytes"],
        }
        if diff is not None:
            failures.append(f"{name}: {diff}")
        report["queries"][name] = entry
        print(json.dumps({"query": name, **entry}))

    # -- closure assertions ---------------------------------------------------
    mem_after_all = _mem()
    moved = {k: int(mem_after_all.get(k, 0) - mem_before_all.get(k, 0))
             for k in set(mem_after_all) | set(mem_before_all)}
    report["memory_totals"] = {k: v for k, v in sorted(moved.items())
                               if v}
    if moved.get("spillBytes", 0) <= 0:
        failures.append("spillBytes == 0: the budget never forced a "
                        "spill — it is not below the working set")
    if moved.get("unspills", 0) <= 0:
        failures.append("unspills == 0: spilled data never round-"
                        "tripped back to the device")
    if moved.get("scanChunks", 0) <= 0:
        failures.append("scanChunks == 0: no scan ever chunked")
    if moved.get("budgetViolations", 0) != 0:
        failures.append(
            f"budgetViolations={moved['budgetViolations']}: a landing "
            "exceeded the budget after spilling — enforcement leaked")
    arb = MEMORY.snapshot()
    report["arbiter"] = arb
    report["budgeted_peak_bytes"] = arb["peakBytes"]
    if arb["budgetViolations"] != 0:
        # (redundant with the scope delta above, but the snapshot is
        # the arbiter's own ground truth for the budgeted phase)
        failures.append(
            f"arbiter recorded {arb['budgetViolations']} budget "
            "violations in the budgeted phase")
    if chaos:
        fires = FAULTS.counters()
        for point in sorted(e.split(":")[0] for e in spec.split(";")):
            if not fires.get(point):
                failures.append(
                    f"armed memory fault point {point} never fired — "
                    "the schedule does not cover the out-of-core path")
        report["fault_fires_total"] = dict(fires)
    recovery = {k: v - recovery_before[k]
                for k, v in RECOVERY.snapshot().items()}
    for k in ("oomRetries", "splitRetries", "spillCorruptions",
              "budgetRaises"):
        recovery[k] = moved.get(k, 0)
    report["recovery"] = recovery
    for field, bound in MEMORY_CHAOS_BOUNDS.items():
        if recovery.get(field, 0) > bound:
            failures.append(f"{field}={recovery[field]} exceeds the "
                            f"memory chaos bound {bound}")

    # -- ladder closure: the full walk, one incident bundle per action -------
    if chaos:
        from spark_rapids_tpu.tools.incident import (
            load_bundles,
            render_incident,
        )
        FAULTS.disarm()
        ladder_before = HEALTH.memory_snapshot()["memoryPressureEvents"]
        # a sustained squeeze (every reservation refused for 10 grants)
        # walks retry -> chunk -> cpu_demote end to end and STILL
        # completes; compared against a baseline re-collected through
        # the same demoted plan
        ladder = TpuSession({
            "spark.rapids.memory.device.budgetBytes": str(int(budget)),
            "spark.rapids.memory.device.scanChunkFraction":
                str(chunk_fraction),
            "spark.rapids.sql.runtimeFallback.enabled": "true",
            "spark.rapids.lint.lockWitness": "true",
            "spark.rapids.test.faults":
                f"mem.reserve:oom:10:{seed * 10 + 9}",
            "spark.rapids.obs.flightRecorder.dir": flight_dir,
        })
        ladder_queries = build(ladder, tables)
        probe = wanted[0]
        # the WALK itself: sustained refusals drive retry -> chunk ->
        # cpu_demote; completion (not identity) is the contract here —
        # attempts mid-walk mix demotion states by design
        got = ladder_queries[probe]().collect_table()
        assert got is not None
        # the POST-WALK contract: with the demotions now in place and
        # the schedule spent, a clean re-run of the same query is
        # bitwise identical to a plain-session run through the same
        # demoted plan at the same chunk share
        FAULTS.disarm()
        got = ladder_queries[probe]().collect_table()
        with forced_chunking(chunk_share):
            redo = plain_queries[probe]().collect_table()
        ladder_snap = HEALTH.memory_snapshot()
        actions_taken = (ladder_snap["memoryPressureEvents"]
                         - ladder_before)
        bundles = load_bundles(flight_dir) if os.path.isdir(flight_dir) \
            and os.listdir(flight_dir) else []
        mem_bundles = [b for b in bundles
                       if b.get("kind") == "memory.ladder"]
        ladder_diff = tables_differ(redo, got)
        report["ladder_probe"] = {
            "query": probe,
            "identical": ladder_diff is None,
            "ladder": ladder_snap,
            "demoted_ops": CIRCUIT_BREAKER.demoted_ops(),
            "actions_taken": actions_taken,
            "memory_ladder_bundles": len(mem_bundles),
            "actions_seen": sorted({b.get("action")
                                    for b in mem_bundles}),
        }
        if ladder_diff is not None:
            failures.append(f"ladder probe {probe} diverged: "
                            f"{ladder_diff}")
        if ladder_snap["memoryChunkedReexecutions"] < 1:
            failures.append("ladder never reached the chunked "
                            "re-execution rung")
        if ladder_snap["memoryCpuDemotions"] < 1:
            failures.append("ladder never reached the per-op CPU "
                            "demotion rung")
        if len(mem_bundles) < actions_taken:
            failures.append(
                f"only {len(mem_bundles)} memory-ladder incident "
                f"bundles for {actions_taken} ladder actions")
        elif mem_bundles:
            rendered = render_incident(mem_bundles, last=1)
            for marker in ("trigger:", "ladder:"):
                if marker not in rendered:
                    failures.append(f"tools incident render missing "
                                    f"its {marker!r} section")
        # leave a clean process for the service phase: the ladder's
        # deliberate demotions are this probe's, not the service's
        FAULTS.disarm()
        CIRCUIT_BREAKER.reset()
        HEALTH.reset()
    report["incident_bundles_total"] = len(
        os.listdir(flight_dir)) if os.path.isdir(flight_dir) else 0
    report["flight_recorder_dir"] = flight_dir

    # -- service closure: budgeted serving ends HEALTHY ----------------------
    from spark_rapids_tpu.service.scheduler import QueryService
    svc = QueryService({
        "spark.rapids.memory.device.budgetBytes": str(int(budget)),
        "spark.rapids.memory.device.scanChunkFraction":
            str(chunk_fraction),
        "spark.rapids.service.maxConcurrentQueries": "2",
        "spark.rapids.lint.lockWitness": "true",
    })
    try:
        svc_probe = wanted[0]
        svc_queries = (build_sql_queries if use_sql
                       else build_queries)(svc.session, tables)
        # the corpus closures return DataFrames when called; submit
        # the plan through the service and compare to the baseline
        handle = svc.submit(svc_queries[svc_probe]())
        out = handle.result(timeout=120)
        health = svc.health()
        report["service"] = {
            "state": health["state"],
            "memory": health["memory"],
        }
        if health["state"] != "HEALTHY":
            failures.append(
                f"service ended {health['state']}, not HEALTHY")
        if "memory" not in health:
            failures.append("health() lacks the memory surface")
        # the service session runs the same budget -> same chunk share
        # -> the same-shape baseline applies bitwise here too
        diff = tables_differ(expected_chunked[svc_probe], out)
        if diff is not None:
            failures.append(f"service probe {svc_probe} diverged: "
                            f"{diff}")
    finally:
        svc.shutdown()

    report["demoted_ops"] = CIRCUIT_BREAKER.demoted_ops()
    report["health_state"] = HEALTH.state()
    _record_lock_witness(report, failures)
    report["ok"] = not failures
    report["failures"] = failures
    FAULTS.disarm()
    if failures:
        err = AssertionError("memory chaos run failed:\n"
                             + "\n".join(failures))
        err.report = report
        raise err
    return report


def mesh_chaos_fault_spec(seed: int) -> str:
    """The seeded mesh-fault schedule: every ``mesh.*`` point fires at
    least once (asserted by run_mesh_chaos), exercising all four
    recovery mechanisms — query replay (crash), checksum-validated
    refetch (corrupt at the ICI counts fetch and at the re-land
    gather), the partial-loss degradation ladder down to a mesh shrink
    (device_lost x4: retry -> single-device -> shrink -> retry), and
    plain slowness. COUNT-based entries only, so the seeded schedule is
    deterministic and the end-of-run restore probe runs fault-free."""
    return ";".join([
        f"mesh.shard.put:crash:1:{seed * 10 + 1}",
        f"mesh.shard.put:slow:2:{seed * 10 + 2}",
        f"mesh.ici.exchange:corrupt:2:{seed * 10 + 3}",
        f"mesh.ici.exchange:crash:1:{seed * 10 + 4}",
        f"mesh.gather:corrupt:2:{seed * 10 + 5}",
        f"mesh.gather:device_lost:4:{seed * 10 + 6}",
        f"mesh.dict.upload:slow:1:{seed * 10 + 7}",
    ])


#: what a DOUBLE sum merged from a mesh's shard partials may differ by
#: from one chip's (a share of the larger magnitude): the limit the
#: benchmark holds the same sums to against its float64 reference
#: (benchmarks/limits/, PERF.md section 2)
MESH_DOUBLE_LIMIT = 2e-7

#: mesh-scope counters of the aggregate on the resident shards
_MESH_AGG_COUNTERS = ("meshAggBatches", "meshAggShards")


def mesh_contract_differ(expected, got, shard_aggregated: bool):
    """The mesh contract (execs/mesh.py) between one chip's answer and
    the mesh's; None when it holds. A query none of whose aggregates ran
    on the resident shards (``meshAggBatches`` did not move) is
    bit-identical. One that did keeps integers, counts, decimals,
    strings, dates, min/max, validity, the number of rows and their
    order bit-identical, and its DOUBLE columns (the sums merged from
    shard partials, and what the plan computes from them) within
    MESH_DOUBLE_LIMIT."""
    return tables_differ(expected, got,
                         MESH_DOUBLE_LIMIT if shard_aggregated else None)


#: whole-run recovery-work ceilings for the mesh chaos closure (a
#: runaway retry loop must fail the run, not grind through it)
MESH_CHAOS_BOUNDS = {"query_replays": 30, "shardRetries": 40,
                     "gatherChecksFailed": 40, "fetch_retries": 100}


def run_mesh_chaos(sf: float, seed: int, ndev: int, queries=None,
                   use_sql: bool = False, shape: str = ""):
    """``--mesh N --chaos``: q1-q22 MESH-NATIVE under the seeded
    mesh-fault schedule, asserting every query within the mesh contract
    of the fault-free single-chip baseline (mesh_contract_differ:
    bit-identical, but for the DOUBLE columns of a query whose
    aggregate ran on the resident shards), every ``mesh.*`` fault point fired
    at least once, recovery counters within MESH_CHAOS_BOUNDS, and the
    mesh back at full strength at the end (a degraded end state is
    tolerated only EXPLAINED — shrink reason + excluded devices in the
    report). This is the MULTICHIP_r07 acceptance harness: the newest,
    most distributed layer of the engine under the same chaos contract
    the host shuffle has carried since PR 3."""
    _ensure_host_mesh(ndev)
    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    from spark_rapids_tpu.runtime.faults import (
        CIRCUIT_BREAKER,
        FAULTS,
        RECOVERY,
    )
    from spark_rapids_tpu.runtime.health import HEALTH, QUARANTINE
    from spark_rapids_tpu.parallel.mesh import MESH
    from spark_rapids_tpu.session import TpuSession

    specs = scale_test_specs(sf)
    tables = {name: spec.generate_table(sf, seed=seed)
              for name, spec in specs.items()}
    build = build_sql_queries if use_sql else build_queries

    spec = mesh_chaos_fault_spec(seed)
    # flight-recorder closure (ISSUE 14): every injected mesh ladder
    # action must dump an incident bundle into this run's fresh dir
    import os
    import tempfile
    flight_dir = tempfile.mkdtemp(prefix="rapids_mesh_flightrec_")
    chip = TpuSession()
    mesh = TpuSession({
        "spark.rapids.mesh.enabled": "true",
        "spark.rapids.mesh.shape": shape or str(ndev),
        "spark.rapids.sql.runtimeFallback.enabled": "true",
        "spark.rapids.lint.lockWitness": "true",
        "spark.rapids.test.faults": spec,
        "spark.rapids.obs.telemetry.enabled": "true",
        "spark.rapids.obs.telemetry.intervalMs": "200",
        "spark.rapids.obs.flightRecorder.dir": flight_dir,
    })
    chip_queries = build(chip, tables)
    mesh_queries = build(mesh, tables)
    wanted = queries or list(chip_queries)
    # the collective-bearing query (q7, the corpus's one explicit
    # repartition) runs FIRST: the seeded ladder may legitimately
    # shrink the mesh mid-corpus, and a shrunken mesh demotes the
    # 8-way exchange to the host shuffle — the ICI fault points must
    # see traffic before that can happen or the closure assertion
    # below ("every armed point fired") could never pass
    wanted = sorted(wanted, key=lambda n: (n != "q7", wanted.index(n)))

    report = {"mode": "mesh-chaos", "n_devices": ndev,
              "backend": _resolved_backend(),
              "mesh_shape": shape or str(ndev), "scale_factor": sf,
              "seed": seed, "sql": use_sql,
              "fault_spec": mesh.conf.to_dict()[
                  "spark.rapids.test.faults"],
              "queries": {}}
    failures = []
    # ALL fault-free baselines first: interleaving the baseline
    # session's arm("") with the chaotic arm(spec) would reset the
    # seeded schedule every query (run_chaos's discipline)
    expected_tables = {name: chip_queries[name]().collect_table()
                       for name in wanted}

    def _scopes():
        snap = scopes_snapshot()
        return dict(snap.get("mesh", {})), dict(snap.get("health", {}))

    recovery_before = RECOVERY.snapshot()
    mesh_before_all, health_before_all = _scopes()
    #: on_mesh_device_loss invocations == mesh ladder actions (each
    #: bumps the cumulative count) — the incident-bundle floor
    mesh_ladder_before = HEALTH.mesh_snapshot()["meshDeviceLost"]
    for name in wanted:
        before_m, before_h = _scopes()
        fires_before = FAULTS.counters()
        t0 = time.perf_counter()
        got = mesh_queries[name]().collect_table()
        wall = time.perf_counter() - t0
        after_m, after_h = _scopes()
        # (an attempt that aggregated on the shards and was then
        # replayed on one device counts too: the limit contains
        # bit-identity)
        shard_aggregated = (after_m.get("meshAggBatches", 0)
                            > before_m.get("meshAggBatches", 0))
        diff = mesh_contract_differ(expected_tables[name], got,
                                    shard_aggregated)
        recollected = False
        if diff is not None and (CIRCUIT_BREAKER.demoted_ops()
                                 or HEALTH.state() != "HEALTHY"):
            # an active demotion or the CPU-only latch changes float
            # accumulation order vs the pre-demotion baseline; both are
            # process-wide, so re-collecting the baseline NOW runs it
            # through the same demoted/latched plan (run_chaos pattern;
            # suspended() keeps the seeded schedule from resetting)
            with FAULTS.suspended():
                redo = chip_queries[name]().collect_table()
            diff = mesh_contract_differ(redo, got, shard_aggregated)
            recollected = True
        entry = {
            "chaos_s": round(wall, 4),
            "within_contract": diff is None,
            "shard_aggregated": shard_aggregated,
            "mesh": {k: int(after_m.get(k, 0) - before_m.get(k, 0))
                     for k in ("shardsDispatched", "iciExchanges",
                               "hostShuffleFallbacks", "shardRetries",
                               "gatherChecksFailed", "meshRelandRows")
                     + _MESH_AGG_COUNTERS
                     if after_m.get(k, 0) != before_m.get(k, 0)},
            "ladder": {k: int(after_h.get(k, 0) - before_h.get(k, 0))
                       for k in ("meshDeviceLost", "meshDegradations",
                                 "meshShrinks", "deviceReinits")
                       if after_h.get(k, 0) != before_h.get(k, 0)},
            "fault_fires": {
                k: v - fires_before.get(k, 0)
                for k, v in FAULTS.counters().items()
                if v - fires_before.get(k, 0)},
            "mesh_shape_now": MESH.shape_str(),
        }
        if recollected:
            entry["compared_vs_demoted_baseline"] = True
        if diff is not None:
            failures.append(f"{name}: {diff}")
        report["queries"][name] = entry
        print(json.dumps({"query": name, **entry}))

    # -- closure assertions ---------------------------------------------------
    fires = FAULTS.counters()
    armed_points = {e.split(":")[0] for e in spec.split(";")}
    for point in sorted(armed_points):
        if not fires.get(point):
            failures.append(
                f"armed mesh fault point {point} never fired — the "
                f"schedule does not cover the distributed path")
    report["fault_fires_total"] = dict(fires)
    recovery = {k: v - recovery_before[k]
                for k, v in RECOVERY.snapshot().items()}
    mesh_after_all, health_after_all = _scopes()
    recovery["shardRetries"] = int(
        mesh_after_all.get("shardRetries", 0)
        - mesh_before_all.get("shardRetries", 0))
    recovery["gatherChecksFailed"] = int(
        mesh_after_all.get("gatherChecksFailed", 0)
        - mesh_before_all.get("gatherChecksFailed", 0))
    report["recovery"] = recovery
    for field, bound in MESH_CHAOS_BOUNDS.items():
        if recovery.get(field, 0) > bound:
            failures.append(f"{field}={recovery[field]} exceeds the "
                            f"mesh chaos bound {bound}")
    report["ladder"] = HEALTH.mesh_snapshot()
    report["quarantine"] = QUARANTINE.snapshot()

    # -- end state: full strength, or an explained degraded state ------------
    end_state = MESH.health_snapshot()
    report["mesh_end_state"] = end_state
    if end_state["excludedDeviceIds"]:
        # the schedule is count-based and spent: restoring and probing
        # must succeed — a mesh that cannot return to full strength
        # after the faults stopped would be a real (reported) problem
        MESH.restore("mesh chaos run complete; probing full strength")
        probe = wanted[0]
        with FAULTS.suspended():
            redo = chip_queries[probe]().collect_table()
        before_m, _ = _scopes()
        got = mesh_queries[probe]().collect_table()
        probe_diff = mesh_contract_differ(
            redo, got, _scopes()[0].get("meshAggBatches", 0)
            > before_m.get("meshAggBatches", 0))
        restored = MESH.health_snapshot()
        report["restore_probe"] = {
            "query": probe,
            "within_contract": probe_diff is None,
            "mesh": restored,
        }
        if probe_diff is not None:
            failures.append(f"restore probe {probe} diverged: {probe_diff}")
        if restored["excludedDeviceIds"]:
            failures.append(
                "mesh did not return to full strength after restore: "
                f"{restored}")
    # -- flight-recorder closure (ISSUE 14) ----------------------------------
    from spark_rapids_tpu.tools.incident import (
        load_bundles,
        render_incident,
    )
    ladder_actions = (HEALTH.mesh_snapshot()["meshDeviceLost"]
                      - mesh_ladder_before)
    bundles = load_bundles(flight_dir) if os.path.isdir(flight_dir) \
        and os.listdir(flight_dir) else []
    mesh_bundles = [b for b in bundles if b.get("kind") == "mesh.ladder"]
    report["incident_bundles"] = len(bundles)
    report["mesh_ladder_bundles"] = len(mesh_bundles)
    report["mesh_ladder_actions"] = ladder_actions
    report["flight_recorder_dir"] = flight_dir
    if len(mesh_bundles) < ladder_actions:
        failures.append(
            f"only {len(mesh_bundles)} mesh-ladder incident bundles "
            f"for {ladder_actions} injected ladder actions")
    elif mesh_bundles:
        rendered = render_incident(mesh_bundles, last=1)
        for marker in ("trigger:", "ladder:", "telemetry tail:"):
            if marker not in rendered:
                failures.append(f"tools incident render missing its "
                                f"{marker!r} section")
        report["incident_actions"] = sorted(
            {b.get("action") for b in mesh_bundles})

    report["demoted_ops"] = CIRCUIT_BREAKER.demoted_ops()
    report["health_state"] = HEALTH.state()
    _record_lock_witness(report, failures)
    report["ok"] = not failures
    report["failures"] = failures
    FAULTS.disarm()
    if failures:
        err = AssertionError("mesh chaos run failed:\n"
                             + "\n".join(failures))
        err.report = report
        raise err
    return report


# ---------------------------------------------------------------------------
# Mesh mode: the corpus executed mesh-native, held to the mesh contract
# ---------------------------------------------------------------------------


def _ensure_host_mesh(n: int) -> None:
    """Force an n-device CPU test mesh BEFORE the JAX backend
    initializes (shared with the dryrun_multichip entry). Real chips
    are not reached from here: chip_smoke.py drives them through the
    session's mesh conf."""
    from spark_rapids_tpu.parallel.mesh import ensure_cpu_test_mesh
    have = ensure_cpu_test_mesh(n)
    if have < n:
        raise SystemExit(
            f"--mesh {n} needs {n} devices but only {have} are available "
            "(the JAX backend initialized before the host device-count "
            "flag could take effect)")


def run_mesh(sf: float, seed: int, ndev: int, queries=None,
             use_sql: bool = False, shape: str = ""):
    """Mesh-native corpus run: q1-q22 single-chip for the baseline, the
    SAME corpus with ``spark.rapids.mesh.enabled`` over an ndev-device
    mesh, asserting THE MESH CONTRACT per query (mesh_contract_differ:
    bit-identity, but for the DOUBLE columns of a query whose aggregate
    ran on the resident shards, which hold MESH_DOUBLE_LIMIT and give
    the same bits on a second mesh run) and reporting per-exchange
    ICI accounting (collective count, payload bytes, host-shuffle
    fallbacks with reasons, re-land rows, shard-aggregated batches)
    from the mesh metric scope and the per-exchange metrics. Raises
    AssertionError on any divergence: this is the corpus-wide check of
    the contract that tests/test_mesh.py pins on a slice."""
    _ensure_host_mesh(ndev)
    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.obs.events import collect_exchanges
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    from spark_rapids_tpu.session import TpuSession

    specs = scale_test_specs(sf)
    tables = {name: spec.generate_table(sf, seed=seed)
              for name, spec in specs.items()}
    build = build_sql_queries if use_sql else build_queries

    chip = TpuSession()
    mesh = TpuSession({
        "spark.rapids.mesh.enabled": "true",
        "spark.rapids.mesh.shape": shape or str(ndev),
    })
    chip_queries = build(chip, tables)
    mesh_queries = build(mesh, tables)
    wanted = queries or list(chip_queries)

    report = {"mode": "mesh", "n_devices": ndev,
              "backend": _resolved_backend(),
              "mesh_shape": shape or str(ndev), "scale_factor": sf,
              "seed": seed, "sql": use_sql, "queries": {}}
    failures = []
    for name in wanted:
        expected = chip_queries[name]().collect_table()
        before = dict(scopes_snapshot().get("mesh", {}))
        t0 = time.perf_counter()
        got = mesh_queries[name]().collect_table()
        wall = time.perf_counter() - t0
        after = dict(scopes_snapshot().get("mesh", {}))
        delta = {k: int(after.get(k, 0) - before.get(k, 0))
                 for k in ("shardsDispatched", "iciExchanges", "iciBytes",
                           "hostShuffleFallbacks", "meshHostUploads",
                           "meshRelandRows", "meshDictInterns",
                           "meshGatherRows") + _MESH_AGG_COUNTERS}
        shard_aggregated = delta["meshAggBatches"] > 0
        diff = mesh_contract_differ(expected, got, shard_aggregated)
        if diff is None and shard_aggregated:
            # a sum merged in (batch, shard, slice) order: the same
            # mesh gives the same bits again
            again = tables_differ(got, mesh_queries[name]().collect_table())
            diff = again and f"a second mesh run gave other bits: {again}"
        exchanges = []
        for e in collect_exchanges(mesh._last_executable):
            exchanges.append({k: e[k] for k in
                              ("op", "loreId", "iciPartitions", "iciBytes",
                               "iciExchangeTime", "hostShuffleFallbacks",
                               "mapOutputBytesMax", "mapOutputBytesMedian",
                               "skewedPartitions")
                              if k in e})
        entry = {"identical": tables_differ(expected, got) is None,
                 "within_contract": diff is None,
                 "shard_aggregated": shard_aggregated,
                 "mesh_wall_s": round(wall, 4),
                 "mesh": delta, "exchanges": exchanges}
        if diff is not None:
            failures.append(f"{name}: {diff}")
        report["queries"][name] = entry
        print(json.dumps({"query": name, **entry}))
    report["totals"] = {
        k: sum(q["mesh"][k] for q in report["queries"].values())
        for k in ("iciExchanges", "iciBytes", "hostShuffleFallbacks",
                  "meshHostUploads", "shardsDispatched")
        + _MESH_AGG_COUNTERS}
    report["double_limit"] = MESH_DOUBLE_LIMIT
    report["shard_aggregated"] = [
        n for n, q in report["queries"].items() if q["shard_aggregated"]]
    report["bits_changed"] = [
        n for n, q in report["queries"].items() if not q["identical"]]
    report["ok"] = not failures
    report["failures"] = failures
    if failures:
        # the report IS the diagnostic (per-query identical flags, mesh
        # deltas, exchange accounting) — carry it on the error so the
        # CLI can still write --out before exiting non-zero
        err = AssertionError("mesh run broke the mesh contract against "
                             "single-chip:\n"
                             + "\n".join(failures))
        err.report = report
        raise err
    return report


# ---------------------------------------------------------------------------
# Multi-host mode: the corpus over the driver/executor protocol,
# bit-identical to single-process (runtime/cluster.py)
# ---------------------------------------------------------------------------


def write_host_corpus(tables, base_dir, files_per_table: int) -> dict:
    """Write each generated table as ``files_per_table`` parquet files
    (contiguous row slices, one file per chunk subdir so the sorted
    file walk preserves row order) — the source-file layout the
    by-host scan partitioner distributes. Returns name -> table dir."""
    import os

    from spark_rapids_tpu.io.parquet import write_parquet
    paths = {}
    for name, table in tables.items():
        tdir = os.path.join(base_dir, name)
        n = table.num_rows
        chunk = max(1, (n + files_per_table - 1) // files_per_table)
        start = i = 0
        while start < n:
            write_parquet(table.slice(start, min(chunk, n - start)),
                          os.path.join(tdir, f"c{i:03d}"))
            start += chunk
            i += 1
        paths[name] = tdir
    return paths


def host_chaos_fault_spec(seed: int) -> str:
    """The seeded HOST-fault schedule: every ``host.*`` point fires at
    least once (asserted by run_hosts), exercising the full ladder
    surface — dispatch crash (query replay), corrupt shard landings
    (CRC-caught re-lands), injected host losses walking retry ->
    re-land-on-survivors, DCN-exchange faults, and dropped executor
    heartbeats. COUNT-based entries only, so the schedule is
    deterministic and the end-of-run restore probe runs fault-free.
    The scripted mid-corpus host KILL (a real SIGKILL of an executor
    process) rides on top of this schedule."""
    return ";".join([
        # raising kinds get their own points: co-located raising
        # entries mask each other (the first raise wins the call and
        # the other's schedule is consumed), so corrupt lives ALONE on
        # the landing point — its CRC-retry path must actually run
        f"host.dispatch:crash:1:{seed * 10 + 1}",
        f"host.dispatch:device_lost:3:{seed * 10 + 2}",
        f"host.shard.land:corrupt:2:{seed * 10 + 3}",
        f"host.dcn.exchange:slow:1:{seed * 10 + 4}",
        f"host.dcn.exchange:crash:1:{seed * 10 + 5}",
        f"host.heartbeat:crash:2:{seed * 10 + 6}",
    ])


#: whole-run recovery-work ceilings for the host chaos closure
HOST_CHAOS_BOUNDS = {"query_replays": 30, "hostShardRetries": 20,
                     "hostsLost": 10, "fetch_retries": 100}

#: harness heartbeat settings: a VERY generous missed-beat budget —
#: the driver shares its process with jax compilation, which can hold
#: the GIL for whole seconds at a time, and a spurious eviction would
#: walk the ladder for no reason. A real SIGKILL is still detected
#: promptly through the beat-connection EOF path, not this window.
_HOSTS_HEARTBEAT_MS = 250
_HOSTS_MISSED_BEATS = 120


def _boot_cluster(nhosts: int):
    """Driver + N subprocess executors, registered and attached."""
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.runtime.cluster import (
        CLUSTER,
        ClusterDriver,
        spawn_executor,
    )
    driver = ClusterDriver(nhosts, RapidsConf({
        "spark.rapids.cluster.heartbeatIntervalMs":
            str(_HOSTS_HEARTBEAT_MS),
        "spark.rapids.cluster.missedBeats": str(_HOSTS_MISSED_BEATS),
    }))
    executors = {
        f"h{i}": spawn_executor(driver.address, f"h{i}",
                                heartbeat_ms=_HOSTS_HEARTBEAT_MS,
                                mode="process")
        for i in range(nhosts)}
    driver.wait_ready(nhosts, timeout_s=120.0)
    CLUSTER.attach_driver(driver)
    return driver, executors


def _teardown_cluster(driver, executors) -> None:
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    CLUSTER.attach_driver(None)
    driver.shutdown()
    for h in executors.values():
        try:
            h.terminate()
        except Exception:
            pass


def _wait_for(predicate, timeout_s: float) -> bool:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(0.1)
    return predicate()


def run_hosts(sf: float, seed: int, nhosts: int, queries=None,
              use_sql: bool = False, chaos: bool = False):
    """``--hosts N [--chaos]``: q1-q22 through the multi-process
    simulation harness — N REAL executor subprocesses scanning their
    by-host file assignments and shipping shards back over the
    driver/executor socket protocol, the corpus running mesh-native on
    the hierarchical (hosts x devices-per-host) mesh so all-to-alls
    physically model ICI-within-a-host / DCN-across. Asserts every
    query bit-identical to a fault-free single-process run over the
    SAME files.

    With ``chaos``, the corpus additionally runs under the seeded
    ``host.*`` fault schedule PLUS a scripted mid-corpus host KILL
    (SIGKILL of one executor): the missed-beat sweep must declare the
    host lost, scans must re-land its shards onto survivors, the
    respawned executor must REJOIN through the heartbeat re-register
    path, and the end-of-run restore probe must return the topology to
    full strength — the MULTIHOST_r01 acceptance harness."""
    _ensure_host_mesh(8)
    import os
    import tempfile

    import jax

    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    from spark_rapids_tpu.runtime.cluster import CLUSTER
    from spark_rapids_tpu.runtime.faults import (
        CIRCUIT_BREAKER,
        FAULTS,
        RECOVERY,
    )
    from spark_rapids_tpu.runtime.health import HEALTH
    from spark_rapids_tpu.session import TpuSession

    ndev = len(jax.devices())
    if ndev % nhosts:
        raise SystemExit(
            f"--hosts {nhosts} must divide the {ndev}-device pool so "
            f"every host owns an equal dcn row")
    shape = f"{nhosts}x{ndev // nhosts}"

    specs = scale_test_specs(sf)
    tables = {name: spec.generate_table(sf, seed=seed)
              for name, spec in specs.items()}
    base = tempfile.mkdtemp(prefix="rapids_hosts_")
    paths = write_host_corpus(tables, base, files_per_table=2 * nhosts)

    spec = host_chaos_fault_spec(seed) if chaos else ""
    driver, executors = _boot_cluster(nhosts)
    # the observability closure (ISSUE 14): the cluster session runs
    # with event log + host tracing + the telemetry sampler on, and
    # the flight recorder pointed at a fresh dir — the run then
    # asserts executor-host spans per routed scan, the tools-profile
    # per-host breakdown over the 95% coverage floor, and (chaos) one
    # incident bundle per injected host ladder action
    obs_dir = tempfile.mkdtemp(prefix="rapids_hosts_obs_")
    eventlog_dir = os.path.join(obs_dir, "eventlog")
    trace_dir = os.path.join(obs_dir, "trace")
    flight_dir = os.path.join(obs_dir, "flightrec")
    report = {"mode": "hosts-chaos" if chaos else "hosts",
              "hosts": nhosts, "n_devices": ndev, "mesh_shape": shape,
              "backend": _resolved_backend(), "scale_factor": sf,
              "seed": seed, "sql": use_sql, "corpus_dir": base,
              "files_per_table": 2 * nhosts,
              "observability": {"eventlog_dir": eventlog_dir,
                                "trace_dir": trace_dir,
                                "flight_recorder_dir": flight_dir},
              "queries": {}}
    failures = []
    try:
        single = TpuSession()
        conf = {
            "spark.rapids.cluster.enabled": "true",
            "spark.rapids.cluster.hosts": str(nhosts),
            "spark.rapids.cluster.heartbeatIntervalMs":
                str(_HOSTS_HEARTBEAT_MS),
            "spark.rapids.cluster.missedBeats":
                str(_HOSTS_MISSED_BEATS),
            "spark.rapids.mesh.enabled": "true",
            "spark.rapids.mesh.shape": shape,
            "spark.rapids.sql.runtimeFallback.enabled": "true",
            "spark.rapids.sql.eventLog.enabled": "true",
            "spark.rapids.sql.eventLog.dir": eventlog_dir,
            "spark.rapids.trace.enabled": "true",
            "spark.rapids.trace.dir": trace_dir,
            "spark.rapids.obs.telemetry.enabled": "true",
            "spark.rapids.obs.telemetry.intervalMs": "200",
            "spark.rapids.obs.flightRecorder.dir": flight_dir,
        }
        if spec:
            conf["spark.rapids.test.faults"] = spec
            conf["spark.rapids.lint.lockWitness"] = "true"
            report["fault_spec"] = spec
        clus = TpuSession(conf)
        build = build_sql_queries if use_sql else build_queries
        single_queries = build(single, tables, paths=paths)
        clus_queries = build(clus, tables, paths=paths)
        wanted = queries or list(single_queries)
        # the collective-bearing query runs FIRST (run_mesh_chaos's
        # discipline): the dcn-exchange fault points must see traffic
        # before the ladder may legitimately degrade the topology
        wanted = sorted(wanted, key=lambda n: (n != "q7",
                                               wanted.index(n)))
        # ALL fault-free baselines first: the seeded schedule must
        # advance uninterrupted across the chaotic corpus
        expected_tables = {name: single_queries[name]().collect_table()
                           for name in wanted}

        recovery_before = RECOVERY.snapshot()
        cluster_before_all = dict(
            scopes_snapshot().get("cluster", {}))
        #: on_host_loss invocations == host ladder actions (each bumps
        #: the cumulative loss count) — the incident-bundle floor
        host_ladder_before = HEALTH.host_snapshot()["hostsLost"]
        # the kill lands mid-corpus and the rejoin ALWAYS fits before
        # the last query — a --queries subset too short for the script
        # must not leave the victim dead into the closure assertions
        kill_at = min(len(wanted) // 2,
                      len(wanted) - 2) if chaos else None
        rejoin_at = (min(kill_at + 2, len(wanted) - 1)
                     if chaos and kill_at is not None and kill_at >= 0
                     else None)
        if chaos and (kill_at is None or kill_at < 0
                      or rejoin_at <= kill_at):
            kill_at = rejoin_at = None  # corpus too short to script
        victim = f"h{nhosts - 1}"
        kill_info = {}
        for qi, name in enumerate(wanted):
            if chaos and qi == kill_at:
                # scripted mid-corpus HOST KILL: a real SIGKILL; the
                # missed-beat sweep must declare the host lost
                t0 = time.time()
                executors[victim].terminate()
                detected = _wait_for(
                    lambda: victim in CLUSTER.health_snapshot()[
                        "lostHosts"]
                    or victim in CLUSTER.health_snapshot()[
                        "excludedHosts"],
                    timeout_s=30.0)
                kill_info = {"host": victim, "atQuery": name,
                             "detected": detected,
                             "detectS": round(time.time() - t0, 3)}
                if not detected:
                    failures.append(
                        f"killed host {victim} never declared lost by "
                        f"the heartbeat sweep")
            if chaos and qi == rejoin_at:
                # respawn: the fresh registration is the rejoin path
                t0 = time.time()
                from spark_rapids_tpu.runtime.cluster import (
                    spawn_executor,
                )
                executors[victim] = spawn_executor(
                    driver.address, victim,
                    heartbeat_ms=_HOSTS_HEARTBEAT_MS, mode="process")
                rejoined = _wait_for(
                    lambda: victim not in CLUSTER.health_snapshot()[
                        "lostHosts"]
                    and victim not in CLUSTER.health_snapshot()[
                        "excludedHosts"],
                    timeout_s=60.0)
                kill_info["rejoined"] = rejoined
                kill_info["rejoinS"] = round(time.time() - t0, 3)
                if not rejoined:
                    failures.append(
                        f"respawned host {victim} never rejoined the "
                        f"topology")
            before_c = dict(scopes_snapshot().get("cluster", {}))
            before_h = HEALTH.host_snapshot()
            fires_before = FAULTS.counters()
            t0 = time.perf_counter()
            got = clus_queries[name]().collect_table()
            wall = time.perf_counter() - t0
            after_c = dict(scopes_snapshot().get("cluster", {}))
            after_h = HEALTH.host_snapshot()
            diff = tables_differ(expected_tables[name], got)
            recollected = False
            if diff is not None and (CIRCUIT_BREAKER.demoted_ops()
                                     or HEALTH.state() != "HEALTHY"):
                with FAULTS.suspended():
                    redo = single_queries[name]().collect_table()
                diff = tables_differ(redo, got)
                recollected = True
            rec = clus.last_event_record or {}
            entry = {
                "chaos_s" if chaos else "wall_s": round(wall, 4),
                "identical": diff is None,
                "cluster": {k: int(after_c.get(k, 0)
                                   - before_c.get(k, 0))
                            for k in ("hostShardsLanded", "hostsLost",
                                      "hostRelands", "hostShrinks",
                                      "hostRestores", "dcnExchanges",
                                      "hostShardRetries",
                                      "executorBeatsDropped",
                                      "clusterScanFallbacks")
                            if after_c.get(k, 0) != before_c.get(k, 0)},
                "ladder": {k: int(after_h[k] - before_h[k])
                           for k in after_h
                           if after_h[k] != before_h[k]},
                "host_topology": CLUSTER.topology_str(),
                "query_index": rec.get("queryIndex"),
                "host_scans": sorted(rec.get("hostScans") or {}),
            }
            if chaos:
                entry["fault_fires"] = {
                    k: v - fires_before.get(k, 0)
                    for k, v in FAULTS.counters().items()
                    if v - fires_before.get(k, 0)}
            if recollected:
                entry["compared_vs_demoted_baseline"] = True
            if diff is not None:
                failures.append(f"{name}: {diff}")
            report["queries"][name] = entry
            print(json.dumps({"query": name, **entry}))
        if chaos:
            report["kill"] = kill_info

        # -- closure assertions ----------------------------------------------
        fires = FAULTS.counters()
        if chaos:
            armed_points = {e.split(":")[0] for e in spec.split(";")}
            for point in sorted(armed_points):
                if not fires.get(point):
                    failures.append(
                        f"armed host fault point {point} never fired — "
                        f"the schedule does not cover the multi-host "
                        f"path")
            report["fault_fires_total"] = dict(fires)
        recovery = {k: v - recovery_before[k]
                    for k, v in RECOVERY.snapshot().items()}
        cluster_after_all = dict(scopes_snapshot().get("cluster", {}))
        for k in ("hostShardRetries", "hostsLost"):
            recovery[k] = int(cluster_after_all.get(k, 0)
                              - cluster_before_all.get(k, 0))
        report["recovery"] = recovery
        if chaos:
            for field, bound in HOST_CHAOS_BOUNDS.items():
                if recovery.get(field, 0) > bound:
                    failures.append(
                        f"{field}={recovery[field]} exceeds the host "
                        f"chaos bound {bound}")
        report["cluster_totals"] = {
            k: int(cluster_after_all.get(k, 0)
                   - cluster_before_all.get(k, 0))
            for k in sorted(cluster_after_all)}
        report["ladder"] = HEALTH.host_snapshot()

        # -- end state: full strength, or restore and prove it ---------------
        end_state = CLUSTER.health_snapshot()
        report["hosts_end_state"] = end_state
        if (end_state["lostHosts"] or end_state["excludedHosts"]
                or end_state["singleProcessReason"]):
            # the count-based schedule is spent: restore and probe —
            # a topology that cannot return to full strength after the
            # faults stopped is a real (reported) problem
            CLUSTER.restore()
            probe = wanted[0]
            with FAULTS.suspended():
                redo = single_queries[probe]().collect_table()
                got = clus_queries[probe]().collect_table()
            restored = CLUSTER.health_snapshot()
            report["restore_probe"] = {
                "query": probe,
                "identical": tables_differ(redo, got) is None,
                "hosts": restored,
            }
            if tables_differ(redo, got) is not None:
                failures.append(f"restore probe {probe} diverged")
            if (restored["lostHosts"] or restored["excludedHosts"]
                    or restored["singleProcessReason"]):
                failures.append(
                    "cluster did not return to full strength after "
                    f"restore: {restored}")
        # -- observability closure (ISSUE 14) --------------------------------
        # (a) the merged Chrome trace carries executor-host spans for
        # every cluster-routed scan: the driver's per-host cluster.scan
        # span AND the executor's own spans merged onto an
        # executor-<host> lane
        for name, entry in report["queries"].items():
            landed = entry["cluster"].get("hostShardsLanded", 0)
            qi = entry.get("query_index")
            if not landed or qi is None:
                continue
            tpath = os.path.join(trace_dir, f"query_{qi}.trace.json")
            if not os.path.exists(tpath):
                failures.append(f"{name}: cluster-routed scan has no "
                                f"Chrome trace at {tpath}")
                continue
            with open(tpath) as f:
                events = json.load(f)["traceEvents"]
            cluster_spans = [e for e in events
                             if e.get("name") == "cluster.scan"]
            exec_lanes = sorted(
                {str((e.get("args") or {}).get("name", ""))
                 for e in events if e.get("ph") == "M"
                 and str((e.get("args") or {}).get("name", ""))
                 .startswith("executor-")})
            exec_spans = [e for e in events
                          if e.get("cat") == "exec-scan"]
            if not cluster_spans:
                failures.append(f"{name}: no cluster.scan span in the "
                                f"merged trace")
            if not exec_lanes or not exec_spans:
                failures.append(f"{name}: no executor-host spans "
                                f"merged into the trace")
            entry["trace"] = {"clusterScanSpans": len(cluster_spans),
                              "executorLanes": exec_lanes,
                              "executorSpans": len(exec_spans)}

        # (b) tools profile over the run's event log: the per-host
        # breakdown exists and telemetry/trace overhead stays above
        # the existing 95% span-coverage floor
        from spark_rapids_tpu.tools.report import (
            build_profile,
            load_events,
        )
        profile = build_profile(load_events(eventlog_dir))
        report["profile"] = {
            "minCoverage": profile["minCoverage"],
            "queriesBelowCoverageFloor":
                profile["queriesBelowCoverageFloor"],
            "perHost": profile["hostResilience"]["perHost"],
        }
        if profile["queriesBelowCoverageFloor"]:
            failures.append(
                "span coverage fell below the 95% floor under "
                f"telemetry: {profile['queriesBelowCoverageFloor']}")
        if not profile["hostResilience"]["perHost"]:
            failures.append("tools profile has no per-host breakdown "
                            "(hostScans never recorded)")

        # (c) flight recorder: every injected host ladder action
        # produced an incident bundle, and tools incident renders them
        from spark_rapids_tpu.tools.incident import (
            load_bundles,
            render_incident,
        )
        ladder_actions = (HEALTH.host_snapshot()["hostsLost"]
                          - host_ladder_before)
        bundles = (load_bundles(flight_dir)
                   if os.path.isdir(flight_dir) else [])
        host_bundles = [b for b in bundles
                        if b.get("kind") == "host.ladder"]
        report["incident_bundles"] = len(bundles)
        report["host_ladder_bundles"] = len(host_bundles)
        report["host_ladder_actions"] = ladder_actions
        if chaos:
            if len(host_bundles) < ladder_actions:
                failures.append(
                    f"only {len(host_bundles)} host-ladder incident "
                    f"bundles for {ladder_actions} injected ladder "
                    f"actions")
            if host_bundles:
                rendered = render_incident(host_bundles, last=1)
                for marker in ("trigger:", "ladder:",
                               "telemetry tail:"):
                    if marker not in rendered:
                        failures.append(
                            f"tools incident render missing its "
                            f"{marker!r} section")
                report["incident_actions"] = sorted(
                    {b.get("action") for b in host_bundles})
            elif ladder_actions:
                failures.append("no host-ladder incident bundles were "
                                "recorded")

        report["demoted_ops"] = CIRCUIT_BREAKER.demoted_ops()
        report["health_state"] = HEALTH.state()
    finally:
        FAULTS.disarm()
        _teardown_cluster(driver, executors)
    _record_lock_witness(report, failures)
    report["ok"] = not failures
    report["failures"] = failures
    if failures:
        err = AssertionError("hosts run failed:\n" + "\n".join(failures))
        err.report = report
        raise err
    return report


# ---------------------------------------------------------------------------
# Fleet closure: composable chaos planes through the QueryService-as-
# cluster-driver — multi-host serving under combined fault domains
# ---------------------------------------------------------------------------


#: scheduling pools for the fleet run: two weights so DEGRADED-mode
#: shedding has a lowest-weight pool to push back on while the
#: interactive pool keeps serving (scheduler.py's shed contract)
FLEET_POOLS = "interactive:weight=2;batch:weight=1"

#: fault-POINT prefix -> fault domain, for the per-domain fleet
#: closure asserts. Distinct from obs.telemetry.fault_domain (which
#: classifies incident KINDS like "memory.ladder"): injection points
#: spell memory "mem.*", and the service plane's points spread over
#: the service./device./dispatch. prefixes (all -> "service").
_FLEET_POINT_DOMAINS = (("host.", "host"), ("mesh.", "mesh"),
                        ("mem.", "memory"), ("stream.", "stream"))


def _fleet_point_domain(point: str) -> str:
    for prefix, domain in _FLEET_POINT_DOMAINS:
        if point.startswith(prefix):
            return domain
    return "service"


def fleet_planes(seed: int) -> dict:
    """The composable chaos PLANES: each contributes fault points,
    recovery-work ceilings and the HEALTH ladder counter its injected
    losses bump, all merged into ONE seeded cross-domain schedule —
    planes COMPOSE instead of the older mutually-exclusive chaos
    modes. COUNT-based entries only (run_hosts's discipline): total
    disruption is deterministic regardless of corpus size, and the
    end-of-run restore probes run fault-free once the schedule is
    spent. Seed offsets are disjoint per plane so composing planes
    never aliases two RNG streams."""
    from spark_rapids_tpu.tools.loadtest import (
        SERVICE_CHAOS_BOUNDS,
        service_chaos_spec,
    )
    return {
        "host": {
            "spec": ";".join([
                f"host.dispatch:crash:1:{seed * 100 + 11}",
                f"host.shard.land:corrupt:1:{seed * 100 + 12}",
                f"host.dispatch:device_lost:2:{seed * 100 + 13}",
            ]),
            "bounds": {"query_replays": 30, "hostShardRetries": 20,
                       "hostsLost": 10, "fetch_retries": 100},
            "ladder_counter": "hostsLost",
            "description": "executor-host faults: dispatch crash "
                           "(query replay), corrupt shard landing "
                           "(CRC re-land), injected host losses "
                           "walking the host ladder; the scripted "
                           "SIGKILL + rejoin rides on top",
        },
        "mesh": {
            "spec": ";".join([
                f"mesh.gather:corrupt:1:{seed * 100 + 21}",
                f"mesh.gather:device_lost:2:{seed * 100 + 22}",
            ]),
            "bounds": {"query_replays": 30, "shardRetries": 40,
                       "gatherChecksFailed": 40, "fetch_retries": 100},
            "ladder_counter": "meshDeviceLost",
            "description": "mesh-device faults: checksummed-gather "
                           "corruption (re-fetch) and partial device "
                           "losses walking the mesh ladder",
        },
        "memory": {
            "spec": ";".join([
                f"mem.reserve:oom:12:{seed * 100 + 31}",
                f"mem.spill:crash:1:{seed * 100 + 32}",
            ]),
            "bounds": {"query_replays": 30, "oomRetries": 4000,
                       "splitRetries": 200, "budgetRaises": 2000},
            "ladder_counter": "memoryPressureEvents",
            "description": "arbiter pressure under the hard device "
                           "budget: sustained reservation refusals "
                           "(retry -> chunk -> cpu_demote) and a "
                           "spill-path crash",
        },
        "service": {
            "spec": service_chaos_spec(seed),
            "bounds": dict(SERVICE_CHAOS_BOUNDS),
            "ladder_counter": "deviceLost",
            "description": "service-level survivability: worker "
                           "crashes, device losses (backend ladder), "
                           "one wedged dispatch the watchdog must "
                           "hard-time-out",
        },
        "exec": {
            "spec": f"exec.execute:crash:1:{seed * 100 + 41}",
            "bounds": {"query_replays": 30},
            "ladder_counter": None,
            "description": "the seeded kernel/exec schedule: one "
                           "executor crash absorbed by query replay",
        },
    }


def fleet_fault_spec(seed: int) -> str:
    """The merged cross-domain schedule: every plane's points in one
    ``spark.rapids.test.faults`` string."""
    return ";".join(p["spec"] for p in fleet_planes(seed).values())


def fleet_bounds(planes: dict) -> dict:
    """Merged recovery-work ceilings: when two planes bound the same
    counter, the LOOSEST wins — each plane's bound was calibrated for
    its own schedule alone and the merged schedule fires them all."""
    merged = {}
    for plane in planes.values():
        for field, bound in plane["bounds"].items():
            merged[field] = max(bound, merged.get(field, 0))
    return merged


def fleet_plan(nhosts: int, seed: int, tenants: int = 2,
               concurrency: int = 2, budget: int = 0,
               sf: float = 0.02, queries=None) -> dict:
    """The --fleet run plan as a JSON document (what ``--dry-run``
    prints after validating the merged schedule parses): planes,
    merged spec + bounds, topology and tenancy — everything the run
    will arm, with no backend initialization."""
    planes = fleet_planes(seed)
    return {
        "mode": "fleet-plan",
        "hosts": nhosts,
        "tenants": tenants,
        "pools": FLEET_POOLS,
        "concurrency": concurrency,
        "scale_factor": sf,
        "seed": seed,
        "device_budget_bytes": (int(budget) if budget else
                                "auto: 0.6 x measured working-set "
                                "peak"),
        "queries": list(queries) if queries else "q1-q22",
        "planes": {name: {"fault_spec": p["spec"],
                          "bounds": p["bounds"],
                          "ladder_counter": p["ladder_counter"],
                          "description": p["description"]}
                   for name, p in planes.items()},
        "merged_fault_spec": fleet_fault_spec(seed),
        "merged_bounds": fleet_bounds(planes),
        "scripted": {
            "sigkill": "one executor host SIGKILLed mid-run, "
                       "respawned two submissions later; the missed-"
                       "beat sweep must declare it lost and the "
                       "rejoin must restore full strength",
            "wedge_stall_env": "SRT_WEDGE_SLEEP_S armed for the "
                               "service plane's wedged dispatch",
        },
    }


def run_fleet(sf: float, seed: int, nhosts: int, tenants: int = 2,
              concurrency: int = 2, budget: int = 0, queries=None,
              use_sql: bool = False, timeout_s: float = 300.0):
    """``--fleet``: the fleet closure (FLEET_r01) — N executor hosts x
    concurrent tenant pools x a hard device budget x the merged
    cross-domain fault schedule, served through a QueryService that IS
    the cluster driver (scheduler.py configures the shared topology;
    DEGRADED/shedding decisions consult live host strength and arbiter
    occupancy). One run, every plane: a scripted SIGKILL + rejoin,
    injected host/mesh device losses, sustained memory pressure under
    the budget, worker crash / device loss / wedged dispatch.

    Asserts: every submission reaches a terminal state (zero hangs),
    every FINISHED result bit-identical to the fault-free twin (the
    shape baseline at the budget's chunk share; demoted-baseline and
    row-multiset escalation recorded per query), at least one fault
    fired in each of the host/mesh/memory/service domains, per-tenant
    p95 SLOs served from the live ``/slo`` endpoint, one incident
    bundle per tripped ladder action (matched by seq id + faultDomain,
    seq ids unique), recovery within the merged bounds, ZERO lock
    witness violations, and the service back to HEALTHY at the end."""
    _ensure_host_mesh(8)
    import os
    import tempfile
    import urllib.request

    import jax

    from spark_rapids_tpu.columnar.table import evict_device_caches
    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.errors import (
        QueryQuarantinedError,
        QueryRejectedError,
    )
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    from spark_rapids_tpu.parallel.mesh import MESH
    from spark_rapids_tpu.runtime.cluster import CLUSTER, spawn_executor
    from spark_rapids_tpu.runtime.faults import (
        CIRCUIT_BREAKER,
        FAULTS,
        RECOVERY,
    )
    from spark_rapids_tpu.runtime.health import HEALTH
    from spark_rapids_tpu.runtime.memory import MEMORY, forced_chunking
    from spark_rapids_tpu.runtime.spill import BufferCatalog
    from spark_rapids_tpu.service.scheduler import QueryService
    from spark_rapids_tpu.session import TpuSession
    from spark_rapids_tpu.tools.incident import load_bundles
    from spark_rapids_tpu.tools.loadtest import (
        _CHAOS_TYPED_ERRORS,
        drive_health_probes,
        service_chaos_settings,
        wedge_stall_env,
    )

    ndev = len(jax.devices())
    if ndev % nhosts:
        raise SystemExit(
            f"--fleet with --hosts {nhosts} must divide the "
            f"{ndev}-device pool so every host owns an equal dcn row")
    shape = f"{nhosts}x{ndev // nhosts}"

    planes = fleet_planes(seed)
    spec = fleet_fault_spec(seed)
    bounds = fleet_bounds(planes)

    specs = scale_test_specs(sf)
    tables = {name: s.generate_table(sf, seed=seed)
              for name, s in specs.items()}
    base = tempfile.mkdtemp(prefix="rapids_fleet_")
    paths = write_host_corpus(tables, base, files_per_table=2 * nhosts)
    flight_dir = tempfile.mkdtemp(prefix="rapids_fleet_flightrec_")

    build = build_sql_queries if use_sql else build_queries
    report = {"mode": "fleet", "backend": _resolved_backend(),
              "hosts": nhosts, "n_devices": ndev, "mesh_shape": shape,
              "tenants": tenants, "pools": FLEET_POOLS,
              "concurrency": concurrency,
              "scale_factor": sf, "seed": seed, "sql": use_sql,
              "fault_spec": spec,
              "planes": {name: {"fault_spec": p["spec"],
                                "bounds": p["bounds"]}
                         for name, p in planes.items()},
              "merged_bounds": bounds,
              "flight_recorder_dir": flight_dir,
              "queries": {}}
    failures = []

    driver, executors = _boot_cluster(nhosts)
    BufferCatalog.reset()
    MEMORY.reset()
    try:
        cluster_conf = {
            "spark.rapids.cluster.enabled": "true",
            "spark.rapids.cluster.hosts": str(nhosts),
            "spark.rapids.cluster.heartbeatIntervalMs":
                str(_HOSTS_HEARTBEAT_MS),
            "spark.rapids.cluster.missedBeats":
                str(_HOSTS_MISSED_BEATS),
            "spark.rapids.mesh.enabled": "true",
            "spark.rapids.mesh.shape": shape,
            "spark.rapids.sql.runtimeFallback.enabled": "true",
        }
        # -- fault-free twin (cluster+mesh, UNBUDGETED): the expected
        # results plus the measured working set the budget must sit
        # below for the memory plane to prove anything ------------------
        twin = TpuSession(dict(cluster_conf))
        twin_queries = build(twin, tables, paths=paths)
        wanted = queries or list(twin_queries)
        # the collective-bearing query first (run_mesh_chaos's
        # discipline): the mesh fault points must see gather traffic
        # before the ladder may legitimately shrink the topology
        wanted = sorted(wanted, key=lambda n: (n != "q7",
                                               wanted.index(n)))
        expected_plain = {name: twin_queries[name]().collect_table()
                          for name in wanted}
        working_set = MEMORY.snapshot()["peakBytes"]
        report["working_set_peak_bytes"] = int(working_set)
        if not budget:
            budget = max(4096, int(working_set * 0.6))
        report["device_budget_bytes"] = int(budget)
        if budget >= working_set:
            failures.append(
                f"device budget {budget} is not below the measured "
                f"unbudgeted working-set peak {working_set} — the "
                "fleet run would prove nothing about memory pressure")
        chunk_fraction = 0.1
        chunk_share = max(1, int(budget * chunk_fraction))
        report["chunk_share_bytes"] = chunk_share
        # the SHAPE baseline (run_memory_chaos's discipline): forced
        # chunking at the service's share, still unbudgeted — what a
        # CPU-demoted storm run reproduces (demoted ops bypass the
        # arbiter, so they never split)
        expected_chunked = {}
        with forced_chunking(chunk_share):
            for name in wanted:
                expected_chunked[name] = (
                    twin_queries[name]().collect_table())
        for name in wanted:
            sem = tables_close(expected_plain[name],
                               expected_chunked[name])
            if sem is not None:
                failures.append(f"{name}: chunked twin changed the "
                                f"answer vs unchunked: {sem}")
        evict_device_caches()
        MEMORY.reset()
        # the EXECUTION baseline: the service enforces this budget for
        # real — reserve refusals split batches and the memory ladder's
        # chunk rung may halve a share mid-collect, all deterministic
        # for a serial run but structurally unlike ANY unbudgeted twin.
        # Collect expected results through a session wearing the exact
        # service memory conf so the recovered-fleet wave has a
        # bit-identical reference (and a warm kernel cache: the wave's
        # first on-device query must not pay whole-pipeline compiles
        # inside its hard wall)
        budgeted_twin = TpuSession(dict(
            cluster_conf, **{
                "spark.rapids.memory.device.budgetBytes":
                    str(int(budget)),
                "spark.rapids.memory.device.scanChunkFraction":
                    str(chunk_fraction)}))
        btwin_queries = build(budgeted_twin, tables, paths=paths)
        expected_budgeted = {}
        for name in wanted:
            expected_budgeted[name] = (
                btwin_queries[name]().collect_table())
        for name in wanted:
            sem = tables_close(expected_plain[name],
                               expected_budgeted[name])
            if sem is not None:
                failures.append(f"{name}: budgeted twin changed the "
                                f"answer vs unbudgeted: {sem}")
        # walking the ladder during that collect is expected (the wave
        # walks the same rungs) — but its demotions are the TWIN's, not
        # the service's; record and clear them
        report["budgeted_twin_ladder"] = HEALTH.memory_snapshot()
        report["budgeted_twin_demoted_ops"] = (
            CIRCUIT_BREAKER.demoted_ops())
        CIRCUIT_BREAKER.reset()
        # a fresh ledger + clean caches for the budgeted service phase
        evict_device_caches()
        MEMORY.reset()

        # -- the service AS the cluster driver ---------------------------
        svc_conf = dict(cluster_conf)
        svc_conf.update({
            "spark.rapids.memory.device.budgetBytes": str(int(budget)),
            "spark.rapids.memory.device.scanChunkFraction":
                str(chunk_fraction),
            "spark.rapids.lint.lockWitness": "true",
            # the closure verifies EXECUTION identity: a fingerprint
            # cache hit would replay the storm's (possibly diverged)
            # table straight back to the recovery wave and mask it
            "spark.rapids.service.resultCache.enabled": "false",
            "spark.rapids.service.pools": FLEET_POOLS,
            "spark.rapids.service.maxConcurrentQueries":
                str(concurrency),
            "spark.rapids.service.queueDepth":
                str(max(64, 2 * len(wanted) * tenants)),
            "spark.rapids.service.introspect.enabled": "true",
            "spark.rapids.service.introspect.port": "0",
            "spark.rapids.obs.telemetry.enabled": "true",
            "spark.rapids.obs.telemetry.intervalMs": "200",
            "spark.rapids.obs.flightRecorder.dir": flight_dir,
            "spark.rapids.test.faults": spec,
        })
        svc_conf.update(service_chaos_settings(concurrency))

        recovery_before = RECOVERY.snapshot()
        health_before = HEALTH.snapshot()
        cluster_before = dict(scopes_snapshot().get("cluster", {}))
        mesh_before = dict(scopes_snapshot().get("mesh", {}))
        ladder_before = {
            "host": HEALTH.host_snapshot()["hostsLost"],
            "mesh": HEALTH.mesh_snapshot()["meshDeviceLost"],
            "memory": HEALTH.memory_snapshot()["memoryPressureEvents"],
            "service": health_before["deviceLost"],
        }

        pools_cycle = tuple(
            p.split(":")[0] for p in FLEET_POOLS.split(";"))
        subs = [(name, pools_cycle[(qi + ti) % len(pools_cycle)],
                 f"tenant{ti}")
                for ti in range(tenants)
                for qi, name in enumerate(wanted)]
        kill_at = len(subs) // 3 if len(subs) >= 6 else None
        rejoin_at = kill_at + 2 if kill_at is not None else None
        victim = f"h{nhosts - 1}"
        kill_info = {}
        shed_rejections = [0]
        typed_outcomes = []
        handles = []
        hung = []
        resubmit = []

        def _submit_retry(name, pool, tenant, label):
            """Submit with bounded retry across the DEGRADED shed
            window: a QueryRejectedError is the scheduler pushing back
            on the lowest-weight pool while the fleet is below
            strength — live traffic retries after the hinted delay.
            Quarantine refusals and a still-shed submission after the
            retry budget are TYPED terminal outcomes, not hangs."""
            for _ in range(20):
                try:
                    return svc.submit(svc_queries[name](),
                                      tenant=tenant, pool=pool,
                                      tag=label)
                except QueryRejectedError as exc:
                    shed_rejections[0] += 1
                    delay = (getattr(exc, "retry_after_ms", None)
                             or 250) / 1000.0
                    time.sleep(min(1.0, max(0.05, delay)))
                except QueryQuarantinedError as exc:
                    typed_outcomes.append({
                        "query": label, "state": "QUARANTINED",
                        "error": f"{type(exc).__name__}: {exc}"})
                    return None
            typed_outcomes.append({
                "query": label, "state": "REJECTED",
                "error": "QueryRejectedError: still shed after the "
                         "retry budget"})
            return None

        t0_run = time.perf_counter()
        with wedge_stall_env():
            svc = QueryService(svc_conf)
            try:
                svc_queries = build(svc.session, tables, paths=paths)
                # arm BEFORE the first submit (run_streaming's
                # discipline): per-query re-arms from the same conf
                # string are no-ops, so the one-shot counters survive
                FAULTS.arm(spec)
                for si, (name, pool, tenant) in enumerate(subs):
                    if si == kill_at:
                        # scripted mid-run HOST KILL: a real SIGKILL
                        # while the service is dispatching; the
                        # missed-beat sweep must declare the host lost
                        t0 = time.time()
                        executors[victim].terminate()
                        detected = _wait_for(
                            lambda: victim in CLUSTER.health_snapshot()[
                                "lostHosts"]
                            or victim in CLUSTER.health_snapshot()[
                                "excludedHosts"],
                            timeout_s=30.0)
                        kill_info = {"host": victim, "atSubmission": si,
                                     "detected": detected,
                                     "detectS": round(
                                         time.time() - t0, 3)}
                        if not detected:
                            failures.append(
                                f"SIGKILLed host {victim} never "
                                f"declared lost by the heartbeat sweep")
                    if si == rejoin_at:
                        t0 = time.time()
                        executors[victim] = spawn_executor(
                            driver.address, victim,
                            heartbeat_ms=_HOSTS_HEARTBEAT_MS,
                            mode="process")
                        rejoined = _wait_for(
                            lambda: victim not in
                            CLUSTER.health_snapshot()["lostHosts"]
                            and victim not in
                            CLUSTER.health_snapshot()["excludedHosts"],
                            timeout_s=60.0)
                        kill_info["rejoined"] = rejoined
                        kill_info["rejoinS"] = round(
                            time.time() - t0, 3)
                        if not rejoined:
                            failures.append(
                                f"respawned host {victim} never "
                                f"rejoined the topology")
                    label = f"{name}@{tenant}/{pool}"
                    h = _submit_retry(name, pool, tenant, label)
                    if h is not None:
                        handles.append((name, pool, tenant, label, h))
                    else:
                        # shed/quarantined to exhaustion mid-storm
                        # (recorded typed): owed a clean run on the
                        # recovered fleet below
                        resubmit.append((name, pool, tenant))
                for name, pool, tenant, label, h in handles:
                    if not h.wait(timeout=timeout_s):
                        hung.append(f"{label}: still {h.state} after "
                                    f"{timeout_s}s")
                        failures.append(hung[-1])
                # the count-based schedule is spent: return the
                # topology to full strength
                end_hosts = CLUSTER.health_snapshot()
                if (end_hosts["lostHosts"] or end_hosts["excludedHosts"]
                        or end_hosts["singleProcessReason"]):
                    CLUSTER.restore()
                if MESH.health_snapshot()["excludedDeviceIds"]:
                    MESH.restore("fleet schedule spent; probing full "
                                 "strength")

                # -- mid-storm verdicts (demotion state still live) --
                compare_modes = {}
                finished = 0
                for name, pool, tenant, label, h in handles:
                    if h.state != "FINISHED":
                        if (type(h.error).__name__
                                in _CHAOS_TYPED_ERRORS):
                            typed_outcomes.append({
                                "query": label, "state": h.state,
                                "error": f"{type(h.error).__name__}: "
                                         f"{h.error}",
                                "requeues": h.requeues})
                            resubmit.append((name, pool, tenant))
                            continue
                        failures.append(
                            f"{label}: {h.state} ({h.error})")
                        continue
                    finished += 1
                    got = h.result_table
                    # verdict ladder: the budgeted twin is THE
                    # reference (same memory conf, same splits); a
                    # CPU-demoted storm run bypasses the arbiter and
                    # reproduces the forced-chunk twin instead
                    mode = "bitwise-budgeted-twin"
                    diff = tables_differ(expected_budgeted[name], got)
                    if diff is not None:
                        if tables_differ(expected_chunked[name],
                                         got) is None:
                            diff, mode = None, "bitwise-chunked-twin"
                    if diff is not None and (
                            CIRCUIT_BREAKER.demoted_ops()
                            or HEALTH.state() != "HEALTHY"):
                        # an active demotion changes float reduction
                        # order vs the pre-demotion twin: re-collect
                        # the twin through the SAME demoted plan at
                        # the same chunk share
                        with FAULTS.suspended(), \
                                forced_chunking(chunk_share):
                            redo = twin_queries[name]().collect_table()
                        diff = tables_differ(redo, got)
                        mode = "bitwise-demoted-twin"
                    if diff is not None:
                        # concurrent budgeted execution may emit rows
                        # in a different ORDER (batching under
                        # pressure); every row must still exist
                        # bitwise on both sides
                        if tables_differ_unordered(
                                expected_plain[name], got) is None:
                            diff, mode = None, "row-multiset"
                    if diff is not None:
                        # a demotion that landed MID-query (the
                        # breaker moved while this ran concurrently)
                        # matches no static twin — record the storm
                        # divergence and require the post-recovery
                        # resubmission below to come back bitwise
                        mode = "diverged-mid-storm"
                        resubmit.append((name, pool, tenant))
                    compare_modes[mode] = (
                        compare_modes.get(mode, 0) + 1)
                    entry = report["queries"].setdefault(
                        name, {"runs": []})
                    entry["runs"].append({
                        "tenant": tenant, "pool": pool,
                        "identical": diff is None,
                        "compare_mode": mode,
                        "latencyS": round(h.latency_s, 4),
                        "queueWaitS": round(h.queue_wait_s or 0.0, 4),
                        "requeues": h.requeues})

                # the storm is over: record what it demoted, reset the
                # breaker (run_memory_chaos's discipline — the ladder's
                # deliberate demotions are the STORM's, not the
                # recovered fleet's), and pay the DEGRADED latch down
                # with live probes (what real traffic does)
                report["storm_demoted_ops"] = (
                    CIRCUIT_BREAKER.demoted_ops())
                CIRCUIT_BREAKER.reset()
                probes = 0
                if not hung:
                    probes = drive_health_probes(
                        svc, svc_queries[wanted[0]],
                        timeout_s=timeout_s)
                report["health_probes"] = probes

                # -- post-recovery wave: every shed-rejected or storm-
                # diverged query resubmits against the recovered fleet
                # and must come back FINISHED and bitwise — rejection
                # during the storm is backpressure, not data loss ----
                recovered = 0
                # the recovered-fleet verdict RE-EXECUTES (the result
                # cache is off): drop the storm's cached scan images —
                # built under ladder-forced chunk shares and OOM
                # splits, they would replay storm-era batch structures
                # into the re-scan and diverge the f64 merge order
                evict_device_caches()
                # the storm's schedule is spent and the breaker reset:
                # the recovered-fleet verdict must be about the FLEET,
                # not about a leftover one-shot fault landing on it
                recovery_retries = 0
                with FAULTS.suspended():
                    for name, pool, tenant in resubmit:
                        label = f"{name}@{tenant}/{pool}#recovery"
                        h = None
                        for attempt in range(2):
                            h = _submit_retry(name, pool, tenant, label)
                            if h is None:
                                break
                            if not h.wait(timeout=timeout_s):
                                hung.append(f"{label}: still {h.state} "
                                            f"after {timeout_s}s")
                                failures.append(hung[-1])
                                h = None
                                break
                            if h.state == "FINISHED":
                                break
                            if attempt == 0:
                                # the last storm wedge can still be
                                # sleeping inside an abandoned dispatch
                                # when the wave starts: its zombie
                                # thread drains through the launch gate
                                # and can push the FIRST wave execution
                                # over the hard wall. That is the
                                # watchdog doing its job — the verdict
                                # is whether the fleet serves the
                                # RETRY, not whether the first probe
                                # threads the drain.
                                recovery_retries += 1
                                continue
                            failures.append(f"{label}: {h.state} "
                                            f"({h.error}) on the "
                                            f"recovered fleet")
                            h = None
                        if h is None:
                            if not any(label in f for f in failures):
                                failures.append(
                                    f"{label}: still refused after "
                                    f"recovery")
                            continue
                        # bit-identity against the fault-free twin
                        # wearing the SAME memory conf; the forced-
                        # chunk twin stays a valid secondary identity
                        # (a query whose working set fits never splits)
                        mode = "bitwise-after-recovery"
                        diff = tables_differ(expected_budgeted[name],
                                             h.result_table)
                        if diff is not None and tables_differ(
                                expected_chunked[name],
                                h.result_table) is not None:
                            # the arbiter splits by LIVE occupancy, so
                            # a wave run late in the sequence can chunk
                            # where the pre-storm twin did not — a
                            # fault-free execution the static twins
                            # cannot represent. Re-collect the twin NOW
                            # (same process, same arbiter state): the
                            # service result must be bit-identical to a
                            # fault-free session execution at the same
                            # instant, or the fleet diverged.
                            live = btwin_queries[name]().collect_table()
                            diff = tables_differ(live, h.result_table)
                            mode = "bitwise-live-twin"
                        if diff is not None:
                            failures.append(f"{label}: {diff}")
                            continue
                        recovered += 1
                        compare_modes[mode] = (
                            compare_modes.get(mode, 0) + 1)
                        entry = report["queries"].setdefault(
                            name, {"runs": []})
                        entry["runs"].append({
                            "tenant": tenant, "pool": pool,
                            "identical": True,
                            "compare_mode": mode,
                            "latencyS": round(h.latency_s, 4),
                            "queueWaitS": round(h.queue_wait_s or 0.0, 4),
                            "requeues": h.requeues})
                report["recovery_retries"] = recovery_retries
                report["recovered_after_storm"] = recovered

                svc_health_live = svc.health()
                topo_live = svc.topology_snapshot()
                svc_stats = svc.stats()
                # live HTTP surfaces: the SLOs come from /slo, the
                # shared-topology snapshot from /topology
                url = f"http://127.0.0.1:{svc.introspect_port}"

                def _get(route):
                    with urllib.request.urlopen(url + route,
                                                timeout=30) as resp:
                        return json.loads(resp.read().decode("utf-8"))
                slo = _get("/slo")
                http_topology = _get("/topology")
                http_health = _get("/health")
            finally:
                fires = FAULTS.counters()
                FAULTS.disarm()
                svc.shutdown()
        report["wall_s"] = round(time.perf_counter() - t0_run, 3)

        report["finished"] = finished
        report["compare_modes"] = compare_modes
        report["typed_outcomes"] = typed_outcomes
        report["shed_rejections"] = shed_rejections[0]
        report["submissions"] = len(subs)
        report["hung"] = hung
        if not finished:
            failures.append("no submission FINISHED mid-storm — the "
                            "fleet run proves nothing")
        # every pool must end with served, verified traffic: a pool
        # that only ever shed proved admission control, not serving
        pool_cover = {}
        for entry in report["queries"].values():
            for run in entry["runs"]:
                if run["identical"]:
                    pool_cover[run["pool"]] = (
                        pool_cover.get(run["pool"], 0) + 1)
        report["pool_coverage"] = pool_cover
        for pool in pools_cycle:
            if not pool_cover.get(pool):
                failures.append(
                    f"pool {pool!r} ended with zero verified runs")
        if kill_at is not None:
            report["kill"] = kill_info

        # -- every plane's domain fired ----------------------------------
        domain_fires = {}
        for point, n in fires.items():
            if n:
                d = _fleet_point_domain(point)
                domain_fires[d] = domain_fires.get(d, 0) + n
        report["fault_fires_total"] = {k: v for k, v in
                                       sorted(fires.items()) if v}
        report["domain_fires"] = domain_fires
        for domain in ("host", "mesh", "memory", "service"):
            if not domain_fires.get(domain):
                failures.append(
                    f"no {domain}-domain fault fired — the merged "
                    f"schedule did not cover the {domain} plane")

        # -- recovery within the merged bounds ---------------------------
        recovery = {k: v - recovery_before.get(k, 0)
                    for k, v in RECOVERY.snapshot().items()}
        cluster_after = dict(scopes_snapshot().get("cluster", {}))
        mesh_after = dict(scopes_snapshot().get("mesh", {}))
        for k in ("hostShardRetries", "hostsLost"):
            recovery[k] = int(cluster_after.get(k, 0)
                              - cluster_before.get(k, 0))
        for k in ("shardRetries", "gatherChecksFailed"):
            recovery[k] = int(mesh_after.get(k, 0)
                              - mesh_before.get(k, 0))
        health_after = HEALTH.snapshot()
        recovery["deviceReinits"] = (health_after["deviceReinits"]
                                     - health_before["deviceReinits"])
        for k in ("workersLost", "workersRespawned", "requeued",
                  "hardTimeouts"):
            recovery[k] = svc_stats[k]
        report["recovery"] = {k: v for k, v in sorted(recovery.items())
                              if v}
        for field, bound in bounds.items():
            if recovery.get(field, 0) > bound:
                failures.append(f"{field}={recovery[field]} exceeds "
                                f"the merged fleet bound {bound}")

        # -- ladder actions <-> incident bundles (seq + faultDomain) -----
        ladder_after = {
            "host": HEALTH.host_snapshot()["hostsLost"],
            "mesh": HEALTH.mesh_snapshot()["meshDeviceLost"],
            "memory": HEALTH.memory_snapshot()["memoryPressureEvents"],
            "service": health_after["deviceLost"],
        }
        actions = {d: int(ladder_after[d] - ladder_before[d])
                   for d in ladder_after}
        bundles = (load_bundles(flight_dir)
                   if os.path.isdir(flight_dir) else [])
        seqs = [b["seq"] for b in bundles if "seq" in b]
        ladder_by_domain = {}
        for b in bundles:
            if str(b.get("kind", "")).endswith(".ladder"):
                d = b.get("faultDomain")
                ladder_by_domain[d] = ladder_by_domain.get(d, 0) + 1
        report["incident_bundles"] = {
            "total": len(bundles),
            "ladder_by_domain": ladder_by_domain,
            "ladder_actions": actions,
            "seq_ids_unique": len(seqs) == len(set(seqs)),
        }
        if len(seqs) != len(set(seqs)):
            failures.append("incident bundle seq ids are not unique")
        if len(seqs) != len(bundles):
            failures.append("incident bundle(s) missing the seq id "
                            "(schema 2)")
        for b in bundles:
            if "faultDomain" not in b:
                failures.append(
                    f"incident bundle kind={b.get('kind')} lacks "
                    f"faultDomain")
                break
        for domain, n_actions in actions.items():
            if n_actions and ladder_by_domain.get(domain,
                                                  0) < n_actions:
                failures.append(
                    f"{domain}: only "
                    f"{ladder_by_domain.get(domain, 0)} ladder "
                    f"bundles for {n_actions} ladder actions")
        report["ladders_tripped"] = sorted(
            d for d, n in actions.items() if n)

        # -- per-tenant SLOs from the live /slo endpoint -----------------
        report["slo"] = slo
        if not slo.get("tenants"):
            failures.append("/slo served no per-tenant percentiles")
        for key, tentry in (slo.get("tenants") or {}).items():
            p95 = tentry.get("latency", {}).get("p95S")
            if p95 is None:
                failures.append(f"/slo tenant {key} lacks p95 latency")
            elif p95 > timeout_s:
                failures.append(f"/slo tenant {key} p95 {p95}s "
                                f"exceeds the {timeout_s}s ceiling")
        # the shared-topology path: generation-stamped, served both
        # in-process and over HTTP, fleet reason wired into health()
        report["topology"] = {
            "generation": topo_live["generation"],
            "state": topo_live["state"],
            "hosts": topo_live["hosts"],
        }
        if http_topology.get("generation") is None:
            failures.append("/topology lacks the generation stamp")
        if "fleetDegradedReason" not in http_health:
            failures.append("health() lacks fleetDegradedReason — the "
                            "service is not consulting the fleet "
                            "topology")

        # -- end state: HEALTHY, full strength ---------------------------
        report["service_end"] = {
            "state": svc_health_live["state"],
            "fleetDegradedReason":
                svc_health_live.get("fleetDegradedReason"),
            "workerCount": svc_health_live.get("workerCount"),
        }
        if svc_health_live["state"] != "HEALTHY":
            failures.append(f"service ended "
                            f"{svc_health_live['state']}, not HEALTHY")
        end_hosts = CLUSTER.health_snapshot()
        report["hosts_end_state"] = end_hosts
        if (end_hosts["lostHosts"] or end_hosts["excludedHosts"]
                or end_hosts["singleProcessReason"]):
            failures.append(f"cluster not at full strength at the end: "
                            f"{end_hosts}")
        end_mesh = MESH.health_snapshot()
        if end_mesh["excludedDeviceIds"]:
            failures.append(f"mesh not at full strength at the end: "
                            f"{end_mesh}")
        report["demoted_ops"] = CIRCUIT_BREAKER.demoted_ops()
        report["health_state"] = HEALTH.state()
    finally:
        FAULTS.disarm()
        _teardown_cluster(driver, executors)
    _record_lock_witness(report, failures)
    report["ok"] = not failures
    report["failures"] = failures
    if failures:
        err = AssertionError("fleet run failed:\n"
                             + "\n".join(failures))
        err.report = report
        raise err
    return report


def run_concurrent(sf: float, seed: int, queries=None, use_sql=False,
                   concurrency: int = 4, tenants: int = 2,
                   eventlog_dir=None):
    """Throughput mode (--concurrency without --chaos): run the corpus
    serially for a baseline, then submit every (tenant, query) pair to a
    QueryService and report aggregate wall, speedup, p50/p95 latency,
    queue wait and result-cache hit rate — the same report shape the
    `tools loadtest` CLI emits (tools/loadtest.py does the work)."""
    from spark_rapids_tpu.tools.loadtest import run_loadtest
    return run_loadtest(sf=sf, seed=seed, queries=queries,
                        use_sql=use_sql, concurrency=concurrency,
                        tenants=tenants, eventlog_dir=eventlog_dir)


def streaming_fault_spec(seed: int) -> str:
    """The seeded streaming fault schedule: one scripted mid-micro-batch
    kill per stream — the rate and file-watch streams die after their
    offsets are durably logged but before the batch executes, the CDF
    tail dies inside the harder window (sink commit staged, marker not
    yet written) — plus the rare seeded kernel crash the retry framework
    absorbs transparently."""
    return ";".join([
        "stream.batch@rate:crash:1",
        "stream.batch@files:crash:1",
        "stream.sink.commit@cdf:crash:1",
        f"exec.execute:crash:0.02:{seed * 10 + 9}",
    ])


def _sink_rows(session, path):
    from spark_rapids_tpu.delta.commands import DeltaTable
    return DeltaTable(session, path).to_df().collect_table()


def run_streaming(sf: float = 0.02, seed: int = 7, chaos: bool = False):
    """``--streaming [--chaos]``: rate + file-watch + CDF-tail streams
    over corpus-derived tables, sinking through the exactly-once Delta
    txn protocol, plus two incrementally-maintained MVs (re-aggregate +
    append strategies) refreshed across every commit epoch.

    The fault-free twin runs FIRST (its own QueryService, no faults
    armed) to record the expected sink row sets; the measured side then
    runs under the seeded streaming schedule when ``chaos`` — each
    stream killed once mid-micro-batch and resumed from its checkpoint
    — asserting: every sink row set bit-identical to the twin, every MV
    read bit-identical to a from-scratch recompute at its epoch with
    >= 1 incremental refresh, the service ending HEALTHY, and the
    ``streaming`` metric scope populated (the STREAM_r01 closure)."""
    import os
    import shutil
    import tempfile

    import spark_rapids_tpu.functions as F
    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.delta.commands import DeltaTable
    from spark_rapids_tpu.delta.table import write_delta
    from spark_rapids_tpu.io.parquet import write_parquet
    from spark_rapids_tpu.obs.metrics import scopes_snapshot
    from spark_rapids_tpu.ops.expr import col, lit
    from spark_rapids_tpu.plan import nodes as P
    from spark_rapids_tpu.runtime.faults import FAULTS
    from spark_rapids_tpu.service.scheduler import QueryService
    from spark_rapids_tpu.streaming import (
        DeltaCDFSource,
        DeltaStreamSink,
        FileWatchSource,
        RateSource,
        StreamingQuery,
    )

    base = tempfile.mkdtemp(prefix="rapids_streaming_")
    specs = scale_test_specs(sf)
    orders = specs["orders"].generate_table(sf, seed=seed)
    lineitem = specs["lineitem"].generate_table(sf, seed=seed)

    # the file-watch corpus: three contiguous lineitem slices, staged
    # through the transactional parquet writer then renamed into the
    # watched directory (one file per micro-batch at maxFiles=1)
    watch_dir = os.path.join(base, "watch")
    os.makedirs(watch_dir)
    rows_per_file = max(1, min(1500, lineitem.num_rows // 3))
    for i in range(3):
        stage = os.path.join(base, f"stage{i}")
        written = write_parquet(
            lineitem.slice(i * rows_per_file, rows_per_file), stage)
        os.replace(written[0],
                   os.path.join(watch_dir, f"batch-{i:05d}.parquet"))
        shutil.rmtree(stage, ignore_errors=True)

    # the CDF corpus: an orders-derived events table created at version
    # 0, CDF enabled at 1, then two appends the tail consumes
    ev_head = orders.slice(0, max(1, min(1000, orders.num_rows // 2)))
    ev_tail = [orders.slice(1000, 500), orders.slice(1500, 500)] \
        if orders.num_rows >= 2000 else [orders.slice(0, 1)] * 2

    def make_events(session, path):
        write_delta(P.LocalScan([ev_head]), session, path, mode="error")
        DeltaTable(session, path).set_properties(
            {"delta.enableChangeDataFeed": "true"})

    def cdf_transform(df):
        # a projection transform: drop the CDF metadata + date columns
        return df.select(col("o_orderkey"), col("o_custkey"),
                         col("o_totalprice"))

    def drive_streams(svc, tag):
        """Run all three streams to completion on ``svc``; when a
        scripted kill fires, restart the stream from its checkpoint
        (fresh StreamingQuery, same offset log). Returns per-stream
        {killedBy, batches} plus the sink paths."""
        session = svc.session
        events = os.path.join(base, f"{tag}_events")
        make_events(session, events)
        sinks = {name: os.path.join(base, f"{tag}_{name}_sink")
                 for name in ("rate", "files", "cdf")}
        cks = {name: os.path.join(base, f"{tag}_{name}_ck")
               for name in ("rate", "files", "cdf")}

        def mk(name):
            src = {
                "rate": lambda: RateSource(rows_per_batch=500, seed=seed,
                                           total_rows=1500, num_keys=32),
                "files": lambda: FileWatchSource(watch_dir, session.conf,
                                                 max_files_per_trigger=1),
                "cdf": lambda: DeltaCDFSource(events, starting_version=1),
            }[name]()
            return StreamingQuery(
                svc, src, DeltaStreamSink(sinks[name], name), cks[name],
                name=name,
                transform=cdf_transform if name == "cdf" else None)

        last_q = {}

        def drain(name, out):
            q = mk(name)
            try:
                out["batches"] += q.process_available()
            except Exception as e:
                # the scripted mid-micro-batch kill: the batch is
                # pending (offsets logged, no commit marker) — a fresh
                # stream over the same checkpoint resumes exactly-once
                out["killedBy"] = type(e).__name__
                q = mk(name)
                out["batches"] += q.process_available()
            last_q[name] = q

        results = {n: {"killedBy": None, "batches": 0}
                   for n in ("rate", "files", "cdf")}
        drain("rate", results["rate"])
        drain("files", results["files"])
        # the CDF tail interleaves with commits to the events table
        for delta in ev_tail:
            write_delta(P.LocalScan([delta]), session, events,
                        mode="append")
            drain("cdf", results["cdf"])
        for q in last_q.values():
            svc.register_stream(q)
        return results, sinks, events

    report = {"mode": "streaming", "seed": seed, "scale_factor": sf,
              "backend": _resolved_backend(), "chaos": chaos,
              "fault_spec": streaming_fault_spec(seed) if chaos else "",
              "streams": {}, "mvs": {}}
    failures = []

    # -- fault-free twin: records the expected sink row sets -----------------
    FAULTS.disarm()
    twin = QueryService({"spark.rapids.service.maxConcurrentQueries": 2})
    try:
        _, twin_sinks, _ = drive_streams(twin, "twin")
        expected = {name: _sink_rows(twin.session, path)
                    for name, path in twin_sinks.items()}
    finally:
        twin.shutdown()

    # -- measured side: seeded kills (with --chaos), MVs across epochs -------
    conf = {"spark.rapids.service.maxConcurrentQueries": 2,
            # a 500-row orders append touches ~1 group per customer;
            # keep the re-aggregate path open at this corpus scale
            "spark.rapids.streaming.mv.maxTouchedGroups": 2048}
    if chaos:
        conf["spark.rapids.test.faults"] = report["fault_spec"]
        conf["spark.rapids.lint.lockWitness"] = "true"
    svc = QueryService(conf)
    try:
        session = svc.session
        if chaos:
            # arm BEFORE the first stream batch: fault_point fires ahead
            # of the batch's execute (which would otherwise arm from
            # conf too late); same spec string, so per-query re-arms
            # are no-ops and the one-shot kill counters survive
            FAULTS.arm(report["fault_spec"])
        events = os.path.join(base, "mv_events")
        make_events(session, events)
        reg = svc.mv_registry()
        ev_df = DeltaTable(session, events).to_df()
        mv_agg = reg.register(
            "rev_by_cust", ev_df.group_by(col("o_custkey")).agg(
                F.sum(col("o_totalprice")).alias("rev"),
                F.count(col("o_orderkey")).alias("n")))
        mv_proj = reg.register(
            "big_orders", ev_df.filter(
                col("o_totalprice") > lit(250_000.0)).select(
                    col("o_orderkey"), col("o_totalprice")))
        mv_epochs_ok = {m.name: 0 for m in (mv_agg, mv_proj)}

        results, sinks, _ = drive_streams(svc, "run")
        if chaos:
            for name, entry in results.items():
                if entry["killedBy"] is None:
                    failures.append(f"{name}: scripted kill never fired")

        # every commit epoch: each MV read must be bit-identical to a
        # from-scratch recompute of its registered plan at that epoch
        for delta in ev_tail:
            write_delta(P.LocalScan([delta]), session, events,
                        mode="append")
            for mv in (mv_agg, mv_proj):
                diff = tables_differ_unordered(mv.read(),
                                               mv.recompute_at_epoch())
                if diff is not None:
                    failures.append(
                        f"mv {mv.name} diverged at epoch {mv.epoch()}: "
                        f"{diff}")
                else:
                    mv_epochs_ok[mv.name] += 1

        for name, path in sinks.items():
            got = _sink_rows(session, path)
            diff = tables_differ_unordered(expected[name], got)
            entry = dict(results[name])
            entry["rows"] = got.num_rows
            entry["identical"] = diff is None
            if diff is not None:
                failures.append(f"{name}: sink diverged: {diff}")
            report["streams"][name] = entry
            print(json.dumps({"stream": name, **entry}))
        for mv in (mv_agg, mv_proj):
            entry = {"strategy": mv.strategy,
                     "epochsVerified": mv_epochs_ok[mv.name],
                     "incrementalRefreshes": mv.incremental_refreshes,
                     "fullRecomputes": mv.full_recomputes,
                     "lastRefreshMode": mv.last_refresh_mode,
                     "fallbackReason": mv.fallback_reason}
            if mv.incremental_refreshes < 1:
                failures.append(
                    f"mv {mv.name}: no refresh took the incremental "
                    f"path (strategy={mv.strategy})")
            report["mvs"][mv.name] = entry
            print(json.dumps({"mv": mv.name, **entry}))

        health = svc.health()
        report["service"] = {"health": health,
                             "streams": svc.streams()}
        if health["state"] != "HEALTHY":
            failures.append(
                f"service ended {health['state']}, not HEALTHY")
        scope = dict(scopes_snapshot().get("streaming", {}))
        report["streaming_scope"] = scope
        for key in ("microBatches", "sinkCommits", "mvRefreshes",
                    "mvIncrementalRefreshes"):
            if not scope.get(key):
                failures.append(
                    f"streaming scope not populated: {key}="
                    f"{scope.get(key, 0)}")
        if chaos:
            report["fault_fires"] = {
                k: v for k, v in FAULTS.counters().items() if v}
    finally:
        svc.shutdown()
        FAULTS.disarm()
        shutil.rmtree(base, ignore_errors=True)
    _record_lock_witness(report, failures)
    report["ok"] = not failures
    report["failures"] = failures
    if failures:
        err = AssertionError(
            "streaming run failed:\n" + "\n".join(failures))
        err.report = report
        raise err
    return report


#: the harness's supported mode combinations — named in every flag-
#: validation error so a bad invocation is a one-line fix, not an
#: archaeology session through silently-ignored flags
SUPPORTED_MODES = (
    "supported modes: (default timing run) | --cpu-baseline | "
    "--chaos [--concurrency N [--service-faults]] | --concurrency N | "
    "--mesh N [--mesh-shape DxI] [--chaos] | --hosts N [--chaos] | "
    "--streaming [--chaos] | --fleet [--hosts N] [--device-budget B] "
    "[--concurrency N] [--tenants N] [--dry-run]")


def _resolved_backend() -> str:
    """The JAX backend this run actually measured — stamped into every
    report artifact so a CPU-backend number can never masquerade as a
    TPU one (it happened once)."""
    import jax
    return jax.default_backend()


def validate_flags(args) -> None:
    """Fail fast on flag combinations the harness does not implement —
    a silently-ignored mode flag reads as a passing run of a contract
    that was never exercised.

    Fault PLANES compose: --fleet (or any two of --hosts /
    --device-budget / --concurrency together) routes to the fleet
    closure, where host, mesh-device, memory, service and exec faults
    merge into one seeded schedule. The single-plane modes keep their
    original harnesses (and their original rejections) — a lone
    --hosts run is still the serial bit-identity harness, not a fleet
    run that happens to have one plane."""
    def bad(msg):
        raise SystemExit(f"{msg} ({SUPPORTED_MODES})")

    fleet = getattr(args, "fleet", False)
    combo = sum(1 for v in (args.hosts, args.device_budget,
                            args.concurrency) if v)
    if fleet or combo >= 2:
        if args.mesh:
            bad("--fleet does not compose with --mesh: the fleet "
                "harness builds its own hierarchical (hosts x "
                "devices-per-host) mesh")
        if args.streaming:
            bad("--fleet does not compose with --streaming: recurring "
                "streams own their kill points; the fleet corpus is "
                "the one-shot q1-q22 set")
        if args.cpu_baseline:
            bad("--fleet does not compose with --cpu-baseline: the "
                "fleet baseline is its own fault-free twin over the "
                "same cluster topology, not the CPU path")
        if args.require_tpu:
            bad("--fleet does not compose with --require-tpu: the "
                "fleet harness pins virtual host-platform (cpu) "
                "devices, and the gate would initialize the backend "
                "before the device-count flag can take effect")
        if args.hosts and args.hosts < 2:
            bad(f"--hosts {args.hosts}: a cluster needs at least 2 "
                "executor hosts")
        if args.device_budget and args.device_budget < 4096:
            bad(f"--device-budget {args.device_budget}: below 4KB not "
                "even a MIN_BUCKET chunk of one column fits")
        return
    if getattr(args, "dry_run", False):
        bad("--dry-run only applies to --fleet: the single-plane "
            "harnesses have no plan document to print")
    if args.mesh:
        if args.mesh < 2:
            bad(f"--mesh {args.mesh}: a mesh needs at least 2 devices")
        if args.concurrency:
            bad("--mesh does not compose with --concurrency: the mesh "
                "harness asserts per-query bit-identity serially")
        if args.service_faults:
            bad("--mesh does not compose with --service-faults: "
                "service-level faults need --chaos --concurrency N")
        if args.cpu_baseline:
            bad("--mesh does not compose with --cpu-baseline: the mesh "
                "baseline is fault-free single-chip, not the CPU path")
        if args.require_tpu:
            bad("--mesh does not compose with --require-tpu: the mesh "
                "harness pins virtual host-platform (cpu) devices, and "
                "the gate would initialize the backend before the "
                "device-count flag can take effect")
    if args.hosts:
        if args.hosts < 2:
            bad(f"--hosts {args.hosts}: a cluster needs at least 2 "
                "executor hosts")
        if args.mesh:
            bad("--hosts does not compose with --mesh: the hosts "
                "harness builds its own hierarchical (hosts x "
                "devices-per-host) mesh")
        if args.concurrency:
            bad("--hosts does not compose with --concurrency: the "
                "hosts harness asserts per-query bit-identity "
                "serially")
        if args.service_faults:
            bad("--hosts does not compose with --service-faults: "
                "service-level faults need --chaos --concurrency N")
        if args.cpu_baseline:
            bad("--hosts does not compose with --cpu-baseline: the "
                "hosts baseline is fault-free single-process over the "
                "same files, not the CPU path")
        if args.require_tpu:
            bad("--hosts does not compose with --require-tpu: the "
                "hosts harness pins virtual host-platform (cpu) "
                "devices, and the gate would initialize the backend "
                "before the device-count flag can take effect")
    if args.device_budget:
        if args.device_budget < 4096:
            bad(f"--device-budget {args.device_budget}: below 4KB not "
                "even a MIN_BUCKET chunk of one column fits")
        if args.mesh or args.hosts:
            bad("--device-budget does not compose with --mesh/--hosts: "
                "the memory harness asserts single-process bit-"
                "identity against unbudgeted execution")
        if args.concurrency or args.service_faults:
            bad("--device-budget does not compose with --concurrency/"
                "--service-faults: the memory harness runs serially "
                "(its own service phase asserts HEALTHY)")
        if args.cpu_baseline:
            bad("--device-budget does not compose with --cpu-baseline: "
                "the memory baseline is unbudgeted device execution, "
                "not the CPU path")
        if args.require_tpu:
            bad("--device-budget does not compose with --require-tpu: "
                "the out-of-core contract is backend-independent and "
                "the artifact records the resolved backend in-band")
    if args.streaming:
        if args.mesh or args.hosts:
            bad("--streaming does not compose with --mesh/--hosts: the "
                "streaming harness drives its own recurring tenants "
                "through a single-process QueryService")
        if args.device_budget:
            bad("--streaming does not compose with --device-budget: "
                "the memory harness runs the one-shot corpus, not "
                "recurring streams")
        if args.concurrency or args.service_faults:
            bad("--streaming does not compose with --concurrency/"
                "--service-faults: streams ARE the concurrent tenants, "
                "and the streaming fault schedule owns the kill points")
        if args.cpu_baseline:
            bad("--streaming does not compose with --cpu-baseline: the "
                "streaming baseline is its own fault-free twin run")
    if args.service_faults and not (args.chaos and args.concurrency > 1):
        bad("--service-faults needs --chaos --concurrency > 1 (the "
            "service fault points live in the worker/watchdog "
            "machinery)")
    if args.cpu_baseline and (args.chaos or args.concurrency):
        bad("--cpu-baseline is a timing-run flag; it does not compose "
            "with --chaos or --concurrency")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sf", type=float, default=None,
                    help="scale factor (default 0.1; chaos mode defaults "
                         "to 0.02 — it exercises recovery paths, not "
                         "throughput)")
    ap.add_argument("--queries", type=str, default="")
    ap.add_argument("--cpu-baseline", action="store_true")
    ap.add_argument("--sql", action="store_true",
                    help="run the q1-q22 SQL-text forms through "
                         "session.sql() instead of the DataFrame DSL")
    ap.add_argument("--seed", type=int, default=None,
                    help="datagen / fault-schedule seed (default 0; "
                         "chaos mode defaults to 7)")
    ap.add_argument("--out", type=str, default="")
    ap.add_argument("--eventlog-dir", type=str,
                    default="/tmp/rapids_tpu_eventlog/scale",
                    help="directory for the per-query event log the "
                         "offline tools analyze (written by default; "
                         "--no-eventlog disables)")
    ap.add_argument("--no-eventlog", action="store_true",
                    help="disable query event logging")
    ap.add_argument("--chaos", action="store_true",
                    help="run the corpus fault-free and under a seeded "
                         "fault schedule, asserting bit-identical "
                         "results and bounded recovery work")
    ap.add_argument("--concurrency", type=int, default=0,
                    help="run through the QueryService at this worker "
                         "concurrency: with --chaos, the chaotic side "
                         "runs concurrently; alone, emits the loadtest "
                         "throughput/latency report vs the serial "
                         "baseline")
    ap.add_argument("--service-faults", action="store_true",
                    help="with --chaos --concurrency N: extend the "
                         "schedule with service-level faults (worker "
                         "crash, device loss, wedged dispatch) and "
                         "assert the survivability contract — all "
                         "terminal, typed failures only, bounded "
                         "recovery, health back to HEALTHY")
    ap.add_argument("--tenants", type=int, default=2,
                    help="simulated tenants for --concurrency runs")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run the corpus MESH-NATIVE over an N-device "
                         "mesh (always virtual host-platform devices "
                         "on the CPU), asserting the mesh contract "
                         "vs single-chip (bit-identity, DOUBLE sums of "
                         "shard-aggregated queries within 2e-7) plus "
                         "per-exchange ICI accounting; "
                         "with --chaos, the corpus runs under the "
                         "seeded MESH-fault schedule instead (the "
                         "MULTICHIP_r07 closure)")
    ap.add_argument("--mesh-shape", type=str, default="",
                    help="with --mesh: explicit spark.rapids.mesh.shape "
                         "('8' or '2x4'; default N on one flat axis)")
    ap.add_argument("--hosts", type=int, default=0, metavar="N",
                    help="run the corpus through the MULTI-HOST "
                         "simulation harness: N executor subprocesses "
                         "scan their by-host parquet assignments and "
                         "ship shards over the driver/executor socket "
                         "protocol, the corpus mesh-native on the "
                         "hierarchical (N x dev/N) mesh, asserting "
                         "bit-identity vs single-process over the same "
                         "files; with --chaos, adds the seeded host.* "
                         "fault schedule plus a scripted mid-corpus "
                         "host KILL + rejoin restore (MULTIHOST_r01)")
    ap.add_argument("--device-budget", type=int, default=0,
                    metavar="BYTES",
                    help="run q1-q22 under a hard device-memory budget "
                         "(runtime/memory.py MemoryArbiter) asserting "
                         "bit-identity to unbudgeted execution with "
                         "spillBytes > 0 and zero budget violations; "
                         "with --chaos, adds the seeded mem.* fault "
                         "schedule, the full memory-ladder walk with "
                         "incident bundles, and a HEALTHY service "
                         "closure (OOC_r01)")
    ap.add_argument("--streaming", action="store_true",
                    help="run the streaming + materialized-view harness "
                         "(rate / file-watch / Delta-CDF streams into "
                         "exactly-once Delta sinks, two incrementally-"
                         "maintained MVs verified bit-identical to a "
                         "from-scratch recompute at every epoch); with "
                         "--chaos, each stream is killed once mid-"
                         "micro-batch under the seeded schedule and "
                         "must resume exactly-once (STREAM_r01)")
    ap.add_argument("--fleet", action="store_true",
                    help="the FLEET closure: N executor hosts x "
                         "concurrent tenant pools x a hard device "
                         "budget x the merged cross-domain fault "
                         "schedule (host + mesh + memory + service + "
                         "exec planes COMPOSED), served through a "
                         "QueryService acting as the cluster driver; "
                         "asserts all-terminal, bit-identity vs the "
                         "fault-free twin, per-tenant /slo p95s, one "
                         "incident bundle per ladder action, zero "
                         "lock-witness violations, HEALTHY at the end "
                         "(FLEET_r01); any two of --hosts/"
                         "--device-budget/--concurrency also route "
                         "here")
    ap.add_argument("--dry-run", action="store_true",
                    help="with --fleet: build the run plan, validate "
                         "the merged fault schedule parses, print the "
                         "plan JSON and exit 0 — no backend "
                         "initialization, no cluster boot")
    ap.add_argument("--require-tpu", action="store_true",
                    help="exit non-zero unless the resolved JAX platform "
                         "is 'tpu' — a perf run that meant to hit the TPU "
                         "must fail loudly, not commit CPU numbers")
    args = ap.parse_args()
    validate_flags(args)

    # the require-tpu gate resolves the backend ONLY when asked: an
    # unconditional jax.default_backend() here would initialize the
    # backend before --mesh's _ensure_host_mesh can force the virtual
    # host-device count (the report dicts each stamp _resolved_backend()
    # themselves, after any mesh setup)
    if args.require_tpu:
        from spark_rapids_tpu.tools import require_tpu_backend
        require_tpu_backend()

    fleet_combo = sum(1 for v in (args.hosts, args.device_budget,
                                  args.concurrency) if v)
    if args.fleet or fleet_combo >= 2:
        nhosts = args.hosts or 2
        fleet_tenants = args.tenants or 2
        fleet_conc = args.concurrency or 2
        wanted = [q.strip() for q in args.queries.split(",")
                  if q.strip()]
        seed = args.seed if args.seed is not None else 7
        sf = args.sf if args.sf is not None else 0.02
        if args.dry_run:
            # plan + validate only: parse the merged cross-domain
            # schedule through the real spec parser (no arming, no
            # jax), print the plan, exit 0 — the under-5s smoke
            from spark_rapids_tpu.runtime.faults import parse_fault_spec
            plan = fleet_plan(nhosts, seed, tenants=fleet_tenants,
                              concurrency=fleet_conc,
                              budget=args.device_budget, sf=sf,
                              queries=wanted or None)
            plan["merged_fault_points"] = len(
                parse_fault_spec(plan["merged_fault_spec"]))
            print(json.dumps(plan))
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(plan, f, indent=1)
            return

        def dump_fleet_report(report):
            print(json.dumps(report))
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(report, f, indent=1)

        try:
            report = run_fleet(
                sf=sf, seed=seed, nhosts=nhosts,
                tenants=fleet_tenants, concurrency=fleet_conc,
                budget=args.device_budget, queries=wanted or None,
                use_sql=args.sql)
        except AssertionError as e:
            if getattr(e, "report", None) is not None:
                dump_fleet_report(e.report)
            raise SystemExit(f"FAILED: {e}")
        dump_fleet_report(report)
        return

    if args.streaming:
        def dump_stream_report(report):
            print(json.dumps(report))
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(report, f, indent=1)

        try:
            report = run_streaming(
                sf=args.sf if args.sf is not None else 0.02,
                seed=args.seed if args.seed is not None else 7,
                chaos=args.chaos)
        except AssertionError as e:
            if getattr(e, "report", None) is not None:
                dump_stream_report(e.report)
            raise SystemExit(f"FAILED: {e}")
        dump_stream_report(report)
        return

    if args.device_budget:
        wanted = [q.strip() for q in args.queries.split(",") if q.strip()]

        def dump_memory_report(report):
            print(json.dumps(report))
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(report, f, indent=1)

        try:
            report = run_memory_chaos(
                sf=args.sf if args.sf is not None else 0.02,
                seed=args.seed if args.seed is not None else 7,
                budget=args.device_budget, queries=wanted or None,
                use_sql=args.sql, chaos=args.chaos)
        except AssertionError as e:
            if getattr(e, "report", None) is not None:
                dump_memory_report(e.report)
            raise SystemExit(f"FAILED: {e}")
        dump_memory_report(report)
        return

    if args.hosts:
        wanted = [q.strip() for q in args.queries.split(",") if q.strip()]

        def dump_hosts_report(report):
            print(json.dumps(report))
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(report, f, indent=1)

        try:
            report = run_hosts(
                sf=args.sf if args.sf is not None else (
                    0.02 if args.chaos else 0.05),
                seed=args.seed if args.seed is not None else (
                    7 if args.chaos else 0),
                nhosts=args.hosts, queries=wanted or None,
                use_sql=args.sql, chaos=args.chaos)
        except AssertionError as e:
            if getattr(e, "report", None) is not None:
                dump_hosts_report(e.report)
            raise SystemExit(f"FAILED: {e}")
        dump_hosts_report(report)
        return

    if args.mesh:
        wanted = [q.strip() for q in args.queries.split(",") if q.strip()]

        def dump_mesh_report(report):
            print(json.dumps(report))
            if args.out:
                with open(args.out, "w") as f:
                    json.dump(report, f, indent=1)

        try:
            if args.chaos:
                # mesh + chaos COMPOSED: the corpus mesh-native under
                # the seeded mesh-fault schedule (MULTICHIP_r07)
                report = run_mesh_chaos(
                    sf=args.sf if args.sf is not None else 0.02,
                    seed=args.seed if args.seed is not None else 7,
                    ndev=args.mesh, queries=wanted or None,
                    use_sql=args.sql, shape=args.mesh_shape)
            else:
                report = run_mesh(
                    sf=args.sf if args.sf is not None else 0.05,
                    seed=args.seed if args.seed is not None else 0,
                    ndev=args.mesh, queries=wanted or None,
                    use_sql=args.sql, shape=args.mesh_shape)
        except AssertionError as e:
            # divergence: the failure report carries exactly what we
            # need to debug it — write it before exiting non-zero
            if getattr(e, "report", None) is not None:
                dump_mesh_report(e.report)
            raise SystemExit(f"FAILED: {e}")
        dump_mesh_report(report)
        return

    if args.chaos:
        wanted = [q.strip() for q in args.queries.split(",") if q.strip()]
        report = run_chaos(sf=args.sf if args.sf is not None else 0.02,
                           seed=args.seed if args.seed is not None else 7,
                           queries=wanted or None, use_sql=args.sql,
                           concurrency=args.concurrency,
                           service_faults=args.service_faults)
        print(json.dumps(report))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        return
    if args.concurrency and args.concurrency > 1:
        wanted = [q.strip() for q in args.queries.split(",") if q.strip()]
        report = run_concurrent(
            sf=args.sf if args.sf is not None else 0.1,
            seed=args.seed if args.seed is not None else 0,
            queries=wanted or None, use_sql=args.sql,
            concurrency=args.concurrency, tenants=args.tenants,
            eventlog_dir=(None if args.no_eventlog else args.eventlog_dir))
        print(json.dumps(report))
        if args.out:
            with open(args.out, "w") as f:
                json.dump(report, f, indent=1)
        if not report["ok"]:
            raise SystemExit(1)
        return
    if args.sf is None:
        args.sf = 0.1
    if args.seed is None:
        args.seed = 0

    from spark_rapids_tpu.datagen import scale_test_specs
    from spark_rapids_tpu.session import TpuSession

    t0 = time.perf_counter()
    specs = scale_test_specs(args.sf)
    tables = {name: spec.generate_table(args.sf, seed=args.seed)
              for name, spec in specs.items()}
    gen_s = time.perf_counter() - t0

    build = build_sql_queries if args.sql else build_queries
    # event logs on by default so every SCALE artifact is analyzable by
    # `python -m spark_rapids_tpu.tools profile/compare`
    tpu_conf = {}
    if not args.no_eventlog:
        tpu_conf = {"spark.rapids.sql.eventLog.enabled": "true",
                    "spark.rapids.sql.eventLog.dir": args.eventlog_dir}
    tpu = TpuSession(tpu_conf)
    queries = build(tpu, tables)
    wanted = ([q.strip() for q in args.queries.split(",") if q.strip()]
              or list(queries))

    cpu_queries = None
    if args.cpu_baseline:
        cpu = TpuSession({"spark.rapids.sql.enabled": "false"})
        cpu_queries = build(cpu, tables)

    report = {"scale_factor": args.sf, "mode": "sql" if args.sql else "dsl",
              "backend": _resolved_backend(),
              "eventlog_dir": (args.eventlog_dir if not args.no_eventlog
                               else None),
              "datagen_s": round(gen_s, 3),
              "rows": {k: t.num_rows for k, t in tables.items()},
              "queries": {}}
    for name in wanted:
        cold, warm, warm_med = time_query(queries[name], session=tpu,
                                          tag=name)
        entry = {"cold_s": round(cold, 4), "warm_s": round(warm, 4),
                 "warm_med_s": round(warm_med, 4)}
        if cpu_queries is not None:
            _, cpu_warm, cpu_med = time_query(cpu_queries[name], runs=3)
            entry["cpu_warm_s"] = round(cpu_warm, 4)
            entry["cpu_warm_med_s"] = round(cpu_med, 4)
            entry["speedup"] = round(cpu_warm / warm, 3) if warm else None
            entry["speedup_med"] = (round(cpu_med / warm_med, 3)
                                    if warm_med else None)
        report["queries"][name] = entry
        print(json.dumps({"query": name, **entry}))
    import math

    def _geomean(vals):
        return round(math.exp(sum(math.log(x) for x in vals) / len(vals)), 3)

    speedups = [e["speedup"] for e in report["queries"].values()
                if e.get("speedup")]
    if speedups:
        report["geomean_speedup"] = _geomean(speedups)
    med_speedups = [e["speedup_med"] for e in report["queries"].values()
                    if e.get("speedup_med")]
    if med_speedups:
        report["geomean_speedup_med"] = _geomean(med_speedups)
    report["warm_total_s"] = round(
        sum(e["warm_s"] for e in report["queries"].values()), 4)
    report["cold_total_s"] = round(
        sum(e["cold_s"] for e in report["queries"].values()), 4)
    print(json.dumps(report))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=1)


if __name__ == "__main__":
    main()
