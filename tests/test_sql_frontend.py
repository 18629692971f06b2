"""SQL front end golden suite (reference: qa_nightly_select_test.py —
the reference's test corpus IS SQL text; ISSUE 1 tentpole).

Three layers:
  * construct-by-construct SQL-vs-DSL equivalence: every supported
    grammar feature collected through session.sql() must equal the
    same query built through the DataFrame DSL;
  * error surfaces: parse errors carry (line, col) + caret; analysis
    errors name the construct with an overrides-style reason;
  * the ScaleTest q1-q22 corpus: SQL text and DSL forms produce
    identical results AND identical device dispatch counts (the SQL
    path lowers onto the same plan layer — no parallel engine).
"""

import datetime

import numpy as np
import pytest

from spark_rapids_tpu import functions as F
from spark_rapids_tpu import types as T
from spark_rapids_tpu.ops.expr import col, lit
from spark_rapids_tpu.session import TpuSession
from spark_rapids_tpu.sql.errors import SqlAnalysisError, SqlParseError


@pytest.fixture(scope="module")
def s():
    sess = TpuSession()
    sess.create_dataframe({
        "id": np.arange(1, 9, dtype=np.int64),
        "k": np.array(["a", "b", "a", "c", "b", "a", None, "c"],
                      dtype=object),
        "v": np.array([10.0, 20.0, 30.0, 40.0, None, 60.0, 70.0, 80.0],
                      dtype=object),
        "d": np.array([0, 100, 200, 300, 400, 500, 600, 700],
                      dtype=np.int32),
    }, dtypes={"id": T.LONG, "k": T.STRING, "v": T.DOUBLE, "d": T.DATE}) \
        .create_or_replace_temp_view("t")
    sess.create_dataframe({
        "k": np.array(["a", "b", "d"], dtype=object),
        "w": np.array([1.0, 2.0, 3.0]),
    }).create_or_replace_temp_view("u")
    return sess


def _canon(rows):
    out = []
    for r in rows:
        out.append(tuple(round(x, 9) if isinstance(x, float) else x
                         for x in r))
    return sorted(out, key=lambda r: tuple(
        (x is None, str(type(x)), x) for x in r))


def check(s, sql, build_dsl):
    got = _canon(s.sql(sql).collect())
    want = _canon(build_dsl(s).collect())
    assert got == want, f"{sql}\n  sql: {got}\n  dsl: {want}"


def t(s):
    return s.table("t")


def u(s):
    return s.table("u")


# -- projection / expressions ------------------------------------------------

def test_select_star(s):
    check(s, "SELECT * FROM t", t)


def test_projection_arithmetic_alias(s):
    check(s, "SELECT id, v * 2 + 1 AS dv, -id AS neg, v / 4, v % 3 FROM t",
          lambda s: t(s).select(
              col("id"), (col("v") * lit(2) + lit(1)).alias("dv"),
              (-col("id")).alias("neg"), col("v") / lit(4),
              col("v") % lit(3)))


def test_comparisons_and_logic(s):
    check(s, "SELECT id FROM t WHERE (v > 15 AND v <= 60) "
             "OR NOT (id < 5) OR v <> 30",
          lambda s: t(s).filter(
              ((col("v") > lit(15)) & (col("v") <= lit(60)))
              | ~(col("id") < lit(5)) | (col("v") != lit(30)))
          .select(col("id")))


def test_null_predicates(s):
    check(s, "SELECT id FROM t WHERE v IS NULL",
          lambda s: t(s).filter(col("v").isnull()).select(col("id")))
    check(s, "SELECT id FROM t WHERE k IS NOT NULL",
          lambda s: t(s).filter(col("k").isnotnull()).select(col("id")))


def test_null_safe_equal(s):
    check(s, "SELECT id FROM t WHERE k <=> NULL",
          lambda s: t(s).filter(
              (col("k").isnull() & lit(None).isnull())
              | (col("k") == lit(None))).select(col("id")))


def test_null_safe_equal_never_null(s):
    """<=> is NEVER null (code-review fix: the old lowering returned
    NULL when exactly one side was null, so NOT(a <=> b) dropped rows)."""
    rows = s.sql("SELECT k <=> NULL FROM t ORDER BY id").collect()
    assert all(v in (True, False) for (v,) in rows), rows
    # row 7 has k NULL -> true; every other row false
    assert [v for (v,) in rows] == [False] * 6 + [True, False]
    n = s.sql("SELECT COUNT(*) AS n FROM t "
              "WHERE NOT (k <=> NULL)").collect()
    assert n == [(7,)]


def test_decimal_literal_positive_exponent(s):
    """1E2BD is 100 = decimal(3,0) (code-review fix: precision ignored a
    positive exponent, so CheckOverflow nulled 1E2BD + 1BD)."""
    assert s.sql("SELECT 1E2BD + 1BD AS v").collect() == [(101,)]


def test_backwards_unbounded_frames_rejected(s):
    """Spark rejects UNBOUNDED FOLLOWING as a frame START (and PRECEDING
    as an END) at parse time; the old parser collapsed both directions
    to None and silently computed a running aggregate."""
    with pytest.raises(SqlParseError, match="frame START"):
        s.sql("SELECT SUM(v) OVER (ORDER BY id ROWS UNBOUNDED FOLLOWING) "
              "FROM t")
    with pytest.raises(SqlParseError, match="frame END"):
        s.sql("SELECT SUM(v) OVER (ORDER BY id ROWS BETWEEN CURRENT ROW "
              "AND UNBOUNDED PRECEDING) FROM t")
    # the legal directions still parse and run
    rows = s.sql("SELECT SUM(v) OVER (ORDER BY id ROWS BETWEEN UNBOUNDED "
                 "PRECEDING AND UNBOUNDED FOLLOWING) AS sv FROM t").collect()
    assert len(rows) == 8


def test_hint_rejects_unsupported_argument(s):
    with pytest.raises(SqlParseError, match="hint argument"):
        s.sql("SELECT /*+ REPARTITION('8', k) */ k FROM t")


def test_between_in_like(s):
    check(s, "SELECT id FROM t WHERE id BETWEEN 2 AND 5",
          lambda s: t(s).filter((col("id") >= lit(2))
                                & (col("id") <= lit(5)))
          .select(col("id")))
    check(s, "SELECT id FROM t WHERE id NOT BETWEEN 2 AND 5",
          lambda s: t(s).filter(~((col("id") >= lit(2))
                                  & (col("id") <= lit(5))))
          .select(col("id")))
    from spark_rapids_tpu.ops.predicates import In
    check(s, "SELECT id FROM t WHERE k IN ('a', 'c')",
          lambda s: t(s).filter(In(col("k"), [lit("a"), lit("c")]))
          .select(col("id")))
    from spark_rapids_tpu.ops.strings import Like, RLike
    check(s, "SELECT id FROM t WHERE k LIKE 'a%'",
          lambda s: t(s).filter(Like(col("k"), lit("a%")))
          .select(col("id")))
    check(s, "SELECT id FROM t WHERE k RLIKE '[ab]'",
          lambda s: t(s).filter(RLike(col("k"), lit("[ab]")))
          .select(col("id")))


def test_concat_operator(s):
    from spark_rapids_tpu.ops.strings import Concat
    check(s, "SELECT k || '_x' AS kk FROM t",
          lambda s: t(s).select(Concat(col("k"), lit("_x")).alias("kk")))


def test_case_when(s):
    from spark_rapids_tpu.ops.conditional import CaseWhen
    check(s, "SELECT id, CASE WHEN v > 50 THEN 'hi' WHEN v > 20 "
             "THEN 'mid' ELSE 'lo' END AS b FROM t",
          lambda s: t(s).select(col("id"), CaseWhen(
              col("v") > lit(50), lit("hi"),
              col("v") > lit(20), lit("mid"), lit("lo")).alias("b")))
    # simple CASE (operand form)
    check(s, "SELECT id, CASE k WHEN 'a' THEN 1 WHEN 'b' THEN 2 END AS c "
             "FROM t",
          lambda s: t(s).select(col("id"), CaseWhen(
              col("k") == lit("a"), lit(1),
              col("k") == lit("b"), lit(2)).alias("c")))


def test_cast(s):
    check(s, "SELECT CAST(v AS INT) AS iv, CAST(id AS STRING) AS sid, "
             "CAST(v AS DECIMAL(10, 2)) AS dv FROM t",
          lambda s: t(s).select(
              col("v").cast(T.INT).alias("iv"),
              col("id").cast(T.STRING).alias("sid"),
              col("v").cast(T.DecimalType(10, 2)).alias("dv")))


def test_literals(s):
    df = s.sql("SELECT 1 AS a, 1.5 AS b, '[x]' AS c, TRUE AS d, "
               "NULL AS e, 2.5BD AS f, 3L AS g, 4D AS h "
               "FROM t LIMIT 1")
    # decimals collect as unscaled ints (engine convention, see
    # test_decimal128: "decimals are BIT-exact"); 2.5BD is dec(2,1) = 25
    assert dict(df.schema)["f"] == T.DecimalType(2, 1)
    assert df.collect() == [(1, 1.5, "[x]", True, None, 25, 3, 4.0)]


def test_date_literal_and_interval(s):
    from spark_rapids_tpu.ops.datetime import AddMonths, DateAdd, DateSub
    check(s, "SELECT id FROM t WHERE d <= DATE '1970-07-20'",
          lambda s: t(s).filter(
              col("d") <= lit(datetime.date(1970, 7, 20)))
          .select(col("id")))
    check(s, "SELECT d + INTERVAL 3 DAYS AS d2, d - INTERVAL 1 WEEK AS "
             "d3, d + INTERVAL 2 MONTHS AS d4 FROM t",
          lambda s: t(s).select(
              DateAdd(col("d"), lit(3)).alias("d2"),
              DateSub(col("d"), lit(7)).alias("d3"),
              AddMonths(col("d"), lit(2)).alias("d4")))


def test_functions_resolve_to_dsl_builders(s):
    check(s, "SELECT upper(k) AS uk, length(k) AS lk, abs(v - 50) AS av, "
             "coalesce(v, 0.0) AS cv, year(d) AS y, round(v / 7, 1) AS r "
             "FROM t",
          lambda s: t(s).select(
              F.upper(col("k")).alias("uk"),
              F.length(col("k")).alias("lk"),
              F.abs(col("v") - lit(50)).alias("av"),
              F.coalesce(col("v"), lit(0.0)).alias("cv"),
              F.year(col("d")).alias("y"),
              F.round(col("v") / lit(7), 1).alias("r")))


# -- aggregates --------------------------------------------------------------

def test_group_by_aggs(s):
    check(s, "SELECT k, SUM(v) AS sv, COUNT(v) AS cv, COUNT(*) AS c, "
             "AVG(v) AS av, MIN(v) AS mn, MAX(v) AS mx FROM t GROUP BY k",
          lambda s: t(s).group_by("k").agg(
              F.sum("v").alias("sv"), F.count(col("v")).alias("cv"),
              F.count().alias("c"), F.avg("v").alias("av"),
              F.min("v").alias("mn"), F.max("v").alias("mx")))


def test_global_agg(s):
    check(s, "SELECT SUM(v) AS sv FROM t",
          lambda s: t(s).agg(F.sum("v").alias("sv")))


def test_group_by_ordinal_and_alias(s):
    check(s, "SELECT k AS grp, SUM(v) AS sv FROM t GROUP BY 1",
          lambda s: t(s).group_by("k").agg(F.sum("v").alias("sv"))
          .select(col("k").alias("grp"), col("sv")))
    check(s, "SELECT k AS grp, SUM(v) AS sv FROM t GROUP BY grp",
          lambda s: t(s).group_by("k").agg(F.sum("v").alias("sv"))
          .select(col("k").alias("grp"), col("sv")))


def test_expression_over_aggregates(s):
    check(s, "SELECT k, SUM(v) / COUNT(v) + 1 AS m FROM t GROUP BY k",
          lambda s: t(s).group_by("k")
          .agg(F.sum("v").alias("__a1"), F.count(col("v")).alias("__a2"))
          .select(col("k"),
                  (col("__a1") / col("__a2") + lit(1)).alias("m")))


def test_having(s):
    check(s, "SELECT k, SUM(v) AS sv FROM t GROUP BY k HAVING SUM(v) > 40",
          lambda s: t(s).group_by("k").agg(F.sum("v").alias("sv"))
          .filter(col("sv") > lit(40)))
    # HAVING over an alias and over a hidden aggregate
    check(s, "SELECT k, SUM(v) AS sv FROM t GROUP BY k HAVING sv > 40",
          lambda s: t(s).group_by("k").agg(F.sum("v").alias("sv"))
          .filter(col("sv") > lit(40)))
    check(s, "SELECT k FROM t GROUP BY k HAVING COUNT(*) >= 2",
          lambda s: t(s).group_by("k").agg(F.count().alias("__c"))
          .filter(col("__c") >= lit(2)).select(col("k")))


def test_distinct(s):
    check(s, "SELECT DISTINCT k FROM t",
          lambda s: t(s).select(col("k")).group_by(col("k")).agg())


def test_count_distinct_unsupported(s):
    with pytest.raises(SqlAnalysisError, match="DISTINCT"):
        s.sql("SELECT COUNT(DISTINCT k) FROM t")


# -- set ops -----------------------------------------------------------------

def test_union_all_and_distinct(s):
    check(s, "SELECT k FROM t UNION ALL SELECT k FROM u",
          lambda s: t(s).select(col("k")).union(u(s).select(col("k"))))
    check(s, "SELECT k FROM t UNION SELECT k FROM u",
          lambda s: t(s).select(col("k")).union(u(s).select(col("k")))
          .group_by(col("k")).agg())


# -- joins -------------------------------------------------------------------

def test_join_on_equi(s):
    check(s, "SELECT id, v, w FROM t JOIN u ON t.k = u.k",
          lambda s: t(s).join(
              u(s).select(col("k").alias("k2"), col("w")),
              on=col("k") == col("k2"), how="inner")
          .select(col("id"), col("v"), col("w")))


def test_join_using_all_types(s):
    for how in ("inner", "left", "right", "full"):
        kw = {"inner": "JOIN", "left": "LEFT JOIN",
              "right": "RIGHT JOIN", "full": "FULL JOIN"}[how]
        check(s, f"SELECT id, v, w FROM t {kw} u USING (k)",
              lambda s, how=how: t(s).join(u(s), on=["k"], how=how)
              .select(col("id"), col("v"), col("w")))


def test_cross_join(s):
    check(s, "SELECT id, w FROM t CROSS JOIN u",
          lambda s: t(s).join(u(s)).select(col("id"), col("w")))


# -- comma-separated FROM (TPC-H's spelling: an inner join whose keys come
# from WHERE) --------------------------------------------------------------

def _plan_text(s, sql):
    return s.sql(sql).plan.tree_string()


def test_comma_from_two_tables_is_a_keyed_inner_join(s):
    sql = "SELECT id, v, w FROM t, u WHERE t.k = u.k AND w > 1.0 AND id < 6"
    check(s, sql,
          lambda s: t(s).filter(col("id") < lit(6)).join(
              u(s).filter(col("w") > lit(1.0)), on=["k"], how="inner")
          .select(col("id"), col("v"), col("w")))
    plan = _plan_text(s, sql)
    # the equality is the join's key, each one-table conjunct a Filter
    # below it, and nothing is left above
    assert "Join[inner]" in plan and "Join[cross]" not in plan
    assert plan.count("Filter[") == 2
    assert plan.index("Join[inner]") < plan.index("Filter[")


def test_comma_from_three_tables_joins_in_from_order(s):
    s.create_dataframe({"w": np.array([1.0, 2.0, 9.0]),
                        "z": np.array([7, 8, 9], dtype=np.int64)}) \
        .create_or_replace_temp_view("x3")
    sql = ("SELECT id, z FROM t, u, x3 "
           "WHERE t.k = u.k AND u.w = x3.w AND z > 7")
    check(s, sql,
          lambda s: t(s).join(u(s), on=["k"], how="inner")
          .join(s.table("x3").filter(col("z") > lit(7))
                .select(col("w").alias("w3"), col("z")),
                on=(col("w") == col("w3")), how="inner")
          .select(col("id"), col("z")))
    plan = _plan_text(s, sql)
    assert plan.count("Join[inner]") == 2 and "Join[cross]" not in plan
    # a table no equality ties to what is joined so far waits its turn:
    # x3 is listed second and joined last
    swapped = ("SELECT id, z FROM t, x3, u "
               "WHERE t.k = u.k AND u.w = x3.w AND z > 7")
    assert _canon(s.sql(swapped).collect()) == _canon(s.sql(sql).collect())
    assert "Join[cross]" not in _plan_text(s, swapped)


def test_comma_from_with_aliases(s):
    check(s, "SELECT a.id, b.w FROM t AS a, u b WHERE a.k = b.k",
          lambda s: t(s).join(u(s), on=["k"], how="inner")
          .select(col("id"), col("w")))


def test_comma_from_keeps_a_cross_table_or_above_the_join(s):
    sql = ("SELECT id, w FROM t, u "
           "WHERE t.k = u.k AND (id > 5 OR w > 1.5)")
    check(s, sql,
          lambda s: t(s).join(u(s), on=["k"], how="inner")
          .filter((col("id") > lit(5)) | (col("w") > lit(1.5)))
          .select(col("id"), col("w")))
    plan = _plan_text(s, sql)
    assert plan.index("Filter[") < plan.index("Join[inner]")
    assert plan.count("Filter[") == 1


def test_comma_from_without_an_equality_is_a_cross_join(s):
    sql = "SELECT id, w FROM t, u WHERE id < 3"
    check(s, sql,
          lambda s: t(s).filter(col("id") < lit(3)).join(u(s))
          .select(col("id"), col("w")))
    assert "Join[cross]" in _plan_text(s, sql)
    check(s, "SELECT id, w FROM t, u",
          lambda s: t(s).join(u(s)).select(col("id"), col("w")))


def test_comma_from_self_join_by_alias(s):
    sql = ("SELECT a.id, b.id FROM t a, t b "
           "WHERE a.k = b.k AND a.id < b.id")
    got = _canon(s.sql(sql).collect())
    rows = s.table("t").select(col("id"), col("k")).collect()
    want = _canon([(i, j) for i, ki in rows for j, kj in rows
                   if ki is not None and ki == kj and i < j])
    assert got == want and got
    assert "Join[inner]" in _plan_text(s, sql)


def test_comma_from_beside_an_explicit_join(s):
    s.create_dataframe({"w": np.array([1.0, 2.0, 9.0]),
                        "z": np.array([7, 8, 9], dtype=np.int64)}) \
        .create_or_replace_temp_view("x3")
    check(s, "SELECT id, z FROM t JOIN u ON t.k = u.k, x3 "
             "WHERE u.w = x3.w",
          lambda s: t(s).join(u(s), on=["k"], how="inner")
          .join(s.table("x3").select(col("w").alias("w3"), col("z")),
                on=(col("w") == col("w3")), how="inner")
          .select(col("id"), col("z")))


def test_semi_anti_join(s):
    check(s, "SELECT id FROM t LEFT SEMI JOIN u USING (k)",
          lambda s: t(s).join(u(s), on=["k"], how="leftsemi")
          .select(col("id")))
    check(s, "SELECT id FROM t LEFT ANTI JOIN u USING (k)",
          lambda s: t(s).join(u(s), on=["k"], how="leftanti")
          .select(col("id")))


def test_join_residual_condition(s):
    # equi conjunct rides the hash join; the rest stays a condition
    check(s, "SELECT id, w FROM t JOIN u ON t.k = u.k AND v > w * 5",
          lambda s: t(s).join(
              u(s).select(col("k").alias("k2"), col("w")),
              on=(col("k") == col("k2")) & (col("v") > col("w") * lit(5)),
              how="inner").select(col("id"), col("w")))


# -- ordering / limit --------------------------------------------------------

def test_order_by_variants(s):
    from spark_rapids_tpu.plan.nodes import SortOrder
    q = "SELECT id, v FROM t ORDER BY v DESC NULLS LAST, id"
    got = s.sql(q).collect()
    want = t(s).select(col("id"), col("v")).sort(
        SortOrder(col("v"), ascending=False, nulls_first=False),
        SortOrder(col("id"), ascending=True)).collect()
    assert got == want
    # ordinal
    assert s.sql("SELECT id, v FROM t ORDER BY 2 DESC NULLS LAST"
                 ).collect()[0][0] == 8


def test_order_by_hidden_input_column(s):
    # SQL: sort keys may reference input columns the projection drops
    got = s.sql("SELECT k FROM t WHERE v IS NOT NULL ORDER BY v DESC"
                ).collect()
    want = [(r[0],) for r in sorted(
        t(s).filter(col("v").isnotnull()).select(col("k"), col("v"))
        .collect(), key=lambda r: -r[1])]
    assert got == want


def test_limit(s):
    assert s.sql("SELECT id FROM t ORDER BY id LIMIT 3").collect() == \
        [(1,), (2,), (3,)]
    assert len(s.sql("SELECT id FROM t LIMIT 2").collect()) == 2


# -- windows -----------------------------------------------------------------

def test_window_functions(s):
    from spark_rapids_tpu.ops.window import Window as W
    check(s, "SELECT id, ROW_NUMBER() OVER (PARTITION BY k ORDER BY v) "
             "AS rn FROM t",
          lambda s: t(s).with_windows(
              rn=F.row_number().over(
                  W.partition_by("k").order_by("v")))
          .select(col("id"), col("rn")))
    check(s, "SELECT id, SUM(v) OVER (PARTITION BY k ORDER BY id) AS rs "
             "FROM t",
          lambda s: t(s).with_windows(
              rs=F.sum("v").over(W.partition_by("k").order_by("id")))
          .select(col("id"), col("rs")))


def test_window_frame(s):
    from spark_rapids_tpu.ops.window import Window as W
    check(s, "SELECT id, SUM(v) OVER (ORDER BY id ROWS BETWEEN 1 "
             "PRECEDING AND CURRENT ROW) AS rs FROM t",
          lambda s: t(s).with_windows(
              rs=F.sum("v").over(
                  W.order_by("id").rows_between(-1, 0)))
          .select(col("id"), col("rs")))


def test_window_lag_lead(s):
    from spark_rapids_tpu.ops.window import Window as W
    check(s, "SELECT id, LAG(v, 1) OVER (ORDER BY id) AS pv, "
             "LEAD(v, 2) OVER (ORDER BY id) AS nv FROM t",
          lambda s: t(s).with_windows(
              pv=F.lag("v", 1).over(W.order_by("id")),
              nv=F.lead("v", 2).over(W.order_by("id")))
          .select(col("id"), col("pv"), col("nv")))


# -- CTEs / subqueries -------------------------------------------------------

def test_cte(s):
    check(s, "WITH big AS (SELECT * FROM t WHERE v > 25), "
             "two AS (SELECT k FROM big) "
             "SELECT k, COUNT(*) AS c FROM two GROUP BY k",
          lambda s: t(s).filter(col("v") > lit(25)).select(col("k"))
          .group_by("k").agg(F.count().alias("c")))


def test_from_subquery(s):
    check(s, "SELECT kk FROM (SELECT k AS kk, v FROM t) WHERE v > 25",
          lambda s: t(s).select(col("k").alias("kk"), col("v"))
          .filter(col("v") > lit(25)).select(col("kk")))


def test_in_subquery_rewrites_to_semi_join(s):
    check(s, "SELECT id FROM t WHERE k IN (SELECT k FROM u)",
          lambda s: s.__class__ and __import__(
              "spark_rapids_tpu.plan", fromlist=["DataFrame"]).DataFrame(
              __import__("spark_rapids_tpu.plan",
                         fromlist=["nodes"]).nodes.Join(
                  t(s).plan, u(s).select(col("k")).plan, "leftsemi",
                  [col("k")], [col("k")]), s).select(col("id")))
    # NOT IN is null-aware (ANSI three-valued logic, Spark's
    # NullAwareAntiJoin): t's NULL-k row is UNKNOWN -> dropped, and only
    # k='c' rows fall outside u's {a, b, d}
    got = s.sql("SELECT id FROM t WHERE k NOT IN "
                "(SELECT k FROM u)").collect()
    assert sorted(r[0] for r in got) == [4, 8]
    # any NULL in the subquery empties the result (t.k has a NULL row)
    got = s.sql("SELECT id FROM t WHERE k NOT IN "
                "(SELECT k FROM t)").collect()
    assert got == []


def test_qualified_refs_across_same_named_join_columns(s):
    """a.x / b.x across a join where BOTH sides have x must bind their
    own side (the analyzer renames right-side duplicates; plan-layer
    name binding would otherwise silently pick the left copy)."""
    s2 = TpuSession()
    s2.create_dataframe({"id": np.array([1, 2], dtype=np.int64),
                         "x": np.array([1.0, 2.0])}) \
        .create_or_replace_temp_view("ta")
    s2.create_dataframe({"id": np.array([1, 2], dtype=np.int64),
                         "x": np.array([10.0, 20.0])}) \
        .create_or_replace_temp_view("tb")
    got = s2.sql("SELECT a.x, b.x FROM ta a JOIN tb b ON a.id = b.id "
                 "ORDER BY a.id").collect()
    assert got == [(1.0, 10.0), (2.0, 20.0)]
    # residual (non-equi) condition across the same-named columns
    got = s2.sql("SELECT a.id FROM ta a JOIN tb b "
                 "ON a.id = b.id AND a.x < b.x").collect()
    assert sorted(got) == [(1,), (2,)]
    # star expansion shows both copies under their SQL-level names
    df = s2.sql("SELECT * FROM ta a JOIN tb b ON a.id = b.id")
    assert [n for n, _ in df.schema] == ["id", "x", "id", "x"]
    row = sorted(df.collect())[0]
    assert row == (1, 1.0, 1, 10.0)


def test_right_full_using_coalesces_key(s):
    """RIGHT/FULL ... USING output the right/merged key, not NULL, for
    unmatched right rows (SQL USING = COALESCE(l.k, r.k))."""
    s2 = TpuSession()
    s2.create_dataframe({"k": np.array([1, 2], dtype=np.int64),
                         "va": np.array([10.0, 20.0])}) \
        .create_or_replace_temp_view("ja")
    s2.create_dataframe({"k": np.array([2, 3], dtype=np.int64),
                         "vb": np.array([200.0, 300.0])}) \
        .create_or_replace_temp_view("jb")
    got = sorted(s2.sql("SELECT k, vb FROM ja RIGHT JOIN jb USING (k)")
                 .collect())
    assert got == [(2, 200.0), (3, 300.0)]
    got = sorted(r[0] for r in s2.sql(
        "SELECT k FROM ja FULL JOIN jb USING (k)").collect())
    assert got == [1, 2, 3]


def test_create_or_replace_view_with_using_table(s, tmp_path):
    """CREATE OR REPLACE ... USING must replace an existing plan view of
    the same name (one namespace), and DROP VIEW must clear both."""
    s2 = TpuSession()
    s2.create_dataframe({"x": np.arange(3, dtype=np.int64)}) \
        .create_or_replace_temp_view("vv")
    p = str(tmp_path / "pq8")
    s2.create_dataframe({"x": np.arange(8, dtype=np.int64)}) \
        .write_parquet(p)
    s2.sql(f"CREATE OR REPLACE TEMP VIEW vv USING parquet "
           f"OPTIONS (path '{p}')")
    assert s2.sql("SELECT COUNT(*) FROM vv").collect()[0][0] == 8
    s2.sql("DROP VIEW vv")
    with pytest.raises(SqlAnalysisError, match="not found"):
        s2.sql("SELECT * FROM vv")


def test_quoted_identifiers_escape_keywords(s):
    """Backtick/double-quoted identifiers are never keywords — columns
    named `order`, `from`, `null` stay reachable."""
    s2 = TpuSession()
    s2.create_dataframe({
        "order": np.arange(3, dtype=np.int64),
        "from": np.array(["x", "y", "z"], dtype=object),
    }).create_or_replace_temp_view("kw")
    got = s2.sql('SELECT `order`, "from" FROM kw WHERE `order` > 0 '
                 "ORDER BY `order` DESC").collect()
    assert got == [(2, "z"), (1, "y")]
    # quoted alias that collides with a keyword
    got = s2.sql("SELECT `order` AS `select` FROM kw "
                 "ORDER BY `select`").collect()
    assert got == [(0,), (1,), (2,)]


def test_scalar_subquery(s):
    got = s.sql("SELECT id FROM t WHERE v > (SELECT AVG(v) FROM t) "
                "ORDER BY id").collect()
    avg = s.sql("SELECT AVG(v) FROM t").collect()[0][0]
    want = [(r[0],) for r in t(s).filter(col("v") > lit(avg))
            .select(col("id")).sort("id").collect()]
    assert got == want


# -- hints -------------------------------------------------------------------

def test_repartition_hint(s):
    check(s, "SELECT /*+ REPARTITION(4, k) */ k, COUNT(*) AS c FROM t "
             "GROUP BY k",
          lambda s: t(s).repartition(4, "k").group_by("k")
          .agg(F.count().alias("c")))


# -- temp views / catalog ----------------------------------------------------

def test_create_drop_temp_view(s):
    s.sql("CREATE TEMP VIEW big AS SELECT * FROM t WHERE v > 25")
    assert s.sql("SELECT COUNT(*) FROM big").collect()[0][0] == 5
    assert "big" in s.catalog.list_tables()
    # resolvable through session.table too
    assert s.table("big").count() == 5
    s.sql("CREATE OR REPLACE TEMP VIEW big AS SELECT * FROM t "
          "WHERE v > 55")
    assert s.sql("SELECT COUNT(*) FROM big").collect()[0][0] == 3
    with pytest.raises(SqlAnalysisError, match="already exists"):
        s.sql("CREATE TEMP VIEW big AS SELECT * FROM t")
    s.sql("DROP VIEW big")
    assert "big" not in s.catalog.list_tables()
    with pytest.raises(SqlAnalysisError, match="not found"):
        s.sql("DROP VIEW big")
    s.sql("DROP VIEW IF EXISTS big")  # no raise


def test_create_view_using_format(s, tmp_path):
    p = str(tmp_path / "pq")
    t(s).select(col("id"), col("v")).write_parquet(p)
    s.sql(f"CREATE TEMP VIEW pq_tbl USING parquet OPTIONS (path '{p}')")
    assert s.sql("SELECT COUNT(*) FROM pq_tbl").collect()[0][0] == 8
    got = _canon(s.sql("SELECT id, v FROM pq_tbl").collect())
    assert got == _canon(t(s).select(col("id"), col("v")).collect())
    s.sql("DROP VIEW pq_tbl")


def test_view_sees_plan_not_name(s):
    """Temp views capture the PLAN: re-registering t does not change an
    existing view built over the old t."""
    s2 = TpuSession()
    s2.create_dataframe({"x": np.arange(3, dtype=np.int64)}) \
        .create_or_replace_temp_view("src")
    s2.sql("CREATE TEMP VIEW snap AS SELECT * FROM src")
    s2.create_dataframe({"x": np.arange(10, dtype=np.int64)}) \
        .create_or_replace_temp_view("src")
    assert s2.sql("SELECT COUNT(*) FROM snap").collect()[0][0] == 3
    assert s2.sql("SELECT COUNT(*) FROM src").collect()[0][0] == 10


# -- function registration ---------------------------------------------------

def test_session_registered_udf(s):
    from spark_rapids_tpu.udf import udf
    s.catalog.register_function("plus_one", udf(lambda x: x + 1))
    try:
        check(s, "SELECT plus_one(id) AS p FROM t",
              lambda s: t(s).select((col("id") + lit(1)).alias("p")))
    finally:
        s.catalog.unregister_function("plus_one")


def test_global_registered_function(s):
    F.register_sql_function("twice", lambda e: e * lit(2))
    try:
        check(s, "SELECT twice(v) AS p FROM t",
              lambda s: t(s).select((col("v") * lit(2)).alias("p")))
    finally:
        F.unregister_sql_function("twice")


def test_hive_udf_resolves(s):
    from spark_rapids_tpu.hive_udf import (
        register_hive_udf,
        unregister_hive_udf,
    )
    register_hive_udf("sql_t_upper",
                      lambda x: x.upper() if x is not None else None,
                      "string")
    try:
        got = _canon(s.sql("SELECT sql_t_upper(k) AS ku FROM t").collect())
        want = _canon([(k.upper() if k else None,)
                       for (k,) in t(s).select(col("k")).collect()])
        assert got == want
    finally:
        unregister_hive_udf("sql_t_upper")


def test_f_expr(s):
    got = _canon(t(s).select(F.expr("v * 2 + id").alias("e")).collect())
    want = _canon(t(s).select(
        (col("v") * lit(2) + col("id")).alias("e")).collect())
    assert got == want


# -- error surfaces ----------------------------------------------------------

def test_parse_error_positions(s):
    with pytest.raises(SqlParseError) as ei:
        s.sql("SELECT id FROM t WHERE")
    assert ei.value.line == 1 and ei.value.col >= 23
    with pytest.raises(SqlParseError) as ei:
        s.sql("SELECT id,\nFROM t")
    assert ei.value.line == 2
    assert "^" in str(ei.value)  # caret snippet
    with pytest.raises(SqlParseError, match="expected BY"):
        s.sql("SELECT id FROM t ORDER id")
    with pytest.raises(SqlParseError, match="after statement"):
        s.sql("SELECT id FROM t garbage extra")
    with pytest.raises(SqlParseError, match="unterminated string"):
        s.sql("SELECT 'oops FROM t")


def test_analysis_error_positions(s):
    with pytest.raises(SqlAnalysisError) as ei:
        s.sql("SELECT nope FROM t")
    assert "cannot resolve column 'nope'" in str(ei.value)
    assert ei.value.line == 1 and ei.value.col == 8
    with pytest.raises(SqlAnalysisError, match="not found"):
        s.sql("SELECT * FROM no_such_table")
    with pytest.raises(SqlAnalysisError, match="undefined function"):
        s.sql("SELECT frobnicate(id) FROM t")
    with pytest.raises(SqlAnalysisError, match="argument"):
        s.sql("SELECT upper(k, v) FROM t")
    with pytest.raises(SqlAnalysisError, match="GROUP BY"):
        s.sql("SELECT k, v FROM t GROUP BY k")


def test_unsupported_constructs_report_reasons(s):
    # overrides-style per-construct reasons
    with pytest.raises(SqlParseError, match="EXISTS subqueries are not "
                                            "supported"):
        s.sql("SELECT id FROM t WHERE EXISTS (SELECT 1 FROM u)")
    with pytest.raises(SqlAnalysisError,
                       match="is not supported by the SQL front end"):
        s.sql("SELECT id FROM t WHERE v > ALL_ROWS(u)" if False else
              "SELECT INTERVAL 3 DAYS FROM t")
    with pytest.raises(SqlAnalysisError,
                       match="window functions must be top-level"):
        s.sql("SELECT ROW_NUMBER() OVER (ORDER BY id) + 1 FROM t")
    with pytest.raises(SqlAnalysisError, match="semi join"):
        s.sql("SELECT id FROM t WHERE k IN (SELECT k FROM u) OR v > 5")
    with pytest.raises(SqlAnalysisError, match="hint"):
        s.sql("SELECT /*+ BROADCAST(u) */ id FROM t")


def test_explain_carries_sql_text(s):
    out = s.sql("SELECT id FROM t WHERE v > 5").explain()
    assert out.startswith("-- SQL: SELECT id FROM t WHERE v > 5")


# -- ScaleTest q1-q10: SQL text == DSL, results AND dispatch counts ----------

@pytest.fixture(scope="module")
def scale_setup():
    from spark_rapids_tpu.datagen import scale_test_specs
    from scale_test import build_queries, build_sql_queries
    sf = 0.002
    specs = scale_test_specs(sf)
    tables = {n: sp.generate_table(sf, seed=0) for n, sp in specs.items()}
    s_dsl, s_sql = TpuSession(), TpuSession()
    return (build_queries(s_dsl, tables),
            build_sql_queries(s_sql, tables), s_dsl, s_sql)


@pytest.mark.parametrize("name", [f"q{i}" for i in range(1, 23)])
def test_scale_query_sql_equals_dsl(scale_setup, name):
    dsl_q, sql_q, s_dsl, s_sql = scale_setup
    a = _canon(dsl_q[name]().collect())
    b = _canon(sql_q[name]().collect())
    assert a == b, f"{name}: SQL and DSL results differ"
    # warm runs: device dispatch counts must match exactly (the SQL path
    # lowers onto the same plan layer — no parallel execution engine)
    dsl_q[name]().collect_table()
    da = s_dsl.last_dispatches
    sql_q[name]().collect_table()
    db = s_sql.last_dispatches
    assert da == db, f"{name}: dispatches dsl={da} sql={db}"
