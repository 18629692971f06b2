"""TPC-H Q3 (spec 2.4.3, Shipping Priority), plainly, over the generator's
arrays: filter each table, look the orders up by key, sum by order, sort,
take the first rows.

`float_dtype` is the type the spec's decimal columns are computed in:
float64 is the reference; float32 is the low-precision control."""

import numpy as np

from benchmarks.datagen.tpch import days

LIMIT = 10


def _ranked(tables, params, f, limit):
    c = tables["customer"]["columns"]
    o = tables["orders"]["columns"]
    li = tables["lineitem"]["columns"]
    date = days(params["DATE"])
    segments = c["c_mktsegment"].dictionary.tolist()
    segment = segments.index(params["SEGMENT"]) \
        if params["SEGMENT"] in segments else -1

    # customers of the segment, as a flag by key
    in_segment = np.zeros(int(c["c_custkey"].values.max()) + 1, dtype=bool)
    in_segment[c["c_custkey"].values[
        c["c_mktsegment"].values == segment]] = True
    # their orders placed before the date, sorted by key for the look-up
    kept = np.flatnonzero((o["o_orderdate"].values < date)
                          & in_segment[o["o_custkey"].values])
    kept = kept[np.argsort(o["o_orderkey"].values[kept], kind="stable")]
    keys = o["o_orderkey"].values[kept]
    # the lines shipped after the date that belong to such an order
    lines = np.flatnonzero(li["l_shipdate"].values > date)
    order_of = np.searchsorted(keys, li["l_orderkey"].values[lines])
    order_of = np.minimum(order_of, max(len(keys) - 1, 0))
    hit = (keys[order_of] == li["l_orderkey"].values[lines]) \
        if len(keys) else np.zeros(len(lines), dtype=bool)
    lines, order_of = lines[hit], order_of[hit]

    price = li["l_extendedprice"].values[lines].astype(f)
    disc = li["l_discount"].values[lines].astype(f)
    revenue = np.zeros(len(keys), dtype=f)
    np.add.at(revenue, order_of, price * (f(1) - disc))
    grouped = np.flatnonzero(np.bincount(order_of, minlength=len(keys)))

    dates = o["o_orderdate"].values[kept]
    # revenue descending, then o_orderdate (lexsort: last key first)
    order = grouped[np.lexsort((dates[grouped], -revenue[grouped]))][:limit]
    return {
        "l_orderkey": [int(k) for k in keys[order]],
        "revenue": [float(r) for r in revenue[order]],
        "o_orderdate": [int(d) for d in dates[order]],
        "o_shippriority": [int(p) for p in
                           o["o_shippriority"].values[kept][order]],
    }


def run(tables, params, float_dtype=np.float64):
    return _ranked(tables, params, float_dtype, LIMIT)


def smallest_revenue_gap(tables, params) -> float:
    """The smallest relative gap between neighbouring `revenue` values
    among the first LIMIT + 1 rows: under about 1e-6 the order of the
    answer's rows would be a matter of rounding."""
    revenue = _ranked(tables, params, np.float64, LIMIT + 1)["revenue"]
    return min(((a - b) / a for a, b in zip(revenue, revenue[1:])),
               default=float("inf"))
