"""TPC-H Q1 (spec 2.4.1), plainly, over the generator's arrays.

`float_dtype` is the type the spec's decimal columns are computed in:
float64 is the reference; float32 is the low-precision control."""

import numpy as np

from benchmarks.datagen.tpch import days


def run(tables, params, float_dtype=np.float64):
    li = tables["lineitem"]["columns"]
    f = float_dtype
    keep = li["l_shipdate"].values <= days("1998-12-01") - int(params["DELTA"])
    flags = li["l_returnflag"].dictionary
    statuses = li["l_linestatus"].dictionary
    # one group id per row, -1 for a row the filter drops; the dictionaries
    # are sorted, so group-id order is the ORDER BY
    group = np.where(keep, li["l_returnflag"].values * len(statuses)
                     + li["l_linestatus"].values, -1).astype(np.int8)
    qty = li["l_quantity"].values.astype(f, copy=False)
    price = li["l_extendedprice"].values.astype(f, copy=False)
    disc = li["l_discount"].values.astype(f, copy=False)
    tax = li["l_tax"].values.astype(f, copy=False)
    disc_price = price * (f(1) - disc)
    charge = disc_price * (f(1) + tax)
    names = ("l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
             "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
             "avg_disc", "count_order")
    out = {name: [] for name in names}
    for g in range(len(flags) * len(statuses)):
        rows = np.flatnonzero(group == g)
        n = len(rows)
        if n == 0:
            continue
        s_qty, s_price, s_disc_price, s_charge, s_disc = (
            v[rows].sum(dtype=f) for v in (qty, price, disc_price, charge,
                                           disc))
        out["l_returnflag"].append(str(flags[g // len(statuses)]))
        out["l_linestatus"].append(str(statuses[g % len(statuses)]))
        out["sum_qty"].append(float(s_qty))
        out["sum_base_price"].append(float(s_price))
        out["sum_disc_price"].append(float(s_disc_price))
        out["sum_charge"].append(float(s_charge))
        out["avg_qty"].append(float(s_qty / f(n)))
        out["avg_price"].append(float(s_price / f(n)))
        out["avg_disc"].append(float(s_disc / f(n)))
        out["count_order"].append(n)
    return out
