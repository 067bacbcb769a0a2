"""TPC-H Query 1 (the Pricing Summary Report) through the lazy plan, beside
a plain numpy / Python-integer reference.

Decimals are int64 counts of hundredths and the QUERY carries the scales:
``1 - l_discount`` is ``100 - l_discount`` at scale 2, so ``sum_disc_price``
comes out at scale 4 and ``sum_charge`` at scale 6. The sums are exact
64-bit integers with or without x64 (without it an int64 column is held as
two 32-bit word planes and the dense groupby adds it up in limbs).

Run: JAX_PLATFORMS=cpu python examples/tpch_q1_example.py
"""
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

import cylon_tpu as ct  # noqa: E402
from cylon_tpu import plan  # noqa: E402
from cylon_tpu.plan import col  # noqa: E402
from generators import tpch_lineitem  # noqa: E402
from references import tpch_q1_exact  # noqa: E402

SHIPDATE_MAX = 10471        # 1998-12-01 - 90 days, in days since 1970-01-01
traffic = {"table": "lineitem", "shipdate_max": SHIPDATE_MAX,
           "columns": ["l_quantity", "l_extendedprice", "l_discount",
                       "l_tax", "l_returnflag", "l_linestatus",
                       "l_shipdate"]}
config = {"rows": 200_000, "scale_factor": 1}
tables = tpch_lineitem.generate(config, traffic, 1, 1.0, seed=7)["tables"]

ctx = ct.CylonContext.Init()
lineitem = ct.Table.from_pydict(ctx, tables["lineitem"])

q1 = (plan.scan(lineitem)
      .filter(col("l_shipdate") <= SHIPDATE_MAX)
      .with_columns({
          "disc_price": col("l_extendedprice") * (100 - col("l_discount")),
          "charge": col("disc_price") * (100 + col("l_tax"))})
      .groupby(["l_returnflag", "l_linestatus"],
               ["l_quantity", "l_extendedprice", "disc_price", "charge",
                "l_quantity", "l_extendedprice", "l_discount", "l_quantity"],
               ["sum", "sum", "sum", "sum", "mean", "mean", "mean", "count"])
      .sort(["l_returnflag", "l_linestatus"]))

print(q1.explain())
out = q1.execute().to_pandas()
out.columns = list(tpch_q1_exact.NAMES)
ref = tpch_q1_exact.reference(tables, config, traffic)


def decimal(value, scale):
    return f"{value // 10 ** scale}.{value % 10 ** scale:0{scale}d}"


print("\nflag status  sum_qty  sum_base_price  sum_disc_price  sum_charge  "
      "avg_qty  avg_price  avg_disc  count   (engine, then reference)")
for i, (f, s) in enumerate(ref["groups"]):
    row = out.iloc[i]
    got = [decimal(int(row[name]), scale) for name, scale in
           zip(tpch_q1_exact.SUMS, (2, 2, 4, 6))]
    want = [decimal(ref["sums"][j][i], scale)
            for j, scale in enumerate((2, 2, 4, 6))]
    avgs = [f"{row[name] / 100:.4f}" for name in tpch_q1_exact.AVGS]
    ravg = [f"{ref['sums'][j][i] / ref['count'][i] / 100:.4f}"
            for j in (0, 1, 4)]
    print(row["l_returnflag"], row["l_linestatus"], *got, *avgs,
          int(row["count_order"]))
    print(ref["flags"][f], ref["status"][s], *want, *ravg, ref["count"][i])
    assert got == want and int(row["count_order"]) == ref["count"][i]
print("\nthe four sums and the count agree as integers")
