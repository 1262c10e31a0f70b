"""tpch-q6 through the session API: Q6 as TPC-H publishes it."""

import datetime

EPOCH = datetime.date(1970, 1, 1).toordinal()


def build(sess, paths: dict, cfg: dict):
    """A fresh DataFrame over the Parquet files; nothing runs until
    `collect()`."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.expr.core import Literal, lit
    from spark_rapids_tpu.types import DATE
    p = cfg["params"]
    # DATE literals as days since the epoch: `lit(datetime.date)` infers
    # DATE but cannot be evaluated (PERF.md, Open questions)
    first = datetime.date.fromisoformat(p["date"])
    lo, hi = (Literal(d.toordinal() - EPOCH, DATE)
              for d in (first, first.replace(year=first.year + 1)))
    return (sess.read_parquet(paths["lineitem"])
            .filter((col("l_shipdate") >= lo) & (col("l_shipdate") < hi)
                    & (col("l_discount") >= lit(float(p["discount_min"])))
                    & (col("l_discount") <= lit(float(p["discount_max"])))
                    & (col("l_quantity") < lit(float(p["quantity_below"]))))
            .agg((F.sum(col("l_extendedprice") * col("l_discount")),
                  "revenue")))
