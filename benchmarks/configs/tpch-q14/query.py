"""tpch-q14 through the session API: Q14 as TPC-H publishes it."""

import datetime

EPOCH = datetime.date(1970, 1, 1).toordinal()


def build(sess, paths: dict, cfg: dict):
    """A fresh DataFrame over the Parquet files of both tables; nothing runs
    until `collect()`."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.expr.conditional import CaseWhen
    from spark_rapids_tpu.expr.core import Literal, lit
    from spark_rapids_tpu.types import DATE
    p = cfg["params"]
    first = datetime.date.fromisoformat(p["date"])
    after = first.replace(year=first.year + first.month // 12,
                          month=first.month % 12 + 1)
    lo, hi = (Literal(d.toordinal() - EPOCH, DATE) for d in (first, after))
    rev = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    promo = CaseWhen([(F.like(col("p_type"), p["type_prefix"] + "%"), rev)],
                     lit(0.0))
    return (sess.read_parquet(paths["lineitem"])
            .filter((col("l_shipdate") >= lo) & (col("l_shipdate") < hi))
            .join(sess.read_parquet(paths["part"]),
                  left_on=col("l_partkey"), right_on=col("p_partkey"))
            .select(promo.alias("promo"), rev.alias("rev"))
            .agg((F.sum(col("promo")), "promo"), (F.sum(col("rev")), "rev"))
            .select((lit(100.0) * col("promo") / col("rev"))
                    .alias("promo_revenue")))
