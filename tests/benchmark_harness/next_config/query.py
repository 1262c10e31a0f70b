"""The next PR's configuration as `test_benchmark_harness.appended_copy`
writes it, through the session API: TPC-H Q3 without the lineitem
predicate, over a lineitem that has no `l_shipdate`."""

import datetime

EPOCH = datetime.date(1970, 1, 1).toordinal()


def build(sess, paths: dict, cfg: dict):
    """A fresh DataFrame over the Parquet files of the three tables; nothing
    runs until `collect()`."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.expr.core import Literal, lit
    from spark_rapids_tpu.types import DATE
    p = cfg["params"]
    date = Literal(datetime.date.fromisoformat(p["date"]).toordinal() - EPOCH,
                   DATE)
    orders = sess.read_parquet(paths["orders"]) \
        .filter(col("o_orderdate") < date)
    customer = sess.read_parquet(paths["customer"]) \
        .filter(col("c_mktsegment") == lit(p["segment"]))
    rev = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (sess.read_parquet(paths["lineitem"])
            .join(orders.join(customer, left_on=col("o_custkey"),
                              right_on=col("c_custkey")),
                  left_on=col("l_orderkey"), right_on=col("o_orderkey"))
            .select(col("l_orderkey"), col("o_orderdate"),
                    col("o_shippriority"), rev.alias("rev"))
            .group_by("l_orderkey", "o_orderdate", "o_shippriority")
            .agg((F.sum(col("rev")), "revenue"))
            .select(col("l_orderkey"), col("revenue"), col("o_orderdate"),
                    col("o_shippriority"))
            .sort(("revenue", False), "o_orderdate")
            .limit(int(p["rows"])))
