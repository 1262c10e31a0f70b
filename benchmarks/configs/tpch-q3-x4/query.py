"""tpch-q3-x4 through the session API: Q3 as TPC-H publishes it, on a
session whose conf spans four chips."""

import datetime

EPOCH = datetime.date(1970, 1, 1).toordinal()

_plan_checked = False
#: `exchange.counters()` and `aggregate.counters()` as the last query
#: found them (`check_only_the_top_n_moved`)
_counted = None


def _nodes(plan):
    yield plan
    for child in plan.children:
        yield from _nodes(child)


def check_plan_is_distributed(df, partitions: int) -> None:
    """Once a process, before the first scan: refuse a program whose plan
    for Q3 does not spread the query over `partitions` chips, or has no
    plan fingerprint, and say why, rather than hold four chips for the
    rest of the run's limit. Read from the plan the program builds
    (`_exec()` plans and runs nothing). A plan with no exchange, or with
    one over fewer partitions, runs the one-chip query on a four-chip host
    (a program whose session takes no mesh from its conf). Without a
    fingerprint an exec keys its program sites by instance, every
    `collect()` traces and compiles the plan's programs anew, and the
    warm-up never sees a `collect()` that compiles nothing."""
    global _plan_checked
    if _plan_checked:
        return
    plan = df._exec()
    counts = [n.n_partitions for n in _nodes(plan)
              if "Exchange" in type(n).__name__
              and hasattr(n, "n_partitions")]
    if not counts or min(counts) < partitions:
        raise RuntimeError(
            f"this program plans Q3 with exchanges over {counts or 'no'} "
            f"partitions:\n{plan.tree_string()}\nso the query runs on "
            f"fewer than the {partitions} chips the configuration spans: "
            "tpch-q3-x4 needs a program whose session takes its mesh from "
            "spark.rapids.shuffle.mode=ICI and "
            "spark.rapids.sql.shuffle.partitions")
    if plan.plan_fingerprint() is None:
        raise RuntimeError(
            "this program plans Q3 on the mesh without a plan "
            f"fingerprint:\n{plan.tree_string()}\nso every collect() "
            "compiles the plan's programs anew and no window can open (the "
            "warm-up needs one collect() that compiles nothing): tpch-q3-x4 "
            "needs a program whose every exec of this plan has a "
            "fingerprint")
    _plan_checked = True


def check_only_the_top_n_moved(partitions: int, rows: int) -> None:
    """Before each query, of the one before it: refuse a program that moved
    more between chips, outside its exchanges, than the top-N's last
    selection does. There each partition but the first hands the first
    one's chip its first `rows` rows, one batch of the smallest bucket that
    holds them, once a run of the plan. A plan that funnels whole
    partitions onto one chip would otherwise answer correctly and pass for
    a slow four-chip run, against the configuration's guarantees that no
    partition's rows are processed on more than one chip."""
    global _counted
    from spark_rapids_tpu.columnar.column import bucket_capacity
    from spark_rapids_tpu.exec import aggregate, exchange
    now = (exchange.counters(), aggregate.counters())
    if _counted is not None:
        moved = {k: now[0][k] - _counted[0][k]
                 for k in ("batches_moved", "rows_moved", "bytes_moved")}
        runs = 1 + now[1]["plan_reruns"] - _counted[1]["plan_reruns"]
        most = (partitions - 1) * runs
        if moved["batches_moved"] > most or \
                moved["rows_moved"] > most * bucket_capacity(rows):
            raise RuntimeError(
                f"the last query moved {moved} between chips outside its "
                f"exchanges, over {runs} run(s) of the plan: more than the "
                f"top-{rows}'s last selection ({partitions - 1} batches of "
                f"at most {bucket_capacity(rows)} rows a run), so some "
                "partition's rows were processed on a chip not its own")
    _counted = now


def build(sess, paths: dict, cfg: dict):
    """A fresh DataFrame over the Parquet files of the three tables; nothing
    runs until `collect()`."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.expr.core import Literal, lit
    from spark_rapids_tpu.types import DATE
    p = cfg["params"]
    # the date as days since the epoch: `lit(datetime.date)` infers DATE but
    # cannot be evaluated
    date = Literal(datetime.date.fromisoformat(p["date"]).toordinal() - EPOCH,
                   DATE)
    orders = sess.read_parquet(paths["orders"]) \
        .filter(col("o_orderdate") < date)
    customer = sess.read_parquet(paths["customer"]) \
        .filter(col("c_mktsegment") == lit(p["segment"]))
    rev = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    df = (sess.read_parquet(paths["lineitem"])
          .filter(col("l_shipdate") > date)
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
    check_plan_is_distributed(df, int(cfg["partitions"]))
    check_only_the_top_n_moved(int(cfg["partitions"]), int(p["rows"]))
    return df
