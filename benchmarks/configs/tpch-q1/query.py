"""tpch-q1 through the session API: Q1 as TPC-H publishes it."""

import datetime

EPOCH = datetime.date(1970, 1, 1).toordinal()
#: the most sort key lanes the chip's compiler is known to finish inside a
#: run: four u32 lanes took it 110 s, twenty were unfinished after 38 min
#: (PERF.md section 6)
MAX_SORT_KEYS = 4

_sort_keys_checked = False


def result_sort_keys() -> int:
    """How many key lanes the PROGRAM sorts Q1's result on: two one-byte
    string keys. Its own `SortExec` measures a two-row batch of such keys
    (a tiny reduction, the only thing that runs) and hands its sort program
    the batch and the width it measured; here that hand-over is recorded in
    the program's place and the sort kernel is traced abstractly
    (`jax.make_jaxpr`: nothing is compiled, no sort runs). The count is the
    `num_keys` of the `sort` in the trace: the lanes after the u64 split,
    and the iota."""
    import jax
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.basic import InMemoryScanExec
    from spark_rapids_tpu.exec.sort import SortExec
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.types import STRING, Schema, StructField
    schema = Schema((StructField("l_returnflag", STRING),
                     StructField("l_linestatus", STRING)))
    batch = ColumnarBatch.from_pydict(
        {"l_returnflag": ["N", "A"], "l_linestatus": ["O", "F"]}, schema)
    sort = SortExec([col(name) for name in schema.names],
                    InMemoryScanExec([batch], schema))
    handed = []

    def record(batch, width):
        handed.append(width)
        return batch

    sort._jit_sort = record
    sort._sort_one(batch)
    (width,) = handed
    jaxpr = jax.make_jaxpr(lambda b: sort._sort_kernel(b, width))(batch)

    def sorts(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "sort":
                yield int(eqn.params["num_keys"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from sorts(sub)

    return max(sorts(jaxpr.jaxpr))


def check_result_sort() -> None:
    """Once a process, before the first scan: refuse a program whose result
    sort the chip's compiler cannot finish inside a run, and say why,
    rather than hold a chip for the rest of the run's limit."""
    global _sort_keys_checked
    if _sort_keys_checked:
        return
    keys = result_sort_keys()
    if keys > MAX_SORT_KEYS:
        raise RuntimeError(
            f"this program sorts Q1's two CHAR(1) keys on {keys} key lanes; "
            f"the chip's compiler is known to finish {MAX_SORT_KEYS} inside "
            f"a run (110 s) and did not finish 20 in 38 min: tpch-q1 needs "
            f"a result sort whose lanes follow the keys' measured width")
    _sort_keys_checked = True


def build(sess, paths: dict, cfg: dict):
    """A fresh DataFrame over the Parquet files; nothing runs until
    `collect()`."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.expr.core import Literal, lit
    from spark_rapids_tpu.types import DATE
    check_result_sort()
    # the cut-off as days since the epoch: `lit(datetime.date)` infers DATE
    # but cannot be evaluated (PERF.md, Open questions)
    last = datetime.date(1998, 12, 1).toordinal() - EPOCH
    cut = Literal(last - int(cfg["params"]["delta_days"]), DATE)
    disc_price = col("l_extendedprice") * (lit(1.0) - col("l_discount"))
    return (sess.read_parquet(paths["lineitem"])
            .filter(col("l_shipdate") <= cut)
            .select(col("l_returnflag"), col("l_linestatus"),
                    col("l_quantity"), col("l_extendedprice"),
                    col("l_discount"), disc_price.alias("disc_price"),
                    (disc_price * (lit(1.0) + col("l_tax"))).alias("charge"))
            .group_by("l_returnflag", "l_linestatus")
            .agg((F.sum(col("l_quantity")), "sum_qty"),
                 (F.sum(col("l_extendedprice")), "sum_base_price"),
                 (F.sum(col("disc_price")), "sum_disc_price"),
                 (F.sum(col("charge")), "sum_charge"),
                 (F.avg(col("l_quantity")), "avg_qty"),
                 (F.avg(col("l_extendedprice")), "avg_price"),
                 (F.avg(col("l_discount")), "avg_disc"),
                 (F.count(), "count_order"))
            .sort("l_returnflag", "l_linestatus"))
