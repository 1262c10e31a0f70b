"""q3 per-stage steady-state costs: each prefix of the pipeline runs ITERS
chained iterations whose outputs fold into a device checksum scalar, and
the wall clock stops only after np.asarray(checksum) lands on the host, so
no iteration can be elided and every queued program has completed.

Prints one line per prefix; the difference between consecutive prefixes is
the marginal steady-state cost of that stage.
"""

import sys
import time

sys.path.insert(0, "/root/repo")

import numpy as np
import jax
import jax.numpy as jnp

import bench
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, bucket_capacity
from spark_rapids_tpu.exec.aggregate import AggregateExec
from spark_rapids_tpu.exec.basic import (FilterExec, InMemoryScanExec,
                                         ProjectExec)
from spark_rapids_tpu.exec.joins import HashJoinExec
from spark_rapids_tpu.exec.sort import TopNExec
from spark_rapids_tpu.exec.speculation import speculation_scope
from spark_rapids_tpu.expr.aggexprs import Sum
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.types import DOUBLE, INT, LONG, Schema, StructField

d = bench.build_q3_data()
o_schema = Schema((StructField("o_orderkey", LONG),
                   StructField("o_flag", INT)))
l_schema = Schema((StructField("l_orderkey", LONG),
                   StructField("l_price", DOUBLE),
                   StructField("l_disc", DOUBLE),
                   StructField("l_flag", INT)))


def mk_batch(schema, n):
    cap = bucket_capacity(n)
    cols = [Column.from_numpy(d[f.name], f.data_type, capacity=cap)
            for f in schema.fields]
    return ColumnarBatch(cols, n, schema)


orders = mk_batch(o_schema, bench.N_ORDERS)
lines = mk_batch(l_schema, bench.N_LINES)


def mk_stages():
    o_scan = FilterExec(col("o_flag") < lit(5),
                        InMemoryScanExec([orders], o_schema))
    l_scan = FilterExec(col("l_flag") != lit(0),
                        InMemoryScanExec([lines], l_schema))
    joined = HashJoinExec(l_scan, o_scan, [col("l_orderkey")],
                          [col("o_orderkey")], "inner", build_side="right")
    proj = ProjectExec([
        col("l_orderkey"),
        (col("l_price") * (lit(1.0) - col("l_disc"))).alias("rev")], joined)
    agg = AggregateExec([col("l_orderkey")], [(Sum(col("rev")), "revenue")],
                        proj)
    agg._spec_enabled = False
    top = TopNExec(10, [(col("revenue"), False)], agg)
    return [("filter_l", l_scan), ("filter_o", o_scan), ("join", joined),
            ("join+proj", proj), ("+agg", agg), ("+topn", top)]


@jax.jit
def checksum(batch, prev):
    total = prev + batch.num_rows.astype(jnp.float64)
    for c in batch.columns:
        if c.data is None:
            continue
        v = jnp.where(c.validity, c.data, jnp.zeros((), c.data.dtype))
        total = total + jnp.sum(v.astype(jnp.float64))
    return total


ITERS = int(sys.argv[1]) if len(sys.argv) > 1 else 6
REPS = int(sys.argv[2]) if len(sys.argv) > 2 else 2

stages = mk_stages()
results = {name: [] for name, _ in stages}

with speculation_scope() as scope:
    # warm every stage once (compile + populate size caches)
    for name, ex in stages:
        chk = jnp.float64(0.0)
        for b in ex.execute():
            chk = checksum(b, chk)
        scope.drain()
        float(np.asarray(chk))

    for rep in range(REPS):
        for name, ex in stages:
            t0 = time.perf_counter()
            chk = jnp.float64(0.0)
            for _ in range(ITERS):
                for b in ex.execute():
                    chk = checksum(b, chk)
                scope.drain()
            float(np.asarray(chk))  # ONE forced sync closes the clock
            dt = (time.perf_counter() - t0) / ITERS * 1e3
            results[name].append(dt)
            print(f"rep{rep} {name:12s} {dt:9.1f} ms", flush=True)

meds = {name: sorted(results[name])[len(results[name]) // 2]
        for name, _ in stages}
prefix = {"filter_l": 0.0, "filter_o": 0.0,
          "join": meds["filter_l"] + meds["filter_o"],
          "join+proj": meds["join"], "+agg": meds["join+proj"],
          "+topn": meds["+agg"]}
for name, _ in stages:
    med = meds[name]
    print(f"{name:12s} {med:9.1f} ms   (marginal +{med - prefix[name]:7.1f})"
          f"   runs={['%.1f' % x for x in results[name]]}", flush=True)
