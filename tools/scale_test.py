"""Scale-test harness — the reference's integration_tests scaletest
(ScaleTest.scala CLI + QuerySpecs.scala + TestReport.scala; SURVEY §4.3):
generate tables at a scale factor, run a fixed query suite, write a JSON
timing report.

Usage:
    python tools/scale_test.py [--scale 1.0] [--out report.json]
                               [--queries q1,q3,...] [--platform cpu|default]

Tables (scaled by --scale, base ~1M rows):
    facts(k long, cat string, v double, ts timestamp)
    dims(k long, name string, weight double)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def build_session(platform: str):
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    from spark_rapids_tpu.api.session import TpuSession
    return TpuSession()


def gen_tables(sess, scale: float):
    from spark_rapids_tpu.types import (DOUBLE, LONG, STRING, TIMESTAMP,
                                        Schema, StructField)
    n_facts = int(1_000_000 * scale)
    n_dims = max(1000, int(10_000 * scale))
    rng = np.random.default_rng(7)
    facts = sess.from_pydict({
        "k": rng.integers(0, n_dims, n_facts).tolist(),
        "cat": [("c%d" % x) for x in rng.integers(0, 23, n_facts)],
        "v": (rng.random(n_facts) * 100).tolist(),
        "ts": rng.integers(1_500_000_000_000_000, 1_700_000_000_000_000,
                           n_facts).tolist(),
    }, Schema((StructField("k", LONG), StructField("cat", STRING),
               StructField("v", DOUBLE), StructField("ts", TIMESTAMP))))
    dims = sess.from_pydict({
        "k": list(range(n_dims)),
        "name": [f"dim-{i}" for i in range(n_dims)],
        "weight": (rng.random(n_dims)).tolist(),
    }, Schema((StructField("k", LONG), StructField("name", STRING),
               StructField("weight", DOUBLE))))
    return facts, dims, n_facts


def query_suite(F, col, lit):
    """Name -> (facts, dims) -> collected result. Mirrors the reference
    QuerySpecs: scan/filter/project, group-by, join, window-ish sort."""
    return {
        "q1_filter_project": lambda f, d:
            f.filter(col("v") > lit(50.0))
             .select((col("v") * lit(2.0)).alias("v2")).count(),
        "q2_groupby": lambda f, d:
            f.group_by("cat").agg((F.sum(col("v")), "s"),
                                  (F.count(), "c")).collect(),
        "q3_join_agg": lambda f, d:
            f.join(d, on="k").group_by("cat")
             .agg((F.sum(col("weight")), "w")).collect(),
        "q4_sort_limit": lambda f, d:
            f.sort(("v", False)).limit(100).collect(),
        "q5_distinct": lambda f, d:
            f.select(col("cat")).distinct().count(),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.1)
    ap.add_argument("--out", default="scale_report.json")
    ap.add_argument("--queries", default="")
    ap.add_argument("--platform", default="cpu",
                    choices=("cpu", "default"),
                    help="cpu pins the CPU backend; default uses whatever "
                         "jax selects — the report names what that was")
    args = ap.parse_args()

    sess = build_session(args.platform)
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.expr.core import lit

    t0 = time.perf_counter()
    facts, dims, n_facts = gen_tables(sess, args.scale)
    gen_s = time.perf_counter() - t0

    suite = query_suite(F, col, lit)
    wanted = [q.strip() for q in args.queries.split(",") if q.strip()] \
        or list(suite)
    import jax
    dev = jax.devices()[0]
    report = {"scale": args.scale, "rows": n_facts,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())},
              "datagen_seconds": round(gen_s, 3), "queries": []}
    for name in wanted:
        fn = suite[name]
        t0 = time.perf_counter()      # cold (includes compile)
        fn(facts, dims)
        cold = time.perf_counter() - t0
        t0 = time.perf_counter()      # warm (compiled)
        fn(facts, dims)
        warm = time.perf_counter() - t0
        report["queries"].append({
            "name": name, "cold_seconds": round(cold, 3),
            "warm_seconds": round(warm, 3),
            "rows_per_second": round(n_facts / max(warm, 1e-9))})
        print(f"{name}: cold={cold:.2f}s warm={warm:.2f}s")
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(f"report -> {args.out} ({report['device']})")


if __name__ == "__main__":
    main()
