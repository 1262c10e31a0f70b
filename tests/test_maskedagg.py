"""Masked-bucket aggregation kernel + speculative execution + whole-stage
fusion (ops/maskedagg.py, exec/speculation.py, exec/aggregate.py).

Oracle pattern mirrors the reference's CPU-vs-GPU equality testing
(SparkQueryCompareTestSuite.scala): every result is checked against an
independent numpy/python aggregation of the same data.
"""

import numpy as np
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column
from spark_rapids_tpu.exec.aggregate import AggregateExec
from spark_rapids_tpu.exec.basic import FilterExec, InMemoryScanExec, ProjectExec
from spark_rapids_tpu.exec.speculation import speculation_scope
from spark_rapids_tpu.expr.aggexprs import (
    Average, Count, First, Last, Max, Min, Sum,
)
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.types import (
    DOUBLE, INT, LONG, Schema, StructField,
)


def _oracle_groupby(keys, vals):
    out = {}
    for k, v in zip(keys, vals):
        e = out.setdefault(k, [0, 0, None, None])
        e[1] += 1
        if v is not None:
            e[0] += v
            e[2] = v if e[2] is None else min(e[2], v)
            e[3] = v if e[3] is None else max(e[3], v)
    return out


def _run_agg(keys, vals, key_type=LONG, batches=1):
    sch = Schema((StructField("k", key_type), StructField("v", LONG)))
    n = len(keys)
    per = max(1, n // batches)
    bs = []
    for i in range(0, n, per):
        bs.append(ColumnarBatch.from_pydict(
            {"k": keys[i:i + per], "v": vals[i:i + per]}, sch))
    plan = AggregateExec(
        [col("k")],
        [(Sum(col("v")), "s"), (Count(), "c"),
         (Min(col("v")), "mn"), (Max(col("v")), "mx")],
        InMemoryScanExec(bs, sch))
    rows = plan.collect()
    return {r[0]: (r[1], r[2], r[3], r[4]) for r in rows}


def _check(keys, vals, **kw):
    got = _run_agg(keys, vals, **kw)
    want = _oracle_groupby(keys, vals)
    assert set(got) == set(want), (set(got), set(want))
    for k, (s, c2, mn, mx) in want.items():
        gs, gc, gmn, gmx = got[k]
        assert gc == c2, (k, got[k], want[k])
        assert gs == (s if c2 and any(
            v is not None for kk, v in zip(keys, vals) if kk == k) else gs)
        assert gmn == mn and gmx == mx, (k, got[k], want[k])


def test_low_cardinality():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 5, 500).tolist()
    vals = rng.integers(-100, 100, 500).tolist()
    _check(keys, vals)


@pytest.mark.slow  # minute-scale single-core; nightly tier (-m slow)
def test_high_cardinality_falls_back_exact():
    # cardinality >> bucketSlots * bucketRounds: fast path must flag and
    # the plan re-run must still be exact
    rng = np.random.default_rng(2)
    keys = rng.integers(0, 400, 2000).tolist()
    vals = rng.integers(-50, 50, 2000).tolist()
    _check(keys, vals)


def test_null_keys_and_values():
    keys = [1, None, 2, None, 1, 2, None, 3]
    vals = [10, 20, None, 40, 50, 60, None, None]
    got = _run_agg(keys, vals)
    assert got[None] == (60, 3, 20, 40)
    assert got[1] == (60, 2, 10, 50)
    assert got[2] == (60, 2, 60, 60)
    assert got[3][1] == 1 and got[3][0] is None  # all-null group sum


def test_multi_batch_merge():
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 7, 999).tolist()
    vals = rng.integers(0, 9, 999).tolist()
    _check(keys, vals, batches=7)


def test_float_keys_nan_normalization():
    sch = Schema((StructField("k", DOUBLE), StructField("v", LONG)))
    keys = [1.5, float("nan"), -0.0, 0.0, float("nan"), 1.5]
    vals = [1, 2, 3, 4, 5, 6]
    b = ColumnarBatch.from_pydict({"k": keys, "v": vals}, sch)
    plan = AggregateExec([col("k")], [(Sum(col("v")), "s")],
                         InMemoryScanExec([b], sch))
    rows = plan.collect()
    got = {}
    for k, s in rows:
        key = "nan" if (k is not None and k != k) else k
        got[key] = s
    # Spark: all NaNs one group; -0.0 == 0.0
    assert got["nan"] == 7
    assert got[0.0] == 7
    assert got[1.5] == 7
    assert len(rows) == 3


def test_speculation_scope_trips_and_rerun_matches():
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 500, 3000).tolist()
    vals = rng.integers(0, 100, 3000).tolist()
    sch = Schema((StructField("k", LONG), StructField("v", LONG)))
    b = ColumnarBatch.from_pydict({"k": keys, "v": vals}, sch)
    plan = AggregateExec([col("k")], [(Sum(col("v")), "s")],
                         InMemoryScanExec([b], sch))
    with speculation_scope() as scope:
        list(plan.execute())
        assert scope.tripped()  # 500 distinct > 32*2 slots
    # collect() transparently re-runs exact
    want = {}
    for k, v in zip(keys, vals):
        want[k] = want.get(k, 0) + v
    got = dict(plan.collect())
    assert got == want


@pytest.mark.slow  # ~5s; fusion equality nightly (round-7 budget move)
def test_fused_filter_project_agg_matches_unfused():
    from spark_rapids_tpu.config import RapidsConf, set_active_conf
    rng = np.random.default_rng(5)
    n = 4096
    k = rng.integers(0, 6, n).tolist()
    q = rng.integers(1, 51, n).tolist()
    p = (rng.random(n) * 100).tolist()
    sch = Schema((StructField("k", INT), StructField("q", LONG),
                  StructField("p", DOUBLE)))

    def build():
        b = ColumnarBatch.from_pydict({"k": k, "q": q, "p": p}, sch)
        scan = InMemoryScanExec([b], sch)
        filt = FilterExec(col("q") <= lit(40), scan)
        proj = ProjectExec([col("k"), col("q"),
                            (col("p") * lit(2.0)).alias("p2")], filt)
        return AggregateExec(
            [col("k")],
            [(Sum(col("q")), "sq"), (Sum(col("p2")), "sp"),
             (Count(), "c"), (Average(col("p2")), "avg")], proj)

    fused = build()
    assert fused._fused_steps, "fusion did not engage"
    got = {r[0]: r[1:] for r in fused.collect()}

    set_active_conf(RapidsConf({"spark.rapids.tpu.fusion.enabled": False}))
    try:
        unfused = build()
        assert not unfused._fused_steps
        want = {r[0]: r[1:] for r in unfused.collect()}
    finally:
        set_active_conf(RapidsConf())

    assert set(got) == set(want)
    for key in want:
        assert got[key][0] == want[key][0]  # exact int sum
        assert got[key][2] == want[key][2]  # count
        assert abs(got[key][1] - want[key][1]) < 1e-9 * max(
            1.0, abs(want[key][1]))
        assert abs(got[key][3] - want[key][3]) < 1e-9 * max(
            1.0, abs(want[key][3]))


def test_fused_count_star_with_filter_mask():
    sch = Schema((StructField("v", LONG),))
    b = ColumnarBatch.from_pydict({"v": list(range(100))}, sch)
    plan = AggregateExec(
        [], [(Count(), "c")],
        FilterExec(col("v") < lit(37), InMemoryScanExec([b], sch)))
    assert plan.collect() == [(37,)]


def test_grand_aggregate_over_large_batch():
    # count(*) with no input columns must not be capped by any bucket
    sch = Schema((StructField("v", LONG),))
    n = 1000
    b = ColumnarBatch.from_pydict({"v": list(range(n))}, sch)
    plan = AggregateExec([], [(Count(), "c"), (Sum(col("v")), "s")],
                         InMemoryScanExec([b], sch))
    assert plan.collect() == [(n, n * (n - 1) // 2)]


def test_first_last_in_masked_path():
    sch = Schema((StructField("k", LONG), StructField("v", LONG)))
    b = ColumnarBatch.from_pydict(
        {"k": [1, 1, 2, 2, 1], "v": [None, 10, 20, None, 30]}, sch)
    plan = AggregateExec(
        [col("k")], [(First(col("v"), ignore_nulls=True), "f"),
         (Last(col("v"), ignore_nulls=True), "l")],
        InMemoryScanExec([b], sch))
    got = {r[0]: r[1:] for r in plan.collect()}
    assert got[1] == (10, 30)
    assert got[2] == (20, 20)


@pytest.mark.slow  # minute-scale on a single-core host; nightly tier
def test_more_than_16_key_columns():
    # beyond the 16-column packed-stats code word: the per-column boolean
    # reductions path must kick in, not an assert/overflow
    n_keys = 17
    sch = Schema(tuple(StructField(f"k{i}", LONG) for i in range(n_keys))
                 + (StructField("v", LONG),))
    data = {f"k{i}": [1, 1, 2, None] for i in range(n_keys)}
    data["v"] = [10, 20, 30, 40]
    b = ColumnarBatch.from_pydict(data, sch)
    plan = AggregateExec(
        [col(f"k{i}") for i in range(n_keys)],
        [(Sum(col("v")), "s")], InMemoryScanExec([b], sch))
    got = sorted(plan.collect(), key=lambda r: (r[0] is None, r[0] or 0))
    assert [r[-1] for r in got] == [30, 30, 40]
