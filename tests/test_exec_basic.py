"""Exec-layer tests: project/filter/range/limit/union/expand + coalesce —
modeled on the reference's SparkQueryCompareTestSuite pattern (every case
states expected rows explicitly or compares against a numpy oracle)."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.basic import (
    ExpandExec, FilterExec, GlobalLimitExec, InMemoryScanExec, LocalLimitExec,
    ProjectExec, RangeExec, UnionExec,
)
from spark_rapids_tpu.exec.coalesce import CoalesceBatchesExec
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.types import (
    DOUBLE, INT, LONG, STRING, Schema, StructField,
)


def make_scan(data: dict, schema: Schema, split: int = 0):
    """Build a scan; split>0 chunks rows into multiple batches."""
    n = len(next(iter(data.values())))
    if split and n > split:
        batches = []
        for s in range(0, n, split):
            chunk = {k: v[s:s + split] for k, v in data.items()}
            batches.append(ColumnarBatch.from_pydict(chunk, schema))
        return InMemoryScanExec(batches, schema)
    return InMemoryScanExec([ColumnarBatch.from_pydict(data, schema)], schema)


SCHEMA = Schema((StructField("a", INT), StructField("b", LONG),
                 StructField("s", STRING)))
DATA = {
    "a": [1, 2, None, 4, 5, None, 7, 8],
    "b": [10, None, 30, 40, 50, 60, None, 80],
    "s": ["x", "yy", None, "zzz", "w", "v", "u", "tt"],
}


def test_project_arithmetic():
    scan = make_scan(DATA, SCHEMA)
    plan = ProjectExec([(col("a") + col("b")).alias("ab"),
                        (col("a") * lit(2)).alias("a2")], scan)
    rows = plan.collect()
    expect = [(11, 2), (None, 4), (None, None), (44, 8), (55, 10),
              (None, None), (None, 14), (88, 16)]
    assert rows == expect


def test_filter_basic():
    scan = make_scan(DATA, SCHEMA)
    plan = FilterExec(col("a") > lit(3), scan)
    rows = plan.collect()
    assert rows == [(4, 40, "zzz"), (5, 50, "w"), (7, None, "u"),
                    (8, 80, "tt")]


def test_filter_null_predicate_dropped():
    # a > 3 is null for null a -> dropped (Spark semantics)
    scan = make_scan(DATA, SCHEMA, split=3)
    plan = FilterExec(col("a") > lit(0), scan)
    assert len(plan.collect()) == 6


def test_project_filter_chain_multibatch():
    scan = make_scan(DATA, SCHEMA, split=3)
    plan = ProjectExec([(col("a") + lit(1)).alias("a1"), col("s")],
                       FilterExec(col("a") > lit(1), scan))
    assert plan.collect() == [(3, "yy"), (5, "zzz"), (6, "w"), (8, "u"),
                              (9, "tt")]


def test_range_exec():
    plan = RangeExec(0, 1000, 7, batch_rows=128)
    rows = [r[0] for r in plan.collect()]
    assert rows == list(range(0, 1000, 7))


def test_local_and_global_limit():
    scan = make_scan(DATA, SCHEMA, split=3)
    assert len(LocalLimitExec(5, scan).collect()) == 5
    scan2 = make_scan(DATA, SCHEMA, split=3)
    got = GlobalLimitExec(3, scan2, offset=2).collect()
    assert got == [(None, 30, None), (4, 40, "zzz"), (5, 50, "w")]


def test_union():
    s1 = make_scan(DATA, SCHEMA)
    s2 = make_scan(DATA, SCHEMA)
    assert len(UnionExec(s1, s2).collect()) == 16


def test_expand_grouping_sets():
    scan = make_scan(DATA, SCHEMA)
    plan = ExpandExec([[col("a"), lit(0).alias("g")],
                       [col("a"), lit(1).alias("g")]], scan)
    rows = plan.collect()
    assert len(rows) == 16
    assert {r[1] for r in rows} == {0, 1}


def test_coalesce_merges_batches():
    scan = make_scan(DATA, SCHEMA, split=2)  # 4 input batches
    plan = CoalesceBatchesExec(scan)
    batches = list(plan.execute())
    assert len(batches) == 1
    assert batches[0].num_rows_host == 8
    # row content preserved in order
    assert batches[0].to_pydict()["a"] == DATA["a"]
    assert batches[0].to_pydict()["s"] == DATA["s"]


#: a small target_bytes (three flushes) and the (active rows, capacity) of
#: five scan batches; "sparse" holds what a selective filter leaves: a
#: handful of rows in a large bucket
_COALESCE_LAYOUTS = {
    "dense": (9000, [(100, 128), (128, 128), (130, 256), (1, 128),
                     (77, 128)]),
    "sparse": (24000, [(100, 128), (3, 1024), (130, 256), (0, 512),
                       (5, 1024)]),
}


@pytest.mark.parametrize("flushes", [3, 1])
@pytest.mark.parametrize("layout", sorted(_COALESCE_LAYOUTS))
def test_coalesce_exact_and_device_row_counts_agree(layout, flushes):
    """concat_batches' exact lane (host row counts known: tight output
    bucket, which can be smaller than an input's capacity) and its device
    lane (row counts are device scalars: bucket of the capacities) give the
    same rows in the same order."""
    schema = Schema((StructField("a", INT), StructField("d", DOUBLE),
                     StructField("s", STRING)))
    target_bytes, shapes = _COALESCE_LAYOUTS[layout]
    if flushes == 1:
        target_bytes = 1 << 30
    exact, expected, base = [], [], 0
    for rows, cap in shapes:
        data = {"a": [None if i % 7 == 3 else base + i for i in range(rows)],
                "d": [None if i % 5 == 1 else (base + i) * 0.5
                      for i in range(rows)],
                "s": [None if i % 4 == 2 else f"r{base + i}"
                      for i in range(rows)]}
        exact.append(ColumnarBatch.from_pydict(data, schema, capacity=cap))
        expected += list(zip(data["a"], data["d"], data["s"]))
        base += rows
    on_device = [ColumnarBatch(b.columns, jnp.asarray(b.num_rows), schema)
                 for b in exact]
    assert all(b._host_rows is not None for b in exact)
    assert all(b._host_rows is None for b in on_device)

    def run(batches):
        plan = CoalesceBatchesExec(InMemoryScanExec(batches, schema),
                                   target_bytes=target_bytes)
        return list(plan.execute())

    out_exact, out_device = run(exact), run(on_device)
    assert len(out_exact) == len(out_device) == flushes
    for e, d in zip(out_exact, out_device):
        assert e.capacity <= d.capacity
    got_exact = [r for b in out_exact for r in b.to_pylist()]
    got_device = [r for b in out_device for r in b.to_pylist()]
    assert got_exact == got_device == expected


def test_coalesce_respects_target_bytes():
    scan = make_scan(DATA, SCHEMA, split=2)
    plan = CoalesceBatchesExec(scan, target_bytes=1)  # force no merging
    batches = list(plan.execute())
    assert len(batches) == 4


def test_metrics_populated():
    scan = make_scan(DATA, SCHEMA)
    plan = FilterExec(col("a") > lit(3), scan)
    _ = plan.collect()
    assert plan.metrics["numOutputRows"].value == 4
    assert plan.metrics["numOutputBatches"].value == 1
