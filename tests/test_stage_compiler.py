"""Whole-stage compilation (ISSUE 14): engine-level fusion on/off
equality for q1- and q3-shaped plans (incl. the PR 3 forced-spill
parquet recipe), the dispatch_summary acceptance rates (q3 fused
filter->probe->partial-agg chain <= 1.5 dispatches/output-batch, q1's
chain at 1.0), the plan-fingerprint program cache (a second collect()
of an identical plan compiles ZERO new programs), map-stage fusion,
breaker demotion to per-operator execution, the stage-boundary chaos
fault point, the stage_fused event, and the report/bench surfaces."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec import stage_compiler
from spark_rapids_tpu.exec.stage_compiler import (CompiledStageExec,
                                                  compile_stages)
from spark_rapids_tpu.expr.aggexprs import Count, Sum
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.obs import dispatch, events
from spark_rapids_tpu.types import (DoubleType, IntegerType, LongType,
                                    Schema, StructField)

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
import profile_report  # noqa: E402

INT, LONG, DOUBLE = IntegerType(), LongType(), DoubleType()

OFF = {"spark.rapids.tpu.stage.fusion.enabled": "false"}


@pytest.fixture(autouse=True)
def _fresh_planes():
    dispatch.reset_dispatch_ledger()
    stage_compiler.reset_stage_counters()
    events.reset_event_bus()
    yield
    dispatch.reset_dispatch_ledger()
    stage_compiler.reset_stage_counters()
    events.reset_event_bus()


def _q1_query(sess, n=3000, batch_rows=None):
    rng = np.random.default_rng(0)
    schema = Schema((StructField("k", INT), StructField("q", LONG),
                     StructField("p", DOUBLE)))
    df = sess.from_pydict({"k": rng.integers(0, 6, n).tolist(),
                           "q": rng.integers(1, 50, n).tolist(),
                           "p": (rng.random(n) * 10).tolist()},
                          schema, batch_rows=batch_rows)
    return (df.filter(col("q") <= lit(40))
              .group_by("k").agg((Sum(col("p")), "s"), (Count(), "c")))


Q3_CONF = {"spark.rapids.sql.broadcastSizeThreshold": "-1",
           "spark.rapids.tpu.agg.speculative.enabled": "false"}


def _q3_query(sess, n=800):
    rng = np.random.default_rng(1)
    osch = Schema((StructField("o", LONG), StructField("d", LONG)))
    lsch = Schema((StructField("o", LONG), StructField("x", DOUBLE)))
    orders = sess.from_pydict(
        {"o": list(range(n)), "d": rng.integers(0, 100, n).tolist()},
        osch)
    lines = sess.from_pydict(
        {"o": [int(v) for v in rng.integers(0, n, 2 * n)],
         "x": (rng.random(2 * n) * 5).tolist()}, lsch)
    return (orders.filter(col("d") < lit(50))
                  .join(lines, on="o")
                  .group_by("o").agg((Sum(col("x")), "rev")))


def _stage_row(sess):
    rows = [r for r in
            sess.last_query_profile().dispatch_summary()["stages"]
            if r["op"] == "CompiledStageExec"]
    assert rows, "no CompiledStageExec in the plan"
    return rows[0]


# -- planner shape -----------------------------------------------------------

def test_q1_plan_compiles_filter_project_agg_chain():
    sess = TpuSession()
    plan = _q1_query(sess)._exec()
    assert isinstance(plan, CompiledStageExec)
    assert plan._kind == "agg"
    ops = [type(o).__name__ for o in plan._absorbed]
    assert ops[0] == "AggregateExec" and "FilterExec" in ops


def test_q3_plan_compiles_join_agg_chain():
    sess = TpuSession(Q3_CONF)
    plan = _q3_query(sess)._exec()
    assert isinstance(plan, CompiledStageExec)
    assert plan._kind == "join_agg"
    ops = [type(o).__name__ for o in plan._absorbed]
    assert ops[0] == "AggregateExec" and ops[-1] == "HashJoinExec"


def test_fusion_off_is_a_noop_rewrite():
    sess = TpuSession(OFF)
    plan = _q1_query(sess)._exec()
    assert not isinstance(plan, CompiledStageExec)
    # and compile_stages itself returns the tree untouched
    assert compile_stages(plan, sess.conf) is plan


def test_bare_group_by_stays_per_operator():
    """A group-by with NO absorbed chain is already one program per
    batch — wrapping it would only rename its profile row."""
    sess = TpuSession()
    schema = Schema((StructField("k", INT), StructField("v", LONG)))
    df = sess.from_pydict({"k": [1, 2, 1], "v": [3, 4, 5]}, schema)
    plan = df.group_by("k").agg((Sum(col("v")), "s"))._exec()
    assert not isinstance(plan, CompiledStageExec)


# -- engine-level equality ---------------------------------------------------

def test_q1_fusion_on_off_byte_identical():
    on = sorted(_q1_query(TpuSession()).collect())
    off = sorted(_q1_query(TpuSession(OFF)).collect())
    assert on == off  # CPU byte-identical (same fold, same programs)


def test_q3_fusion_on_off_byte_identical():
    on = sorted(_q3_query(TpuSession(Q3_CONF)).collect())
    off = sorted(_q3_query(TpuSession(dict(Q3_CONF, **OFF))).collect())
    assert on == off


def test_q3_speculative_tier_on_off_equality():
    """With agg speculation ON the q3 cardinality trips the bucket
    table and the plan re-runs exact — the stage must replay the same
    trip-and-rerun contract."""
    conf = {"spark.rapids.sql.broadcastSizeThreshold": "-1"}
    on = sorted(_q3_query(TpuSession(conf)).collect())
    off = sorted(_q3_query(TpuSession(dict(conf, **OFF))).collect())
    assert on == off


def test_empty_input_corners_match_per_op():
    sess_on, sess_off = TpuSession(), TpuSession(OFF)
    schema = Schema((StructField("k", INT), StructField("v", LONG)))
    for sess, out in ((sess_on, {}), (sess_off, {})):
        df = sess.from_pydict({"k": [1, 2], "v": [3, 4]}, schema)
        # filter removes everything -> keyed agg emits nothing
        keyed = (df.filter(col("v") > lit(100))
                   .group_by("k").agg((Sum(col("v")), "s"))).collect()
        # grand aggregate over empty input still emits one row
        grand = (df.filter(col("v") > lit(100))
                   .agg((Count(), "c"))).collect()
        out["keyed"], out["grand"] = keyed, grand
        if sess is sess_on:
            on = dict(out)
    assert on["keyed"] == keyed == []
    assert on["grand"] == grand == [(0,)]


def _rows_equal_float_tolerant(xs, ys, float_cols=(1,)):
    if len(xs) != len(ys):
        return False
    for x, y in zip(xs, ys):
        for i, (a, b) in enumerate(zip(x, y)):
            if i in float_cols:
                if abs(a - b) > 1e-9 * max(abs(a), abs(b), 1.0):
                    return False
            elif a != b:
                return False
    return True


def test_forced_spill_parquet_equality(tmp_path):
    """The PR 3 forced-spill recipe (scan->filter->join->agg->sort
    parquet shape, 192 KiB budget): the catalog really spills under
    the fused stage, and results match the per-op path (float sums to
    reduction-order tolerance — OOM splits depend on interleaving)."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.memory.budget import reset_memory_budget
    from spark_rapids_tpu.memory.catalog import (buffer_catalog,
                                                 reset_buffer_catalog)
    rng = np.random.default_rng(3)
    n_l, n_o = 4000, 500
    lp = str(tmp_path / "lines.parquet")
    op = str(tmp_path / "orders.parquet")
    pq.write_table(pa.table({
        "l_key": pa.array(rng.integers(0, n_o, n_l), pa.int64()),
        "l_val": pa.array(rng.random(n_l) * 100.0, pa.float64()),
        "l_flag": pa.array(rng.integers(0, 4, n_l), pa.int64()),
    }), lp, row_group_size=512)
    pq.write_table(pa.table({
        "o_key": pa.array(np.arange(n_o), pa.int64()),
        "o_flag": pa.array(rng.integers(0, 10, n_o), pa.int64()),
    }), op, row_group_size=128)

    results, spilled, fused = {}, {}, {}
    try:
        for mode, settings in (("on", {}), ("off", dict(OFF))):
            reset_buffer_catalog()
            reset_memory_budget(192 * 1024)
            sess = TpuSession(dict(
                settings,
                **{"spark.rapids.memory.spillDirectory": str(tmp_path)}))
            lines = sess.read_parquet(lp).filter(col("l_flag") != lit(0))
            orders = sess.read_parquet(op).filter(col("o_flag") < lit(5))
            j = lines.join(orders, left_on=["l_key"],
                           right_on=["o_key"])
            agg = j.group_by("l_key").agg((F.sum("l_val"), "rev"),
                                          (F.count(), "cnt"))
            before = stage_compiler.counters()["executions"]
            results[mode] = agg.sort(("rev", False)).collect()
            fused[mode] = stage_compiler.counters()["executions"] - before
            spilled[mode] = buffer_catalog().spilled_device_bytes
    finally:
        reset_buffer_catalog()
        reset_memory_budget()
    assert spilled["on"] > 0 and spilled["off"] > 0  # the budget DID bite
    assert fused["on"] > 0 and fused["off"] == 0  # the stage DID engage
    assert _rows_equal_float_tolerant(results["on"], results["off"])


# -- acceptance: dispatches per output batch ---------------------------------

def test_q1_chain_one_dispatch_per_output_batch():
    """Acceptance (ISSUE 14): the fused q1 chain runs at 1.0
    dispatches/output-batch — the whole filter->project->partial-agg
    chain is ONE program per input batch (vs the 4.0 the PR 13
    baseline measured at 4 input batches/execution)."""
    sess = TpuSession()
    q = _q1_query(sess)
    q.collect()
    row = _stage_row(sess)
    assert row["dispatches_per_batch"] == 1.0, row
    assert row["programs"] >= 1


def test_q3_chain_dispatch_rate_acceptance():
    """Acceptance (ISSUE 14): the fused filter->probe->partial-agg
    chain at <= 1.5 dispatches/output-batch (vs HashJoinExec 3.0 +
    AggregateExec 2.0 in the PR 13 baseline); a WARM execution —
    sizing cache hot, build fused into the first step — is exactly
    1.0."""
    sess = TpuSession(Q3_CONF)
    q = _q3_query(sess)
    q.collect()  # cold: sizing program + fused step
    cold = _stage_row(sess)
    assert cold["dispatches_per_batch"] <= 2.0, cold
    prev = None
    for _ in range(2):
        q.collect()
        row = _stage_row(sess)
        # warm execution (fresh exec instance per collect; the sizing
        # cache is fingerprint-shared): ONE dispatch, ONE output batch
        assert row["dispatches"] == 1 and row["batches"] == 1, row
        assert row["dispatches_per_batch"] == 1.0
        prev = row
    # cumulative over cold + 2 warm executions: (2 + 1 + 1) / 3 <= 1.5
    total_d = cold["dispatches"] + 2 * prev["dispatches"]
    total_b = cold["batches"] + 2 * prev["batches"]
    assert total_d / total_b <= 1.5


# -- acceptance: plan-fingerprint program cache ------------------------------

@pytest.mark.parametrize("conf,build", [({}, _q1_query),
                                        (Q3_CONF, _q3_query)],
                         ids=["q1", "q3"])
def test_second_collect_is_all_cache_hits(conf, build):
    """Acceptance (ISSUE 14): a second collect() of an identical plan
    reports 100% ledger cache hits — ZERO fresh traces (every
    DataFrame.collect() rebuilds its exec tree; the program cache
    hands the rebuilt execs their already-compiled programs)."""
    sess = TpuSession(conf)
    q = build(sess)
    r1 = sorted(q.collect())
    c1 = dispatch.counters()
    assert c1["traces"] > 0  # the first collect really compiled
    r2 = sorted(q.collect())
    c2 = dispatch.counters()
    assert r1 == r2
    assert c2["traces"] == c1["traces"], "second collect re-traced"
    delta = c2["dispatches"] - c1["dispatches"]
    assert delta > 0
    assert c2["cache_hits"] - c1["cache_hits"] == delta  # 100% hits


def test_fingerprints_distinguish_plans_and_conf():
    """Soundness: semantically DIFFERENT plans (another predicate) or a
    different trace-affecting conf (agg bucket slots) never share
    program sites."""
    sess = TpuSession()
    p1 = _q1_query(sess)._exec()
    sess2 = TpuSession()
    p2 = sess2.from_pydict(
        {"k": [1], "q": [2], "p": [3.0]},
        Schema((StructField("k", INT), StructField("q", LONG),
                StructField("p", DOUBLE)))) \
        .filter(col("q") <= lit(7)) \
        .group_by("k").agg((Sum(col("p")), "s"), (Count(), "c"))._exec()
    assert p1.plan_fingerprint() != p2.plan_fingerprint()
    sess3 = TpuSession({"spark.rapids.tpu.agg.bucketSlots": "16"})
    p3 = _q1_query(sess3)._exec()
    assert p1.plan_fingerprint() != p3.plan_fingerprint()
    # identical plan + conf => identical fingerprint (the cache key)
    sess4 = TpuSession()
    p4 = _q1_query(sess4)._exec()
    assert p1.plan_fingerprint() == p4.plan_fingerprint()


def test_fingerprints_are_value_complete_not_repr():
    """Regression (caught live by the full suite): expression __repr__
    omits non-child parameters — trim sets, percentile fractions,
    first()'s ignore_nulls — so repr-keyed fingerprints handed one
    expression another's compiled program (trim(s, "ag ") returned
    plain-trim results). Fingerprints ride semantic_key / the new
    AggregateFunction.semantic_key, which the CSE contract keeps
    value-complete."""
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.expr.aggexprs import (ApproxPercentile, First,
                                                Percentile)
    from spark_rapids_tpu.types import STRING

    def proj_fp(expr):
        sess = TpuSession()
        df = sess.from_pydict({"s": ["x"]},
                              Schema((StructField("s", STRING),)))
        return df.select(expr.alias("r"))._exec().plan_fingerprint()

    assert proj_fp(F.trim(col("s"))) != proj_fp(F.trim(col("s"), "ag "))

    # aggregate-function parameters distinguish too
    assert Percentile(col("x"), 0.05).semantic_key() != \
        Percentile(col("x"), 0.95).semantic_key()
    assert ApproxPercentile(col("x"), 0.5, 100).semantic_key() != \
        ApproxPercentile(col("x"), 0.5, 200).semantic_key()
    assert First(col("x"), ignore_nulls=True).semantic_key() != \
        First(col("x"), ignore_nulls=False).semantic_key()

    def agg_fp(fn):
        sess = TpuSession()
        df = sess.from_pydict(
            {"k": [1], "x": [2.0]},
            Schema((StructField("k", INT), StructField("x", DOUBLE))))
        return (df.filter(col("x") > lit(0)).group_by("k")
                  .agg((fn, "r"))._exec().plan_fingerprint())

    from spark_rapids_tpu.expr.aggexprs import Last
    assert agg_fp(First(col("x"), ignore_nulls=True)) != \
        agg_fp(First(col("x"), ignore_nulls=False))
    assert agg_fp(First(col("x"))) != agg_fp(Last(col("x")))

    # non-deterministic expressions (UDFs key per-instance) opt OUT
    from spark_rapids_tpu.expr.udf import PythonUDF
    from spark_rapids_tpu.types import LongType as _L
    sess = TpuSession()
    df = sess.from_pydict({"a": [1, 2]},
                          Schema((StructField("a", LONG),)))
    udf = PythonUDF(lambda x: x + 1, _L(), col("a"))
    plan = df.select(udf.alias("r"))._exec()

    def walk(n):
        yield n
        for c in n.children:
            yield from walk(c)
    projs = [n for n in walk(plan)
             if type(n).__name__ in ("ProjectExec", "HostProjectExec")]
    assert all(n.plan_fingerprint() is None for n in projs), \
        "a UDF-bearing projection must opt out of the program cache"


# -- map stages --------------------------------------------------------------

def test_map_stage_fuses_filter_project_chain():
    """filter->project chains feeding a non-fusable consumer compile
    to a map stage: every projection + ONE compaction in one program
    per input batch, results byte-identical to the per-op chain."""
    def q(sess):
        schema = Schema((StructField("a", LONG), StructField("b", LONG)))
        df = sess.from_pydict(
            {"a": list(range(40)), "b": [i * 3 for i in range(40)]},
            schema, batch_rows=16)
        return (df.filter(col("a") > lit(4))
                  .select(col("a"), (col("b") + col("a")).alias("c"))
                  .filter(col("c") > lit(30))
                  .sort(("c", False)))
    sess = TpuSession()
    plan = q(sess)._exec()
    kinds = []

    def walk(n):
        kinds.append((type(n).__name__,
                      getattr(n, "_kind", None)))
        for c in n.children:
            walk(c)
    walk(plan)
    assert ("CompiledStageExec", "map") in kinds, kinds
    on = q(sess).collect()
    off = q(TpuSession(OFF)).collect()
    assert on == off
    row = _stage_row(sess)
    # one program per input batch, one output per input batch
    assert row["dispatches_per_batch"] == 1.0, row


def test_a_string_group_by_over_a_fused_stage_drives_that_stage():
    """A group-by on a string key runs its filter/project chain as a row
    mask and forms no stage: the rewrite goes on from its `_source`, here a
    fused `agg` stage of its own, and the group-by drives that stage; the
    chain between them forms no map stage."""
    from spark_rapids_tpu.exec.aggregate import AggregateExec
    from spark_rapids_tpu.types import StringType

    def q(sess):
        schema = Schema((StructField("k", LONG), StructField("v", LONG)))
        df = sess.from_pydict({"k": [i % 5 for i in range(200)],
                               "v": list(range(200))}, schema)
        inner = (df.filter(col("v") > lit(10))
                   .group_by("k").agg((Sum(col("v")), "s")))
        return (inner.filter(col("s") > lit(3900))
                     .select(col("k").cast(StringType()).alias("ks"),
                             col("s"))
                     .group_by("ks").agg((Sum(col("s")), "t"), (Count(), "n"))
                     .sort("ks"))
    sess = TpuSession()
    plan = q(sess)._exec()
    outer = plan.children[0]
    assert isinstance(outer, AggregateExec) and outer._lane_ok
    assert isinstance(outer._source, CompiledStageExec)
    assert outer._source._kind == "agg"
    # the absorbed chain's last link leads to the stage too
    assert outer.children[0].children[0].children[0] is outer._source
    on = q(sess).collect()
    assert on == q(TpuSession(OFF)).collect()
    assert [r[0] for r in on] == ["1", "2", "3", "4"]   # 0's sum is 3885


def test_map_stage_expand_fans_out_from_one_program():
    """An expand inside a map chain emits ALL its projections from ONE
    program per input batch (grouping-sets shape)."""
    from spark_rapids_tpu.exec.basic import (ExpandExec, FilterExec,
                                             InMemoryScanExec)
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    schema = Schema((StructField("k", LONG), StructField("v", LONG)))
    batch = ColumnarBatch.from_pydict(
        {"k": [1, 2, 3, 4], "v": [10, 20, 30, 40]}, schema)
    def tree():
        scan = InMemoryScanExec([batch], schema)
        expand = ExpandExec([[col("k"), col("v")],
                             [col("k"), (col("v") * lit(2)).alias("v")]],
                            scan)
        return FilterExec(col("v") > lit(15), expand)
    per_op = sorted(r for b in tree().execute() for r in b.to_pylist())
    fused = compile_stages(tree(), TpuSession().conf)
    assert isinstance(fused, CompiledStageExec) and fused._kind == "map"
    got = sorted(r for b in fused.execute() for r in b.to_pylist())
    assert got == per_op
    # 1 input batch -> 2 output batches from ONE dispatch
    assert fused.metrics["numDispatches"].value == 1
    assert fused.metrics["numOutputBatches"].value == 2


# -- governance at the stage boundary ---------------------------------------

def test_breaker_demotes_stage_to_per_op():
    """PR 5 degradation at stage granularity: an OPEN device_dispatch
    breaker demotes the fused stage back to per-operator execution —
    results unchanged, the fallback counter proves the lane."""
    from spark_rapids_tpu.exec import lifecycle
    conf = {"spark.rapids.tpu.breaker.enabled": "true",
            "spark.rapids.tpu.breaker.threshold": "1",
            "spark.rapids.tpu.breaker.cooldownMs": "600000"}
    sess = TpuSession(conf)
    baseline = sorted(_q1_query(sess).collect())
    try:
        lifecycle.record_domain_failure("device_dispatch")
        assert not lifecycle.breaker_allows("device_dispatch")
        before = stage_compiler.counters()
        demoted = sorted(_q1_query(sess).collect())
        after = stage_compiler.counters()
        assert demoted == baseline
        assert after["fallbacks"] > before["fallbacks"]
        assert after["executions"] == before["executions"]
    finally:
        lifecycle.reset_lifecycle()


def test_stage_fault_point_recovers_via_task_retry():
    """Chaos coverage of the new seam: the stage-boundary harness
    draws the device.dispatch fault point with a stage-keyed work item
    — one injected device fault converges through task re-execution."""
    from spark_rapids_tpu import faults
    sess = TpuSession({"spark.rapids.tpu.task.maxAttempts": "5"})
    expect = sorted(_q1_query(sess).collect())
    try:
        faults.install("device.dispatch:prob=1.0,seed=7,kind=device,"
                       "max=1")
        got = sorted(_q1_query(sess).collect())
    finally:
        faults.install("")
    assert got == expect


def test_stage_fused_event_fields(tmp_path):
    bus = events.enable(str(tmp_path), level="MODERATE")
    sess = TpuSession({"spark.rapids.tpu.eventLog.enabled": "true",
                       "spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    _q1_query(sess).collect()
    log = events.active_bus().path
    events.reset_event_bus()
    recs = [json.loads(ln) for ln in open(log)]
    fused = [r for r in recs if r["kind"] == "stage_fused"]
    assert fused, "no stage_fused event"
    e = fused[0]
    assert e["stage"] == "agg" and e["ops"] >= 2
    assert "AggregateExec" in e["label"]
    assert e["batches"] >= 1 and e["dispatches"] >= 1
    assert e["donated_bytes"] > 0  # the carried state really donates
    # report roll-up renders it; pre-fusion logs stay silent
    s = profile_report.build_summary(recs)
    fs = s["fused_stages"]
    assert fs["executions"] >= 1 and fs["ops_absorbed"] >= 2
    text = profile_report.build_report(recs)
    assert "fused stages:" in text
    old = [{"ts_ns": 1, "kind": "op_close", "query": 1, "op": "X",
            "op_id": 1, "wall_ns": 5, "batches": 1, "rows": 1}]
    assert profile_report.build_summary(old)["fused_stages"][
        "executions"] == 0
    assert "fused stages" not in profile_report.build_report(old)


def test_bench_stage_attribution_deltas():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench", Path(__file__).resolve().parents[1] / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._attr_prev.pop("stage", None)
    first = bench.stage_attribution()
    assert set(first) == {"stages_fused", "ops_fused", "dispatches",
                          "cache_hits"}
    sess = TpuSession()
    _q1_query(sess).collect()
    delta = bench.stage_attribution()
    assert delta["stages_fused"] >= 1 and delta["dispatches"] >= 1
    # --stage-fusion argv contract: usage error JSON on bad argv
    with pytest.raises(SystemExit) as ei:
        bench.maybe_stage_fusion(["bench.py", "--stage-fusion",
                                  "maybe"])
    assert ei.value.code == 2
