"""Dispatch & compile observability plane (ISSUE 13): the ledger
chokepoint (counting, first-trace vs cache-hit discrimination, nested
passthrough, off-path), the recompile-storm detector, the per-exec
numDispatches/compileTimeNs metrics and QueryProfile.dispatch_summary()
replay stability, the profile_report dispatch roll-up, bench deltas,
health section, and the Chrome trace exporter (structural: thread
tracks, nested operator spans, compile instants)."""

from __future__ import annotations

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.expr.aggexprs import Count, Sum
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.obs import dispatch, events
from spark_rapids_tpu.types import (DoubleType, IntegerType, LongType,
                                    Schema, StructField)

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
import profile_report  # noqa: E402
import trace_export  # noqa: E402

INT, LONG, DOUBLE = IntegerType(), LongType(), DoubleType()


@pytest.fixture(autouse=True)
def _fresh_ledger():
    dispatch.reset_dispatch_ledger()
    events.reset_event_bus()
    yield
    dispatch.reset_dispatch_ledger()
    events.reset_event_bus()


# -- ledger unit behavior ----------------------------------------------------

def test_dispatch_counts_and_cache_hit_discrimination():
    site = dispatch.instrument(lambda x: x * 2, label="t.double")
    a = jnp.arange(100, dtype=jnp.int32)
    assert int(site(a)[3]) == 6
    c = dispatch.counters()
    assert (c["dispatches"], c["traces"], c["cache_hits"]) == (1, 1, 0)
    site(a)  # same exact shape: jit cache hit, still a dispatch
    c = dispatch.counters()
    assert (c["dispatches"], c["traces"], c["cache_hits"]) == (2, 1, 1)
    progs = dispatch.programs()
    assert len(progs) == 1 and progs[0]["label"] == "t.double"
    assert progs[0]["dispatches"] == 2 and progs[0]["traces"] == 1
    assert progs[0]["compile_ns"] > 0 and progs[0]["trace_ns"] > 0
    # a new shape in a DIFFERENT log2 bucket is a new program key
    site(jnp.arange(300, dtype=jnp.int32))
    assert dispatch.counters()["programs"] == 2


def test_same_bucket_retrace_is_one_program_key():
    """Distinct exact shapes inside one log2 bucket re-trace the SAME
    key — the churn signal the storm detector watches."""
    site = dispatch.instrument(lambda x: x + 1, label="t.churn")
    for n in (130, 140, 150):  # all bucket to 8 (129..256)
        site(jnp.arange(n, dtype=jnp.int32))
    progs = dispatch.programs()
    assert len(progs) == 1
    assert progs[0]["traces"] == 3 and progs[0]["cache_hits"] == 0


def test_nested_instrumented_call_is_not_a_second_dispatch():
    inner = dispatch.instrument(lambda x: x + 1, label="t.inner")
    outer = dispatch.instrument(lambda x: inner(x) * 2, label="t.outer")
    outer(jnp.arange(64, dtype=jnp.int32))
    labels = {p["label"] for p in dispatch.programs()}
    assert labels == {"t.outer"}
    assert dispatch.counters()["dispatches"] == 1


def test_ledger_records_what_a_program_inlined():
    """Each outer trace names the instrumented programs it inlined — on
    every trace, not only the first (jax caches the inner jit's trace,
    the ledger must not depend on it). This is how a run shows which
    kernel tier a program was built from (chip_smoke.py's join-hash
    proof)."""
    inner = dispatch.instrument(lambda x: x + 1, label="t.kernel")
    a = dispatch.instrument(lambda x: inner(x) * 2, label="t.build")
    b = dispatch.instrument(lambda x: inner(x) * 3, label="t.probe")
    plain = dispatch.instrument(lambda x: x * 5, label="t.plain")
    x = jnp.arange(64, dtype=jnp.int32)
    a(x), b(x), plain(x), a(x)
    inlined = {p["label"]: p["inlined"] for p in dispatch.programs()}
    assert inlined == {"t.build": ["t.kernel"], "t.probe": ["t.kernel"],
                       "t.plain": []}


def test_eval_shape_is_not_a_dispatch():
    site = dispatch.instrument(lambda x: x * 2, label="t.abstract")
    out = jax.eval_shape(site, jax.ShapeDtypeStruct((16,), jnp.int32))
    assert out.shape == (16,)
    assert dispatch.counters()["dispatches"] == 0


def test_donated_vs_retained_bytes():
    site = dispatch.instrument(lambda x, y: x + y, label="t.donate",
                               donate_argnums=(0,))
    x = jnp.arange(256, dtype=jnp.int32)
    site(x, x + 1)
    p = dispatch.programs()[0]
    assert p["donated_bytes"] == 256 * 4
    assert p["retained_bytes"] == 256 * 4


def test_off_path_is_pointer_check_and_results_identical():
    site = dispatch.instrument(lambda x: x * 3, label="t.off")
    a = jnp.arange(50, dtype=jnp.int32)
    on = np.asarray(site(a))
    dispatch.configure(__import__(
        "spark_rapids_tpu.config", fromlist=["RapidsConf"]).RapidsConf(
        {"spark.rapids.tpu.dispatch.ledger.enabled": "false"}))
    assert dispatch.active_ledger() is None
    off = np.asarray(site(a))
    np.testing.assert_array_equal(on, off)
    assert dispatch.counters() == {
        "programs": 0, "dispatches": 0, "traces": 0, "cache_hits": 0,
        "compile_ns": 0, "trace_ns": 0, "storms": 0, "syncs": 0,
        "sync_ns": 0}
    # a default conf re-enables (the conf defaults ON)
    dispatch.configure(__import__(
        "spark_rapids_tpu.config", fromlist=["RapidsConf"]).RapidsConf({}))
    assert dispatch.active_ledger() is not None


def test_recompile_storm_fires_once_per_window(tmp_path):
    from spark_rapids_tpu.config import RapidsConf
    bus = events.enable(str(tmp_path), level="ESSENTIAL")
    dispatch.configure(RapidsConf({
        "spark.rapids.tpu.dispatch.storm.traces": "3",
        "spark.rapids.tpu.dispatch.storm.windowMs": "60000"}))
    site = dispatch.instrument(lambda x: x + 1, label="t.storm")
    for n in range(130, 138):  # 8 exact shapes, one bucket: 8 traces
        site(jnp.arange(n, dtype=jnp.int32))
    assert dispatch.counters()["storms"] == 1  # quiet until the
    bus.close()                                # window rolls past
    recs = [json.loads(ln) for ln in open(bus.path)]
    storms = [r for r in recs if r["kind"] == "recompile_storm"]
    assert len(storms) == 1
    s = storms[0]
    assert s["label"] == "t.storm" and s["threshold"] == 3
    assert s["traces_in_window"] >= 3 and s["window_ms"] == 60000
    # recompile_storm is ESSENTIAL: it survived the ESSENTIAL cut
    assert all(r["kind"] in ("recompile_storm",) for r in recs)


def test_many_sites_one_label_is_not_a_storm(tmp_path):
    """Review fix: distinct program sites legitimately share a ledger
    key (ExpandExec's per-projection jits, fresh exec instances per
    collect). Each site's FIRST trace of a bucket is a new program —
    first=True on its compile event, and never a storm contribution;
    only a re-trace within one site's own jit cache is churn."""
    from spark_rapids_tpu.config import RapidsConf
    bus = events.enable(str(tmp_path), level="MODERATE")
    dispatch.configure(RapidsConf({
        "spark.rapids.tpu.dispatch.storm.traces": "3"}))
    sites = [dispatch.instrument(lambda x, i=i: x + i, label="t.fan")
             for i in range(6)]
    a = jnp.arange(64, dtype=jnp.int32)
    for s in sites:  # 6 fresh traces of ONE ledger key, zero churn
        s(a)
    assert dispatch.counters()["storms"] == 0
    bus.close()
    recs = [json.loads(ln) for ln in open(bus.path)]
    comps = [r for r in recs if r["kind"] == "program_compile"]
    assert len(comps) == 6 and all(r["first"] for r in comps)
    assert not any(r["kind"] == "recompile_storm" for r in recs)
    # genuine churn on ONE of the sites still fires
    for n in (65, 66, 67, 68):  # same bucket, new exact shapes
        sites[0](jnp.arange(n, dtype=jnp.int32))
    assert dispatch.counters()["storms"] == 1


def test_dispatch_summary_claims_inherited_site_labels():
    """Review fix: TopNExec inherits SortExec.__init__'s jit site
    (label "SortExec.sort") — its stage row must still report the
    program, joined by the exec's own site labels, not its class
    name."""
    sess = TpuSession()
    q = _q3_query(sess)  # ends in sort+limit? ensure a TopN via limit
    q.limit(5).collect()
    summary = sess.last_query_profile().dispatch_summary()
    rows = {r["op"]: r for r in summary["stages"]}
    top = rows.get("TopNExec") or rows.get("SortExec")
    assert top is not None and top["dispatches"] > 0
    assert top["programs"] > 0, summary


def test_program_compile_event_fields(tmp_path):
    bus = events.enable(str(tmp_path), level="MODERATE")
    site = dispatch.instrument(lambda x: x * 2, label="t.ev")
    site(jnp.arange(64, dtype=jnp.int32))
    site(jnp.arange(64, dtype=jnp.int32))  # cache hit: no second event
    bus.close()
    recs = [json.loads(ln) for ln in open(bus.path)]
    comps = [r for r in recs if r["kind"] == "program_compile"]
    assert len(comps) == 1
    c = comps[0]
    assert c["label"] == "t.ev" and c["first"] is True
    assert c["compile_ns"] > 0 and c["trace_ns"] > 0
    assert c["platform"] == jax.default_backend()
    assert "thread" in c  # ISSUE 13 satellite: track assignment field


# -- engine integration ------------------------------------------------------

def _q1_query(sess, n=3000):
    rng = np.random.default_rng(0)
    schema = Schema((StructField("k", INT), StructField("q", LONG),
                     StructField("p", DOUBLE)))
    df = sess.from_pydict({"k": rng.integers(0, 6, n).tolist(),
                           "q": rng.integers(1, 50, n).tolist(),
                           "p": (rng.random(n) * 10).tolist()},
                          schema, batch_rows=1024)
    return (df.filter(col("q") <= lit(40))
              .group_by("k").agg((Sum(col("p")), "s"), (Count(), "c")))


def _q3_query(sess, n=800):
    rng = np.random.default_rng(1)
    osch = Schema((StructField("o", LONG), StructField("d", LONG)))
    lsch = Schema((StructField("o", LONG), StructField("x", DOUBLE)))
    orders = sess.from_pydict(
        {"o": list(range(n)),
         "d": rng.integers(0, 100, n).tolist()}, osch, batch_rows=256)
    lines = sess.from_pydict(
        {"o": [int(v) for v in rng.integers(0, n, 2 * n)],
         "x": (rng.random(2 * n) * 5).tolist()}, lsch, batch_rows=256)
    return (orders.filter(col("d") < lit(50))
                  .join(lines, on="o")
                  .group_by("o").agg((Sum(col("x")), "rev"))
                  .sort((col("rev"), False)))


def _summary_key(summary):
    """The replay-stable projection of a dispatch summary: per stage,
    (dispatches, batches, dispatches/batch)."""
    return [(r["op"], r["dispatches"], r["batches"],
             r["dispatches_per_batch"]) for r in summary["stages"]]


@pytest.mark.parametrize("build", [_q1_query, _q3_query],
                         ids=["q1", "q3"])
def test_dispatch_summary_exact_and_replayed_across_collects(build):
    """Acceptance (ISSUE 13): per-stage dispatches/batch is exact and
    identical across 3 repeated collects — jit cache hits must not
    zero the counts (dispatches are counted at call time)."""
    sess = TpuSession()
    q = build(sess)
    keys, results = [], []
    for _ in range(3):
        results.append(sorted(q.collect()))
        keys.append(_summary_key(
            sess.last_query_profile().dispatch_summary()))
    assert results[0] == results[1] == results[2]
    assert keys[0] == keys[1] == keys[2], keys
    # the plan actually dispatched programs, and some stage reports an
    # exact per-batch rate
    total = sum(r[1] for r in keys[0])
    assert total > 0
    assert any(r[3] for r in keys[0])


def test_cache_hits_do_not_zero_counts_on_one_plan():
    """Drive ONE exec tree twice (the bench shape: one plan, many
    iterations): the second execution is all jit cache hits, yet its
    dispatch delta equals the first's and the per-batch rate holds."""
    from spark_rapids_tpu.obs.profile import QueryProfile
    sess = TpuSession()
    plan = _q1_query(sess)._exec()
    r1 = sorted(plan.collect())
    s1 = QueryProfile(plan).dispatch_summary()
    hits1 = dispatch.counters()["cache_hits"]
    r2 = sorted(plan.collect())
    s2 = QueryProfile(plan).dispatch_summary()
    hits2 = dispatch.counters()["cache_hits"]
    assert r1 == r2
    assert hits2 > hits1  # second run really rode the jit cache
    for a, b in zip(s1["stages"], s2["stages"]):
        assert b["dispatches"] == 2 * a["dispatches"]
        assert b["batches"] == 2 * a["batches"]
        assert b["dispatches_per_batch"] == a["dispatches_per_batch"]


def test_results_byte_identical_with_plane_on_and_off():
    on = sorted(_q1_query(TpuSession()).collect())
    off_sess = TpuSession(
        {"spark.rapids.tpu.dispatch.ledger.enabled": "false"})
    assert dispatch.active_ledger() is None
    off = sorted(_q1_query(off_sess).collect())
    assert on == off
    dispatch.reset_dispatch_ledger()


def test_health_section():
    sess = TpuSession()
    _q1_query(sess).collect()
    h = sess.health()["dispatch"]
    assert h["enabled"] is True
    assert h["dispatches"] > 0 and h["programs"] > 0
    assert h["top_programs"][0]["compile_ns"] >= \
        h["top_programs"][-1]["compile_ns"]


def test_dispatch_stats_event_and_report_rollup(tmp_path):
    sess = TpuSession({"spark.rapids.tpu.eventLog.enabled": "true",
                       "spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    _q1_query(sess).collect()
    log = events.active_bus().path
    events.reset_event_bus()
    evs = profile_report.read_event_files(log)
    kinds = {e["kind"] for e in evs}
    assert "program_compile" in kinds and "dispatch_stats" in kinds
    s = profile_report.build_summary(evs)
    dp = s["dispatch"]
    assert dp["programs_compiled"] > 0 and dp["compile_ns"] > 0
    assert dp["top_by_compile_ns"][0]["compile_ns"] > 0
    assert any(r["dispatches_per_batch"]
               for r in dp["top_by_dispatches_per_batch"])
    text = profile_report.build_report(evs)
    assert "program compiles:" in text
    assert "dispatches/batch" in text


def test_report_tolerates_pre_dispatch_logs(tmp_path):
    """A log from a build without dispatch events still renders — the
    roll-up reports zeros and prints nothing."""
    log = tmp_path / "old.jsonl"
    log.write_text(json.dumps(
        {"ts_ns": 1, "kind": "op_close", "query": 1, "op": "X",
         "op_id": 1, "wall_ns": 5, "batches": 1, "rows": 1}) + "\n")
    evs = profile_report.read_event_files(str(log))
    s = profile_report.build_summary(evs)
    assert s["dispatch"]["programs_compiled"] == 0
    assert s["dispatch"]["storms"] == []
    assert "program compiles" not in profile_report.build_report(evs)


def test_bench_dispatch_attribution_deltas():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench", Path(__file__).resolve().parents[1] / "bench.py")
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    bench._attr_prev.pop("dispatch", None)
    first = bench.dispatch_attribution()
    assert set(first) == {"programs", "dispatches", "compile_ns",
                          "cache_hits", "storms"}
    site = dispatch.instrument(lambda x: x + 1, label="t.bench")
    site(jnp.arange(32, dtype=jnp.int32))
    delta = bench.dispatch_attribution()
    assert delta["dispatches"] == 1 and delta["programs"] == 1


# -- trace exporter ----------------------------------------------------------

def _mk(ts_ns, kind, thread, **f):
    return dict(ts_ns=ts_ns, kind=kind, query=1, thread=thread, **f)


def test_trace_export_structure_handcrafted():
    """Structural acceptance on a deterministic log: >=3 thread tracks,
    NESTED operator spans (parent op_close encloses the child's), and
    compile instants."""
    us = 1_000
    evs = [
        _mk(100 * us, "program_compile", "MainThread", label="A.k",
            compile_ns=5, trace_ns=2, first=True),
        # child closes at 900us after 500us; parent at 1000us after
        # 800us: parent span [200..1000] strictly encloses [400..900]
        _mk(900 * us, "op_close", "MainThread", op="ChildExec", op_id=2,
            wall_ns=500 * us, batches=3, rows=9),
        _mk(1000 * us, "op_close", "MainThread", op="RootExec", op_id=1,
            wall_ns=800 * us, batches=3, rows=9),
        _mk(300 * us, "semaphore_acquire", "pipeline-scan-1",
            task_id=1, wait_ns=10),
        _mk(350 * us, "spill", "spill-writer", tier="device->host",
            bytes=123),
        _mk(400 * us, "telemetry_sample", "telemetry-sampler",
            **{"hbm.device_bytes": 42, "workload.queue_depth": 1}),
    ]
    trace = trace_export.build_trace(evs)
    te = trace["traceEvents"]
    tracks = {t["args"]["name"]: t["tid"] for t in te
              if t.get("ph") == "M" and t["name"] == "thread_name"}
    assert len(tracks) >= 3
    assert tracks["MainThread"] == 1
    spans = {t["name"]: t for t in te if t.get("ph") == "X"}
    root, child = spans["RootExec"], spans["ChildExec"]
    assert root["ts"] <= child["ts"]
    assert root["ts"] + root["dur"] >= child["ts"] + child["dur"]
    assert root["tid"] == child["tid"] == 1
    instants = {t["name"] for t in te if t.get("ph") == "i"}
    assert "program_compile" in instants and "spill" in instants
    counters = [t for t in te if t.get("ph") == "C"]
    assert {c["name"] for c in counters} == {"hbm.device_bytes",
                                             "workload.queue_depth"}


def test_trace_export_live_query_perfetto_shape(tmp_path):
    """Acceptance (ISSUE 13): a real host-shuffled run with eventLog on
    produces a Chrome trace with >=3 thread tracks (consumer +
    pipeline producers), nested op spans, and compile instants; the
    JSON is structurally Perfetto-loadable (traceEvents list, M/X/i
    phases only from the known set)."""
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.enabled": "true",
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.sql.shuffle.partitions": "2",
        "spark.rapids.sql.broadcastSizeThreshold": "-1"})
    _q3_query(sess).collect()
    log = events.active_bus().path
    events.reset_event_bus()
    out = str(tmp_path / "trace.json")
    assert trace_export.main([log, "-o", out]) == 0
    trace = json.load(open(out))
    te = trace["traceEvents"]
    assert trace["displayTimeUnit"] == "ms"
    assert all(t["ph"] in ("M", "X", "i", "C") for t in te)
    tracks = [t["args"]["name"] for t in te
              if t["ph"] == "M" and t["name"] == "thread_name"]
    assert len(tracks) >= 3, tracks
    assert "MainThread" in tracks
    assert any(t.startswith("pipeline-") for t in tracks)
    spans = [t for t in te if t["ph"] == "X"]
    # nested operator spans on the consumer track: some span strictly
    # inside another (the pull model's inclusive wall time)
    main_spans = sorted((t for t in spans if t["tid"] == 1),
                        key=lambda t: t["dur"], reverse=True)
    outer = main_spans[0]
    assert any(outer["ts"] <= s["ts"] and
               s["ts"] + s["dur"] <= outer["ts"] + outer["dur"]
               for s in main_spans[1:])
    assert any(t["name"] == "program_compile" for t in te
               if t["ph"] == "i")
