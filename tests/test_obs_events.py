"""Structured event pipeline (ISSUE 2 tentpole): JSONL schema, span
nesting/attribution, disabled-mode zero-emission, metric reconciliation
against last_query_metrics(), and the profile_report CLI."""

import glob
import json
import os
import re
import sys
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.expr.aggexprs import Count, Sum
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.obs import events, op_span
from spark_rapids_tpu.types import DOUBLE, INT, LONG, Schema, StructField

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
import profile_report  # noqa: E402


@pytest.fixture(autouse=True)
def _bus_isolation():
    """Every test leaves the process bus off (other suites rely on the
    disabled-mode fast path)."""
    yield
    events.reset_event_bus()
    TpuSession()


def _q1_query(sess, n=4000):
    """The q1-shaped bench pipeline: filter -> derived projection ->
    group-by aggregate (acceptance criterion shape)."""
    rng = np.random.default_rng(0)
    schema = Schema((StructField("returnflag", INT),
                     StructField("quantity", LONG),
                     StructField("extendedprice", DOUBLE),
                     StructField("discount", DOUBLE)))
    df = sess.from_pydict(
        {"returnflag": rng.integers(0, 4, n).tolist(),
         "quantity": rng.integers(1, 51, n).tolist(),
         "extendedprice": (rng.random(n) * 1000).tolist(),
         "discount": (rng.random(n) * 0.1).tolist()}, schema)
    return (df.filter(col("quantity") <= lit(45))
              .select(col("returnflag"), col("quantity"),
                      (col("extendedprice") * (lit(1.0) - col("discount")))
                      .alias("disc_price"))
              .group_by("returnflag")
              .agg((Sum(col("quantity")), "sum_qty"),
                   (Sum(col("disc_price")), "sum_disc"), (Count(), "cnt")))


def _enabled_session(tmp_path, level="DEBUG"):
    return TpuSession({"spark.rapids.tpu.eventLog.enabled": True,
                       "spark.rapids.tpu.eventLog.dir": str(tmp_path),
                       "spark.rapids.tpu.eventLog.level": level})


def _read_log(tmp_path):
    files = glob.glob(str(tmp_path / "events-*.jsonl"))
    assert len(files) == 1, files
    with open(files[0]) as f:
        return [json.loads(ln) for ln in f if ln.strip()]


def test_disabled_mode_emits_nothing(tmp_path):
    """Conf off (default): no bus, no files — even with a dir set."""
    sess = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    assert events.active_bus() is None
    rows = _q1_query(sess).collect()
    assert rows
    assert glob.glob(str(tmp_path / "*")) == []
    events.emit("spill", bytes=1)  # cold-path emit is a no-op too
    assert glob.glob(str(tmp_path / "*")) == []


def test_jsonl_schema_and_reconciliation(tmp_path):
    """The acceptance criterion: a q1-shaped query writes a parseable
    JSONL log whose op_close span times and row counts reconcile with
    last_query_metrics() totals."""
    sess = _enabled_session(tmp_path)
    rows = _q1_query(sess).collect()
    assert len(rows) == 4
    recs = _read_log(tmp_path)
    kinds = {r["kind"] for r in recs}
    assert {"query_start", "query_end", "op_open", "op_batch",
            "op_close"} <= kinds
    for r in recs:  # every record is self-describing
        assert isinstance(r["ts_ns"], int)
        assert isinstance(r["kind"], str)
        assert "query" in r
    (qid,) = {r["query"] for r in recs if r["kind"] == "op_close"}
    closes = [r for r in recs if r["kind"] == "op_close"]
    for r in closes:
        assert r["wall_ns"] >= 0 and r["batches"] >= 0 and r["rows"] >= 0
        assert r["op_id"] is not None
    # op_batch wall times sum to <= their op_close (close adds nothing)
    for r in closes:
        steps = [b for b in recs if b["kind"] == "op_batch"
                 and b["op_id"] == r["op_id"]]
        assert len(steps) == r["batches"]
        assert sum(b["wall_ns"] for b in steps) <= r["wall_ns"] * 1.01 + 1
    # row counts reconcile with the session metric roll-up, per operator
    m = sess.last_query_metrics()
    metric_rows = {}
    for path, v in m.items():
        if path.startswith("ops.") and path.endswith(".numOutputRows"):
            label = path[: -len(".numOutputRows")].split("/")[-1]
            label = label.removeprefix("ops.")
            label = re.sub(r"\[\d+\]$", "", label)  # sibling ordinal
            metric_rows[label] = metric_rows.get(label, 0) + v
    close_rows = {}
    for r in closes:
        close_rows[r["op"]] = close_rows.get(r["op"], 0) + r["rows"]
    for op, n in close_rows.items():
        assert metric_rows.get(op, 0) == n, (op, n, metric_rows)
    # the end event closes the query the spans ran under
    end = [r for r in recs if r["kind"] == "query_end"]
    assert end and end[-1]["ok"] and end[-1]["query"] == qid


def test_event_level_filters_span_records(tmp_path):
    """eventLog.level=ESSENTIAL keeps the query begin/end/phase-ledger
    records only (query_phases joined the essential set in ISSUE 17)."""
    sess = _enabled_session(tmp_path, level="ESSENTIAL")
    _q1_query(sess).collect()
    kinds = {r["kind"] for r in _read_log(tmp_path)}
    assert kinds == {"query_start", "query_end", "query_phases"}


def test_span_nesting_and_attribution(tmp_path):
    """op_span is the NvtxWithMetrics analog: nested spans all record,
    each accrues its block's EXCLUSIVE time to its phase, and every
    record carries the enclosing query id."""
    from spark_rapids_tpu.obs import phase as obs_phase
    events.enable(str(tmp_path), "DEBUG")
    before = obs_phase.counters()
    with events.query_scope(77):
        with op_span("outer", phase="plan", detail="a"):
            with op_span("inner", phase="sort"):
                pass
    after = obs_phase.counters()
    recs = _read_log(tmp_path)
    spans = {r["op"]: r for r in recs if r["kind"] == "span"}
    assert set(spans) == {"outer", "inner"}
    assert all(r["query"] == 77 and r["ok"] for r in spans.values())
    assert spans["outer"]["detail"] == "a"
    # inner closes first (nesting), and its wall time is contained
    assert spans["inner"]["ts_ns"] <= spans["outer"]["ts_ns"]
    assert 0 < spans["inner"]["wall_ns"] <= spans["outer"]["wall_ns"]
    # each phase took its own span's exclusive time, and no more than
    # the outer span's wall between them
    grew = {p: after[p] - before[p] for p in ("plan", "sort")}
    assert grew["sort"] > 0 and grew["plan"] > 0
    assert grew["sort"] + grew["plan"] <= spans["outer"]["wall_ns"] + 10**6


def test_span_records_failure_and_still_accrues_its_phase(tmp_path):
    from spark_rapids_tpu.obs import phase as obs_phase
    events.enable(str(tmp_path), "DEBUG")
    before = obs_phase.counters()["plan-rerun"]
    with pytest.raises(ValueError):
        with op_span("boom", phase="plan-rerun"):
            raise ValueError("x")
    assert obs_phase.counters()["plan-rerun"] > before
    (rec,) = _read_log(tmp_path)
    assert rec["op"] == "boom" and rec["ok"] is False
    assert rec["wall_ns"] > 0


def test_memory_events_spill_and_retry(tmp_path):
    """Spill and OOM-retry producers land structured records."""
    import jax.numpy as jnp

    from spark_rapids_tpu.memory.catalog import (buffer_catalog,
                                                 reset_buffer_catalog)
    from spark_rapids_tpu.memory.retry import (TpuRetryOOM, force_retry_oom,
                                               register_task,
                                               unregister_task, with_retry)
    events.enable(str(tmp_path), "MODERATE")
    cat = reset_buffer_catalog()
    h = cat.add(jnp.arange(1024))
    cat.synchronous_spill(None)
    register_task(9)
    try:
        force_retry_oom(1)
        assert list(with_retry(1, lambda x: x * 2)) == [2]
    finally:
        unregister_task()
        cat.remove(h)
        reset_buffer_catalog()
    recs = _read_log(tmp_path)
    spills = [r for r in recs if r["kind"] == "spill"]
    assert spills and spills[0]["tier"] == "device->host"
    assert spills[0]["bytes"] == jnp.arange(1024).nbytes
    retries = [r for r in recs if r["kind"] == "oom_retry"]
    assert retries and retries[0]["oom"] == "retry"
    assert retries[0]["task_id"] == 9


def test_profile_report_cli_renders_top_table(tmp_path, capsys):
    """tools/profile_report.py turns an event log into the top-N
    operator time/bytes table (acceptance criterion)."""
    sess = _enabled_session(tmp_path)
    _q1_query(sess).collect()
    (log,) = glob.glob(str(tmp_path / "events-*.jsonl"))
    assert profile_report.main([log, "--top", "3"]) == 0
    out = capsys.readouterr().out
    assert "top 3 operators by inclusive wall time" in out
    # ISSUE 14: the filter+group-by chain executes as a fused stage
    assert "CompiledStageExec" in out or "AggregateExec" in out
    assert "1 queries (1 completed)" in out
    # machine surface: the builder is also importable on raw lines
    with open(log) as f:
        report = profile_report.build_report(
            profile_report.read_events(f), top=2)
    assert "CompiledStageExec" in report or "AggregateExec" in report


def test_bus_reconfigure_reuses_and_closes(tmp_path):
    """Same dir+level keeps one file across queries; the bus is
    process-wide, so a default-conf session leaves it alone and only an
    EXPLICIT enabled=false tears it down."""
    sess = _enabled_session(tmp_path)
    q = _q1_query(sess)
    q.collect()
    q.collect()
    recs = _read_log(tmp_path)  # asserts exactly one file
    assert sum(1 for r in recs if r["kind"] == "query_end") == 2
    qids = {r["query"] for r in recs if r["kind"] == "query_end"}
    assert len(qids) == 2  # fresh id per query
    TpuSession()  # eventLog.enabled UNSET: another session's log lives on
    assert events.active_bus() is not None
    TpuSession({"spark.rapids.tpu.eventLog.enabled": False})  # explicit
    assert events.active_bus() is None


def test_write_failure_deactivates_bus(tmp_path):
    """A dead sink removes itself: producers must drop back to the
    uninstrumented fast path instead of serializing records into a
    closed bus forever."""
    events.enable(str(tmp_path / "f"), "MODERATE")
    (tmp_path / "f").write_text("not a directory")  # makedirs will fail
    events.emit("spill", bytes=1)
    assert events.active_bus() is None
