"""The one span primitive (`obs.op_span`) and the sites that open it
(ISSUE 27): exclusive phase accounting under nesting, folding from a
non-driving thread, each ingest-path site accruing its phase on a small
Parquet query with the books still closed, the span names as host
events in a profiler trace, the dispatch ledger naming its programs'
XLA modules, and results byte-identical with the accounting off."""

import glob
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec import lifecycle
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.obs import dispatch, events, op_span, phase
from spark_rapids_tpu.obs.phase import PHASES

SPAN_NAMES = ("session.plan", "scan.decode", "upload.pack", "upload.put",
              "result.fetch")
NEW_PHASES = ("plan", "scan-decode", "upload", "device-wait")


@pytest.fixture(autouse=True)
def _isolation():
    yield
    phase.reset_phase_counters()
    events.reset_event_bus()
    TpuSession()  # restore the default active conf


def _spin(ns: int) -> None:
    t0 = time.perf_counter_ns()
    while time.perf_counter_ns() - t0 < ns:
        pass


def _parquet(tmp_path, files=3, rows=600):
    """`files` files x 3 row groups: more than one decode task, so the
    scan goes through the shared `multifile-read` pool."""
    rng = np.random.default_rng(5)
    d = tmp_path / "t"
    d.mkdir()
    for i in range(files):
        pq.write_table(pa.table({
            "k": pa.array(rng.integers(0, 7, rows), pa.int64()),
            "v": pa.array(rng.random(rows) * 10.0, pa.float64())}),
            str(d / f"part-{i}.parquet"), row_group_size=rows // 3)
    return str(d)


def _query(sess, path):
    return (sess.read_parquet(path).filter(col("k") != lit(0))
            .group_by("k").agg((F.sum("v"), "s"), (F.count(), "c")))


# -- the primitive -------------------------------------------------------------

def test_phase_span_is_exclusive_under_nesting_and_notifies_the_parent():
    phase.reset_phase_counters()
    t0 = time.perf_counter_ns()
    with op_span("outer", phase="ici-collective"):
        assert phase.in_span()
        _spin(1_000_000)
        with op_span("inner", phase="upload"):
            _spin(2_000_000)
            # a nested after-the-fact accrual is carved out of `inner`
            phase.add("spill-wait", 500_000)
    wall = time.perf_counter_ns() - t0
    assert not phase.in_span()
    cur = phase.counters()
    assert cur["spill-wait"] == 500_000
    assert cur["upload"] >= 1_500_000
    assert cur["ici-collective"] >= 1_000_000
    # exclusive: the three never count one nanosecond twice
    assert cur["ici-collective"] + cur["upload"] + cur["spill-wait"] <= wall
    # the parent was told of the child's WHOLE block, not its exclusive part
    assert cur["ici-collective"] <= wall - 2_000_000


def test_a_cached_dispatch_inside_a_phase_span_stays_with_the_span():
    phase.reset_phase_counters()
    with op_span("upload.put", phase="upload"):
        phase.note_dispatch(900, traced=False)   # the span keeps it
        phase.note_dispatch(500, traced=True)    # a trace is compile anywhere
    cur = phase.counters()
    assert cur["device-compute"] == 0 and cur["compile"] == 500
    assert cur["upload"] > 0


@pytest.mark.parametrize("with_phase", [False, True])
def test_event_with_and_without_a_phase(tmp_path, with_phase):
    phase.reset_phase_counters()
    events.enable(str(tmp_path), "DEBUG")
    with pytest.raises(ValueError):
        with op_span("boom", phase="plan" if with_phase else None,
                     side="left"):
            _spin(200_000)
            raise ValueError("x")
    assert (phase.counters()["plan"] >= 200_000) is with_phase
    assert not phase.in_span()                   # the frame was popped
    events.reset_event_bus()
    (log,) = glob.glob(str(tmp_path / "events-*.jsonl"))
    (rec,) = [json.loads(ln) for ln in open(log) if ln.strip()]
    assert rec["kind"] == "span" and rec["op"] == "boom"
    assert rec["ok"] is False and rec["side"] == "left"
    assert rec["wall_ns"] >= 200_000


def test_the_map_write_times_its_metric_beside_its_span():
    """The shuffle map write, the one engine caller of op_span's former
    `metric=` (PR 40), times `shuffleWriteTime` with the metric's own
    timer; its span still accrues host-pack-serialize."""
    from spark_rapids_tpu.types import LONG, Schema
    phase.reset_phase_counters()
    sess = TpuSession({"spark.rapids.sql.shuffle.partitions": "2",
                       "spark.rapids.sql.broadcastSizeThreshold": "-1",
                       "spark.rapids.tpu.adaptive.enabled": "false"})
    df = sess.from_pydict({"k": [i % 7 for i in range(200)],
                           "v": list(range(200))},
                          Schema.of(k=LONG, v=LONG), batch_rows=50)
    out = df.group_by("k").agg((F.sum("v"), "s")).collect()
    assert sorted(out) == sorted(
        (k, sum(v for v in range(200) if v % 7 == k)) for k in range(7))
    m = sess.last_query_metrics()
    assert m["total.shuffleWriteTime"] > 0
    assert phase.counters()["host-pack-serialize"] > 0


def test_a_span_on_a_non_driving_thread_lands_in_the_ledgers_folded_map():
    with lifecycle.governed() as ctx:
        led = phase.attach(ctx)

        def producer():
            lifecycle.adopt_context(ctx)
            with op_span("scan.decode", phase="scan-decode"):
                _spin(1_000_000)

        t = threading.Thread(target=producer)
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        assert led._folded.get("scan-decode", 0) >= 1_000_000
        assert "scan-decode" not in led._direct
        # folded time displaces the stall the consumer measured, no more
        led.add("pipeline-stall", 400_000)
        snap = led.snapshot()
    assert snap["scan-decode"] == 400_000 and snap["pipeline-stall"] == 0
    assert sum(snap.values()) == led.wall_ns and min(snap.values()) >= 0


# -- the sites -----------------------------------------------------------------

@pytest.mark.parametrize("phase_name", NEW_PHASES)
def test_each_site_accrues_its_phase_and_the_books_stay_closed(
        tmp_path, phase_name):
    path = _parquet(tmp_path)
    sess = TpuSession()
    stall0 = phase.counters()["pipeline-stall"]
    before = phase.counters()[phase_name]
    rows = _query(sess, path).collect()
    assert len(rows) == 6
    assert phase.counters()[phase_name] > before     # the global books
    prof = sess.last_query_profile()
    led = prof._phase_ledger
    raw = led._direct.get(phase_name, 0) + led._folded.get(phase_name, 0)
    assert raw > 0                                   # and the query's own
    if phase_name in ("plan", "device-wait"):
        # driving-thread sites are direct, so they show as they are
        assert phase_name in led._direct
        assert prof.phases()[phase_name] == led._direct[phase_name]
    ph = prof.phases()
    assert set(ph) == set(PHASES)
    assert sum(ph.values()) == prof.phases_wall_ns()
    assert min(ph.values()) >= 0
    # pipeline-stall's global counter is still the consumer's own clock:
    # no span site writes into it
    assert phase.counters()["pipeline-stall"] >= stall0


def test_decode_on_the_shared_pool_reaches_the_querys_ledger(tmp_path):
    """The decode tasks run on `multifile-read` pool threads with empty
    thread-locals; the submitting thread's lifecycle context rides each
    job, so their time is in the query's ledger (folded), and the pool
    thread is left as it was found."""
    path = _parquet(tmp_path, files=4)
    sess = TpuSession()
    _query(sess, path).collect()
    led = sess.last_query_profile()._phase_ledger
    assert led._folded.get("scan-decode", 0) > 0
    from spark_rapids_tpu.io.multifile import shared_read_pool
    assert shared_read_pool().submit(
        lifecycle.current_context).result(timeout=30) is None


def test_the_span_names_are_host_events_in_a_profiler_trace(tmp_path):
    path = _parquet(tmp_path)
    sess = TpuSession()
    _query(sess, path).collect()                     # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        _query(sess, path).collect()
    finally:
        jax.profiler.stop_trace()
    (xplane,) = glob.glob(str(tmp_path / "trace" / "**" / "*.xplane.pb"),
                          recursive=True)
    seen = {}
    for plane in jax.profiler.ProfileData.from_file(xplane).planes:
        if not plane.name.startswith("/host:"):
            continue
        for n, line in enumerate(plane.lines):      # one line per thread
            for e in line.events:
                if e.name in SPAN_NAMES and e.duration_ns > 0:
                    seen.setdefault(e.name, set()).add((plane.name, n))
    assert set(seen) == set(SPAN_NAMES)
    # the decode ran on the pool, the fetch on the thread that collected:
    # the events sit on the lines of the threads that did the work
    assert seen["scan.decode"].isdisjoint(seen["result.fetch"])


# -- the ledger names its programs ---------------------------------------------

def test_module_labels_after_one_query(tmp_path):
    dispatch.reset_dispatch_ledger()
    path = _parquet(tmp_path)
    _query(TpuSession(), path).collect()
    ml = dispatch.module_labels()
    assert ml["jit__concat_pair"] == ["coalesce.concat_pair"]
    assert ml["jit__unpack_batch_impl"] == ["upload.unpack_batch"]
    progs = dispatch.programs()
    assert all(p["module"].startswith("jit_") for p in progs)
    assert {p["module"] for p in progs} == set(ml)
    assert all(labels == sorted(set(labels)) for labels in ml.values())


def _named(name):
    def f(x):
        return x + 1
    f.__name__ = name
    return f


@pytest.mark.parametrize("fn", [
    _named("_concat_pair"), _named("trailing_"), lambda x: x * 2,
    _named("a.b-c<d>")], ids=["plain", "trailing", "lambda", "odd"])
def test_the_module_name_is_spelled_as_jax_spells_it(fn):
    """`site.module` against the name in the program JAX lowers: what a
    device trace shows as `<module>(<fingerprint>)`."""
    site = dispatch.instrument(fn, label="test.spelling")
    hlo = site._jit.lower(jnp.ones(3)).as_text()
    first = hlo.splitlines()[0]
    assert first.split("@", 1)[1].split()[0].strip('"') == site.module


def test_two_labels_on_one_module_are_both_listed():
    dispatch.reset_dispatch_ledger()
    for label in ("zeta.step", "alpha.step"):
        dispatch.instrument(_named("_step"), label=label)(jnp.ones(3))
    assert dispatch.module_labels() == {
        "jit__step": ["alpha.step", "zeta.step"]}


# -- off is off ----------------------------------------------------------------

def test_results_are_byte_identical_with_phases_and_the_ledger_off(tmp_path):
    path = _parquet(tmp_path)
    rows_on = sorted(_query(TpuSession(), path).collect())
    off = TpuSession({"spark.rapids.tpu.phases.enabled": "false",
                      "spark.rapids.tpu.dispatch.ledger.enabled": "false"})
    try:
        assert dispatch.active_ledger() is None
        assert dispatch.module_labels() == {}
        rows_off = sorted(_query(off, path).collect())
        assert off.last_query_profile().phases() is None
    finally:
        TpuSession()                                 # the ledger comes back
    assert dispatch.active_ledger() is not None
    assert repr(rows_off) == repr(rows_on)
