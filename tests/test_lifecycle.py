"""Query lifecycle governor (ISSUE 6): deadlines + cooperative
cancellation (thread hygiene asserted), partition-granular shuffle
recovery vs the whole-plan fallback, degradation circuit breakers, the
heartbeat deadlock fix, and the tooling roll-ups.

Deterministic on single-core CPU: cancellations are either self-induced
(a pandas UDF cancels its own session mid-stream) or deadline-driven
against an artificially stalled producer; breaker transitions use
injected device faults and tiny cooldowns; shuffle corruption is the
PR 4 seeded injection plan."""

import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_tpu import QueryCancelledError
from spark_rapids_tpu import config as C
from spark_rapids_tpu import faults
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col, lit
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.exec import lifecycle
from spark_rapids_tpu.exec.task_retry import with_task_retry
from spark_rapids_tpu.memory.budget import (memory_budget,
                                            reset_memory_budget)
from spark_rapids_tpu.memory.catalog import (buffer_catalog,
                                             reset_buffer_catalog)
from spark_rapids_tpu.obs import events
from spark_rapids_tpu.types import LONG, Schema

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

FAST = {
    "spark.rapids.tpu.io.retryBackoffMs": "1",
    "spark.rapids.tpu.task.retryBackoffMs": "1",
    "spark.rapids.tpu.retry.backoffMs": "1",
}


def _threads():
    return {t for t in threading.enumerate()
            if t.name.startswith(("pipeline-", "spill-writer"))}


@pytest.fixture(autouse=True)
def _lifecycle_isolation():
    """Every test starts with a clean governor (no breakers, no
    contexts), injection off, the conf restored, and zero NEW
    pipeline-*/spill-writer threads leaked."""
    pre = _threads()
    prev_conf = C.active_conf()
    lifecycle.reset_lifecycle()
    faults.install(None)
    yield
    faults.install(None)
    lifecycle.reset_lifecycle()
    C.set_active_conf(prev_conf)
    assert _threads() <= pre, "leaked threads"


@pytest.fixture
def spy(monkeypatch):
    rows = []
    real = events.emit

    def spy_emit(kind, **fields):
        rows.append({"kind": kind, **fields})
        real(kind, **fields)

    monkeypatch.setattr(events, "emit", spy_emit)
    return rows


def _kinds(rows, kind):
    return [r for r in rows if r["kind"] == kind]


# ---------------------------------------------------------------------------
# QueryContext unit contracts
# ---------------------------------------------------------------------------

def test_context_deadline_and_tick_cadence(spy):
    ctx = lifecycle.QueryContext(timeout_ms=0, check_every=3)
    ctx.tick(); ctx.tick(); ctx.tick()  # healthy: no raise
    ctx.cancel("user")
    ctx.tick(); ctx.tick()  # below the check cadence: still no raise
    with pytest.raises(QueryCancelledError) as ei:
        ctx.tick()
    assert ei.value.phase == "compute" and ei.value.reason == "user"
    # the event is emitted exactly once, by the first checker
    with pytest.raises(QueryCancelledError):
        ctx.check("sem-wait")
    evs = _kinds(spy, "query_cancelled")
    assert len(evs) == 1 and evs[0]["phase"] == "compute"

    expired = lifecycle.QueryContext(timeout_ms=10, check_every=1)
    time.sleep(0.02)
    with pytest.raises(QueryCancelledError) as ei:
        expired.check("spill-wait")
    assert ei.value.reason == "timeout"
    assert ei.value.phase in lifecycle.CANCEL_PHASES


def test_governed_registry_and_cancel_owner():
    owner = object()
    assert lifecycle.cancel_owner(owner) == 0  # nothing running
    with lifecycle.governed(C.RapidsConf({}), owner=owner) as ctx:
        assert ctx.ctx_id in lifecycle.active_query_ids()
        assert lifecycle.current_context() is ctx
        assert lifecycle.cancel_owner(owner) == 1
        assert ctx.cancelled() and ctx.reason == "user"
        # an unrelated owner's cancel does not touch it
        assert lifecycle.cancel_owner(object()) == 0
    assert lifecycle.active_query_ids() == []
    assert lifecycle.current_context() is None


def test_check_current_is_noop_without_context():
    lifecycle.check_current("compute")  # must not raise
    assert not lifecycle.current_cancelled()


# ---------------------------------------------------------------------------
# circuit breakers
# ---------------------------------------------------------------------------

BREAKER = dict(FAST, **{
    "spark.rapids.tpu.breaker.enabled": "true",
    "spark.rapids.tpu.breaker.threshold": "2",
    "spark.rapids.tpu.breaker.cooldownMs": "120",
    "spark.rapids.tpu.task.maxAttempts": "6",
})


@pytest.fixture
def hash_tier(monkeypatch):
    """The consult that a breaker demotes: ops/hashing._use_pallas (the
    murmur3 kernels, domain `pallas_hash`), with the platform probe
    answering "on a TPU" so that only the breaker decides here."""
    from spark_rapids_tpu.ops import hashing, pallas_kernels
    monkeypatch.setattr(pallas_kernels, "on_tpu", lambda: True)
    return hashing._use_pallas


def test_breaker_disabled_by_default_records_nothing():
    C.set_active_conf(C.RapidsConf(dict(FAST)))
    for _ in range(5):
        lifecycle.record_domain_failure("pallas_hash")
    assert lifecycle.open_breakers() == []
    assert lifecycle.breaker_allows("pallas_hash")


def test_breaker_demotes_fused_tier_and_rearms_after_cooldown(
        spy, hash_tier):
    """Acceptance criterion: N injected device failures demote the
    Pallas hash domain to XLA (the consult answers False); after the
    cooldown the half-open probe re-engages and a successful attempt
    closes the breaker."""
    conf = C.RapidsConf(dict(BREAKER))
    C.set_active_conf(conf)
    engagements = []

    def flaky(attempt):
        engagements.append(hash_tier())
        if attempt <= 2:
            raise faults.InjectedDeviceError("device.dispatch")
        return "ok"

    assert with_task_retry(flaky, conf=conf) == "ok"
    # attempts 1+2 engaged and failed -> breaker opens -> attempt 3
    # runs demoted on the XLA safe path
    assert engagements == [True, True, False]
    opens = _kinds(spy, "breaker_open")
    assert {e["domain"] for e in opens} == {"pallas_hash",
                                            "device_dispatch"}
    assert any(e["safe_path"] for e in opens)
    assert set(lifecycle.open_breakers()) == {"device_dispatch",
                                              "pallas_hash"}
    h = lifecycle.health()
    assert h["breakers"]["pallas_hash"]["state"] == "open"
    assert h["breakers"]["pallas_hash"]["trips"] == 1

    # demoted inside the cooldown window
    assert not hash_tier()

    # cooldown -> half-open probe -> success closes and re-arms
    time.sleep(0.15)
    assert with_task_retry(lambda a: hash_tier(), conf=conf) is True
    assert lifecycle.open_breakers() == []
    assert [e["domain"] for e in _kinds(spy, "breaker_half_open")
            if e["domain"] == "pallas_hash"] == ["pallas_hash"]
    assert [e["domain"] for e in _kinds(spy, "breaker_close")].count(
        "pallas_hash") == 1
    assert hash_tier()


def test_breaker_reopens_on_failed_probe(spy, hash_tier):
    conf = C.RapidsConf(dict(BREAKER))
    C.set_active_conf(conf)

    def flaky(attempt):
        if hash_tier():  # fails every time the Pallas kernels engage
            raise faults.InjectedDeviceError("device.dispatch")
        return "xla"

    assert with_task_retry(flaky, conf=conf) == "xla"
    assert "pallas_hash" in lifecycle.open_breakers()
    time.sleep(0.15)
    # half-open probe engages, fails again -> re-open (trips == 2)
    assert with_task_retry(flaky, conf=conf) == "xla"
    assert lifecycle.health()["breakers"]["pallas_hash"]["trips"] == 2
    assert "pallas_hash" in lifecycle.open_breakers()


def test_breaker_half_open_single_probe_and_kill_switch(spy):
    """Review r4: half_open lets exactly ONE probe through (concurrent
    consults stay demoted while it is in flight), and the
    breaker.enabled kill-switch restores the accelerated path
    immediately, recorded state notwithstanding."""
    conf = C.RapidsConf(dict(BREAKER, **{
        "spark.rapids.tpu.breaker.threshold": "1",
        "spark.rapids.tpu.breaker.cooldownMs": "60"}))
    C.set_active_conf(conf)
    lifecycle.record_domain_failure("pallas_hash")
    assert not lifecycle.breaker_allows("pallas_hash")  # open
    time.sleep(0.08)
    assert lifecycle.breaker_allows("pallas_hash")       # the probe
    assert not lifecycle.breaker_allows("pallas_hash"), \
        "a second consult engaged while the probe was in flight"
    lifecycle.record_domain_success("pallas_hash")       # probe passed
    assert lifecycle.breaker_allows("pallas_hash")
    assert lifecycle.open_breakers() == []
    # kill-switch: an open breaker must not outlive the conf
    lifecycle.record_domain_failure("pallas_hash")
    assert not lifecycle.breaker_allows("pallas_hash")
    C.set_active_conf(C.RapidsConf(dict(FAST, **{
        "spark.rapids.tpu.breaker.enabled": "false"})))
    assert lifecycle.breaker_allows("pallas_hash")


def test_breaker_counts_the_exhausted_final_attempt(spy, hash_tier):
    """Review r2: the FINAL failing attempt (the strongest persistence
    signal) must count toward the breaker before with_task_retry
    re-raises — with maxAttempts=1 it is the only signal there is."""
    conf = C.RapidsConf(dict(BREAKER, **{
        "spark.rapids.tpu.task.maxAttempts": "1",
        "spark.rapids.tpu.breaker.threshold": "1"}))
    C.set_active_conf(conf)

    def doomed(attempt):
        assert hash_tier()
        raise faults.InjectedDeviceError("device.dispatch")

    with pytest.raises(faults.InjectedDeviceError):
        with_task_retry(doomed, conf=conf)
    assert "pallas_hash" in lifecycle.open_breakers()
    assert _kinds(spy, "breaker_open")


def test_cancelled_producer_never_reads_as_clean_end():
    """Review r2: a pipeline producer that stops on lifecycle
    cancellation must carry the cancellation to its consumer — a clean
    _END would let a truncated stream read as normal completion (silent
    wrong results)."""
    from spark_rapids_tpu.exec.pipeline import pipelined
    C.set_active_conf(C.RapidsConf(dict(FAST)))
    with lifecycle.governed(C.RapidsConf(dict(FAST))) as ctx:
        def src():
            yield 1
            ctx.cancel("user")  # lands between producer steps
            yield 2
            yield 3

        stage = pipelined(src(), depth=1, emit_events=False)
        got = []
        try:
            with pytest.raises(QueryCancelledError):
                for x in stage:
                    got.append(x)
        finally:
            stage.close()
        assert 3 not in got, "producer ran past the cancellation"


def test_breaker_session_health_surface(spy):
    """Session-level: a query whose guarded dispatch dies twice still
    succeeds via task retry, and health() surfaces the opened
    device_dispatch breaker."""
    settings = dict(BREAKER)
    # long cooldown: the breaker must still be OPEN when the successful
    # third attempt lands (a short one would legitimately half-open and
    # close it mid-query — compile time alone outlasts 120ms)
    settings["spark.rapids.tpu.breaker.cooldownMs"] = "60000"
    settings["spark.rapids.tpu.test.faults"] = \
        "device.dispatch:prob=1,seed=3,kind=device,max=2"
    sess = TpuSession(settings)
    df = sess.from_pydict({"a": list(range(64))}, Schema.of(a=LONG))
    out = df.agg((F.sum("a"), "s")).collect()
    assert out == [(sum(range(64)),)]
    h = sess.health()
    assert h["breakers"]["device_dispatch"]["state"] == "open"
    assert h["counters"]["breaker_open"] >= 1
    assert h["counters"]["whole_plan_retries"] >= 2
    assert _kinds(spy, "breaker_open")


# ---------------------------------------------------------------------------
# cooperative cancellation through the session (thread hygiene)
# ---------------------------------------------------------------------------

def _cancel_after(sess, k):
    """A mapInPandas fn that cancels its own session after k batches —
    a deterministic mid-query cancellation trigger."""
    seen = {"n": 0}

    def fn(it):
        for pdf in it:
            seen["n"] += 1
            if seen["n"] == k:
                assert sess.cancel_query() == 1
            yield pdf

    return fn


def _assert_clean_and_rerunnable(sess, df, spy, pre_threads):
    """Shared post-cancellation contract: the event fired, no
    robustness threads leaked, and the SAME session runs the next query
    clean (no poisoned semaphore/catalog state)."""
    evs = _kinds(spy, "query_cancelled")
    assert len(evs) == 1 and evs[0]["phase"] in lifecycle.CANCEL_PHASES
    assert _threads() <= pre_threads, "cancellation leaked threads"
    assert lifecycle.active_query_ids() == []
    follow = sess.from_pydict({"z": [1, 2, 3]}, Schema.of(z=LONG))
    assert follow.agg((F.sum("z"), "s")).collect() == [(6,)]


def test_cancel_mid_scan(spy):
    pre = _threads()
    # small coalesce target: the scan's batches must NOT collapse into
    # one, or there is no "mid"-scan left to cancel in
    sess = TpuSession(dict(FAST, **{
        "spark.rapids.tpu.query.cancelCheckBatches": "1",
        "spark.rapids.sql.batchSizeBytes": "4k"}))
    df = sess.from_pydict({"a": list(range(5000))}, Schema.of(a=LONG),
                          batch_rows=250)
    out_schema = Schema.of(a=LONG)
    with pytest.raises(QueryCancelledError) as ei:
        df.map_in_pandas(_cancel_after(sess, 2), out_schema).collect()
    assert ei.value.reason == "user"
    _assert_clean_and_rerunnable(sess, df, spy, pre)


def test_cancel_mid_shuffle_read(spy):
    """Cancellation lands while host-shuffle partition streams are
    still pending: the unwind must close the pipelined shuffle readers
    and unregister the handle."""
    pre = _threads()
    sess = TpuSession(dict(FAST, **{
        "spark.rapids.tpu.query.cancelCheckBatches": "1",
        "spark.rapids.sql.shuffle.partitions": "3",
        "spark.rapids.sql.broadcastSizeThreshold": "-1"}))
    rng = np.random.default_rng(5)
    df = sess.from_pydict(
        {"k": [int(x) for x in rng.integers(0, 40, 1200)],
         "v": [int(x) for x in rng.integers(0, 100, 1200)]},
        Schema.of(k=LONG, v=LONG), batch_rows=300)
    agg = df.group_by("k").agg((F.sum("v"), "s"))
    out_schema = Schema.of(k=LONG, s=LONG)
    with pytest.raises(QueryCancelledError):
        agg.map_in_pandas(_cancel_after(sess, 1), out_schema).collect()
    _assert_clean_and_rerunnable(sess, df, spy, pre)


def test_cancel_mid_spill_writeback(spy):
    """Cancellation under a spill-forcing budget with the async writer
    active: the unwind settles in-flight writebacks, catalog entries
    and the budget counter."""
    pre = _threads()
    prev_cat_entries = None
    try:
        reset_buffer_catalog()
        reset_memory_budget(192 * 1024)
        sess = TpuSession(dict(FAST, **{
            "spark.rapids.tpu.query.cancelCheckBatches": "1",
            "spark.rapids.tpu.spill.asyncWrite": "true",
            "spark.rapids.sql.batchSizeBytes": str(16 * 1024),
            "spark.rapids.sql.broadcastSizeThreshold": "-1"}))
        used_before = memory_budget().used
        prev_cat_entries = buffer_catalog().num_entries()
        rng = np.random.default_rng(9)
        n_l, n_o = 6000, 300
        lines = sess.from_pydict(
            {"l_key": [int(x) for x in rng.integers(0, n_o, n_l)],
             "l_val": [int(x) for x in rng.integers(0, 100, n_l)]},
            Schema.of(l_key=LONG, l_val=LONG), batch_rows=1500)
        orders = sess.from_pydict(
            {"o_key": list(range(n_o))}, Schema.of(o_key=LONG))
        j = lines.join(orders, left_on=["l_key"], right_on=["o_key"])
        out_schema = Schema.of(l_key=LONG, l_val=LONG, o_key=LONG)
        with pytest.raises(QueryCancelledError):
            j.map_in_pandas(_cancel_after(sess, 1), out_schema).collect()
        buffer_catalog().drain_writeback()
        assert memory_budget().used == used_before, \
            "cancellation leaked budget"
        assert buffer_catalog().num_entries() == prev_cat_entries, \
            "cancellation leaked catalog entries"
        _assert_clean_and_rerunnable(sess, j, spy, pre)
    finally:
        reset_buffer_catalog()
        reset_memory_budget()


class _StallingSource:
    """batches() sleeps before every batch after the first — an
    artificially stalled producer for the deadline acceptance test."""

    def __init__(self, batches, schema, stall_s):
        self._batches = batches
        self.schema = schema
        self.stall_s = stall_s

    def batches(self):
        for i, b in enumerate(self._batches):
            if i >= 1:
                time.sleep(self.stall_s)
            yield b

    def estimated_size_bytes(self):
        return sum(b.device_size_bytes() for b in self._batches)

    def estimated_num_rows(self):
        return sum(b.num_rows_host for b in self._batches)


def test_deadline_bounds_stalled_producer(spy):
    """Acceptance criterion: a stalled producer query returns
    QueryCancelledError within timeoutMs + slack (the slack covers one
    producer step + the stage join) with zero leaked threads."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.plan import logical as L
    pre = _threads()
    schema = Schema.of(a=LONG)
    batches = [ColumnarBatch.from_pydict({"a": [i] * 64}, schema)
               for i in range(6)]
    sess = TpuSession(dict(FAST, **{
        "spark.rapids.tpu.query.timeoutMs": "300",
        "spark.rapids.tpu.query.cancelCheckBatches": "1"}))
    df = sess._df(L.LogicalScan(_StallingSource(batches, schema, 1.2)))
    t0 = time.monotonic()
    with pytest.raises(QueryCancelledError) as ei:
        df.filter(col("a") >= lit(0)).collect()
    wall = time.monotonic() - t0
    assert ei.value.reason == "timeout"
    # timeoutMs + slack: one 1.2s producer step may be in flight when
    # the deadline fires and the unwind joins it; 8s is generous slack
    # for a loaded 1-core box against the 7.2s un-cancelled runtime
    assert 0.3 <= wall < 8.0, wall
    evs = _kinds(spy, "query_cancelled")
    assert len(evs) == 1 and evs[0]["reason"] == "timeout"
    assert evs[0]["phase"] in lifecycle.CANCEL_PHASES
    assert _threads() <= pre, "deadline expiry leaked threads"
    # the same session runs the next query clean — with the deadline
    # lifted first: the 300ms budget governs EVERY collect on this
    # session, and a fresh plan's cold jit compile alone can outlast it
    # (a single-test run has no warm caches), which would measure cache
    # temperature instead of state hygiene
    sess.conf = C.RapidsConf(dict(FAST))
    ok = sess.from_pydict({"z": [4, 5]}, Schema.of(z=LONG))
    assert ok.agg((F.sum("z"), "s")).collect() == [(9,)]


def test_deadline_spans_task_retry_attempts(spy):
    """The deadline bounds the query's TOTAL wall-clock: a query whose
    attempts keep dying transiently stops retrying once the deadline
    passes (phase task-retry), instead of burning all maxAttempts."""
    conf = C.RapidsConf(dict(FAST, **{
        "spark.rapids.tpu.task.maxAttempts": "50",
        "spark.rapids.tpu.task.retryBackoffMs": "30"}))
    calls = []

    def always_transient(attempt):
        calls.append(attempt)
        raise faults.InjectedDeviceError("device.dispatch")

    with lifecycle.governed(conf, timeout_ms=120):
        with pytest.raises(QueryCancelledError) as ei:
            with_task_retry(always_transient, conf=conf)
    assert ei.value.phase == "task-retry"
    assert len(calls) < 50, "deadline did not bound the retry loop"


# ---------------------------------------------------------------------------
# partition-granular recovery
# ---------------------------------------------------------------------------

def _shuffle_query_data():
    rng = np.random.default_rng(7)
    data = {"k": [int(x) for x in rng.integers(0, 50, 2000)],
            "v": [int(x) for x in rng.integers(0, 1000, 2000)]}
    oracle = {}
    for k, v in zip(data["k"], data["v"]):
        oracle[k] = oracle.get(k, 0) + v
    return data, sorted(oracle.items())


SHUFFLED = dict(FAST, **{
    "spark.rapids.sql.shuffle.partitions": "3",
    "spark.rapids.sql.broadcastSizeThreshold": "-1",
})


def _drive_shuffled_agg(settings, data):
    sess = TpuSession(settings)
    df = sess.from_pydict(data, Schema.of(k=LONG, v=LONG),
                          batch_rows=500)
    return sorted(df.group_by("k").agg((F.sum("v"), "s")).collect())


def test_shuffle_corruption_recomputes_one_map_output(spy):
    """Acceptance criterion: one corrupted committed shuffle block
    mid-query recomputes ONE map output (the producing sub-plan), not
    the query — asserted via event counts — with results equal to the
    fault-free run (numpy oracle)."""
    data, oracle = _shuffle_query_data()
    settings = dict(SHUFFLED)
    settings["spark.rapids.tpu.test.faults"] = \
        "shuffle.decode:prob=1,seed=6,kind=corrupt,max=1"
    got = _drive_shuffled_agg(settings, data)
    assert got == oracle
    assert len(_kinds(spy, "integrity_fail")) == 1, \
        "the corruption was never read back — test lost its teeth"
    recs = _kinds(spy, "partition_recompute")
    assert len(recs) == 1
    assert recs[0]["map_path"].startswith("shuffle_")
    assert _kinds(spy, "task_retry") == [], \
        "recovery escalated to the whole-plan lane"
    assert lifecycle.counters()["partition_recompute"] == 1


# moved to the slow tier by ISSUE 13 budget relief (21s: conf-off
# fallback variant of the same recovery e2e)
@pytest.mark.slow
def test_shuffle_corruption_whole_plan_fallback_when_disabled(spy):
    """With partitionRecovery off, the same corruption takes the PR 4
    whole-plan lane — and the task_retry event now names the lane and
    the shuffle-block provenance."""
    data, oracle = _shuffle_query_data()
    settings = dict(SHUFFLED)
    settings["spark.rapids.tpu.task.partitionRecovery.enabled"] = "false"
    settings["spark.rapids.tpu.test.faults"] = \
        "shuffle.decode:prob=1,seed=6,kind=corrupt,max=1"
    got = _drive_shuffled_agg(settings, data)
    assert got == oracle
    assert _kinds(spy, "partition_recompute") == []
    evs = _kinds(spy, "task_retry")
    assert evs and evs[0]["lane"] == "whole_plan"
    assert evs[0]["provenance"]["kind"] == "shuffle_block"
    assert "map_path" in evs[0]["provenance"]


# moved to the slow tier by ISSUE 13 budget relief (23s: second-
# corruption fallback variant; the primary one-map-recompute lane
# stays tier-1)
@pytest.mark.slow
def test_repeated_corruption_of_one_map_output_falls_back(spy):
    """max=2 decode corruption hits the original block AND its
    recovered re-decode: the second failure of the same map output must
    not recompute forever — it surfaces with provenance and the
    whole-plan lane converges."""
    data, oracle = _shuffle_query_data()
    settings = dict(SHUFFLED)
    settings["spark.rapids.tpu.test.faults"] = \
        "shuffle.decode:prob=1,seed=6,kind=corrupt,max=2"
    got = _drive_shuffled_agg(settings, data)
    assert got == oracle
    assert len(_kinds(spy, "partition_recompute")) == 1  # one attempt
    evs = _kinds(spy, "task_retry")
    assert evs and evs[0]["lane"] == "whole_plan"
    assert evs[0]["provenance"]["kind"] == "shuffle_block"


def test_spill_quarantine_provenance_is_ambiguous(spy):
    """A quarantined spill file carries spill provenance (no lineage —
    intermediate state), so the task-retry event documents WHY the
    whole-plan lane ran."""
    from spark_rapids_tpu.memory.spillable import SpillableBatch
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    import tempfile
    prev = C.active_conf()
    try:
        reset_buffer_catalog()
        with tempfile.TemporaryDirectory() as d:
            C.set_active_conf(C.RapidsConf(dict(FAST, **{
                "spark.rapids.tpu.spill.asyncWrite": "false",
                "spark.rapids.memory.host.spillStorageSize": "1",
                "spark.rapids.memory.spillDirectory": d})))
            faults.install(
                "spill.disk_write:prob=1,seed=4,kind=corrupt,max=1")
            sb = SpillableBatch.from_batch(ColumnarBatch.from_pydict(
                {"a": list(range(256))}, Schema.of(a=LONG)))
            buffer_catalog().synchronous_spill(None)
            with pytest.raises(faults.IntegrityError) as ei:
                sb.get_batch()
            assert ei.value.provenance["kind"] == "spill_file"
            sb.close()
    finally:
        faults.install(None)
        C.set_active_conf(prev)
        reset_buffer_catalog()


# ---------------------------------------------------------------------------
# heartbeat satellite: deadlock fix + liveness events
# ---------------------------------------------------------------------------

def test_heartbeat_of_unknown_executor_does_not_deadlock():
    """Regression (ISSUE 6 satellite): heartbeat() used to call
    register() while holding the non-reentrant lock — an unregistered
    executor's first beat hung forever. Watchdog-timed thread proves
    the fix."""
    from spark_rapids_tpu.parallel.heartbeat import HeartbeatManager
    m = HeartbeatManager(timeout_s=5.0)
    m.register("e1")
    result = {}

    def beat():
        result["peers"] = m.heartbeat("never-registered")

    t = threading.Thread(target=beat, daemon=True)
    t.start()
    t.join(timeout=10.0)
    assert not t.is_alive(), \
        "heartbeat() deadlocked on an unregistered executor"
    # first beat == registration: the reply carries the known peers
    assert [p.executor_id for p in result["peers"]] == ["e1"]
    assert set(m.live_peers()) == {"e1", "never-registered"}


def test_peer_dead_event_per_transition(spy):
    from spark_rapids_tpu.parallel.heartbeat import HeartbeatManager
    m = HeartbeatManager(timeout_s=0.05)
    m.register("e1")
    m.register("e2")
    time.sleep(0.1)
    m.heartbeat("e2")  # e2 beats back to life
    assert m.dead_peers() == ["e1"]
    evs = _kinds(spy, "peer_dead")
    assert len(evs) == 1 and evs[0]["executor_id"] == "e1"
    assert evs[0]["silent_ms"] >= 50 and evs[0]["timeout_ms"] == 50
    m.dead_peers()  # still dead: no second event
    assert len(_kinds(spy, "peer_dead")) == 1
    m.heartbeat("e1")  # back alive -> transition re-arms
    time.sleep(0.1)
    assert "e1" in m.dead_peers()  # (e2 died again too by now)
    e1_evs = [e for e in _kinds(spy, "peer_dead")
              if e["executor_id"] == "e1"]
    assert len(e1_evs) == 2


# ---------------------------------------------------------------------------
# task_retry settle-error satellite
# ---------------------------------------------------------------------------

def test_settle_failure_between_attempts_is_observable(spy, monkeypatch):
    conf = C.RapidsConf(dict(FAST))
    C.set_active_conf(conf)
    cat = buffer_catalog()

    def wedged():
        raise RuntimeError("catalog wedged between attempts")

    monkeypatch.setattr(cat, "drain_writeback", wedged)
    calls = []

    def flaky(attempt):
        calls.append(attempt)
        if attempt == 1:
            raise faults.InjectedDeviceError("device.dispatch")
        return "ok"

    assert with_task_retry(flaky, conf=conf) == "ok"
    evs = _kinds(spy, "task_retry_settle_error")
    assert len(evs) == 1
    assert "catalog wedged" in evs[0]["error"]


# ---------------------------------------------------------------------------
# tooling: profile_report roll-up + bench wiring
# ---------------------------------------------------------------------------

def test_profile_report_lifecycle_rollup():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    import profile_report
    evs = [
        {"kind": "query_cancelled", "phase": "sem-wait"},
        {"kind": "query_cancelled", "phase": "compute"},
        {"kind": "query_cancelled", "phase": "compute"},
        {"kind": "breaker_open", "domain": "pallas_hash"},
        {"kind": "breaker_half_open", "domain": "pallas_hash"},
        {"kind": "breaker_close", "domain": "pallas_hash"},
        {"kind": "partition_recompute", "partition": 1},
        {"kind": "task_retry", "attempt": 1},
    ]
    report = profile_report.build_report(evs)
    assert "query cancellations: 3 (compute:2, sem-wait:1)" in report
    assert "breaker trips: 1 open, 1 half-open, 1 close" in report
    assert ("recovery lanes: 1 partition-granular recompute(s), "
            "1 whole-plan re-execution(s)") in report


def test_bench_query_timeout_flag(monkeypatch):
    import bench
    monkeypatch.setattr(bench, "_QUERY_TIMEOUT_MS", None)
    monkeypatch.setattr(bench, "_attr_prev", {})
    assert bench.maybe_query_timeout(["bench.py"]) is None
    with pytest.raises(SystemExit):
        bench.maybe_query_timeout(["bench.py", "--query-timeout-ms"])
    assert bench.maybe_query_timeout(
        ["bench.py", "--query-timeout-ms", "5000"]) == 5000
    rec = bench.lifecycle_attribution()
    assert rec["query_timeout_ms"] == 5000
    assert set(rec) >= {"cancelled", "partition_recompute",
                        "breaker_open", "whole_plan_retries"}
    # deltas, not cumulative totals
    assert bench.lifecycle_attribution()["cancelled"] == 0
    # guarded_run runs the lane under a governed deadline
    seen = {}

    def probe():
        ctx = lifecycle.current_context()
        seen["deadline"] = ctx is not None and ctx.deadline is not None
        return 7

    assert bench.guarded_run(probe) == 7
    assert seen["deadline"] is True


# ---------------------------------------------------------------------------
# slow tier: bounded per-query wall-clock under chaos
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_soak_bounded_wall_clock_under_chaos():
    """5 seeded chaos queries (every point armed at 5%, capped) under a
    2-minute deadline each: all equal to the fault-free run AND each
    attempt chain bounded in wall-clock — the --query-timeout-ms
    contract the nightly bench soak relies on."""
    data, oracle = _shuffle_query_data()
    base = dict(SHUFFLED, **{
        "spark.rapids.tpu.task.maxAttempts": "20",
        "spark.rapids.tpu.query.timeoutMs": "120000"})
    for seed in range(5):
        settings = dict(base)
        settings["spark.rapids.tpu.test.faults"] = ";".join(
            part + ",max=2" for part in
            faults.uniform_spec(0.05, seed).split(";"))
        t0 = time.monotonic()
        got = _drive_shuffled_agg(settings, data)
        wall = time.monotonic() - t0
        faults.install(None)
        assert got == oracle, f"seed {seed} diverged"
        assert wall < 120.0, f"seed {seed} blew the deadline: {wall}"
