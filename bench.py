"""Headline benchmarks on one chip: q1-style aggregation + q3-style join.

Runs the flagship pipeline (filter -> derived projection -> group-by
aggregate, the TPC-H q1 shape) through the full exec layer (spillable
batches, retry guards, planner-built operators) on the TPU — it fails when
jax selects anything else — and compares steady-state wall-clock against a
vectorized numpy oracle of the same query.

Timing methodology: the engine's steady-state hot path is sync-free — row
counts, collision flags and merge decisions all stay on device — so the
timed loop runs ITERS full pipelines back-to-back with a device-side
checksum chained across iterations (each checksum consumes the previous
one, so no iteration can be elided), and the clock stops on the ONE d2h
fetch of the final checksum, which forces completion of every queued
program. Result correctness is verified against the numpy oracle after the
clock stops, and the checksum is cross-checked against the fetched result
so all ITERS iterations are proven to have produced it.

Prints one JSON line per lane: {"metric", "value", "unit",
"vs_baseline"}. The q1 lane (headline) prints FIRST. The q3 lane runs
the scan -> filter -> hash join -> group-by -> top-N shape through the
exec layer's EXACT aggregation tier (orderkey cardinality is far past
the speculative bucket table) so join+sort regressions are visible to
the driver loop (round-2 verdict item 9).
"""

import json
import os
import sys
import threading
import time

import numpy as np


def maybe_enable_faults(argv=None):
    """`bench.py --fault-rate R` (ISSUE 4 satellite): run the standard
    bench under seeded chaos injection at every registered fault point
    with per-call probability R, so nightly rounds track recovery
    overhead alongside throughput. The seed comes from
    SPARK_RAPIDS_TPU_FAULT_SEED (default 42) — a failing chaos round
    replays exactly. Returns the rate (None = injection off)."""
    global _FAULT_RATE
    argv = sys.argv if argv is None else argv
    if "--fault-rate" not in argv:
        return None
    idx = argv.index("--fault-rate")
    try:
        rate = float(argv[idx + 1])
    except (IndexError, ValueError):
        print(json.dumps({"error_kind": "usage",
                          "error": "--fault-rate requires a numeric "
                                   "probability argument"}))
        raise SystemExit(2)
    seed = int(os.environ.get("SPARK_RAPIDS_TPU_FAULT_SEED", "42"))
    from spark_rapids_tpu import faults
    faults.install(faults.uniform_spec(rate, seed))
    _FAULT_RATE = rate
    return rate


_FAULT_RATE = None

#: per-lane query deadline (--query-timeout-ms): every guarded_run
#: iteration runs under a lifecycle QueryContext with this deadline, so
#: a chaos soak proves BOUNDED per-query wall-clock, not just eventual
#: convergence (ISSUE 6)
_QUERY_TIMEOUT_MS = None


def maybe_query_timeout(argv=None):
    """`bench.py --query-timeout-ms N`: run every bench iteration under
    the lifecycle governor with an N-ms deadline (exec/lifecycle.py). A
    lane that would exceed it raises QueryCancelledError and fails
    loudly instead of wedging a nightly round. Returns the timeout
    (None = no deadline)."""
    global _QUERY_TIMEOUT_MS
    argv = sys.argv if argv is None else argv
    if "--query-timeout-ms" not in argv:
        return None
    idx = argv.index("--query-timeout-ms")
    try:
        ms = int(argv[idx + 1])
        assert ms > 0
    except (IndexError, ValueError, AssertionError):
        print(json.dumps({"error_kind": "usage",
                          "error": "--query-timeout-ms requires a "
                                   "positive integer millisecond "
                                   "argument"}))
        raise SystemExit(2)
    _QUERY_TIMEOUT_MS = ms
    return ms


#: `bench.py --stage-fusion on|off` (ISSUE 14): A/B the whole-stage
#: compiler on the handmade lane plans. Default (None) follows the
#: conf (stage.fusion.enabled, default on).
_STAGE_FUSION = None


def maybe_stage_fusion(argv=None):
    """Parse `--stage-fusion on|off`. Bad argv emits the usage-error
    JSON convention and exits 2 — never a traceback."""
    global _STAGE_FUSION
    argv = sys.argv if argv is None else argv
    if "--stage-fusion" not in argv:
        return None
    idx = argv.index("--stage-fusion")
    try:
        mode = argv[idx + 1]
        assert mode in ("on", "off")
    except (IndexError, AssertionError):
        print(json.dumps({"error_kind": "usage",
                          "error": "--stage-fusion requires 'on' or "
                                   "'off'"}))
        raise SystemExit(2)
    _STAGE_FUSION = mode == "on"
    from spark_rapids_tpu.config import (RapidsConf, active_conf,
                                         set_active_conf)
    settings = dict(active_conf()._settings)
    settings["spark.rapids.tpu.stage.fusion.enabled"] = str(
        _STAGE_FUSION).lower()
    set_active_conf(RapidsConf(settings))
    return _STAGE_FUSION


def compile_lane_plan(plan):
    """Route a handmade lane's exec tree through the stage planner
    (ISSUE 14) — the same rewrite DataFrame._exec applies to planner-
    built trees; a no-op with fusion off, so `--stage-fusion off` is
    the per-operator baseline."""
    from spark_rapids_tpu.exec.stage_compiler import compile_stages
    return compile_stages(plan)


def stage_attribution():
    """{"stage": ...} block for each BENCH record (ISSUE 14): stages
    fused, operators absorbed, fused-stage program dispatches and
    plan-fingerprint program-cache hits this lane generated
    (exec/stage_compiler.py + obs/dispatch.py counters, as deltas
    since the previous record; the _delta_since pattern). All zeros
    with --stage-fusion off — a round reads dispatches next to the
    q1/q3 throughput to see the per-operator overhead collapse."""
    from spark_rapids_tpu.exec import stage_compiler
    cur = stage_compiler.counters()
    return _delta_since("stage", {
        "stages_fused": cur["stages_fused"],
        "ops_fused": cur["ops_fused"],
        "dispatches": cur["dispatches"],
        "cache_hits": cur["cache_hits"]})


#: `bench.py --concurrency N` (ISSUE 7): drive each lane from N
#: threads, every iteration admitted through the workload governor —
#: the nightly proof that fair admission + per-query quotas compose
#: with the recovery lanes under real contention
_CONCURRENCY = 1


def maybe_concurrency(argv=None):
    """Parse `--concurrency N` (N >= 1 lane threads). Bad argv emits
    the usage-error JSON convention and exits 2 — never a traceback."""
    global _CONCURRENCY
    argv = sys.argv if argv is None else argv
    if "--concurrency" not in argv:
        return None
    idx = argv.index("--concurrency")
    try:
        n = int(argv[idx + 1])
        assert n >= 1
    except (IndexError, ValueError, AssertionError):
        print(json.dumps({"error_kind": "usage",
                          "error": "--concurrency requires a positive "
                                   "integer thread-count argument"}))
        raise SystemExit(2)
    _CONCURRENCY = n
    return n


def run_concurrent(worker):
    """Run worker(i) once (concurrency 1: exactly the single-lane
    path), or from N threads under --concurrency N. Re-raises the first
    worker failure so a broken lane fails the round loudly."""
    n = _CONCURRENCY
    if n <= 1:
        return [worker(0)]
    results = [None] * n
    errors = [None] * n

    def drive(i):
        try:
            results[i] = worker(i)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            errors[i] = e

    threads = [threading.Thread(target=drive, args=(i,),
                                name=f"bench-lane-{i}") for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for e in errors:
        if e is not None:
            raise e
    return results


#: per-family counter snapshots for the attribution blocks below —
#: the underlying counters are process-cumulative, each BENCH record
#: must report only ITS OWN lane's deltas (the chaos-delta pattern,
#: ONE implementation shared by every flat counter family)
_attr_prev = {}


def _delta_since(family, cur):
    prev = _attr_prev.get(family, {})
    _attr_prev[family] = cur
    return {k: v - prev.get(k, 0) for k, v in cur.items()}


def workload_attribution():
    """{"workload": ...} block for each BENCH record: admissions,
    queue residency, sheds and quota spills this lane generated
    (exec/workload.py counters, as deltas since the previous record)."""
    from spark_rapids_tpu.exec import workload
    out = _delta_since("workload", workload.counters())
    out["concurrency"] = _CONCURRENCY
    return out


def lifecycle_attribution():
    """{"lifecycle": ...} block for each BENCH record: cancellations,
    breaker transitions and partition-vs-whole-plan recovery counts
    this lane absorbed (exec/lifecycle.py counters, as deltas since the
    previous record)."""
    from spark_rapids_tpu.exec import lifecycle
    out = _delta_since("lifecycle", lifecycle.counters())
    if _QUERY_TIMEOUT_MS is not None:
        out["query_timeout_ms"] = _QUERY_TIMEOUT_MS
    return out


def gather_attribution():
    """{"gather": ...} block for each BENCH record: materializing row
    gathers this lane dispatched, how many rode a packed (multi-column)
    row gather, and the estimated bytes moved (ops/gather.py counters,
    as deltas since the previous record)."""
    from spark_rapids_tpu.ops import gather as gather_engine
    return _delta_since("gather", gather_engine.counters())


def shuffle_attribution():
    """{"shuffle": ...} block for each BENCH record (ISSUE 9): batches
    split per lane (device vs host), frames/bytes written, host-side
    row gathers (0 on the device-partition lanes), and the write-time
    split pack/serialize/IO (shuffle/manager.py counters, as deltas
    since the previous record). Lanes that never shuffle report zeros —
    the block is present in every record so a round can assert the
    device lane actually engaged."""
    from spark_rapids_tpu.shuffle import manager as shuffle_mgr
    return _delta_since("shuffle", shuffle_mgr.counters())


#: `bench.py --shuffle-mode ici|host` (ISSUE 16): pin the ICI
#: device-resident shuffle lane on or off for the whole run. Default
#: (None) follows the conf (shuffle.ici.enabled, default off).
_SHUFFLE_MODE = None


def maybe_shuffle_mode(argv=None):
    """Parse `--shuffle-mode ici|host`. Bad argv emits the usage-error
    JSON convention and exits 2 — never a traceback."""
    global _SHUFFLE_MODE
    argv = sys.argv if argv is None else argv
    if "--shuffle-mode" not in argv:
        return None
    idx = argv.index("--shuffle-mode")
    try:
        mode = argv[idx + 1]
        assert mode in ("ici", "host")
    except (IndexError, AssertionError):
        print(json.dumps({"error_kind": "usage",
                          "error": "--shuffle-mode requires 'ici' or "
                                   "'host'"}))
        raise SystemExit(2)
    _SHUFFLE_MODE = mode
    from spark_rapids_tpu.config import (RapidsConf, active_conf,
                                         set_active_conf)
    settings = dict(active_conf()._settings)
    settings["spark.rapids.tpu.shuffle.ici.enabled"] = str(
        mode == "ici").lower()
    set_active_conf(RapidsConf(settings))
    return _SHUFFLE_MODE


def ici_attribution():
    """{"ici": ...} block for each BENCH record (ISSUE 16): exchange
    rounds the ICI device-resident lane ran, map batches and bytes it
    moved over the collective, collective wall-ns and host-lane
    fallbacks (shuffle/manager.py ici_counters, as deltas since the
    previous record). Zeros with --shuffle-mode host (or off-mesh lanes
    that never shuffle) — the block is present in every record so a pod
    round can assert the ICI lane actually engaged, and read the
    serialize frames collapse in the neighboring shuffle block."""
    from spark_rapids_tpu.shuffle import manager as shuffle_mgr
    out = _delta_since("ici", shuffle_mgr.ici_counters())
    if _SHUFFLE_MODE is not None:
        out["mode"] = _SHUFFLE_MODE
    return out


#: counter snapshot at the previous chaos_attribution() call — the
#: underlying counters are process-cumulative, each BENCH record must
#: report only ITS OWN lane's deltas
_chaos_prev = {"points": {}, "io": 0, "task": 0}


def chaos_attribution():
    """{"chaos": ...} block for each BENCH record under --fault-rate:
    which points fired DURING THIS LANE, and how many recoveries each
    layer (IO retry / task re-execution) absorbed to keep it green."""
    global _chaos_prev
    if _FAULT_RATE is None:
        return None
    from spark_rapids_tpu import faults
    from spark_rapids_tpu.exec.task_retry import task_retry_total
    from spark_rapids_tpu.io.retrying import io_retry_recoveries
    points = faults.stats()
    io_rec, task_rec = io_retry_recoveries(), task_retry_total()
    prev = _chaos_prev
    points_hit = {p: c - prev["points"].get(p, 0)
                  for p, c in points.items()
                  if c - prev["points"].get(p, 0)}
    rec = {
        "fault_rate": _FAULT_RATE,
        "points_hit": points_hit,
        "injections": sum(points_hit.values()),
        "recoveries": {"io_retry": io_rec - prev["io"],
                       "task_retry": task_rec - prev["task"]},
        "task_retries": task_rec - prev["task"],
    }
    _chaos_prev = {"points": points, "io": io_rec, "task": task_rec}
    return rec


#: cached chaos/workload conf overlays, keyed by (base conf identity,
#: the argv-derived flags): guarded_run sits inside each lane's timed
#: steady-state loop — rebuilding the settings dict + RapidsConf per
#: iteration would charge the concurrency metric overhead the
#: single-lane baseline never pays
_overlay_cache = {}


def _overlaid_conf():
    from spark_rapids_tpu.config import RapidsConf, active_conf
    base = active_conf()
    # the conf OBJECT in the key (identity hash) pins it: an id()-only
    # key could alias a recycled address after the base is collected
    key = (base, _FAULT_RATE, _CONCURRENCY)
    cached = _overlay_cache.get(key)
    if cached is not None:
        return cached
    settings = dict(base._settings)
    if _FAULT_RATE is not None:
        # OVERLAY on the active conf, don't replace it: a chaos round
        # that set task.retryBackoffMs must keep it, or retry sleeps
        # land inside the timed loops at the 100ms default
        settings["spark.rapids.tpu.task.maxAttempts"] = "20"
    if _CONCURRENCY > 1:
        # --concurrency N: every iteration is admitted through the
        # workload governor (exec/workload.py) — maxConcurrentQueries
        # at half the lane threads forces real queue residency, the
        # queue depth keeps honest lanes from ever being shed
        settings.update({
            "spark.rapids.tpu.workload.enabled": "true",
            "spark.rapids.tpu.workload.maxConcurrentQueries":
                str(max(1, _CONCURRENCY // 2)),
            "spark.rapids.tpu.workload.queueDepth":
                str(max(16, 2 * _CONCURRENCY))})
    cached = RapidsConf(settings)
    _overlay_cache[key] = cached
    return cached


def guarded_run(fn):
    """Run one bench iteration under the task-attempt layer: a
    transient failure (injected or real) re-executes the iteration
    instead of killing the lane. With injection off this is one
    function call of overhead.

    maxAttempts is raised well past the session default: chaos arming
    here is prob-only (no per-point max caps — nightly rounds want a
    SUSTAINED injection rate, not a budget that runs dry mid-lane), so
    convergence is probabilistic. The plan's call indexes advance across
    attempts, each retry faces fresh seeded draws, and at 20 attempts
    even a 50% per-attempt kill rate fails a lane ~1e-6 of the time."""
    from spark_rapids_tpu.config import active_conf
    from spark_rapids_tpu.exec.task_retry import with_task_retry
    conf = _overlaid_conf() \
        if _FAULT_RATE is not None or _CONCURRENCY > 1 else None
    if _QUERY_TIMEOUT_MS is not None or _CONCURRENCY > 1:
        # --query-timeout-ms: the deadline spans the iteration's whole
        # retry chain (exec/lifecycle.py), proving bounded per-query
        # wall-clock under chaos instead of just eventual convergence;
        # the governed context also carries the workload ticket
        from spark_rapids_tpu.exec import lifecycle, workload
        base = conf if conf is not None else active_conf()
        with lifecycle.governed(base,
                                timeout_ms=_QUERY_TIMEOUT_MS) as ctx:
            with workload.admitted(base, ctx):
                return with_task_retry(lambda attempt: fn(), conf=conf)
    return with_task_retry(lambda attempt: fn(), conf=conf)


def maybe_enable_event_log():
    """Opt-in structured event log for bench runs: set
    SPARK_RAPIDS_TPU_EVENTLOG_DIR to get a JSONL operator-span log
    (obs/events.py) next to the BENCH records; render it with
    tools/profile_report.py. SPARK_RAPIDS_TPU_EVENTLOG_MAX_BYTES
    rotates the sink so a bench storm never grows one unbounded file.
    Default: off, zero per-batch cost."""
    d = os.environ.get("SPARK_RAPIDS_TPU_EVENTLOG_DIR")
    if d:
        from spark_rapids_tpu.obs import events
        events.enable(d, os.environ.get("SPARK_RAPIDS_TPU_EVENTLOG_LEVEL",
                                        "MODERATE"),
                      max_bytes=int(os.environ.get(
                          "SPARK_RAPIDS_TPU_EVENTLOG_MAX_BYTES", "0")))


def maybe_enable_history():
    """Opt-in query-history capsules for bench runs (ISSUE 17): set
    SPARK_RAPIDS_TPU_HISTORY_DIR to append one JSONL capsule per
    governed query (obs/history.py) — two bench runs into separate
    dirs, then `tools/history_report.py CUR --diff BASE` ranks any
    regression by the phase that moved.
    SPARK_RAPIDS_TPU_HISTORY_MAX_BYTES rotates the capsule file.
    Default: off, one pointer check per collect."""
    d = os.environ.get("SPARK_RAPIDS_TPU_HISTORY_DIR")
    if d:
        from spark_rapids_tpu.obs import history
        history.enable(d, max_bytes=int(os.environ.get(
            "SPARK_RAPIDS_TPU_HISTORY_MAX_BYTES", "0")))


def phases_attribution():
    """{"phases": ...} block for each BENCH record (ISSUE 17): the
    process-cumulative wall-clock phase counters (obs/phase.py) as
    deltas since the previous record — which phases this lane's wall
    went to, even for lanes that drive plan.execute() directly with no
    governed query (where no per-query ledger exists)."""
    from spark_rapids_tpu.obs import phase
    return _delta_since("phases", phase.counters())


def maybe_enable_telemetry():
    """Opt-in live telemetry for bench runs (ISSUE 11): set
    SPARK_RAPIDS_TPU_TELEMETRY_MS to a sampling interval to start the
    registry + sampler thread; samples flush into the event log (when
    enabled above) as telemetry_sample records — render with
    tools/telemetry_export.py. Default: off, one pointer check per
    push site."""
    ms = os.environ.get("SPARK_RAPIDS_TPU_TELEMETRY_MS")
    if ms:
        from spark_rapids_tpu.obs import telemetry
        telemetry.enable(interval_ms=int(ms))


def query_attribution(plan, before):
    """Per-operator attribution embedded in each BENCH record (ISSUE 2:
    BENCH deltas stop being single scalar GB/s numbers): the
    GpuTaskMetrics-style per-query summary + top operators by time."""
    try:
        from spark_rapids_tpu.obs.profile import bench_profile_summary
        return bench_profile_summary(plan, before)
    except Exception as e:  # noqa: BLE001 — attribution must never
        return {"error": f"{type(e).__name__}: {e}"[:200]}  # kill a lane

def upload_attribution():
    """{"upload": ...} block for each BENCH record (ISSUE 10): batch
    uploads per lane (packed = one transfer | per-buffer), actual
    host->device transfers dispatched, bytes moved, pack+transfer time
    and staging-pool hit/miss counts (columnar/upload.py counters, as
    deltas since the previous record). Lanes that never ingest report
    zeros — the block is present in every record so a TPU round can
    assert the packed lane actually engaged."""
    from spark_rapids_tpu.columnar import upload as upload_engine
    return _delta_since("upload", upload_engine.counters())


def encoded_attribution():
    """{"encoded": ...} block for each BENCH record (ISSUE 18):
    dictionary-encoded lane activity — columns kept encoded at the
    scan, code/dictionary byte split, eager-decode bytes avoided,
    late materializations (and their bytes), code-space predicates
    and dictionary hash tables served (columnar/encoded.py counters,
    as deltas since the previous record). All zeros with
    scan.encoded.enabled=false — a TPU round reads
    decoded_bytes_avoided next to the upload block to see the H2D
    shrink the encoded lane bought."""
    from spark_rapids_tpu.columnar import encoded as encoded_engine
    return _delta_since("encoded", encoded_engine.counters())


def adaptive_attribution():
    """{"adaptive": ...} block for each BENCH record (ISSUE 19):
    runtime-replanner activity — exchange consults, skew splits,
    broadcast demotions, single-build conversions, partition
    coalesces, OOM batch right-sizings, breaker stand-downs and lane
    errors (exec/adaptive.py counters, as deltas since the previous
    record). All zeros with adaptive.enabled=false — a round compares
    the on/off delta next to shuffle/statistics to see what acting on
    the measured sizes actually bought."""
    from spark_rapids_tpu.exec import adaptive as adaptive_engine
    return _delta_since("adaptive", adaptive_engine.counters())


def speculation_attribution():
    """{"speculation": ...} block for each BENCH record (ISSUE 20):
    straggler-shield activity — stall episodes and their actions,
    speculative sub-reads launched/won/denied, post-bound wait ns,
    dispatch-timeout trips, dead-peer invalidations
    (exec/speculation_shield.py counters, as deltas since the previous
    record). All zeros with the shield's confs at defaults — a chaos
    round with delay injection reads spec_wins next to shuffle to see
    what racing the tail bought."""
    from spark_rapids_tpu.exec import speculation_shield
    return _delta_since("speculation", speculation_shield.counters())


def dispatch_attribution():
    """{"dispatch": ...} block for each BENCH record (ISSUE 13):
    compiled programs, program dispatches, fresh traces vs jit cache
    hits, compile wall-ns and recompile storms this lane generated
    (obs/dispatch.py ledger counters, as deltas since the previous
    record). All zeros with dispatch.ledger.enabled=false — a TPU
    round reads dispatches/compile_ns next to throughput to see what
    whole-stage compilation (ROADMAP 2) must collapse."""
    from spark_rapids_tpu.obs import dispatch as dispatch_ledger
    cur = dispatch_ledger.counters()
    return _delta_since("dispatch",
                        {"programs": cur["programs"],
                         "dispatches": cur["dispatches"],
                         "compile_ns": cur["compile_ns"],
                         "cache_hits": cur["cache_hits"],
                         "storms": cur["storms"]})


def telemetry_attribution():
    """{"telemetry": ...} block for each BENCH record (ISSUE 11):
    registry activity (samples taken, registry writes, push counters)
    this lane generated, as deltas since the previous record — all
    zeros with telemetry off, so a round can assert the plane actually
    engaged."""
    from spark_rapids_tpu.obs import telemetry
    return _delta_since("telemetry", telemetry.counters())


def statistics_attribution():
    """{"statistics": ...} block for each BENCH record (ISSUE 11):
    exchange map outputs/bytes this lane wrote (deltas, chaos-delta
    pattern) plus the point-in-time distribution summary — the p95
    map-output bytes and last observed partition skew ratio — so an
    accumulated TPU round reads skew/attribution next to throughput.
    Lanes that never shuffle report zeros; the block is present in
    every record."""
    from spark_rapids_tpu.obs import stats as runtime_stats
    cur = runtime_stats.counters()
    out = _delta_since("statistics",
                       {"maps": cur["maps"], "bytes": cur["bytes"]})
    out["p95_map_output_bytes"] = cur["p95_map_output_bytes"]
    out["skew_ratio"] = cur["skew_ratio_x1000"] / 1000.0
    return out


def pipeline_attribution():
    """{"pipeline": ...} block for each BENCH record (ISSUE 3
    satellite): the synthetic slow-producer/slow-consumer overlap
    microbench (tools/pipeline_bench.py), run once per process — cheap
    (<1s) and device-free, it tracks whether the bounded stage boundary
    still buys its overlap on this host alongside the engine numbers."""
    global _PIPELINE_SUMMARY
    if _PIPELINE_SUMMARY is None:
        try:
            sys.path.insert(0, os.path.join(
                os.path.dirname(os.path.abspath(__file__)), "tools"))
            from pipeline_bench import run_bench
            _PIPELINE_SUMMARY = run_bench(items=30, produce_s=0.01,
                                          consume_s=0.01, depth=2)
        except Exception as e:  # noqa: BLE001 — attribution must never
            _PIPELINE_SUMMARY = {  # kill a lane
                "error": f"{type(e).__name__}: {e}"[:200]}
    return _PIPELINE_SUMMARY


_PIPELINE_SUMMARY = None

ROWS = 1 << 24  # 16M rows, ~448 MB
BATCHES = 1
ITERS = 30

def init_backend():
    """Import jax, require a TPU and force REAL backend initialization.

    `jax.devices()` alone is not enough: a backend can enumerate devices
    and still fail at the first dispatched program, so the probe
    dispatches a tiny cast and blocks on its result. No retry and no
    error record: a backend that cannot start, or a run that landed on
    the CPU, fails here with the raw traceback and a non-zero exit."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"bench.py measures the chip; jax selected {dev.platform!r} "
            f"({dev.device_kind})")
    jax.block_until_ready(
        jnp.arange(8, dtype=jnp.int32).astype(jnp.float32).sum())
    return jax


def build_data():
    rng = np.random.default_rng(0)
    return {
        "returnflag": rng.integers(0, 4, ROWS, dtype=np.int32),
        "quantity": rng.integers(1, 51, ROWS, dtype=np.int64),
        "extendedprice": rng.random(ROWS) * 1000.0,
        "discount": rng.random(ROWS) * 0.1,
    }


def numpy_oracle(d):
    keep = d["quantity"] <= 45
    flag = d["returnflag"][keep]
    qty = d["quantity"][keep]
    dp = (d["extendedprice"] * (1.0 - d["discount"]))[keep]
    out = {}
    for k in np.unique(flag):
        m = flag == k
        out[int(k)] = (int(qty[m].sum()), float(dp[m].sum()), int(m.sum()))
    return out


def _median_time(fn, reps=3):
    """Median-of-N oracle timing: one-shot numpy timings swung the
    recorded vs_baseline 389x->65x between rounds at near-identical
    engine GB/s (VERDICT r4 Weak #5) — the median makes the driver's
    trend line signal."""
    times = []
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return out, sorted(times)[len(times) // 2]


def main():
    d = build_data()
    numpy_oracle(d)  # warm the page cache
    oracle, t_np = _median_time(lambda: numpy_oracle(d))

    jax = init_backend()
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column, bucket_capacity
    from spark_rapids_tpu.exec.aggregate import AggregateExec
    from spark_rapids_tpu.exec.basic import FilterExec, InMemoryScanExec, ProjectExec
    from spark_rapids_tpu.expr.aggexprs import Count, Sum
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.types import (
        DOUBLE, INT, LONG, Schema, StructField,
    )

    schema = Schema((
        StructField("returnflag", INT), StructField("quantity", LONG),
        StructField("extendedprice", DOUBLE), StructField("discount", DOUBLE),
    ))
    per = ROWS // BATCHES
    cap = bucket_capacity(per)
    batches = []
    for i in range(BATCHES):
        sl = slice(i * per, (i + 1) * per)
        cols = [Column.from_numpy(d[f.name][sl], f.data_type, capacity=cap)
                for f in schema.fields]
        batches.append(ColumnarBatch(cols, per, schema))

    def make_plan():
        scan = InMemoryScanExec(batches, schema)
        filt = FilterExec(col("quantity") <= lit(45), scan)
        proj = ProjectExec([
            col("returnflag"), col("quantity"),
            (col("extendedprice") * (lit(1.0) - col("discount")))
            .alias("disc_price")], filt)
        agg = AggregateExec(
            [col("returnflag")],
            [(Sum(col("quantity")), "sum_qty"),
             (Sum(col("disc_price")), "sum_disc"),
             (Count(), "cnt")], proj)
        # ISSUE 14: the scan->filter->project->agg chain compiles to
        # one fused stage (a no-op under --stage-fusion off)
        return compile_lane_plan(agg)

    from spark_rapids_tpu.exec.speculation import speculation_scope
    from spark_rapids_tpu.exec.task_metrics import query_snapshot

    metrics_before = query_snapshot()

    @jax.jit
    def checksum(batch, prev, spec_flags):
        total = prev + batch.num_rows.astype(jnp.float64)
        for c in batch.columns:
            v = jnp.where(c.validity, c.data, jnp.zeros((), c.data.dtype))
            total = total + jnp.sum(v).astype(jnp.float64)
        for f in spec_flags:
            # a tripped speculation flag poisons the checksum: no invalid
            # iteration can pass the final assertion
            total = total + jnp.where(f, jnp.nan, 0.0)
        return total

    def q1_lane(_i):
        # one plan per lane: exec instances own their compiled kernels,
        # so reuse across iterations exercises the steady-state compiled
        # path, while concurrent lanes never share operator state
        plan = make_plan()

        def run_once(prev, scope):
            outs = list(plan.execute())
            flags = tuple(scope.drain())
            chk = prev
            for b in outs:
                chk = checksum(b, chk, flags)
                flags = ()
            return outs, chk

        # warmup (compile + one full round trip); the with-block keeps
        # an assertion failure from leaking the thread-local scope into
        # later benchmarks in the same process
        with speculation_scope() as scope:
            outs, chk = guarded_run(
                lambda: run_once(jnp.float64(0.0), scope))
            rows = [r for b in outs for r in b.to_pylist()]
            got = {r[0]: (r[1], r[2], r[3]) for r in rows}
            for k, (sq, sd, c) in oracle.items():
                assert got[k][0] == sq and got[k][2] == c, \
                    (k, got[k], oracle[k])
                assert abs(got[k][1] - sd) / max(abs(sd), 1) < 1e-9
            expect_chk_1 = float(np.asarray(chk))

            # timed steady state: ITERS chained pipelines, ONE sync at
            # the end
            t0 = time.perf_counter()
            chk = jnp.float64(0.0)
            for _ in range(ITERS):
                _, chk = guarded_run(lambda c=chk: run_once(c, scope))
            final_chk = float(np.asarray(chk))  # completes all ITERS
            dt = (time.perf_counter() - t0) / ITERS

        # every iteration produced the verified result (telescoping)
        assert abs(final_chk - ITERS * expect_chk_1) <= \
            1e-9 * max(abs(final_chk), 1.0), \
            (final_chk, ITERS * expect_chk_1)
        return plan, dt

    lanes = run_concurrent(q1_lane)
    plan, dt = lanes[0]
    if _CONCURRENCY > 1:
        # aggregate the lanes' STEADY-STATE per-iteration rates (each
        # lane's timed loop ran concurrently with the others'): a wall
        # clock over the whole fan-out would fold every lane's jit
        # warmup and oracle verification into the metric and understate
        # it against the single-lane baseline
        dt = 1.0 / sum(1.0 / lane_dt for _plan, lane_dt in lanes)

    bytes_in = sum(v.nbytes for v in d.values())
    gbps = bytes_in / dt / 1e9
    rec = {
        "metric": "q1_agg_throughput",
        "value": round(gbps, 3),
        "unit": "GB/s",
        "vs_baseline": round(t_np / dt, 3),
        "profile": query_attribution(plan, metrics_before),
        "pipeline": pipeline_attribution(),
        "lifecycle": lifecycle_attribution(),
        "workload": workload_attribution(),
        "gather": gather_attribution(),
        "shuffle": shuffle_attribution(),
        "ici": ici_attribution(),
        "upload": upload_attribution(),
        "encoded": encoded_attribution(),
        "dispatch": dispatch_attribution(),
        "adaptive": adaptive_attribution(),
        "speculation": speculation_attribution(),
        "stage": stage_attribution(),
        "telemetry": telemetry_attribution(),
        "statistics": statistics_attribution(),
        "phases": phases_attribution(),
    }
    chaos = chaos_attribution()
    if chaos is not None:
        rec["chaos"] = chaos
    print(json.dumps(rec))


N_ORDERS = 1 << 19   # 512K orders
N_LINES = 1 << 21    # 2M lineitems


def build_q3_data():
    rng = np.random.default_rng(1)
    return {
        "o_orderkey": np.arange(N_ORDERS, dtype=np.int64),
        "o_flag": rng.integers(0, 10, N_ORDERS, dtype=np.int32),
        "l_orderkey": rng.integers(0, N_ORDERS, N_LINES, dtype=np.int64),
        "l_price": rng.random(N_LINES) * 1000.0,
        "l_disc": rng.random(N_LINES) * 0.1,
        "l_flag": rng.integers(0, 4, N_LINES, dtype=np.int32),
    }


def q3_oracle(d):
    keep_o = d["o_flag"] < 5
    keep_l = d["l_flag"] != 0
    okeys = d["o_orderkey"][keep_o]
    lkey = d["l_orderkey"][keep_l]
    rev = (d["l_price"] * (1.0 - d["l_disc"]))[keep_l]
    sel = np.isin(lkey, okeys)
    lkey, rev = lkey[sel], rev[sel]
    order = np.argsort(lkey, kind="stable")
    lkey, rev = lkey[order], rev[order]
    uk, starts = np.unique(lkey, return_index=True)
    sums = np.add.reduceat(rev, starts)
    top = np.argsort(-sums, kind="stable")[:10]
    return {int(uk[i]): float(sums[i]) for i in top}


def q3_bench():
    d = build_q3_data()
    q3_oracle(d)  # warm
    oracle, t_np = _median_time(lambda: q3_oracle(d))

    jax = init_backend()
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column, bucket_capacity
    from spark_rapids_tpu.exec.aggregate import AggregateExec
    from spark_rapids_tpu.exec.basic import (FilterExec, InMemoryScanExec,
                                             ProjectExec)
    from spark_rapids_tpu.exec.joins import HashJoinExec
    from spark_rapids_tpu.exec.sort import TopNExec
    from spark_rapids_tpu.expr.aggexprs import Sum
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.types import DOUBLE, INT, LONG, Schema, StructField

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

    orders = mk_batch(o_schema, N_ORDERS)
    lines = mk_batch(l_schema, N_LINES)

    def make_q3_plan():
        o_scan = FilterExec(col("o_flag") < lit(5),
                            InMemoryScanExec([orders], o_schema))
        l_scan = FilterExec(col("l_flag") != lit(0),
                            InMemoryScanExec([lines], l_schema))
        joined = HashJoinExec(l_scan, o_scan, [col("l_orderkey")],
                              [col("o_orderkey")], "inner",
                              build_side="right")
        proj = ProjectExec([
            col("l_orderkey"),
            (col("l_price") * (lit(1.0) - col("l_disc"))).alias("rev")],
            joined)
        agg = AggregateExec([col("l_orderkey")],
                            [(Sum(col("rev")), "revenue")], proj)
        # the agg runs its EXACT tier (orderkey cardinality is far past
        # the speculative bucket table — speculating would trip every
        # iteration); the scope below exists for the JOIN's speculative
        # candidate sizing
        agg._spec_enabled = False
        # ISSUE 14: filter->probe->project->partial-agg fuses to one
        # program per stream batch (no-op under --stage-fusion off)
        return compile_lane_plan(TopNExec(10, [(col("revenue"), False)],
                                          agg))

    from spark_rapids_tpu.exec.speculation import speculation_scope
    from spark_rapids_tpu.exec.task_metrics import query_snapshot

    metrics_before = query_snapshot()

    @jax.jit
    def checksum(batch, prev, spec_flags):
        total = prev + batch.num_rows.astype(jnp.float64)
        for c in batch.columns:
            v = jnp.where(c.validity, c.data, jnp.zeros((), c.data.dtype))
            total = total + jnp.sum(v).astype(jnp.float64)
        for f in spec_flags:
            # a tripped join-sizing flag poisons the checksum: no invalid
            # iteration can pass the final assertion
            total = total + jnp.where(f, jnp.nan, 0.0)
        return total

    iters = 10

    def q3_lane(_i):
        plan = make_q3_plan()
        with speculation_scope() as scope:

            def run_once(prev):
                outs = list(plan.execute())
                flags = tuple(scope.drain())
                for b in outs:
                    prev = checksum(b, prev, flags)
                    flags = ()
                return outs, prev

            outs, chk = guarded_run(
                lambda: run_once(jnp.float64(0.0)))  # warm + verify
            rows = [r for b in outs for r in b.to_pylist()]
            got = {r[0]: r[1] for r in rows}
            assert set(got) == set(oracle), \
                (sorted(got)[:3], sorted(oracle)[:3])
            for k, v in oracle.items():
                assert abs(got[k] - v) / max(abs(v), 1) < 1e-9
            # second warm pass compiles the speculative (cached-bucket)
            # probe path
            _, chk2 = guarded_run(lambda: run_once(jnp.float64(0.0)))
            assert abs(float(np.asarray(chk2)) - float(np.asarray(chk))) \
                <= 1e-9 * max(abs(float(np.asarray(chk))), 1.0)
            expect1 = float(np.asarray(chk))

            t0 = time.perf_counter()
            chk = jnp.float64(0.0)
            for _ in range(iters):
                _, chk = guarded_run(lambda c=chk: run_once(c))
            final = float(np.asarray(chk))
            dt = (time.perf_counter() - t0) / iters
        assert abs(final - iters * expect1) <= 1e-9 * max(abs(final), 1.0)
        return plan, dt

    lanes = run_concurrent(q3_lane)
    plan, dt = lanes[0]
    if _CONCURRENCY > 1:
        # steady-state rate aggregate — see the q1 lane note
        dt = 1.0 / sum(1.0 / lane_dt for _plan, lane_dt in lanes)

    bytes_in = sum(v.nbytes for v in d.values())
    rec = {
        "metric": "q3_join_topn_throughput",
        "value": round(bytes_in / dt / 1e9, 3),
        "unit": "GB/s",
        "vs_baseline": round(t_np / dt, 3),
        "profile": query_attribution(plan, metrics_before),
        "pipeline": pipeline_attribution(),
        "lifecycle": lifecycle_attribution(),
        "workload": workload_attribution(),
        "gather": gather_attribution(),
        "shuffle": shuffle_attribution(),
        "ici": ici_attribution(),
        "upload": upload_attribution(),
        "encoded": encoded_attribution(),
        "dispatch": dispatch_attribution(),
        "adaptive": adaptive_attribution(),
        "speculation": speculation_attribution(),
        "stage": stage_attribution(),
        "telemetry": telemetry_attribution(),
        "statistics": statistics_attribution(),
        "phases": phases_attribution(),
    }
    chaos = chaos_attribution()
    if chaos is not None:
        rec["chaos"] = chaos
    print(json.dumps(rec))


if __name__ == "__main__":
    maybe_enable_event_log()
    maybe_enable_telemetry()
    maybe_enable_history()
    maybe_enable_faults()
    maybe_query_timeout()
    maybe_concurrency()
    maybe_stage_fusion()
    maybe_shuffle_mode()
    main()
    q3_bench()
