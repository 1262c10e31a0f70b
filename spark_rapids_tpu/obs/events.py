"""Process-wide structured event bus (ISSUE 2 tentpole part 1).

Reference analog: the GpuMetric stream merged into the Spark SQL UI plus
the NVTX range timeline — here, one JSON-lines file per configured bus,
each line a self-describing record:

    {"ts_ns": ..., "kind": ..., "query": <id or null>, ...fields}

Event kinds and their levels (spark.rapids.tpu.eventLog.level):

  ESSENTIAL  query_start, query_end, query_cancelled, query_shed,
             recompile_storm, query_phases, adaptive_demote,
             query_stalled
  MODERATE   op_close, semaphore_acquire, spill, oom_retry,
             plan_fallback, plan_not_on_tpu, exchange,
             pipeline_wait, pipeline_full, op_error, fault_inject,
             io_retry, task_retry, integrity_fail, pipeline_stuck,
             spill_error, spill_writer_dead, task_retry_settle_error,
             partition_recompute, breaker_open, breaker_half_open,
             breaker_close, peer_dead, query_queued, query_admitted,
             quota_spill, ici_exchange, adaptive_replan
  DEBUG      op_open, op_batch, span

Cost discipline: `active_bus()` returns None when logging is disabled —
every producer guards with one pointer check, so the steady-state batch
loop pays nothing (acceptance: per-batch overhead not measurable in the
kern/bench timings). When enabled, writes are line-buffered behind a
lock and flushed per record so a crashed query still leaves a parseable
log.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Any, Dict, Iterator, Optional

ESSENTIAL = 0
MODERATE = 1
DEBUG = 2

_LEVEL_NAMES = {"ESSENTIAL": ESSENTIAL, "MODERATE": MODERATE, "DEBUG": DEBUG}

#: event kind -> minimum eventLog.level at which it is written
EVENT_LEVELS: Dict[str, int] = {
    "query_start": ESSENTIAL,
    "query_end": ESSENTIAL,
    "op_close": MODERATE,
    "op_error": MODERATE,
    "semaphore_acquire": MODERATE,
    "spill": MODERATE,
    "oom_retry": MODERATE,
    "plan_fallback": MODERATE,
    "plan_not_on_tpu": MODERATE,
    "exchange": MODERATE,
    # shuffle-write breakdown (ISSUE 9): one record per map task with
    # the lane (device|host), frame/byte totals and the write-time
    # split (pack = device partition + packed D2H, serialize, file IO)
    "shuffle_write": MODERATE,
    # ICI device-resident shuffle lane (ISSUE 16): one record per
    # collective round with bytes moved over the mesh axis, the
    # negotiated slot_cap, the send-grid fill ratio and the collective
    # wall time
    "ici_exchange": MODERATE,
    "pipeline_wait": MODERATE,
    "pipeline_full": MODERATE,
    # robustness events (ISSUE 4): injected faults, retries at every
    # level (IO -> OOM -> task), integrity quarantines and watchdog
    # trips — the failure-story records a production operator reads
    "fault_inject": MODERATE,
    "io_retry": MODERATE,
    "task_retry": MODERATE,
    "integrity_fail": MODERATE,
    "pipeline_stuck": MODERATE,
    "spill_error": MODERATE,
    "spill_writer_dead": MODERATE,
    # lifecycle-governor events (ISSUE 6): cancellations are headline
    # (ESSENTIAL, like query begin/end); breaker transitions, the
    # partition-granular recovery lane, settle failures between task
    # attempts and heartbeat liveness transitions are MODERATE
    "query_cancelled": ESSENTIAL,
    "task_retry_settle_error": MODERATE,
    "partition_recompute": MODERATE,
    "breaker_open": MODERATE,
    "breaker_half_open": MODERATE,
    "breaker_close": MODERATE,
    "peer_dead": MODERATE,
    # workload-governor events (ISSUE 7): a shed query is headline (the
    # caller got an error, like a cancellation); queue/admission
    # transitions and quota-triggered self-spills are MODERATE
    "query_queued": MODERATE,
    "query_admitted": MODERATE,
    "query_shed": ESSENTIAL,
    "quota_spill": MODERATE,
    # packed upload engine (ISSUE 10): one record per host->device batch
    # upload with the lane (packed = one transfer | per-buffer), the
    # ingest seam (scan / shuffle / unspill) and the pack+transfer time
    "upload": MODERATE,
    # gather engine (ISSUE 8): one record per wired-exec execution with
    # its materializing-gather totals (count/packed/bytes) —
    # reconciles with the numGathers metric and op_close batch counts
    "gather_stats": MODERATE,
    # runtime statistics plane (ISSUE 11): one record per exchange
    # execution with its map-output/partition distributions and skew
    # summary (obs/stats.py), and one per telemetry sampler tick with
    # the registry snapshot (obs/telemetry.py) — the JSONL half of the
    # periodic exporter
    "exchange_stats": MODERATE,
    "telemetry_sample": MODERATE,
    # dispatch/compile observability plane (ISSUE 13): one record per
    # fresh program trace with its trace/compile cost and donated vs
    # retained argument bytes (obs/dispatch.py); one per wired-exec
    # execution with its dispatch/compile deltas (exec/base.py, the
    # gather_stats shape); recompile_storm is headline — shape-bucket
    # churn silently destroys TPU throughput
    "program_compile": MODERATE,
    "dispatch_stats": MODERATE,
    "recompile_storm": ESSENTIAL,
    # wall-clock phase attribution (ISSUE 17): one record per governed
    # query at query end with the closed phase ledger (obs/phase.py,
    # sum(phases) == wall_ns exactly), outcome, priority and attempt
    # count — headline, like query_end (it IS the query's cost story)
    "query_phases": ESSENTIAL,
    # whole-stage compilation (ISSUE 14): one record per fused-stage
    # execution — kind (map | agg | join_agg), the absorbed-op label,
    # ops absorbed, input batches, program dispatches this execution
    # issued, and the donated carried-state bytes (the in-place HBM
    # reuse the donate_argnums contract buys on real hardware)
    "stage_fused": MODERATE,
    # dictionary-encoded execution (ISSUE 18): one encoded_scan record
    # per scan batch that kept columns encoded (code/dict byte split
    # and the eager-decode bytes avoided), and one encoded_materialize
    # per late decode through the gather engine with the seam that
    # forced it (boundary | concat | output | spill)
    "encoded_scan": MODERATE,
    "encoded_materialize": MODERATE,
    # adaptive runtime replanning (ISSUE 19): one adaptive_replan
    # record per applied decision (skew_split / single_build_convert /
    # partition_coalesce / batch_right_size) with its measured-bytes
    # evidence and chosen action; adaptive_demote is headline — a
    # planned strategy measured unaffordable (broadcast_demote) or the
    # replan lane itself stood down (breaker_open / error)
    "adaptive_replan": MODERATE,
    "adaptive_demote": ESSENTIAL,
    # straggler & stall shield (ISSUE 20): a stalled governed query is
    # headline (its SLO is already lost — the event names the frozen
    # seam and the phase the time went into); speculative sub-read
    # resolutions, dispatch hang-bound trips and dead-peer map-output
    # invalidations are MODERATE, like the other recovery-lane records
    "query_stalled": ESSENTIAL,
    "speculative_fetch": MODERATE,
    "dispatch_timeout": MODERATE,
    "map_output_invalidated": MODERATE,
    "op_open": DEBUG,
    "op_batch": DEBUG,
    "span": DEBUG,
}

DEFAULT_DIR = "/tmp/spark_rapids_tpu_events"


def parse_level(name: str, default: int = MODERATE) -> int:
    return _LEVEL_NAMES.get(str(name).strip().upper(), default)


class EventBus:
    """Append-only JSONL sink. The file is created lazily on the first
    record, so an enabled-but-silent process leaves no empty files."""

    _seq = 0
    _seq_lock = threading.Lock()

    def __init__(self, directory: str, level: int = MODERATE,
                 max_bytes: int = 0):
        self.directory = directory or DEFAULT_DIR
        self.level = level
        #: rotation threshold (spark.rapids.tpu.eventLog.maxBytes,
        #: ISSUE 11 satellite): past it the current file closes and
        #: writing continues in <base>.<n>.jsonl — a soak/bench storm
        #: never grows one file without bound. 0 = unbounded.
        self.max_bytes = max(0, int(max_bytes))
        with EventBus._seq_lock:
            EventBus._seq += 1
            seq = EventBus._seq
        self._base = os.path.join(self.directory,
                                  f"events-{os.getpid()}-{seq}")
        self._rot = 0
        self._written = 0
        self.path = f"{self._base}.jsonl"
        self._lock = threading.Lock()
        self._file = None
        self._closed = False

    def _rotate_locked(self) -> None:
        """Caller holds self._lock. Close the full file and point the
        bus at the next member of the rotated set; the new file is
        created lazily by the next record, like the first one."""
        if self._file is not None:
            self._file.close()
            self._file = None
        self._rot += 1
        self._written = 0
        self.path = f"{self._base}.{self._rot}.jsonl"

    def emit(self, kind: str, **fields: Any) -> None:
        if self._closed or EVENT_LEVELS.get(kind, MODERATE) > self.level:
            return
        # `thread` (ISSUE 13 satellite): the emitting thread's name, so
        # tools/trace_export.py assigns timeline tracks (consumer vs
        # pipeline-* producers vs spill-writer vs decode-pool workers)
        # without heuristics. Read only once the record is known kept —
        # a disabled bus or filtered level pays nothing.
        rec = {"ts_ns": time.time_ns(), "kind": kind,
               "query": current_query_id(),
               "thread": threading.current_thread().name}
        rec.update(fields)
        try:
            line = json.dumps(rec, separators=(",", ":"), default=str)
            with self._lock:
                if self._closed:
                    return
                if self._file is None:
                    os.makedirs(self.directory, exist_ok=True)
                    # contract: ok lock-blocking-call — the bus lock is
                    # the declared LEAF lock and exists precisely to
                    # serialize this lazy open + append; nothing is ever
                    # acquired under it
                    self._file = open(self.path, "a")
                self._file.write(line + "\n")
                self._file.flush()
                self._written += len(line) + 1
                if self.max_bytes and self._written >= self.max_bytes:
                    self._rotate_locked()
        except Exception as e:  # noqa: BLE001 — emit runs inside
            # operator/collect finally blocks: an unwritable event log
            # must never fail a query or mask its real exception. One
            # warning, then the bus stays down.
            import logging
            logging.getLogger("spark_rapids_tpu.obs").warning(
                "event log disabled: cannot write %s (%s: %s)",
                self.path, type(e).__name__, e)
            self.close()
            _deactivate(self)  # producers drop back to the fast path

    def close(self) -> None:
        with self._lock:
            self._closed = True
            if self._file is not None:
                self._file.close()
                self._file = None


_bus: Optional[EventBus] = None
_bus_lock = threading.Lock()


def active_bus() -> Optional[EventBus]:
    """The configured bus, or None when event logging is disabled. Hot
    paths call this once and guard on None — the entire disabled-mode
    cost."""
    return _bus


def emit(kind: str, **fields: Any) -> None:
    """Emit one event if logging is enabled (cold-path convenience)."""
    b = _bus
    if b is not None:
        b.emit(kind, **fields)


def _deactivate(bus: EventBus) -> None:
    """Uninstall `bus` if it is still the active one (write-failure
    self-removal: a dead bus must not keep producers instrumented)."""
    global _bus
    with _bus_lock:
        if _bus is bus:
            _bus = None


def configure(conf=None) -> Optional[EventBus]:
    """(Re)configure the process bus from a RapidsConf (None = the
    thread's active conf). The bus is PROCESS-wide, like a Spark event
    log: a conf that leaves eventLog.enabled unset keeps whatever bus
    another session enabled (a default-conf session must not fragment
    someone else's log); an EXPLICIT enabled=false tears it down. An
    enabled conf with unchanged dir+level keeps the current file open
    rather than starting a new one per query."""
    global _bus
    from ..config import (EVENT_LOG_DIR, EVENT_LOG_ENABLED,
                          EVENT_LOG_LEVEL, EVENT_LOG_MAX_BYTES,
                          active_conf)
    conf = conf if conf is not None else active_conf()
    enabled = conf.get(EVENT_LOG_ENABLED)
    with _bus_lock:
        if not enabled:
            if EVENT_LOG_ENABLED.key in conf._settings \
                    and _bus is not None:
                _bus.close()
                _bus = None
            return _bus
        directory = conf.get(EVENT_LOG_DIR) or DEFAULT_DIR
        level = parse_level(conf.get(EVENT_LOG_LEVEL))
        max_bytes = max(0, conf.get(EVENT_LOG_MAX_BYTES))
        if _bus is not None and _bus.directory == directory \
                and _bus.level == level and _bus.max_bytes == max_bytes:
            return _bus
        if _bus is not None:
            _bus.close()
        _bus = EventBus(directory, level, max_bytes=max_bytes)
        return _bus


def enable(directory: str, level: str = "MODERATE",
           max_bytes: int = 0) -> EventBus:
    """Conf-free switch-on (bench / tooling entry)."""
    global _bus
    with _bus_lock:
        if _bus is not None:
            _bus.close()
        _bus = EventBus(directory, parse_level(level),
                        max_bytes=max_bytes)
        return _bus


def reset_event_bus() -> None:
    """Tear down the bus (test isolation)."""
    global _bus
    with _bus_lock:
        if _bus is not None:
            _bus.close()
        _bus = None


# -- query attribution ------------------------------------------------------

_qlocal = threading.local()
_query_counter = 0
_query_counter_lock = threading.Lock()


def current_query_id() -> Optional[int]:
    return getattr(_qlocal, "qid", None)


def adopt_query_id(qid: Optional[int]) -> None:
    """Attribute this thread's events to an existing query id — used by
    pipeline producer threads (exec/pipeline.py) so events emitted
    behind a stage boundary carry their consumer's query."""
    _qlocal.qid = qid


def with_query_id(qid: Optional[int], fn, *args, **kwargs):
    """Run `fn` with this thread's events attributed to `qid`,
    restoring the previous attribution after (ISSUE 12): the shared
    decode/serialize pools and the spill writer serve MANY queries from
    one long-lived thread, so per-job adoption — the submitter captures
    current_query_id() and wraps the work item — is the only
    granularity that keeps io_retry/spill events attributed. Accepted
    by the thread-adopt contract rule as a spawn target."""
    prev = current_query_id()
    adopt_query_id(qid)
    try:
        return fn(*args, **kwargs)
    finally:
        adopt_query_id(prev)


@contextlib.contextmanager
def query_scope(qid: Optional[int] = None) -> Iterator[int]:
    """Attribute every event emitted by this thread inside the body to
    one query id (fresh monotonic id when not given). Nests: an inner
    scope shadows and restores."""
    global _query_counter
    if qid is None:
        with _query_counter_lock:
            _query_counter += 1
            qid = _query_counter
    prev = getattr(_qlocal, "qid", None)
    _qlocal.qid = qid
    try:
        yield qid
    finally:
        _qlocal.qid = prev
