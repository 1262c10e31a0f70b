"""Query lifecycle governor (ISSUE 6 tentpole): deadlines + cooperative
cancellation, partition-granular recovery accounting, and degradation
circuit breakers — the control plane that bounds what one query may cost
the process.

The reference engine leans on Spark's scheduler for all three: tasks are
killed cooperatively (`TaskContext.isInterrupted` polled at batch
boundaries), recovery is task/stage-granular rather than query-granular,
and a persistently failing executor is blacklisted instead of burning
every job's retry budget (SURVEY §2.5). Standalone, this module rebuilds
those contracts for the single-process multi-thread engine:

* **QueryContext** — one cancellation token per driven query.
  `DataFrame.collect()` installs it thread-locally (pipeline producer
  threads adopt it like conf/query-id/attempt); `TpuExec.execute()`
  ticks it every batch (one pointer check when no query is governed,
  the faults/eventLog cost discipline) and the blocking seams — the
  admission semaphore, pipeline stage waits, spill-writeback waits —
  check it inside their poll loops. A deadline
  (`spark.rapids.tpu.query.timeoutMs`, spanning ALL task re-execution
  attempts) or `TpuSession.cancel_query()` makes every checker raise
  `QueryCancelledError`; the query unwinds through the existing
  try/finally chains (stages join, spillables close, budget settles)
  and a single `query_cancelled` event records WHERE the cancellation
  was noticed (compute / sem-wait / pipeline-wait / spill-wait /
  task-retry).

* **Partition-recovery accounting** — the recovery itself lives where
  the lineage is alive (shuffle/manager.py consults the handle's
  committed map outputs + the lineage the exchange captured at write
  time); this module carries the provenance vocabulary, the
  conf gate, and the partition-vs-whole-plan counters that
  tools/profile_report.py and bench.py roll up.

* **Circuit breakers** — a sliding failure window per fault domain
  (`BREAKER_DOMAINS`). `exec/task_retry.py` records every
  classified-transient attempt failure against the domains the attempt
  engaged (the murmur3 Pallas kernels note engagement at trace time;
  device-ish errors always implicate `device_dispatch`); at
  `spark.rapids.tpu.breaker.threshold` failures inside `windowMs` the
  breaker opens and the domain's consult (`breaker_allows`) demotes it
  to its safe path until a post-cooldown half-open probe succeeds. One
  persistently bad kernel path degrades one domain instead of spending
  all of `task.maxAttempts` on every query. `TpuSession.health()`
  surfaces the whole state.
"""

from __future__ import annotations

import contextlib
import itertools
import threading
import time
from typing import Any, Dict, Iterator, List, Optional


class QueryCancelledError(RuntimeError):
    """The governed query was cancelled (deadline or user) — classified
    `fatal` by faults.classify, so it unwinds straight through the
    task-retry layer instead of burning attempts."""

    def __init__(self, msg: str, phase: str = "compute",
                 reason: str = "user"):
        super().__init__(msg)
        self.phase = phase
        self.reason = reason


#: phases a cancellation can be noticed in (docs/robustness.md);
#: admission-wait is the workload governor's queue (exec/workload.py)
CANCEL_PHASES = ("compute", "sem-wait", "pipeline-wait", "spill-wait",
                 "task-retry", "admission-wait")


# ---------------------------------------------------------------------------
# counters (bench.py {"lifecycle": ...} deltas + profile_report roll-up)
# ---------------------------------------------------------------------------

_counter_lock = threading.Lock()
_counters: Dict[str, int] = {
    "cancelled": 0,
    "partition_recompute": 0,
    "breaker_open": 0,
    "breaker_half_open": 0,
    "breaker_close": 0,
}


def _count(key: str) -> None:
    with _counter_lock:
        _counters[key] += 1


def counters() -> Dict[str, int]:
    """Snapshot of the process-cumulative lifecycle counters, plus the
    whole-plan re-execution total from exec/task_retry.py — one dict so
    bench.py can delta it per record."""
    from .task_retry import task_retry_total
    with _counter_lock:
        out = dict(_counters)
    out["whole_plan_retries"] = task_retry_total()
    return out


def note_partition_recompute() -> None:
    """Called by the shuffle read path when one map output was
    recomputed in place (the partition-granular lane)."""
    _count("partition_recompute")


# ---------------------------------------------------------------------------
# QueryContext + registry
# ---------------------------------------------------------------------------

_tls = threading.local()

_reg_lock = threading.Lock()
_active: Dict[int, "QueryContext"] = {}


class QueryContext:
    """Per-query cancellation token + deadline + engaged-domain notes.
    Shared across every thread serving the query (pipeline producers
    adopt it); all methods are thread-safe."""

    _ids = itertools.count(1)

    __slots__ = ("ctx_id", "owner", "t0", "deadline", "check_every",
                 "_cancel", "reason", "_ticks", "_emit_lock", "_emitted",
                 "engaged_domains", "workload_ticket",
                 "phase", "current_op", "root_op_id", "batches_produced",
                 "rows_produced", "attempt_no", "spill_count",
                 "spill_bytes", "runtime_stats", "phase_ledger",
                 "events_qid", "adaptive_batch_target", "stall_retry")

    def __init__(self, timeout_ms: int = 0, check_every: int = 8,
                 owner: Any = None):
        self.ctx_id = next(QueryContext._ids)
        self.owner = owner
        self.t0 = time.monotonic()
        self.deadline = (self.t0 + timeout_ms / 1000.0
                         if timeout_ms and timeout_ms > 0 else None)
        self.check_every = max(1, check_every)
        self._cancel = threading.Event()
        self.reason: Optional[str] = None
        self._ticks = 0
        self._emit_lock = threading.Lock()
        self._emitted = False
        #: fault domains this attempt engaged (noted at trace or
        #: dispatch time); cleared per task attempt by begin_attempt()
        self.engaged_domains: set = set()
        #: workload-governor admission ticket (exec/workload.py) —
        #: rides the context so producer threads that adopt_context
        #: resolve the same per-query memory quota
        self.workload_ticket = None
        # -- live introspection surface (ISSUE 11): read lock-free by
        # TpuSession.active_queries(); every field is a single attribute
        # assignment on its write path, and torn reads are harmless
        # (the snapshot is advisory, never a control decision)
        #: queued | admitted | executing | retrying (ADMISSION-adjacent
        #: phases are set by exec/workload.py, the others by task_retry)
        self.phase = "executing"
        #: operator that most recently yielded a batch on any thread
        self.current_op: Optional[str] = None
        #: the plan root's op id (set by DataFrame._collect_once) —
        #: batches/rows produced count only ROOT output, i.e. actual
        #: query results, not inner-operator traffic
        self.root_op_id = -1
        self.batches_produced = 0
        self.rows_produced = 0
        self.attempt_no = 1
        self.spill_count = 0
        self.spill_bytes = 0
        #: per-attempt RuntimeStats (obs/stats.py) — exchanges record
        #: map-output/partition distributions into it mid-flight
        self.runtime_stats = None
        #: per-query wall-clock phase ledger (obs/phase.py, ISSUE 17):
        #: attached by DataFrame.collect when phases.enabled; every
        #: accrual site pays one pointer check when None
        self.phase_ledger = None
        #: the events-plane query id of the LATEST attempt's
        #: query_scope (api/session._collect_once) — the id space
        #: query_start/query_end records carry. query_phases must join
        #: them in the log, and the lifecycle ctx_id drifts from it as
        #: soon as any query retries (one events id per attempt, one
        #: ctx per governed drive)
        self.events_qid = None
        #: OOM-feedback batch right-sizing (exec/adaptive.py): set by
        #: the first with_retry SPLIT of the query, consumed by
        #: CoalesceBatchesExec as a shrunken target so later batches of
        #: the same query stop re-triggering the retry lane. Persists
        #: across attempts (unlike runtime_stats) — the signal is about
        #: the query's data shape, not one attempt's luck
        self.adaptive_batch_target: Optional[int] = None
        #: progress-watchdog verdict under stall.action=retry-seam
        #: (exec/speculation_shield.py): set by the watchdog thread,
        #: consumed ONCE by check() at the stalled attempt's next
        #: cancellation checkpoint — the seam raises a transient
        #: QueryStalledError onto the bounded task-retry lane
        self.stall_retry = False

    def note_batch(self, op: str, op_id: int,
                   rows: Optional[int]) -> None:
        """Batch-boundary progress note (TpuExec._drive): cheap enough
        to run per batch on every governed query — two attribute writes,
        three when the batch is root output."""
        self.current_op = op
        if op_id == self.root_op_id:
            self.batches_produced += 1
            if rows:
                self.rows_produced += rows

    def info(self) -> Dict[str, Any]:
        """One query's live introspection row — assembled lock-light
        from this context + its workload ticket (quota read through the
        manager only when a ticket exists)."""
        now = time.monotonic()
        out = {
            "query": self.ctx_id,
            "phase": self.phase,
            "current_op": self.current_op,
            "batches": self.batches_produced,
            "rows": self.rows_produced,
            "elapsed_ms": int((now - self.t0) * 1000),
            "deadline_remaining_ms": (
                int((self.deadline - now) * 1000)
                if self.deadline is not None else None),
            "attempt": self.attempt_no,
            "spill_count": self.spill_count,
            "spill_bytes": self.spill_bytes,
            "cancelled": self._cancel.is_set(),
        }
        t = self.workload_ticket
        if t is not None:
            from ..memory.budget import memory_budget
            from . import workload
            limit = memory_budget().limit
            quota = workload.manager().quota_bytes(limit, t.quota_frac)
            out["quota"] = {
                "priority": t.priority,
                "used_bytes": t.device_bytes,
                "granted_bytes": quota if quota is not None else limit,
            }
        return out

    def cancel(self, reason: str = "user") -> None:
        if not self._cancel.is_set():
            if self.reason is None:
                self.reason = reason
            self._cancel.set()

    def cancelled(self) -> bool:
        if self._cancel.is_set():
            return True
        if self.deadline is not None and time.monotonic() >= self.deadline:
            self.cancel("timeout")
            return True
        return False

    def check(self, phase: str = "compute") -> None:
        """Raise QueryCancelledError when the query is cancelled or past
        its deadline. The FIRST checker (any thread) emits the single
        `query_cancelled` event with its phase attribution — that is the
        wait the query actually died in."""
        if self.stall_retry:
            # watchdog retry-seam verdict: consume the flag (a retried
            # attempt starts clean) and fail THIS attempt transiently —
            # it routes onto the task-retry lane, not the fatal unwind
            self.stall_retry = False
            from ..faults import QueryStalledError
            raise QueryStalledError(
                f"query stalled at seam {self.current_op!r}; retrying "
                f"the attempt (noticed in phase {phase})")
        if not self.cancelled():
            return
        reason = self.reason or "user"
        emit = False
        with self._emit_lock:
            if not self._emitted:
                self._emitted = True
                emit = True
        if emit:
            _count("cancelled")
            from ..obs import events as obs_events
            obs_events.emit(
                "query_cancelled", phase=phase, reason=reason,
                elapsed_ms=int((time.monotonic() - self.t0) * 1000))
        raise QueryCancelledError(
            f"query cancelled ({reason}) in phase {phase} after "
            f"{time.monotonic() - self.t0:.3f}s", phase=phase,
            reason=reason)

    def tick(self) -> None:
        """Batch-boundary hook (TpuExec.execute): cheap counter, a real
        deadline/cancel check every `check_every` ticks."""
        self._ticks += 1
        if self._ticks >= self.check_every:
            self._ticks = 0
            self.check("compute")


def current_context() -> Optional[QueryContext]:
    """This thread's governed query context (None outside one — the
    entire cost of the disabled mode)."""
    return getattr(_tls, "ctx", None)


def adopt_context(ctx: Optional[QueryContext]) -> None:
    """Install a captured context on this (producer) thread, like
    conf/query-id/speculation/attempt adoption at a stage boundary."""
    _tls.ctx = ctx


def check_current(phase: str = "compute") -> None:
    """Raise QueryCancelledError if this thread's governed query is
    cancelled; no-op (one pointer check) otherwise. The call blocking
    waits put inside their poll loops."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        ctx.check(phase)


def current_cancelled() -> bool:
    """Predicate flavor of check_current (for callers that must clean
    up before raising)."""
    ctx = getattr(_tls, "ctx", None)
    return ctx is not None and ctx.cancelled()


@contextlib.contextmanager
def governed(conf=None, owner: Any = None,
             timeout_ms: Optional[int] = None) -> Iterator[QueryContext]:
    """Install a QueryContext around one driven query (the
    DataFrame.collect wrapper — OUTSIDE with_task_retry, so the deadline
    spans every task re-execution attempt). Registers the context so
    cancel_owner / the conftest leak tripwire can see it; always
    unregisters on the way out."""
    from ..config import (QUERY_CANCEL_CHECK_BATCHES, QUERY_TIMEOUT_MS,
                          active_conf)
    conf = conf if conf is not None else active_conf()
    if timeout_ms is None:
        timeout_ms = conf.get(QUERY_TIMEOUT_MS)
    ctx = QueryContext(timeout_ms=timeout_ms,
                       check_every=conf.get(QUERY_CANCEL_CHECK_BATCHES),
                       owner=owner)
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    with _reg_lock:
        _active[ctx.ctx_id] = ctx
    try:
        yield ctx
    finally:
        _tls.ctx = prev
        with _reg_lock:
            _active.pop(ctx.ctx_id, None)


def cancel_owner(owner: Any, reason: str = "user") -> int:
    """Cancel every registered context belonging to `owner` (the
    TpuSession.cancel_query entry — runs on any thread). Returns how
    many contexts were cancelled."""
    with _reg_lock:
        targets = [c for c in _active.values() if c.owner is owner]
    for c in targets:
        c.cancel(reason)
    return len(targets)


def active_query_ids() -> List[int]:
    with _reg_lock:
        return sorted(_active)


def set_phase(phase: str) -> None:
    """Live-introspection phase note for this thread's governed query
    (no-op outside one — a single pointer check)."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        ctx.phase = phase


def note_spill(freed_bytes: int) -> None:
    """Per-query spill attribution (ISSUE 11): the catalog calls this
    once per synchronous_spill pass that freed anything, on the thread
    whose reservation triggered it — the query that EXPERIENCED the
    pressure, which is what active_queries() reports."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        ctx.spill_count += 1
        ctx.spill_bytes += freed_bytes


def active_queries(owner: Any = None) -> List[Dict[str, Any]]:
    """Live introspection rows for every registered (in-flight) query,
    oldest first — the TpuSession.active_queries() payload. The
    registry lock is held only to snapshot the context list; each row
    assembles from lock-free attribute reads. `owner` marks (never
    filters) rows: introspection is engine-wide, `mine` says which
    queries belong to the asking session."""
    with _reg_lock:
        ctxs = sorted(_active.values(), key=lambda c: c.ctx_id)
    out = []
    for c in ctxs:
        row = c.info()
        row["mine"] = owner is not None and c.owner is owner
        out.append(row)
    return out


# ---------------------------------------------------------------------------
# degradation circuit breakers
# ---------------------------------------------------------------------------

#: domain -> (what it covers, its safe path when open). The
#: docs/robustness.md domain table is lint-checked against this
#: registry (tests/test_docs_lint.py), like the fault-point table.
BREAKER_DOMAINS: Dict[str, str] = {
    "pallas_hash": "murmur3 Pallas kernels (ops/pallas_kernels.py) "
                   "-> XLA elementwise murmur3 (ops/hashing.py)",
    "device_dispatch": "guarded device dispatch (memory/retry.py "
                       "oom_guard) -> advisory: already the guarded "
                       "path; open state surfaces in health()/events",
    "ici_exchange": "ICI device-to-device shuffle lane "
                    "(exec/exchange.py + parallel/exchange.py) "
                    "-> host serialize/LZ4 shuffle lane",
    "adaptive": "runtime replanner (exec/adaptive.py) "
                "-> static plan: measured-statistics decisions (skew "
                "split, broadcast demotion, coalescing, batch "
                "right-sizing) are skipped while open",
}

#: Pallas kernel family -> breaker domain (ops/hashing._use_pallas
#: consults and engages through it)
FAMILY_DOMAINS: Dict[str, str] = {
    "murmur3": "pallas_hash",
}

BREAKER_STATES = ("closed", "open", "half_open")


class _Breaker:
    __slots__ = ("domain", "state", "failures", "opened_at", "trips",
                 "probe_at")

    def __init__(self, domain: str):
        self.domain = domain
        self.state = "closed"
        self.failures: List[float] = []  # monotonic failure timestamps
        self.opened_at = 0.0
        self.trips = 0
        #: when the half-open probe was let through (0 = none in
        #: flight): concurrent consults stay demoted while one probe
        #: runs, and a probe that never concludes (fatal crash skips
        #: the attempt hooks) expires after another cooldown
        self.probe_at = 0.0


_breaker_lock = threading.Lock()
_breakers: Dict[str, _Breaker] = {}


def _breaker_conf(conf=None):
    from ..config import (BREAKER_COOLDOWN_MS, BREAKER_ENABLED,
                          BREAKER_THRESHOLD, BREAKER_WINDOW_MS, active_conf)
    conf = conf if conf is not None else active_conf()
    return (bool(conf.get(BREAKER_ENABLED)),
            max(1, conf.get(BREAKER_THRESHOLD)),
            max(1, conf.get(BREAKER_WINDOW_MS)) / 1000.0,
            max(1, conf.get(BREAKER_COOLDOWN_MS)) / 1000.0)


def _emit_breaker(kind: str, br: _Breaker, **fields) -> None:
    _count(kind)
    from ..obs import events as obs_events
    obs_events.emit(kind, domain=br.domain, trips=br.trips,
                    failures=len(br.failures), **fields)


def breaker_allows(domain: str) -> bool:
    """May `domain`'s accelerated path engage right now? closed ->
    yes; open -> no until cooldown, then the consult itself half-opens
    the breaker and lets ONE probe through; half_open -> only while no
    probe is in flight (a probe that never concludes expires after
    another cooldown, so a crashed probe cannot wedge the breaker).
    An explicitly disabled conf (breaker.enabled=false — the operator
    kill-switch) answers yes regardless of recorded state. With no
    breaker ever tripped this is one empty-dict check."""
    if not _breakers:
        return True
    enabled, _thr, _window, cooldown = _breaker_conf()
    if not enabled:
        # the kill-switch must restore the accelerated tier NOW, not
        # after a cooldown + lucky probe (review r4)
        return True
    emit = None
    with _breaker_lock:
        br = _breakers.get(domain)
        if br is None or br.state == "closed":
            return True
        now = time.monotonic()
        if br.state == "open":
            if now - br.opened_at < cooldown:
                return False
            br.state = "half_open"
            br.probe_at = now
            emit = br
        else:  # half_open
            if br.probe_at and now - br.probe_at <= cooldown:
                return False  # one probe at a time
            br.probe_at = now
    if emit is not None:
        _emit_breaker("breaker_half_open", emit)
    return True


def record_domain_failure(domain: str) -> None:
    """One classified-transient failure attributed to `domain`.
    Conf-gated (spark.rapids.tpu.breaker.enabled, default off): runs
    only on failure paths, so the conf read costs nothing steady-state."""
    enabled, threshold, window, _cooldown = _breaker_conf()
    if not enabled or domain not in BREAKER_DOMAINS:
        return
    now = time.monotonic()
    opened = None
    with _breaker_lock:
        br = _breakers.get(domain)
        if br is None:
            br = _breakers[domain] = _Breaker(domain)
        br.failures = [t for t in br.failures if now - t <= window]
        br.failures.append(now)
        if br.state == "half_open" or (br.state == "closed"
                                       and len(br.failures) >= threshold):
            br.state = "open"
            br.opened_at = now
            br.probe_at = 0.0
            br.trips += 1
            opened = br
    if opened is not None:
        _emit_breaker("breaker_open", opened,
                      safe_path=BREAKER_DOMAINS[domain])


def record_domain_success(domain: str) -> None:
    """A successful attempt that engaged `domain`: a half-open breaker's
    probe passed — close it and forget the failure history."""
    if not _breakers:
        return
    closed = None
    with _breaker_lock:
        br = _breakers.get(domain)
        if br is not None and br.state == "half_open":
            br.state = "closed"
            br.failures = []
            br.probe_at = 0.0
            closed = br
    if closed is not None:
        _emit_breaker("breaker_close", closed)


def breaker_shed_hint_ms(domain: str, conf=None) -> Optional[int]:
    """Read-only admission consult (exec/workload.py, ISSUE 7): while
    `domain`'s breaker is OPEN and still inside its cooldown, return the
    remaining cooldown in ms (the shed retry-after hint); None
    otherwise. Unlike breaker_allows this never transitions state —
    half-open probes belong to already-running attempts; admission must
    not consume (or block behind) the single probe slot. `conf` is the
    ADMITTING conf: admission runs before collect installs the session
    conf thread-locally, so active_conf() could answer for the wrong
    session."""
    if not _breakers:
        return None
    enabled, _thr, _window, cooldown = _breaker_conf(conf)
    if not enabled:
        return None
    with _breaker_lock:
        br = _breakers.get(domain)
        if br is None or br.state != "open":
            return None
        remaining = cooldown - (time.monotonic() - br.opened_at)
        if remaining <= 0:
            return None
        return max(1, int(remaining * 1000))


def open_breakers() -> List[str]:
    """Domains whose breaker is not closed (conftest leak tripwire +
    health surface)."""
    with _breaker_lock:
        return sorted(d for d, b in _breakers.items()
                      if b.state != "closed")


# -- attempt attribution (exec/task_retry.py hooks) -------------------------

def note_engagement(family: str) -> None:
    """Trace-time note from ops/hashing.py that a Pallas kernel
    family engaged for the current attempt; maps the family onto its
    breaker domain. Lands on the QueryContext when one is governed
    (shared across producer threads), else on a thread-local attempt
    scope installed by begin_attempt()."""
    domain = FAMILY_DOMAINS.get(family)
    if domain is None:
        return
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        ctx.engaged_domains.add(domain)
        return
    s = getattr(_tls, "engaged", None)
    if s is not None:
        s.add(domain)


def engage_domain(domain: str) -> None:
    """Engage a breaker DOMAIN directly (ISSUE 14): a CompiledStageExec
    notes `device_dispatch` at its stage boundary so a classified-
    transient failure of the fused execution counts against the domain
    and PR 5 degradation demotes the stage back to per-operator
    execution. The family-keyed twin (note_engagement) is the Pallas
    kernels' surface; this one is for callers that ARE a domain."""
    if domain not in BREAKER_DOMAINS:
        return
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        ctx.engaged_domains.add(domain)
        return
    s = getattr(_tls, "engaged", None)
    if s is not None:
        s.add(domain)


def _engaged_set(create: bool = False) -> set:
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        return ctx.engaged_domains
    s = getattr(_tls, "engaged", None)
    if s is None and create:
        s = _tls.engaged = set()
    return s if s is not None else set()


def capture_engagement() -> Optional[set]:
    """The live engaged-domain set serving this thread's attempt (the
    QueryContext's when governed, else the thread-local attempt set) —
    captured at a pipeline stage boundary so producer-thread
    engagements land in the CONSUMER's attempt set even for un-governed
    queries (a bench lane without a deadline; a test driving
    with_task_retry directly)."""
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        return ctx.engaged_domains
    return getattr(_tls, "engaged", None)


def adopt_engagement(s: Optional[set]) -> None:
    """Install a captured engagement set on this (producer) thread.
    The governed case needs nothing (adopt_context already shares the
    QueryContext's set); this covers the thread-local fallback."""
    if s is not None and getattr(_tls, "ctx", None) is None:
        _tls.engaged = s


def begin_attempt(attempt: int = 1) -> None:
    """Task-attempt start (with_task_retry): clear the engaged-domain
    notes so failures attribute to THIS attempt's engagements, and note
    the attempt number + executing phase on the governed context (the
    live-introspection surface)."""
    _engaged_set(create=True).clear()
    ctx = getattr(_tls, "ctx", None)
    if ctx is not None:
        ctx.attempt_no = attempt
        ctx.phase = "executing"
        # per-attempt progress, like the per-attempt RuntimeStats: a
        # re-executed plan starts its root output from zero — without
        # this, active_queries() double-counts across task retries
        ctx.current_op = None
        ctx.batches_produced = 0
        ctx.rows_produced = 0


def attempt_failed(exc: BaseException) -> None:
    """A classified-transient task-attempt failure: record it against
    every domain the attempt engaged, plus device_dispatch for
    device-ish errors (an injected device fault or a non-OOM XLA
    runtime error always implicates the dispatch domain)."""
    domains = set(_engaged_set())
    from ..faults import InjectedDeviceError
    if isinstance(exc, InjectedDeviceError) \
            or type(exc).__name__ == "XlaRuntimeError":
        domains.add("device_dispatch")
    for d in domains:
        record_domain_failure(d)


def _rearm_if_cooled(domain: str) -> None:
    """open + cooldown elapsed -> half_open. The advisory
    device_dispatch domain is consulted by nothing, so a successful
    attempt performs its cooldown transition here (NOT via
    breaker_allows, whose single-probe gate would refuse while the
    attempt's own probe is in flight)."""
    enabled, _thr, _window, cooldown = _breaker_conf()
    if not enabled:
        return
    emit = None
    with _breaker_lock:
        br = _breakers.get(domain)
        if br is not None and br.state == "open" \
                and time.monotonic() - br.opened_at >= cooldown:
            br.state = "half_open"
            br.probe_at = 0.0
            emit = br
    if emit is not None:
        _emit_breaker("breaker_half_open", emit)


def attempt_succeeded() -> None:
    """A task attempt completed: any half-open breaker whose domain the
    attempt engaged (probed) closes unconditionally — the success IS
    the probe outcome; device_dispatch's probe is every successful
    attempt (dispatch is engaged by running at all), re-armed from open
    first when its cooldown has elapsed."""
    if not _breakers:
        return
    for d in set(_engaged_set()) | {"device_dispatch"}:
        _rearm_if_cooled(d)
        record_domain_success(d)


# ---------------------------------------------------------------------------
# health surface + test reset
# ---------------------------------------------------------------------------

def health() -> Dict[str, Any]:
    """The TpuSession.health() payload: breaker states, governed-query
    count, the cumulative lifecycle counters, and the workload
    governor's admission surface (queue depth / admitted / shed)."""
    now = time.monotonic()
    with _breaker_lock:
        breakers = {
            d: {"state": b.state, "trips": b.trips,
                "failures_in_window": len(b.failures),
                "open_for_ms": int((now - b.opened_at) * 1000)
                if b.state != "closed" else 0}
            for d, b in _breakers.items()}
    from . import workload
    return {"breakers": breakers,
            "active_queries": len(active_query_ids()),
            "counters": counters(),
            "workload": workload.snapshot()}


def reset_lifecycle() -> None:
    """Test isolation: drop every breaker, registered context and
    counter (the conftest tripwire resets at module boundaries, like
    faults.install(None))."""
    with _breaker_lock:
        _breakers.clear()
    with _reg_lock:
        _active.clear()
    with _counter_lock:
        for k in _counters:
            _counters[k] = 0
