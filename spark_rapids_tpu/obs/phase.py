"""Wall-clock phase attribution (ISSUE 17 tentpole piece 1): partition
each governed query's total wall-clock into a CLOSED set of named
phases, with the invariant `sum(phases) == wall_ns` exactly.

The reference's profiling tool reads Spark's task metrics (semaphore
wait, spill time, shuffle write/read time, ...) and attributes stage
wall-clock to them; standalone we rebuild that from the hooks the obs
plane already has — the dispatch ledger times every device call and
knows which ones traced (compile), the shuffle write path splits
pack/serialize/io, the ICI lane times its collective, the semaphore and
workload governor time their waits, the pipelined iterator times its
stalls, the retry layers time their backoffs.

Two accounting surfaces, both fed by the same `add`/`span` calls
(engine code opens a span through `obs.op_span(name, phase=...)`, which
also writes the host event into the profiler's trace; `span()` here is
the accounting under it, `add()` the form for after-the-fact durations
a site measured with its own clock):

* **Process-global cumulative counters** (`counters()`), always on —
  the obs/stats.py `_global_*` precedent. bench.py deltas them per
  record even for lanes that drive `plan.execute()` directly without a
  governed query (q1_lane), where no ledger exists.
* **Per-query PhaseLedger**, attached to the governed QueryContext by
  `DataFrame.collect()` when `spark.rapids.tpu.phases.enabled` (default
  on; off = the ledger is None and every site's ledger branch is one
  pointer check). `snapshot()` closes the books: `other` is the derived
  remainder, never negative.

Exactness rules:

* Accruals on the query's DRIVING thread are sequential and exclusive —
  `span()` keeps a thread-local stack and subtracts child-notified time
  from the enclosing frame, so nesting (a dispatch inside the ICI
  collective; a spill wait inside the shuffle write) never
  double-counts. Their sum can therefore never exceed wall.
* Accruals from OTHER threads (pipeline producers, adopted via the
  lifecycle adopt_context pattern) land in a separate `folded` map.
  Producer work overlaps consumer work; the only consumer wall-clock it
  can explain is the time the consumer spent *waiting on the producer*
  — the pipeline-stall budget. `snapshot()` re-attributes folded time
  into that budget (scaled down proportionally when producers report
  more time than the consumer stalled), shrinking pipeline-stall by the
  attributed amount, so the total never grows past wall.
"""

from __future__ import annotations

import contextlib
import threading
import time
from typing import Dict, Iterator, Optional

#: the closed phase set — docs/observability.md's phase table is
#: lint-checked against this tuple (tests/test_docs_lint.py), like the
#: event-kind and fault-point tables. `other` is always derived
#: (wall minus the sum of the measured phases), never accrued directly.
PHASES = (
    "admission-wait",      # workload-governor queue (exec/workload.py)
    "compile",             # traced dispatches (obs/dispatch.py)
    "device-compute",      # cached-program dispatches outside any span
    "host-pack-serialize", # shuffle write pack/serialize (exec/exchange.py)
    "shuffle-io",          # shuffle file write/read io_ns
    "ici-collective",      # device all-to-all rounds (ICI lane)
    "spill-wait",          # catalog writeback waits + synchronous spill
    "semaphore-wait",      # device admission (memory/semaphore.py)
    "pipeline-stall",      # consumer blocked on producer (exec/pipeline.py)
    "retry-backoff",       # task-retry + OOM-retry backoff sleeps
    "spec-wait",           # post-bound straggler wait the speculation
                           # shield raced against (exec/speculation_shield)
    "plan",                # logical plan -> exec tree (api/session.py)
    "scan-decode",         # file decode tasks (io/multifile.py)
    "upload",              # host column build, staging pack, device_put
                           # and unpack enqueue (columnar/upload.py)
    "device-wait",         # blocking device->host reads
                           # (columnar/transfer.py, TpuExec.collect)
    "join-build",          # a fused join's build side: child drain,
                           # concat, decode enqueue (exec/stage_compiler)
    "group-agg",           # a group-by the stage compiler did not fuse:
                           # each source batch's update with its
                           # leftover syncs, and the merge (exec/aggregate)
    "sort",                # a result sort with its key-width sync
                           # (exec/sort.py)
    "plan-rerun",          # a whole plan run a second time after its
                           # speculation flag tripped: what of the second
                           # pass no inner phase claims (exec/base.py)
    "host-sync",           # a device-to-host read outside every other
                           # span (obs/dispatch.host_read, span sync.*)
    "other",               # derived remainder — never negative
)

#: phases a site may accrue into (everything but the derived remainder)
ACCRUABLE = PHASES[:-1]


# ---------------------------------------------------------------------------
# process-global counters (bench.py {"phases": ...} deltas)
# ---------------------------------------------------------------------------

_global_lock = threading.Lock()
_global_ns: Dict[str, int] = {p: 0 for p in ACCRUABLE}


def counters() -> Dict[str, int]:
    """Snapshot of the process-cumulative per-phase nanoseconds — one
    dict so bench.py can delta it per record (chaos-delta pattern)."""
    with _global_lock:
        return dict(_global_ns)


def reset_phase_counters() -> None:
    """Test isolation (conftest tripwire companion)."""
    with _global_lock:
        for k in _global_ns:
            _global_ns[k] = 0


# ---------------------------------------------------------------------------
# thread-local span stack (exclusive accounting)
# ---------------------------------------------------------------------------

_tls = threading.local()


def _stack() -> list:
    s = getattr(_tls, "spans", None)
    if s is None:
        s = _tls.spans = []
    return s


def in_span() -> bool:
    """Is this thread inside an attribution span? The dispatch hook
    uses this to leave un-traced dispatch time to the enclosing span's
    phase (the ICI all-to-all is ici-collective, not device-compute)."""
    s = getattr(_tls, "spans", None)
    return bool(s)


def _ledger() -> Optional["PhaseLedger"]:
    from ..exec import lifecycle
    ctx = lifecycle.current_context()
    return getattr(ctx, "phase_ledger", None) if ctx is not None else None


def add(phase: str, ns: int) -> None:
    """Accrue `ns` of wall-clock to `phase`: process-global counters
    always; this thread's governed query's ledger when one is attached
    (one pointer check otherwise); and notify the enclosing span frame
    so the parent phase excludes this time."""
    if ns <= 0:
        return
    ns = int(ns)
    with _global_lock:
        _global_ns[phase] += ns
    s = getattr(_tls, "spans", None)
    if s:
        s[-1][1] += ns
    led = _ledger()
    if led is not None:
        led.add(phase, ns)


@contextlib.contextmanager
def span(phase: str) -> Iterator[None]:
    """Attribute this block's EXCLUSIVE elapsed time to `phase`: time
    any nested add()/span() reports is subtracted, and the block's full
    elapsed is notified upward — so arbitrarily nested attribution
    still sums to the outermost block's wall-clock, once."""
    t0 = time.perf_counter_ns()
    frame = [phase, 0]
    stack = _stack()
    stack.append(frame)
    try:
        yield
    finally:
        stack.pop()
        elapsed = time.perf_counter_ns() - t0
        exclusive = elapsed - frame[1]
        if exclusive > 0:
            with _global_lock:
                _global_ns[phase] += exclusive
            led = _ledger()
            if led is not None:
                led.add(phase, exclusive)
        if stack and elapsed > 0:
            stack[-1][1] += elapsed


def note_dispatch(wall_ns: int, traced: bool) -> None:
    """Per-dispatch hook (obs/dispatch.DispatchLedger._account, outside
    the ledger lock). Traced dispatches are compile time wherever they
    happen; cached dispatches are device-compute ONLY outside a span —
    inside one (ICI collective, shuffle pack) the enclosing phase keeps
    the time, matching how the site already reports it."""
    if traced:
        add("compile", wall_ns)
    elif not in_span():
        add("device-compute", wall_ns)


# ---------------------------------------------------------------------------
# per-query ledger
# ---------------------------------------------------------------------------

class PhaseLedger:
    """Per-governed-query phase books. Created on the driving thread by
    DataFrame.collect; accruals from that thread land in `_direct`
    (sequential, exclusive — their sum cannot exceed wall), accruals
    from adopted producer threads land in `_folded` (overlapped —
    snapshot() folds them into the pipeline-stall budget)."""

    __slots__ = ("_t0", "_thread", "_direct", "_folded", "_lock", "_wall")

    def __init__(self):
        self._t0 = time.perf_counter_ns()
        self._thread = threading.get_ident()
        self._direct: Dict[str, int] = {}
        self._folded: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._wall: Optional[int] = None

    def add(self, phase: str, ns: int) -> None:
        direct = threading.get_ident() == self._thread
        with self._lock:
            tgt = self._direct if direct else self._folded
            tgt[phase] = tgt.get(phase, 0) + ns

    def finish(self) -> int:
        """Close the measurement window (idempotent); returns wall_ns."""
        if self._wall is None:
            self._wall = time.perf_counter_ns() - self._t0
        return self._wall

    def dominant_phase(self) -> Optional[str]:
        """The largest phase accrued SO FAR, read mid-flight without
        closing the measurement window (the stall watchdog's `query
        stuck in <phase>` attribution — snapshot() would freeze wall).
        None when nothing has accrued yet."""
        with self._lock:
            merged = dict(self._direct)
            for p, v in self._folded.items():
                merged[p] = merged.get(p, 0) + v
        if not merged:
            return None
        return max(merged, key=merged.get)

    @property
    def wall_ns(self) -> int:
        return self.finish()

    def snapshot(self) -> Dict[str, int]:
        """The closed phase dict: every name in PHASES present,
        `sum(values) == wall_ns` exactly, nothing negative. Folded
        producer time re-attributes pipeline-stall budget: the consumer
        stalled exactly while producers worked, so folded accruals
        displace stall ns one-for-one, scaled down when producers
        report more than the consumer stalled (deeper overlap — that
        surplus genuinely did not cost the query wall-clock)."""
        wall = self.finish()
        with self._lock:
            direct = dict(self._direct)
            folded = dict(self._folded)
        out: Dict[str, int] = {p: 0 for p in PHASES}
        for p, v in direct.items():
            out[p] += v
        folded_total = sum(folded.values())
        if folded_total > 0:
            budget = out["pipeline-stall"]
            attributed = 0
            for p, v in folded.items():
                share = v if folded_total <= budget \
                    else v * budget // folded_total
                out[p] += share
                attributed += share
            out["pipeline-stall"] = budget - attributed
        total = sum(out.values())
        if total > wall:
            # defensive: direct spans are exclusive on one thread and
            # folded time never exceeds the stall budget, so this
            # should be unreachable — but the invariant is load-bearing
            # (tier-1 asserts it), so trim largest-first rather than
            # ever reporting sum > wall
            excess = total - wall
            for p in sorted(out, key=out.__getitem__, reverse=True):
                take = min(out[p], excess)
                out[p] -= take
                excess -= take
                if excess <= 0:
                    break
        out["other"] = wall - sum(v for k, v in out.items()
                                  if k != "other")
        return out


def attach(ctx) -> PhaseLedger:
    """Attach a fresh ledger to a governed QueryContext (the collect
    wrapper, conf-gated by spark.rapids.tpu.phases.enabled)."""
    led = PhaseLedger()
    ctx.phase_ledger = led
    return led
