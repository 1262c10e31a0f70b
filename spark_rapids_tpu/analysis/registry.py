"""THE rule registry (ISSUE 12): rule metadata plus the engine contract
data the rules check against — named locks with reentrancy and a
declared partial order, the thread-local adopt helpers, the cross-query
entry points whose call paths must not read `active_conf`, and the
paired accounting calls that must stay symmetric.

One registry, lint-checked three ways: docs/static_analysis.md's rule
table must list exactly RULES (tests/test_contract_check.py), every
lock/entry spec must name a real module (same test), and
tests/test_docs_lint.py delegates its conf-key AST scan to the
`conf-key-registered` rule's scanner so the registries cannot drift.
"""

from __future__ import annotations

import re
from typing import Callable, Dict, Iterable, List, Optional, Tuple


class LockSpec:
    """One named engine lock. `expr` is the acquisition expression as
    written at the hold sites (`with self._lock:`), `cls` scopes it to
    a class (None = module-global name)."""

    __slots__ = ("name", "module", "cls", "expr", "reentrant", "note")

    def __init__(self, name: str, module: str, cls: Optional[str],
                 expr: str, reentrant: bool, note: str):
        self.name = name
        self.module = module
        self.cls = cls
        self.expr = expr
        self.reentrant = reentrant
        self.note = note


class EntrySpec:
    """A function that runs on a producer/cross-query thread (or on an
    arbitrary caller's thread servicing OTHER queries' state): conf
    reads along its module-local call paths must ride a captured
    conf/Ticket, never the executing thread's `active_conf`."""

    __slots__ = ("module", "cls", "func", "note")

    def __init__(self, module: str, cls: Optional[str], func: str,
                 note: str):
        self.module = module
        self.cls = cls
        self.func = func
        self.note = note


class PairSpec:
    """Registry-declared paired accounting calls. `escrow` maps a
    function qualname to the justification for holding the obligation
    open past its own frame (ownership transfer)."""

    __slots__ = ("name", "open_attr", "close_attr", "receiver_hint",
                 "modules", "escrow")

    def __init__(self, name: str, open_attr: str, close_attr: str,
                 receiver_hint: str, modules: Tuple[str, ...],
                 escrow: Dict[str, str]):
        self.name = name
        self.open_attr = open_attr
        self.close_attr = close_attr
        self.receiver_hint = receiver_hint
        self.modules = modules
        self.escrow = escrow


class ContractRegistry:
    """The data half of the registry. Tests run rules against a fixture
    instance; the CLI and tier-1 use DEFAULT_REGISTRY."""

    def __init__(self, locks: List[LockSpec], lock_order: List[str],
                 cross_query_entries: List[EntrySpec],
                 pairs: List[PairSpec],
                 adopt_helpers: Iterable[str],
                 extra_blocking_calls: Dict[str, str],
                 scope_prefix: str = "spark_rapids_tpu/"):
        #: path substring gating the package-wide rules (thread/trace):
        #: tools/bench are scripts — module scope IS their main — so the
        #: engine registry scopes those rules to the package; fixture
        #: registries pass "" to run them anywhere
        self.scope_prefix = scope_prefix
        self.locks = locks
        #: outermost-first acquisition order; acquiring a lock that
        #: sorts EARLIER than one already held is a lock-order finding
        self.lock_order = lock_order
        self.cross_query_entries = cross_query_entries
        self.pairs = pairs
        self.adopt_helpers = frozenset(adopt_helpers)
        #: cross-module calls known to block (module-level walks cannot
        #: see into them): callable name -> why it blocks
        self.extra_blocking_calls = dict(extra_blocking_calls)

    def locks_for(self, relpath: str) -> List[LockSpec]:
        return [s for s in self.locks if relpath.endswith(s.module)]

    def entries_for(self, relpath: str) -> List[EntrySpec]:
        return [e for e in self.cross_query_entries
                if relpath.endswith(e.module)]

    def pairs_for(self, relpath: str) -> List[PairSpec]:
        return [p for p in self.pairs
                if any(relpath.endswith(m) for m in p.modules)]


#: attribute calls that block (or do IO) regardless of receiver
BLOCKING_ATTRS = frozenset({
    "wait", "join", "sleep", "fsync", "savez", "device_get",
    "block_until_ready", "result",
})
#: .get()/.put() block only on queue-like receivers (dict.get is not IO)
QUEUE_BLOCKING_ATTRS = frozenset({"get", "put"})
QUEUE_RECEIVER_RE = re.compile(r"(^|\.)_?(write_)?q(ueue)?$")
#: bare-name calls that do IO
BLOCKING_NAMES = frozenset({"open"})
#: bare-name calls that wait for the device (obs.dispatch.host_read: the
#: one door of a device-to-host read, ISSUE 40)
DEVICE_SYNC_NAMES = frozenset({"host_read"})
#: `.emit(...)` on an event-bus-ish receiver — the PR 6 r4 class: the
#: bus takes its own lock and writes a file, never do that under an
#: engine lock
EMIT_RECEIVER_HINTS = ("events", "bus")

#: thread-local capture/adopt helpers a spawned target must route
#: through (PRs 3/4/5/6: conf, query id, speculation scope, task
#: attempt, lifecycle context, breaker engagement)
ADOPT_HELPERS = frozenset({
    "set_active_conf", "adopt_query_id", "adopt_context",
    "adopt_attempt", "adopt_engagement", "query_scope",
    # pool-thread wrapper (obs.events): submit(with_query_id, qid, fn, ...)
    "with_query_id",
})

#: host-sync / materialization calls that must not run on tracer values
#: inside a @jit / Pallas body
HOST_SYNC_ATTRS = frozenset({
    "item", "tolist", "block_until_ready", "device_get",
})
HOST_SYNC_NP_ATTRS = frozenset({"asarray", "array", "frombuffer"})


class RuleMeta:
    __slots__ = ("id", "family", "bug_class", "origin", "example",
                 "checker")

    def __init__(self, id: str, family: str, bug_class: str, origin: str,
                 example: str, checker: Optional[Callable]):
        self.id = id
        self.family = family
        self.bug_class = bug_class
        self.origin = origin
        self.example = example
        self.checker = checker


def _build_rules() -> Dict[str, RuleMeta]:
    from . import (rules_accounting, rules_bounded, rules_conf,
                   rules_dispatch, rules_locks, rules_registry,
                   rules_stage, rules_threads, rules_trace)
    rules = [
        RuleMeta(
            "lock-blocking-call", "lock-discipline",
            "blocking call (IO, wait, queue op, event emit, device "
            "transfer) reachable while a registered engine lock is held",
            "PR 6 r4 (admission events under the manager cond); "
            "PR 3 r2 (writer drain under the catalog lock)",
            "obs_events.emit(...) inside `with self._lock:`",
            rules_locks.check_blocking),
        RuleMeta(
            "lock-reacquire", "lock-discipline",
            "re-acquisition of a non-reentrant lock along a "
            "module-local call path",
            "PR 5 (HeartbeatManager.heartbeat -> register deadlock)",
            "method holding self._lock calls a method that takes it",
            rules_locks.check_reacquire),
        RuleMeta(
            "lock-order", "lock-discipline",
            "acquiring a lock that sorts EARLIER in the declared "
            "partial order than one already held",
            "declared order (registry.lock_order), PR 3 writer/catalog "
            "deadlock analysis",
            "taking the catalog lock while holding the event-bus lock",
            rules_locks.check_order),
        RuleMeta(
            "bounded-wait", "lock-discipline",
            "unbounded blocking rendezvous — wait/get/result/sleep "
            "with no positional args and no timeout= keyword parks "
            "its thread beyond every watchdog, deadline and "
            "cancellation poll",
            "ISSUE 20 (straggler & stall shield: stalls the shield "
            "cannot observe cannot be mitigated)",
            "self._done.wait() / fut.result() with no timeout",
            rules_bounded.check),
        RuleMeta(
            "thread-adopt", "thread-propagation",
            "threading.Thread / pool submit whose target never routes "
            "through the thread-local capture/adopt helpers",
            "PRs 3/4/5 (conf, query id, speculation, attempt, "
            "engagement adoption at every producer boundary)",
            "threading.Thread(target=self._loop) with no adopt_* in "
            "_loop",
            rules_threads.check),
        RuleMeta(
            "trace-module-jnp", "trace-purity",
            "module-level jnp.* call binding — captures a tracer when "
            "the module is first imported inside a jit trace",
            "PR 2 (order-dependent tracer leak across 7 ops modules)",
            "_C1 = jnp.uint32(0xcc9e2d51) at module scope",
            rules_trace.check_module_jnp),
        RuleMeta(
            "trace-host-sync", "trace-purity",
            "host-sync / materialization call inside a @jit or Pallas "
            "kernel body",
            "PR 1/2 jit discipline (device syncs belong at the batch "
            "boundary)",
            "np.asarray(x) inside a @jax.jit function",
            rules_trace.check_host_sync),
        RuleMeta(
            "conf-provenance", "conf-provenance",
            "active_conf() read reachable from a producer-thread or "
            "cross-query entry point — the value must ride a captured "
            "conf or the admitting Ticket",
            "PR 6 (3x: release cap, quota fraction, breaker consult "
            "all read the CALLING thread's conf)",
            "active_conf().get(...) inside the spill-writer's reach",
            rules_conf.check),
        RuleMeta(
            "accounting-symmetry", "accounting-symmetry",
            "registry-declared paired calls (reserve/release, "
            "charge/discharge) unbalanced: open with no close on any "
            "path, or an exception edge that drops the close",
            "PRs 3/4/6 (budget counters asymmetric on failure "
            "branches, quota charge/discharge mirrors)",
            "memory_budget().reserve(n) with no release on the raise "
            "path",
            rules_accounting.check),
        RuleMeta(
            "conf-key-registered", "registry-drift",
            "full spark.rapids.* conf-key literal not present in the "
            "config registry",
            "PR 2 docs lint (folded into the analyzer, ISSUE 12 "
            "satellite)",
            '"spark.rapids.tpu.sucht.nicht" anywhere in code',
            rules_registry.check_conf_keys),
        RuleMeta(
            "event-kind-registered", "registry-drift",
            "emit() with a literal event kind missing from "
            "obs.events.EVENT_LEVELS (it would silently default to "
            "MODERATE and never reach the docs schema table)",
            "PR 2 docs lint (EVENT_LEVELS registry)",
            'obs_events.emit("not_a_kind", ...)',
            rules_registry.check_event_kinds),
        RuleMeta(
            "dispatch-ledger", "dispatch-discipline",
            "jax.jit / pallas_call site that does not route through "
            "the dispatch-ledger chokepoint (obs.dispatch.instrument) "
            "— its dispatches/compiles/storms are invisible to the "
            "observability plane",
            "ISSUE 13 (dispatch & compile observability plane)",
            "self._jit = jax.jit(self._kernel) in an exec",
            rules_dispatch.check),
        RuleMeta(
            "host-sync-chokepoint", "dispatch-discipline",
            "jax.device_get / .block_until_ready() that does not go "
            "through the host-read chokepoint (obs.dispatch.host_read) "
            "— a host sync the ledger cannot count and the device trace "
            "cannot name",
            "ISSUE 40 (host syncs get one chokepoint)",
            "sizes = jax.device_get(_partial_size(batch)) in an exec",
            rules_dispatch.check_host_sync),
        RuleMeta(
            "stage-governance", "dispatch-discipline",
            "per-batch governance hook (lifecycle tick, chaos fault "
            "point, metric timer, event emit, gather observe, breaker "
            "engagement) inside a traced stage body handed to the "
            "dispatch chokepoint — it runs once per TRACE, not per "
            "batch, so it is silently dead under jit caching; hooks "
            "belong in the stage-boundary harness",
            "ISSUE 14 (whole-stage compilation: governance extracted "
            "to the stage boundary)",
            "faults.check(...) inside a fn passed to instrument()",
            rules_stage.check),
        RuleMeta(
            "suppression-empty", "analyzer-meta",
            "a `# contract: ok` suppression with no justification, or "
            "naming a rule that does not exist",
            "ISSUE 12 (justification required, linted non-empty)",
            "# contract: ok lock-blocking-call —",
            None),
        RuleMeta(
            "baseline-invalid", "analyzer-meta",
            "a baseline entry with an empty/UNREVIEWED justification "
            "or a non-positive count",
            "ISSUE 12 (baselined findings carry a why, like "
            "suppressions)",
            '{"count": 0, "why": ""}',
            None),
    ]
    return {r.id: r for r in rules}


RULES: Dict[str, RuleMeta] = _build_rules()

#: rule families (docs/static_analysis.md groups its table by these)
FAMILIES = tuple(dict.fromkeys(r.family for r in RULES.values()))


DEFAULT_REGISTRY = ContractRegistry(
    locks=[
        LockSpec("catalog", "memory/catalog.py", "BufferCatalog",
                 "self._lock", reentrant=True,
                 note="3-tier spill store registry (RLock: the writer's "
                 "finalize re-enters via _recover_dead_writer_locked)"),
        LockSpec("budget-cond", "memory/budget.py", "MemoryBudget",
                 "self._lock", reentrant=True,
                 note="HBM budget condition (reserve/release/waiters)"),
        LockSpec("workload-cond", "exec/workload.py", "WorkloadManager",
                 "self._cond", reentrant=True,
                 note="admission queue + quota accounting condition"),
        LockSpec("semaphore-cond", "memory/semaphore.py", "_FairPermits",
                 "self._cond", reentrant=True,
                 note="fair permit registry condition"),
        LockSpec("semaphore", "memory/semaphore.py", "TpuSemaphore",
                 "self._lock", reentrant=False,
                 note="per-task hold table"),
        LockSpec("breaker", "exec/lifecycle.py", None, "_breaker_lock",
                 reentrant=False,
                 note="circuit-breaker domain state"),
        LockSpec("heartbeat", "parallel/heartbeat.py",
                 "HeartbeatManager", "self._lock", reentrant=False,
                 note="peer table (the PR 5 deadlock lived here)"),
        LockSpec("telemetry", "obs/telemetry.py", "TelemetryRegistry",
                 "self._lock", reentrant=False,
                 note="counter + ring-buffer state"),
        LockSpec("telemetry-config", "obs/telemetry.py", None,
                 "_registry_lock", reentrant=False,
                 note="registry singleton install/teardown"),
        LockSpec("stats", "obs/stats.py", "ExchangeStats", "self._lock",
                 reentrant=False, note="per-exchange distribution state"),
        LockSpec("stats-global", "obs/stats.py", None, "_global_lock",
                 reentrant=False, note="process-wide stats collector"),
        LockSpec("dispatch-ledger", "obs/dispatch.py", "DispatchLedger",
                 "self._lock", reentrant=False,
                 note="program-stats registry (events buffered under "
                 "it, emitted after it drops)"),
        LockSpec("dispatch-config", "obs/dispatch.py", None,
                 "_ledger_lock", reentrant=False,
                 note="ledger singleton install/teardown"),
        LockSpec("phase-global", "obs/phase.py", None, "_global_lock",
                 reentrant=False,
                 note="process-cumulative per-phase ns counters"),
        LockSpec("phase-ledger", "obs/phase.py", "PhaseLedger",
                 "self._lock", reentrant=False,
                 note="per-query phase books (direct/folded maps)"),
        LockSpec("event-bus-config", "obs/events.py", None, "_bus_lock",
                 reentrant=False, note="bus singleton install/teardown"),
        LockSpec("event-bus", "obs/events.py", "EventBus", "self._lock",
                 reentrant=False,
                 note="JSONL sink write serialization (leaf lock: nothing "
                 "may be acquired under it)"),
        LockSpec("history-config", "obs/history.py", None, "_store_lock",
                 reentrant=False,
                 note="history store singleton install/teardown"),
        LockSpec("history", "obs/history.py", "HistoryStore",
                 "self._lock", reentrant=False,
                 note="capsule JSONL sink write serialization (leaf "
                 "lock, the event-bus pattern)"),
    ],
    # outermost-first: a lock may only be acquired while holding locks
    # that sort strictly BEFORE it
    lock_order=[
        "catalog", "workload-cond", "budget-cond", "semaphore-cond",
        "semaphore", "heartbeat", "breaker", "telemetry-config",
        "telemetry", "stats", "stats-global", "dispatch-config",
        "dispatch-ledger", "phase-global", "phase-ledger",
        "event-bus-config", "event-bus", "history-config", "history",
    ],
    cross_query_entries=[
        EntrySpec("memory/catalog.py", "BufferCatalog", "_writer_loop",
                  "spill-writer thread serves every query's hops"),
        EntrySpec("memory/catalog.py", "BufferCatalog",
                  "_recover_dead_writer_locked",
                  "drains OTHER queries' stranded hops on the "
                  "detecting thread"),
        EntrySpec("memory/catalog.py", "BufferCatalog",
                  "synchronous_spill",
                  "a neighbor's reserve pressure spills THIS query's "
                  "entries on the neighbor's thread"),
        EntrySpec("memory/semaphore.py", "TpuSemaphore", "__init__",
                  "process singleton sized by whichever thread "
                  "constructs it first"),
        EntrySpec("exec/workload.py", "WorkloadManager", "release",
                  "releasing thread pumps grants for OTHER queries "
                  "(the PR 6 cap bug lived here)"),
        EntrySpec("exec/workload.py", "WorkloadManager", "charge",
                  "mirrors catalog accounting from any spilling thread"),
        EntrySpec("exec/workload.py", "WorkloadManager", "discharge",
                  "mirrors catalog accounting from any spilling thread"),
        EntrySpec("obs/telemetry.py", "TelemetryRegistry", "_loop",
                  "sampler thread carries no query context"),
        EntrySpec("parallel/heartbeat.py", "HeartbeatEndpoint", "_loop",
                  "heartbeat daemon carries no query context"),
        EntrySpec("io/multifile.py", None, "retrying",
                  "shared decode-pool worker (conf must ride the "
                  "captured closure, never the pool thread's TLS)"),
    ],
    pairs=[
        PairSpec(
            # add() is deliberately NOT escrowed here: its reserve has
            # no release in-frame AND no guarding except — the window
            # between reserve and registration is accepted debt,
            # carried in the baseline with its justification
            "hbm-budget", "reserve", "release", receiver_hint="budget",
            modules=("memory/catalog.py",),
            escrow={}),
        PairSpec(
            "workload-quota", "charge", "discharge",
            receiver_hint="workload",
            modules=("memory/catalog.py",),
            escrow={
                "BufferCatalog.add":
                    "quota charge mirrors the entry's budget reserve; "
                    "remove()/writeback discharges it",
            }),
    ],
    adopt_helpers=ADOPT_HELPERS,
    extra_blocking_calls={
        "upload_leaves": "host->device transfer (may compile + block "
                         "on the device)",
        "device_put": "host->device transfer",
        "with_io_retry": "file IO with bounded retry + backoff sleeps",
        "synchronous_spill": "spill pass: d2h copies / disk writes (or "
                             "writer-queue hand-off) per victim",
        "shutdown": "joins a worker/sampler thread on teardown",
    },
)
