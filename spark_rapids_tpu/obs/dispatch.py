"""Process-wide dispatch ledger (ISSUE 13 tentpole part 1): THE
chokepoint every engine jit entry point routes through.

The engine dispatches many small jitted programs per batch — exactly the
per-operator interpretation overhead whole-stage compilation (ROADMAP
open item 2) must collapse — yet until this plane existed nothing
recorded how many programs run, what tracing/compiling them costs, or
why a program re-traces. `instrument()` replaces bare `jax.jit(...)` at
every entry point (exec operators, exchange split, upload unpack,
transfer pack, the Pallas kernel families) and records, per compiled
program:

  * a stable program key — (owning exec/family label, arg-shape
    bucket, backend platform) — log2 buckets per dimension (the
    engine's capacities are powers of two), so one key covers every
    batch that compiles to the same program shape;
  * dispatch count, first-trace vs cache-hit discriminated;
  * trace-ns (the Python tracing of the body, measured inside the
    traced function — it only runs when jax actually traces) and
    compile-ns (wall-clock of the compiling dispatch, inclusive of
    trace + lowering + compilation);
  * donated vs retained argument bytes (from the tracer avals at trace
    time, against the site's `donate_argnums`).

Per-exec attribution mirrors the GatherTracker pattern: a site built
with `owner=<exec>` adds to that exec's `numDispatches` /
`compileTimeNs` canonical metrics on every call — dispatches are
counted at CALL time, so jit cache hits never zero the counts and
repeated collects replay identical per-stage dispatches/batch.
Module-level program sites (upload unpack, coalesce concat) attribute
through the thread-local `metric_scope` sink instead.

Each fresh trace emits a `program_compile` event (MODERATE), and the
recompile-storm detector emits `recompile_storm` (ESSENTIAL) when one
program key traces more than `spark.rapids.tpu.dispatch.storm.traces`
times inside `spark.rapids.tpu.dispatch.storm.windowMs` — the
shape-bucket-churn failure mode that silently destroys TPU throughput
(every batch a new exact shape, every dispatch a fresh XLA compile).

Host reads (ISSUE 40): `host_read(site, value)` is the ONE door a
device-to-host read takes — `jax.device_get` (or, with `fetch=False`,
a wait that fetches nothing) inside the span `sync.<site>`, counted per
site (`sync_sites()`: reads and ns; `counters()`: `syncs`, `sync_ns`).
A read outside every other span accrues to phase `host-sync`; inside
one (`agg.update`, `result.fetch`, ...) that span's phase keeps it, the
rule `note_dispatch` applies to `device-compute`.

Compiles ahead (mesh sessions): XLA compiles a program once per device
it runs on. When a dispatch traces a program whose array arguments are
all committed to one chip of the active mesh (downstream of a mesh
exchange: partition i on chip i; ingest leaves its arrays uncommitted),
the same program is compiled for the mesh's other chips on background
threads (`_compile_ahead`), and a later dispatch on one of them waits for
that compile instead of starting its own (`_await_ahead`). Partition 0's
programs then compile once on the dispatching thread while the other
chips' copies compile beside them, instead of four compiles in a row.

Cost discipline: `spark.rapids.tpu.dispatch.ledger.enabled` defaults
ON (the ledger is host-side bookkeeping, ~one dict update per program
dispatch — noise against jit dispatch overhead); explicitly false =
`active_ledger()` None and every instrumented site pays exactly one
pointer check before calling straight into its jitted function.
Results are byte-identical either way — the wrapper never touches the
computation.
"""

from __future__ import annotations

import functools
import re
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional, Tuple

__all__ = [
    "DispatchLedger", "InstrumentedJit", "instrument", "active_ledger",
    "configure", "reset_dispatch_ledger", "counters", "programs",
    "health_section", "metric_scope", "site_cache_counters",
    "reset_site_cache", "module_labels", "host_read", "sync_sites",
    "in_host_read", "ahead_counters",
]

#: canonical per-exec metric names (exec/base.py re-exports them into
#: CANONICAL_METRICS; literals here so obs/ never imports exec/)
NUM_DISPATCHES = "numDispatches"
COMPILE_TIME = "compileTimeNs"

_tls = threading.local()

#: jax's own rule for a module name (interpreters/mlir.sanitize_name)
_NOT_IN_MODULE_NAME = re.compile(r"[^\w.-]")


def _module_name(fn_name: str) -> str:
    """The XLA module name of `jax.jit(fn)`, as JAX spells it and a
    device trace shows it: `jit(<name>)` with every character XLA would
    alter replaced and trailing underscores dropped — `_concat_pair`
    reads `jit__concat_pair`, `<lambda>` reads `jit__lambda`."""
    return _NOT_IN_MODULE_NAME.sub("_", f"jit({fn_name})").rstrip("_")


#: backend platform, resolved once (it cannot change in-process)
_platform_cache: Optional[str] = None


def _platform() -> str:
    global _platform_cache
    if _platform_cache is None:
        import jax
        _platform_cache = jax.default_backend()
    return _platform_cache


def _shape_bucket(shape) -> Tuple[int, ...]:
    """log2-ceiling bucket per dimension (engine capacities are already
    powers of two, so this is usually exact)."""
    out = []
    for s in (shape if isinstance(shape, (tuple, list)) else (shape,)):
        s = max(int(s), 1)
        out.append(s.bit_length() - (1 if s & (s - 1) == 0 else 0))
    return tuple(out)


def _args_bucket(args, kwargs) -> Tuple:
    """Stable arg-shape bucket: log2-bucketed dims + dtype per array
    leaf, hashable statics verbatim. Long static pytrees (the upload
    unpack's nested column specs) fold into one hash so keys stay
    small."""
    from jax.tree_util import tree_leaves
    parts: List[Any] = []
    for leaf in tree_leaves((args, kwargs)):
        shp = getattr(leaf, "shape", None)
        if shp is not None:
            dt = getattr(leaf, "dtype", None)
            parts.append((_shape_bucket(shp),
                          dt.name if dt is not None else None))
        elif isinstance(leaf, (int, float, bool, str, bytes,
                               type(None))):
            parts.append(leaf)
        else:
            try:
                parts.append(hash(leaf) & 0xFFFFFFFF)
            except TypeError:
                parts.append(type(leaf).__name__)
    if len(parts) > 12:
        parts = parts[:8] + [hash(tuple(parts[8:])) & 0xFFFFFFFF]
    return tuple(parts)


class _Pending:
    """Per-call trace capture: the traced function body sets these when
    jax actually traces (on a cache hit it never runs)."""

    __slots__ = ("traced", "trace_ns", "donated", "retained", "depth",
                 "inlined")

    def __init__(self):
        self.traced = False
        #: labels of instrumented programs traced INLINE into this one
        #: (the murmur3 Pallas kernels inside a join's build program)
        self.inlined: set = set()
        self.trace_ns = 0
        self.donated = 0
        self.retained = 0
        #: nesting depth of instrumented bodies under this call — only
        #: the outermost frame records time/bytes (an inner instrumented
        #: program inlined into the outer trace is part of it)
        self.depth = 0


class ProgramStats:
    """Cumulative ledger record of one compiled program key."""

    # counters accumulate; donated/retained_bytes hold the LATEST
    # trace's aval sizes (a shape property, not a running total)
    __slots__ = ("label", "bucket", "platform", "module", "dispatches",
                 "traces", "cache_hits", "compile_ns", "trace_ns",
                 "donated_bytes", "retained_bytes", "trace_times",
                 "storms", "storm_open_until", "inlined")

    def __init__(self, label: str, bucket, platform: str, module: str):
        self.label = label
        self.bucket = bucket
        self.platform = platform
        #: the XLA module name this program carries in a device trace
        #: (InstrumentedJit.module) — the join key from device time to
        #: this label
        self.module = module
        self.dispatches = 0
        self.traces = 0
        self.cache_hits = 0
        self.compile_ns = 0
        self.trace_ns = 0
        self.donated_bytes = 0
        self.retained_bytes = 0
        #: recent trace timestamps (ns) for the storm window
        self.trace_times: deque = deque()
        self.storms = 0
        #: suppress repeat storm events until the window rolls past
        self.storm_open_until = 0
        #: instrumented programs this one's traces inlined — how a run
        #: shows WHICH kernel tier a program was built from
        self.inlined: set = set()

    def to_dict(self) -> Dict[str, Any]:
        return {"label": self.label, "bucket": list(self.bucket),
                "platform": self.platform, "module": self.module,
                "dispatches": self.dispatches, "traces": self.traces,
                "cache_hits": self.cache_hits,
                "compile_ns": self.compile_ns,
                "trace_ns": self.trace_ns,
                "donated_bytes": self.donated_bytes,
                "retained_bytes": self.retained_bytes,
                "storms": self.storms,
                "inlined": sorted(self.inlined)}


class DispatchLedger:
    """Process-wide program registry. All mutation happens under one
    leaf lock; events are buffered and emitted after it drops (the
    lock-blocking-call contract)."""

    def __init__(self, storm_traces: int = 8,
                 storm_window_ms: int = 10_000, timeout_ms: int = 0):
        self.storm_traces = max(1, int(storm_traces))
        self.storm_window_ms = max(1, int(storm_window_ms))
        #: dispatch hang bound (ISSUE 20): > 0 routes every dispatch
        #: through a watchdog-timed helper thread that also blocks
        #: until the program's outputs are ready — a wedged device
        #: program becomes a transient DispatchTimeoutError instead of
        #: hanging the process. 0 (the default) = the plain inline path.
        self.timeout_ms = max(0, int(timeout_ms))
        self._lock = threading.Lock()
        self._programs: Dict[Tuple, ProgramStats] = {}
        self._dispatches = 0
        self._traces = 0
        self._cache_hits = 0
        self._compile_ns = 0
        self._trace_ns = 0
        self._storms = 0
        #: host reads by site: {site: [reads, ns]}
        self._syncs: Dict[str, List[int]] = {}
        self._sync_reads = 0
        self._sync_ns = 0

    # -- the per-call accounting (InstrumentedJit.__call__ fast path) --
    def dispatch(self, site: "InstrumentedJit", args, kwargs):
        bucket = _args_bucket(args, kwargs)
        key = (site.label, bucket, _platform())
        # a bucket THIS site never traced before is a NEW program, not
        # churn: ledger keys aggregate per label family, so distinct
        # program sites (ExpandExec's per-projection jits, a fresh exec
        # instance per collect) legitimately share a key — only a
        # re-trace within ONE site's own jit cache is the shape-churn
        # signal the storm detector (and the event's `first` flag)
        # discriminate on
        site_first = bucket not in site._seen_buckets
        pend = _Pending()
        t0 = time.perf_counter_ns()
        try:
            if self.timeout_ms > 0:
                return _timed_dispatch(site, args, kwargs, pend,
                                       self.timeout_ms)
            _tls.pending = pend
            try:
                out = site._jit(*args, **kwargs)
            finally:
                _tls.pending = None
            if pend.traced:
                _compile_ahead(site, args, kwargs)
            return out
        finally:
            if pend.traced and site_first:
                site._seen_buckets.add(bucket)
            self._account(site, key, pend, site_first,
                          time.perf_counter_ns() - t0)

    def _account(self, site, key, pend: _Pending, site_first: bool,
                 wall_ns: int) -> None:
        out_events = []
        with self._lock:
            prog = self._programs.get(key)
            if prog is None:
                prog = self._programs[key] = ProgramStats(*key,
                                                          site.module)
            prog.dispatches += 1
            self._dispatches += 1
            if pend.traced:
                prog.traces += 1
                prog.compile_ns += wall_ns
                prog.trace_ns += pend.trace_ns
                prog.inlined |= pend.inlined
                # arg bytes are a per-program-shape PROPERTY, not a
                # counter: the latest trace's aval sizes (re-traces
                # inside one bucket differ only marginally)
                prog.donated_bytes = pend.donated
                prog.retained_bytes = pend.retained
                self._traces += 1
                self._compile_ns += wall_ns
                self._trace_ns += pend.trace_ns
                out_events.append((
                    "program_compile",
                    dict(label=prog.label, bucket=list(prog.bucket),
                         platform=prog.platform,
                         compile_ns=wall_ns, trace_ns=pend.trace_ns,
                         first=site_first, traces=prog.traces,
                         donated_bytes=pend.donated,
                         retained_bytes=pend.retained)))
                if not site_first:
                    storm = self._note_trace_locked(prog)
                    if storm is not None:
                        out_events.append(storm)
            else:
                prog.cache_hits += 1
                self._cache_hits += 1
        # metric attribution outside the lock: TpuMetric.add is a plain
        # int accumulate on the dispatching thread
        from . import phase as obs_phase
        obs_phase.note_dispatch(wall_ns, pend.traced)
        metrics = site._owner.metrics if site._owner is not None else None
        if metrics is not None:
            m = metrics.get(NUM_DISPATCHES)
            if m is not None:
                m.add(1)
                if pend.traced:
                    tm = metrics.get(COMPILE_TIME)
                    if tm is not None:
                        tm.add(wall_ns)
        else:
            sink = getattr(_tls, "sink", None)
            if sink is not None:
                sink[0].add(1)
                if pend.traced and sink[1] is not None:
                    sink[1].add(wall_ns)
        if out_events:
            from . import events as obs_events
            if obs_events.active_bus() is not None:
                for kind, fields in out_events:
                    obs_events.emit(kind, **fields)

    def note_sync(self, site: str, ns: int) -> None:
        with self._lock:
            rec = self._syncs.get(site)
            if rec is None:
                rec = self._syncs[site] = [0, 0]
            rec[0] += 1
            rec[1] += ns
            self._sync_reads += 1
            self._sync_ns += ns

    def _note_trace_locked(self, prog: ProgramStats):
        """Caller holds self._lock. Slide the storm window; past the
        conf'd trace count one `recompile_storm` fires and the key goes
        quiet until the window rolls past (a storm is one incident, not
        one event per churning batch)."""
        now = time.monotonic_ns()
        window_ns = self.storm_window_ms * 1_000_000
        prog.trace_times.append(now)
        while prog.trace_times and prog.trace_times[0] < now - window_ns:
            prog.trace_times.popleft()
        if len(prog.trace_times) < self.storm_traces \
                or now < prog.storm_open_until:
            return None
        prog.storms += 1
        self._storms += 1
        prog.storm_open_until = now + window_ns
        return ("recompile_storm",
                dict(label=prog.label, bucket=list(prog.bucket),
                     platform=prog.platform,
                     traces_in_window=len(prog.trace_times),
                     window_ms=self.storm_window_ms,
                     threshold=self.storm_traces,
                     compile_ns=prog.compile_ns))

    # -- read surfaces ------------------------------------------------
    def counters(self) -> Dict[str, int]:
        with self._lock:
            return {"programs": len(self._programs),
                    "dispatches": self._dispatches,
                    "traces": self._traces,
                    "cache_hits": self._cache_hits,
                    "compile_ns": self._compile_ns,
                    "trace_ns": self._trace_ns,
                    "storms": self._storms,
                    "syncs": self._sync_reads,
                    "sync_ns": self._sync_ns}

    def sync_sites(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {site: {"reads": r, "ns": ns}
                    for site, (r, ns) in self._syncs.items()}

    def programs(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [p.to_dict() for p in self._programs.values()]

    def module_labels(self) -> Dict[str, List[str]]:
        with self._lock:
            pairs = {(p.module, p.label) for p in self._programs.values()}
        out: Dict[str, List[str]] = {}
        for module, label in sorted(pairs):
            out.setdefault(module, []).append(label)
        return out


def _timed_dispatch(site: "InstrumentedJit", args, kwargs,
                    pend: _Pending, timeout_ms: int):
    """Hang-bounded dispatch (ISSUE 20): the program runs — and is
    blocked until ready, so a wedged device execution cannot hide
    behind async dispatch — on a watchdog-timed helper thread. The
    helper adopts the caller's pending frame (jax traces on the calling
    thread, which is the helper here); the breaker domain comes from
    the thread-local override so the ICI collective seam books its
    timeouts against `ici_exchange` (exec/speculation_shield)."""
    from ..exec import speculation_shield
    domain = speculation_shield.current_dispatch_domain()

    def run():
        _tls.pending = pend
        try:
            out = site._jit(*args, **kwargs)
            import jax
            # contract: ok host-sync-chokepoint — the hang bound waits for
            # the program it dispatched (a timing, not a read): counting it
            # as a sync would book every bounded dispatch twice
            jax.block_until_ready(out)
            return out
        finally:
            _tls.pending = None

    return speculation_shield.timed_call(run, timeout_ms, domain,
                                         site.label)


_ledger: Optional[DispatchLedger] = DispatchLedger()
_ledger_lock = threading.Lock()


def active_ledger() -> Optional[DispatchLedger]:
    """The process ledger, or None when disabled — instrumented sites
    check this pointer once per dispatch (the entire off-mode cost)."""
    return _ledger


def configure(conf=None) -> Optional[DispatchLedger]:
    """(Re)configure from a RapidsConf (None = the thread's active
    conf). Like the event bus the ledger is PROCESS-wide; unlike it the
    conf defaults ON, so a default session (re)creates the ledger and
    only an explicit dispatch.ledger.enabled=false tears it down.
    Storm thresholds are re-read here — never per dispatch."""
    global _ledger
    from ..config import (DISPATCH_LEDGER_ENABLED, DISPATCH_STORM_TRACES,
                          DISPATCH_STORM_WINDOW_MS, DISPATCH_TIMEOUT_MS,
                          active_conf)
    conf = conf if conf is not None else active_conf()
    enabled = conf.get(DISPATCH_LEDGER_ENABLED)
    traces = conf.get(DISPATCH_STORM_TRACES)
    window = conf.get(DISPATCH_STORM_WINDOW_MS)
    timeout = conf.get(DISPATCH_TIMEOUT_MS)
    with _ledger_lock:
        if not enabled:
            _ledger = None
            return None
        if _ledger is None:
            _ledger = DispatchLedger(traces, window, timeout)
        else:
            _ledger.storm_traces = max(1, int(traces))
            _ledger.storm_window_ms = max(1, int(window))
            _ledger.timeout_ms = max(0, int(timeout))
        return _ledger


def reset_dispatch_ledger() -> None:
    """Fresh default-enabled ledger (test isolation). The program-site
    cache resets with it: the two surfaces are one plane — a test that
    wants fresh-trace accounting (program_compile events, trace
    counters) must not inherit another test's already-traced sites."""
    global _ledger
    with _ledger_lock:
        _ledger = DispatchLedger()
    reset_site_cache()


def counters() -> Dict[str, int]:
    led = _ledger
    if led is None:
        return {"programs": 0, "dispatches": 0, "traces": 0,
                "cache_hits": 0, "compile_ns": 0, "trace_ns": 0,
                "storms": 0, "syncs": 0, "sync_ns": 0}
    return led.counters()


def sync_sites() -> Dict[str, Dict[str, int]]:
    """{site: {"reads", "ns"}} of every `host_read` since the ledger
    was (re)made; empty with the ledger off."""
    led = _ledger
    return led.sync_sites() if led is not None else {}


def programs() -> List[Dict[str, Any]]:
    led = _ledger
    return led.programs() if led is not None else []


def module_labels() -> Dict[str, List[str]]:
    """{XLA module name: sorted ledger labels} of every program the
    ledger has seen: how a device trace's `XLA Modules` events (named
    `<module>(<fingerprint>)`) join to the engine's own names. One
    module can serve several labels (two sites jitting functions of one
    name); device time outside this map bypassed `instrument`."""
    led = _ledger
    return led.module_labels() if led is not None else {}


def health_section() -> Dict[str, Any]:
    """`TpuSession.health()["dispatch"]`: enabled flag + the cumulative
    counters + the worst compile-cost programs."""
    led = _ledger
    out: Dict[str, Any] = {"enabled": led is not None}
    out.update(counters())
    if led is not None:
        progs = led.programs()
        progs.sort(key=lambda p: -p["compile_ns"])
        out["top_programs"] = progs[:5]
        sites = sorted(led.sync_sites().items(), key=lambda kv: -kv[1]["ns"])
        out["top_sync_sites"] = [{"site": site, **rec}
                                 for site, rec in sites[:5]]
    return out


def in_host_read() -> bool:
    """Is this thread inside `host_read`'s fetch? (tier-1 holds every
    device-to-host conversion of a collect() to this door.)"""
    return getattr(_tls, "host_read", False)


def _read(value, fetch: bool):
    import jax
    # contract: ok host-sync-chokepoint — the door itself
    return jax.device_get(value) if fetch else jax.block_until_ready(value)


def host_read(site: str, value, *, fetch: bool = True):
    """THE door for a device-to-host read: `jax.device_get(value)` (host
    ints and numpy arrays for the caller to take apart), or with
    `fetch=False` a wait that fetches nothing (`jax.block_until_ready`,
    returning `value`). With the ledger on it runs inside
    `op_span(f"sync.{site}")`, so a kept trace names the runtime's read
    events by the engine site that issued them, and counts the read and
    its ns under `site`. Its time accrues to phase `host-sync` only
    outside every other span. A value with no device array in it is no
    read: it passes through uncounted. With the ledger off: a plain
    `device_get` / `block_until_ready`, no count and no span."""
    import jax
    led = _ledger
    if led is None or not any(isinstance(x, jax.Array)
                              for x in jax.tree_util.tree_leaves(value)):
        return _read(value, fetch)
    from . import phase as obs_phase
    from .span import op_span
    phase = None if obs_phase.in_span() else "host-sync"
    prev = getattr(_tls, "host_read", False)
    t0 = time.perf_counter_ns()
    try:
        with op_span(f"sync.{site}", phase=phase):
            _tls.host_read = True
            return _read(value, fetch)
    finally:
        _tls.host_read = prev
        led.note_sync(site, time.perf_counter_ns() - t0)


@contextmanager
def metric_scope(num_metric, time_metric=None):
    """Attribute module-level program dispatches inside the with-block
    to an exec's (numDispatches, compileTimeNs) metric pair — the
    upload/coalesce sites have no owning exec instance at definition
    time (the upload.metric_sink shape). Owner-bound sites ignore the
    sink."""
    prev = getattr(_tls, "sink", None)
    _tls.sink = (num_metric, time_metric)
    try:
        yield
    finally:
        _tls.sink = prev


# ---------------------------------------------------------------------------
# plan-fingerprint program-site cache (ISSUE 14): every DataFrame.
# collect() rebuilds its exec tree, so per-instance jit wrappers used to
# recompile the WHOLE plan per collect (the PR 13 finding: ~1.9s/collect
# on the scaled q1 CPU lane). Sites built with a `cache_key` — the
# owning exec's canonical plan-subtree fingerprint — are process-cached
# per (label, cache_key): a semantically identical exec instance reuses
# the SAME InstrumentedJit, so its dispatches ride the existing jax jit
# cache (the ledger records them as cache hits, zero fresh traces). The
# fingerprint must capture everything the trace depends on (expression
# semantics, schemas, trace-affecting conf values, platform) — that
# contract lives in exec/stage_compiler.plan_fingerprint.
# ---------------------------------------------------------------------------

_site_cache_lock = threading.Lock()
#: (label, cache_key) -> InstrumentedJit, LRU-ordered (dict order)
_site_cache: "Dict[Tuple[str, Any], InstrumentedJit]" = {}
_site_cache_hits = 0
_site_cache_misses = 0


def _site_cache_max() -> int:
    try:
        from ..config import STAGE_PROGRAM_CACHE_ENTRIES, active_conf
        return max(0, int(active_conf().get(STAGE_PROGRAM_CACHE_ENTRIES)))
    except Exception:  # noqa: BLE001 — conf unavailable early
        return 512


def _cached_site(fn, label: str, owner, cache_key, jit_kwargs):
    global _site_cache_hits, _site_cache_misses
    limit = _site_cache_max()
    if limit <= 0:
        return InstrumentedJit(fn, label, owner=owner, **jit_kwargs)
    key = (label, cache_key)
    with _site_cache_lock:
        site = _site_cache.pop(key, None)
        if site is not None:
            _site_cache[key] = site  # re-append: most recently used
            _site_cache_hits += 1
    if site is not None:
        site.rebind(owner)
        return site
    site = InstrumentedJit(fn, label, owner=owner, **jit_kwargs)
    with _site_cache_lock:
        _site_cache_misses += 1
        _site_cache[key] = site
        while len(_site_cache) > limit:
            _site_cache.pop(next(iter(_site_cache)))
    return site


def site_cache_counters() -> Dict[str, int]:
    """bench `{"stage"}` block + tests: program-site cache activity."""
    with _site_cache_lock:
        return {"sites": len(_site_cache), "hits": _site_cache_hits,
                "misses": _site_cache_misses}


def reset_site_cache() -> None:
    """Drop every cached program site (test isolation; already-built
    exec trees keep the sites they hold — only NEW lookups re-trace)."""
    global _site_cache_hits, _site_cache_misses
    with _site_cache_lock:
        _site_cache.clear()
        _site_cache_hits = 0
        _site_cache_misses = 0


_ahead_lock = threading.Lock()
_ahead_pool = None
_ahead_counts = {"started": 0, "awaited": 0}


def _ahead_executor():
    global _ahead_pool
    with _ahead_lock:
        if _ahead_pool is None:
            from concurrent.futures import ThreadPoolExecutor
            _ahead_pool = ThreadPoolExecutor(
                max_workers=8, thread_name_prefix="compile-ahead")
        return _ahead_pool


def ahead_counters() -> Dict[str, int]:
    """Compiles started ahead for other chips of a mesh, and dispatches
    that waited for one."""
    return dict(_ahead_counts)


def _one_chip_signature(site: "InstrumentedJit", args, kwargs):
    """(signature, device) of a dispatch whose committed array arguments
    all sit on one device (uncommitted ones follow them there), else None.
    The signature is what the jit cache keys a trace on: static arguments
    whole, every other leaf by shape, dtype and weak type (a Python scalar
    by its type)."""
    import jax
    import numpy as np
    dev = None

    def leaf(x):
        nonlocal dev
        if isinstance(x, jax.Array):
            if not x.committed:
                return ("u", x.shape, x.dtype, x.weak_type)
            # the sharding, not `devices()`: a donated argument is deleted
            # by the time a dispatch that traced looks at it
            devs = x.sharding.device_set
            if len(devs) != 1:
                raise LookupError
            (d,) = devs
            if dev is None:
                dev = d
            elif d != dev:
                raise LookupError
            return ("a", x.shape, x.dtype, x.weak_type)
        if isinstance(x, np.ndarray):
            return ("n", x.shape, x.dtype)
        return ("p", type(x))

    def dynamic(a):
        leaves, tree = jax.tree_util.tree_flatten(a)
        return tree, tuple(leaf(x) for x in leaves)

    try:
        sig = (tuple(("s", a) if i in site._static_nums else dynamic(a)
                     for i, a in enumerate(args)),
               tuple(sorted((k, ("s", v) if k in site._static_names
                             else dynamic(v)) for k, v in kwargs.items())))
        hash(sig)
    except (LookupError, TypeError):
        return None
    return None if dev is None else (sig, dev)


def _compile_ahead(site: "InstrumentedJit", args, kwargs) -> None:
    """After `site` traced a program for one chip of the active mesh:
    compile it for the mesh's other chips on background threads. Never
    raises: a compile that fails here is made again by its dispatch."""
    try:
        from ..parallel.mesh import active_mesh
        mesh = active_mesh()
        if mesh is None or mesh.size < 2:
            return
        found = _one_chip_signature(site, args, kwargs)
        if found is None:
            return
        sig, here = found
        devices = list(mesh.devices.flat)
        if here not in devices:
            return
        import jax
        from jax.sharding import SingleDeviceSharding
        ahead = site._ahead if site._ahead is not None else {}
        for d in devices:
            if d == here or (sig, d.id) in ahead:
                continue

            def spec(x, d=d):
                if not isinstance(x, jax.Array):
                    return x
                return jax.ShapeDtypeStruct(
                    x.shape, x.dtype, weak_type=x.weak_type,
                    sharding=SingleDeviceSharding(d) if x.committed else None)
            a = tuple(x if i in site._static_nums
                      else jax.tree_util.tree_map(spec, x)
                      for i, x in enumerate(args))
            k = {n: v if n in site._static_names
                 else jax.tree_util.tree_map(spec, v)
                 for n, v in kwargs.items()}
            # contract: ok thread-adopt — the thread lowers and compiles a
            # program the dispatching thread has traced already (the jit
            # cache holds its trace): no engine code runs there
            ahead[(sig, d.id)] = _ahead_executor().submit(
                _compile_for, site, a, k)
            _ahead_counts["started"] += 1
        site._ahead = ahead or None
    except Exception:  # noqa: BLE001 — the dispatch compiles for itself
        return


def _compile_for(site: "InstrumentedJit", args, kwargs) -> None:
    try:
        site._jit.lower(*args, **kwargs).compile()
    except Exception:  # noqa: BLE001 — the dispatch compiles for itself
        pass


def _await_ahead(site: "InstrumentedJit", args, kwargs) -> None:
    """Before a dispatch of `site`: wait for the compile started ahead for
    this signature on this chip, if one is running, rather than start a
    second. Finished compiles are dropped, so a site whose compiles are
    all done pays nothing more."""
    ahead = site._ahead
    for key in [k for k, f in list(ahead.items()) if f.done()]:
        ahead.pop(key, None)
    if not ahead:
        site._ahead = None
        return
    found = _one_chip_signature(site, args, kwargs)
    if found is None:
        return
    fut = ahead.pop((found[0], found[1].id), None)
    if fut is not None:
        _ahead_counts["awaited"] += 1
        # contract: ok bounded-wait — the wait is the XLA compile this
        # dispatch would otherwise run itself, on this thread
        fut.result()


def _no_trace_in_progress() -> bool:
    """True when no trace is in progress on this thread: a call made
    while another program is being traced (jit, eval_shape, a pallas
    kernel body) is inlined into that trace, not dispatched."""
    global _no_trace_in_progress
    import jax.core
    # Bound method resolved once — the per-dispatch path must not pay
    # import machinery (jax is imported before any site is built).
    _no_trace_in_progress = jax.core.trace_ctx.is_top_level
    return _no_trace_in_progress()


class InstrumentedJit:
    """`jax.jit` plus ledger accounting — the chokepoint wrapper.

    Call-time behavior: with the ledger off, one pointer check then the
    bare jitted call. Nested calls — an instrumented program traced
    inline into another program's trace (the murmur3 kernels inside an
    exec's update kernel), or an abstract evaluation like
    `jax.eval_shape` — pass straight through: they are not device
    dispatches, and counting them would double-book the outer trace."""

    # __weakref__: jax.eval_shape weakly caches the callable it is
    # given — an un-weakref-able wrapper would reject abstract eval
    __slots__ = ("label", "module", "_owner", "_jit", "_donate",
                 "_seen_buckets", "_static_nums", "_static_names", "_ahead",
                 "__weakref__")

    def __init__(self, fn, label: str, owner=None, **jit_kwargs):
        import jax
        self.label = label
        #: owning exec instance (per-exec metric attribution + the
        #: QueryProfile dispatch summary walk); None for module sites
        self._owner = owner
        donate = jit_kwargs.get("donate_argnums", ()) or ()
        self._donate = tuple(donate) if isinstance(
            donate, (tuple, list)) else (donate,)
        #: arg-shape buckets THIS site has traced: discriminates a new
        #: program (first trace of a bucket here) from shape churn (a
        #: re-trace the site's own jit cache rejected)
        self._seen_buckets = set()
        #: what the jit cache keys whole, and the compiles started for
        #: other chips of a mesh ((signature, device id) -> future), None
        #: while none is pending (`_compile_ahead`)
        static = jit_kwargs.get("static_argnums", ()) or ()
        self._static_nums = frozenset(
            (static,) if isinstance(static, int) else static)
        names = jit_kwargs.get("static_argnames", ()) or ()
        self._static_names = frozenset(
            (names,) if isinstance(names, str) else names)
        self._ahead = None

        @functools.wraps(fn)
        def _traced(*a, **k):
            pend = getattr(_tls, "pending", None)
            if pend is None:
                return fn(*a, **k)
            pend.traced = True
            pend.depth += 1
            t0 = time.perf_counter_ns()
            try:
                out = fn(*a, **k)
            finally:
                pend.depth -= 1
            if pend.depth == 0:
                pend.trace_ns += time.perf_counter_ns() - t0
                pend.donated, pend.retained = self._arg_bytes(a, k)
            return out

        #: the XLA module name of this site's programs in a device
        #: trace (functools.wraps gave `_traced` the name of `fn`)
        self.module = _module_name(_traced.__name__)
        self._jit = jax.jit(_traced, **jit_kwargs)
        if owner is not None:
            # per-exec site registry: QueryProfile._node records these
            # labels so dispatch_summary() joins ledger programs to
            # plan stages by EXACT label (subclass-safe)
            owner.__dict__.setdefault("_dispatch_sites", []).append(self)

    def rebind(self, owner) -> None:
        """Re-point metric attribution at a new owning exec — the
        program-site cache hands one compiled site to every
        semantically identical exec instance (one per collect), and
        each execution's numDispatches/compileTimeNs must land on the
        CURRENTLY executing exec, not the instance that first traced
        the program. Concurrent identical plans (bench --concurrency)
        share the site: their per-exec metric split follows the latest
        rebind — the process ledger stays exact either way."""
        if owner is None or owner is self._owner:
            return
        self._owner = owner
        sites = owner.__dict__.setdefault("_dispatch_sites", [])
        if self not in sites:
            sites.append(self)

    def _arg_bytes(self, args, kwargs) -> Tuple[int, int]:
        """Donated vs retained bytes from the trace-time avals (shapes
        are concrete there; no device data is touched)."""
        from jax.tree_util import tree_leaves
        donated = retained = 0
        for i, a in enumerate(args):
            total = 0
            for leaf in tree_leaves(a):
                shp = getattr(leaf, "shape", None)
                dt = getattr(leaf, "dtype", None)
                if shp is None or dt is None:
                    continue
                n = 1
                for d in shp:
                    n *= int(d)
                total += n * dt.itemsize
            if i in self._donate:
                donated += total
            else:
                retained += total
        for a in kwargs.values():
            for leaf in tree_leaves(a):
                shp = getattr(leaf, "shape", None)
                dt = getattr(leaf, "dtype", None)
                if shp is not None and dt is not None:
                    n = 1
                    for d in shp:
                        n *= int(d)
                    retained += n * dt.itemsize
        return donated, retained

    def __call__(self, *args, **kwargs):
        led = _ledger
        if led is None:
            return self._jit(*args, **kwargs)
        pend = getattr(_tls, "pending", None)
        if pend is not None:
            # nested under another instrumented dispatch's trace
            pend.inlined.add(self.label)
            return self._jit(*args, **kwargs)
        if not _no_trace_in_progress():
            # traced inline into an un-instrumented outer program, or
            # abstractly evaluated (eval_shape) — not a device dispatch
            return self._jit(*args, **kwargs)
        if self._ahead is not None:
            _await_ahead(self, args, kwargs)
        return led.dispatch(self, args, kwargs)


def instrument(fn=None, *, label: str, owner=None, cache_key=None,
               **jit_kwargs):
    """THE jit entry point: `instrument(fn, label=...)` replaces
    `jax.jit(fn)` everywhere the engine compiles a program (the
    dispatch-ledger contract rule holds every `jax.jit`/`pallas_call`
    site in the package to this chokepoint or a justified suppression).
    Usable as a decorator factory: `@instrument(label=...)`.

    `cache_key` (ISSUE 14): a hashable canonical plan-subtree
    fingerprint. When given, the site is served from the process-wide
    program cache — a semantically identical exec built by a later
    collect() reuses the SAME compiled programs (ledger cache hits,
    zero fresh traces) with metric attribution rebound to the new
    owner. The caller owns the soundness contract: equal fingerprints
    MUST imply byte-identical traces."""
    if fn is None:
        if cache_key is not None:
            return lambda f: _cached_site(f, label, owner, cache_key,
                                          jit_kwargs)
        return lambda f: InstrumentedJit(f, label, owner=owner,
                                         **jit_kwargs)
    if cache_key is not None:
        return _cached_site(fn, label, owner, cache_key, jit_kwargs)
    return InstrumentedJit(fn, label, owner=owner, **jit_kwargs)
