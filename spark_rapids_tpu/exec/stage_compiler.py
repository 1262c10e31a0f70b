"""Whole-stage compilation (ISSUE 14 tentpole) — one jitted program per
pipeline stage, with a plan-fingerprint program cache.

The engine dispatched one jitted program per operator per batch with
Python at every batch boundary — PR 13's dispatch ledger measured it:
q3 ran HashJoinExec at 3.0 + AggregateExec at 2.0 dispatches per output
batch, and every `DataFrame.collect()` rebuilt its exec tree and
recompiled the whole plan (~1.9s/collect on the scaled q1 CPU lane).
Flare (PAPERS.md) shows the per-operator interpretation overhead
collapses when stages compile to one native unit; XLA is our codegen.

Two halves, both gated by `spark.rapids.tpu.stage.fusion.enabled`:

1. **Stage planner** — `compile_stages(root)` walks the converted
   `TpuExec` tree top-down and greedily groups maximal chains of
   whitelisted operators into `CompiledStageExec` nodes:

   * ``map``: a Filter/Project/Expand chain (>= 2 ops) feeding a
     non-fusable consumer — per input batch ONE program evaluates every
     projection, ANDs every filter into one row mask and compacts ONCE
     (filters become masks, not gathers — the FilterExec.fused_step
     contract, now generalized past aggregates).
   * ``agg``: an AggregateExec (complete/partial, masked-bucket
     eligible) that already absorbed a filter/project chain — the
     stage drives the agg's one-program-per-batch streaming step with
     buffer DONATION on the carried state (donate_argnums: the fold's
     in-place HBM reuse) and the stage-boundary governance harness.
   * ``join_agg``: the flagship — filter -> inner-join probe ->
     project -> partial/complete aggregate as ONE program per stream
     batch: the build table is computed INSIDE the first fused
     dispatch and carried as program state, candidate sizing rides the
     join's speculative size-cache contract (cold execution: one
     standalone sizing program; warm: zero host syncs), and the
     probe's output never materializes between operators.

   Non-whitelisted operators (exchanges, sorts, windows, UDFs,
   generators, limits) break the stage and keep their per-op execs.

2. **Program cache** — exec program sites built through
   `TpuExec._site` carry a canonical plan-subtree fingerprint
   (`fingerprint_node`: node semantics x output schema x child
   fingerprints x trace-affecting conf digest x backend platform) as
   their `cache_key`; `obs.dispatch` then serves one process-wide
   `InstrumentedJit` per (label, fingerprint), so a reused plan's
   second collect() is ALL jit cache hits — zero fresh traces,
   measured by the PR 13 ledger. The same fingerprint is the seed for
   ROADMAP item 5's sub-plan result cache.

Governance at stage granularity (the enabling refactor ROADMAP 2 calls
out): compute bodies handed to the dispatch chokepoint are PURE traced
dataflow — the `stage-governance` analyzer rule enforces it — and the
per-batch hooks live in the stage-boundary harness
(`TpuExec.batch_harness` + the lifecycle tick in `TpuExec._drive`):
cooperative cancellation per batch, a keyed `device.dispatch` chaos
fault point per fused dispatch, gather/dispatch metric attribution
around the one program, and `device_dispatch` breaker engagement — an
OPEN breaker demotes the stage back to per-operator execution for that
run (PR 5 degradation, now at stage granularity).

CPU results are identical with fusion on or off (tier-1 asserted; the
spec-tier fold replays the exact same program composition, the exact
tier reuses the agg's own merge machinery). Donation is a no-op on CPU
backends; TPU rounds validate the donated-state fold — and must watch
the OOM-retry lane, where a failed donated dispatch's state buffer is
the documented open risk.
"""

from __future__ import annotations

import threading
import time
import warnings
from typing import Dict, List, Optional, Tuple

from ..columnar.batch import ColumnarBatch
from ..obs.dispatch import host_read
from ..types import Schema
from .base import (AGG_TIME, DISPATCH_METRICS, GATHER_METRICS,
                   GATHER_TIME, NUM_DISPATCHES, NUM_GATHERS, TpuExec)

__all__ = [
    "CompiledStageExec", "compile_stages", "fingerprint_node",
    "trace_conf_digest", "schema_sig", "counters",
    "reset_stage_counters", "FUSABLE_OPS",
]

#: the fusion whitelist (docs/perf.md's fusion-whitelist table is
#: lint-checked against these keys): operator class -> how it fuses
#: into a stage program. Everything else breaks the stage.
FUSABLE_OPS: Dict[str, str] = {
    "FilterExec": "row mask ANDed into the stage program (one "
                  "compaction per stage, not one gather per filter)",
    "ProjectExec": "expression evaluation inlined via the engine's own "
                   "columnar_eval compiler",
    "ExpandExec": "all projections emitted from ONE program per input "
                  "batch (grouping sets)",
    "HashJoinExec": "inner-join probe fused into the consuming "
                    "partial aggregate's per-stream-batch program; the "
                    "build table is computed inside the first fused "
                    "dispatch and carried as program state",
    "AggregateExec": "masked-bucket update + fold into donated carried "
                     "state (complete/partial modes), evaluate "
                     "in-program",
}


# ---------------------------------------------------------------------------
# process counters (bench `{"stage"}` block, the chaos-delta pattern)
# ---------------------------------------------------------------------------

_COUNTER_LOCK = threading.Lock()
_COUNTERS = {"stages_fused": 0, "ops_fused": 0, "executions": 0,
             "fallbacks": 0, "dispatches": 0, "batches": 0,
             # the join's size cache: a miss is a shape it never sized,
             # a refresh an entry that expired (SPEC_REFRESH); each pays
             # one sizing dispatch and its host sync
             "size_cache_misses": 0, "size_cache_refreshes": 0}


def _note(**deltas) -> None:
    with _COUNTER_LOCK:
        for k, v in deltas.items():
            _COUNTERS[k] += v


def counters() -> Dict[str, int]:
    """Stage-fusion process counters + the program-site cache's
    activity (obs/dispatch.py) — ONE surface for the bench block."""
    from ..obs import dispatch as obs_dispatch
    with _COUNTER_LOCK:
        out = dict(_COUNTERS)
    sc = obs_dispatch.site_cache_counters()
    out["cache_sites"] = sc["sites"]
    out["cache_hits"] = sc["hits"]
    return out


def reset_stage_counters() -> None:
    """Counters, and what the process remembers of plan shapes by
    fingerprint: the joins' sizes and the aggregates that tripped."""
    from .speculation import reset_trip_memory
    with _COUNTER_LOCK:
        for k in _COUNTERS:
            _COUNTERS[k] = 0
        _SIZE_CACHES.clear()
    reset_trip_memory()


#: fingerprint -> {(stream_cap, build_cap): (cand_cap, s_caps,
#: b_caps)} — the join sizing caches shared across rebuilt identical
#: plans; LRU-capped so distinct plans cannot grow it unboundedly
_SIZE_CACHES: Dict[str, Dict] = {}
_SIZE_CACHE_MAX = 128


def _shared_size_cache(fp: Optional[str]) -> Dict:
    if fp is None:
        return {}
    with _COUNTER_LOCK:
        cache = _SIZE_CACHES.pop(fp, None)
        if cache is None:
            cache = {}
        _SIZE_CACHES[fp] = cache  # re-append: most recently used
        while len(_SIZE_CACHES) > _SIZE_CACHE_MAX:
            _SIZE_CACHES.pop(next(iter(_SIZE_CACHES)))
        return cache


# ---------------------------------------------------------------------------
# plan fingerprints (the program-cache key contract)
# ---------------------------------------------------------------------------

def schema_sig(schema: Schema) -> Tuple:
    """Hashable signature of a schema — name, full type (decimal
    precision/scale, nested element types via simple_name), nullability."""
    return tuple((f.name, f.data_type.simple_name(), bool(f.nullable))
                 for f in schema.fields)


#: conf entries whose values a trace can depend on (consulted at trace
#: time inside exec kernels, or captured into exec closures at plan
#: build). Two plans tracing under different values of ANY of these
#: must never share compiled programs — they are part of the digest.
def _digest_entries():
    from .. import config as C
    return (C.FUSION_ENABLED, C.STAGE_FUSION_ENABLED, C.AGG_SPECULATIVE,
            C.AGG_GROUP_SLOTS, C.AGG_ROUNDS, C.PALLAS_ENABLED,
            C.IMPROVED_FLOAT_OPS, C.STABLE_SORT, C.SORT_OOC_ENABLED,
            C.DECIMAL_ENABLED, C.SHUFFLE_DEVICE_PARTITION,
            C.UPLOAD_PACKED, C.BATCH_SIZE_BYTES, C.SCAN_ENCODED)


def trace_conf_digest(conf=None) -> Optional[Tuple]:
    """The trace-affecting slice of the active conf as a hashable
    tuple, plus the backend platform — folded into every plan
    fingerprint. None when the stage.fusion gate is off (fingerprints
    disabled => per-instance program sites, the pre-ISSUE-14 shape)."""
    from ..config import STAGE_FUSION_ENABLED, active_conf
    conf = conf if conf is not None else active_conf()
    if not conf.get(STAGE_FUSION_ENABLED):
        return None
    import jax
    vals = tuple(str(conf.get(e)) for e in _digest_entries())
    return vals + (jax.default_backend(),)


def fingerprint_node(node: TpuExec, extras) -> Optional[str]:
    """Canonical fingerprint of `node`'s subtree: class name + the
    node's semantic extras + output-schema signature + every child's
    fingerprint + the conf digest. Equal fingerprints MUST imply
    byte-identical traces — that is the program cache's soundness
    contract (a trace-time consult that reads mutable state outside
    the digest — a breaker opening — bakes per compiled shape,
    exactly as it already did under bench-style plan reuse)."""
    digest = trace_conf_digest()
    if digest is None:
        return None
    child_fps = []
    for c in node.children:
        fp = c.plan_fingerprint()
        if fp is None:
            return None
        child_fps.append(fp)
    import hashlib
    payload = repr((type(node).__name__, extras,
                    schema_sig(node.output_schema),
                    tuple(child_fps), digest))
    return hashlib.sha1(payload.encode()).hexdigest()


_donation_filter_installed = False


def _filter_cpu_donation_warning() -> None:
    """CPU backends can NEVER honor buffer donation, so jax's 'Some
    donated buffers were not usable' warning is pure noise there — the
    fused fold's donation is the intentional TPU optimization. Installed
    lazily, once, and ONLY on cpu-family backends: on real TPU the
    warning is a genuine signal (a donated buffer that unexpectedly
    could not be aliased) and must stay audible."""
    global _donation_filter_installed
    if _donation_filter_installed:
        return
    _donation_filter_installed = True
    import jax
    if jax.default_backend() == "cpu":
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")


def _nbytes_of(tree) -> int:
    """Total bytes of a pytree's array leaves, from shapes only —
    never a device sync (the stage_fused event's donated-bytes field)."""
    import jax
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shp = getattr(leaf, "shape", None)
        dt = getattr(leaf, "dtype", None)
        if shp is None or dt is None:
            continue
        n = 1
        for d in shp:
            n *= int(d)
        total += n * dt.itemsize
    return total


# ---------------------------------------------------------------------------
# the fused stage operator
# ---------------------------------------------------------------------------

class CompiledStageExec(TpuExec):
    """One compiled pipeline stage: a whitelisted operator chain whose
    per-batch body is ONE dispatch-ledger-routed jitted program.

    `children` are the stage's dataflow SOURCES (the first
    non-whitelisted execs below the chain); the absorbed operator
    nodes are kept (``_absorbed``, outermost first) both for
    description/metadata (output schema, grouping contract) and as the
    per-operator FALLBACK path: a demotion — open `device_dispatch`
    breaker, ineligible flavor, empty input corner — re-drives the
    original chain root over the same sources, so degradation (PR 5)
    works at stage granularity and results never depend on the stage
    engaging.

    Accounting: the stage owns its program sites (numDispatches /
    compileTimeNs land here; `QueryProfile.dispatch_summary()` shows
    the fused chain as one row), runs the gather engine's structural
    accounting around each fused dispatch, and emits one `stage_fused`
    event per fused execution. The exact-tier multi-batch merge
    delegates to the absorbed aggregate's own merge machinery — those
    merge dispatches attribute to the (hidden) aggregate node, so the
    stage row stays the honest per-stream-batch figure."""

    def __init__(self, kind: str, absorbed: List[TpuExec],
                 sources: List[TpuExec], join=None, agg=None):
        self._kind = kind
        self._absorbed = list(absorbed)
        self._terminal = absorbed[0]
        self._join = join
        self._agg = agg
        super().__init__(*sources)
        _filter_cpu_donation_warning()
        from ..ops.gather import GatherTracker
        self._gather_track = GatherTracker(self.metrics[NUM_GATHERS],
                                           self.metrics[GATHER_TIME])
        #: (stream_cap, build_cap) -> [cand_cap, s_caps, b_caps, uses]:
        #: the join's speculative sizing contract. Keyed process-wide
        #: by plan fingerprint so a rebuilt identical plan (every
        #: collect) stays WARM — stale caps are safe by the same
        #: overflow-flag contract that makes them safe within one
        #: instance; no fingerprint = instance-local cache. Only the
        #: join_agg kind sizes probes — map/agg stages must not churn
        #: the shared LRU with dead entries.
        self._size_cache = _shared_size_cache(
            self.plan_fingerprint() if kind == "join_agg" else None)
        if kind == "map":
            self._steps = [op.stage_step()
                           for op in reversed(self._absorbed)]
            self._jit_map = self._site(self._map_body,
                                       label="CompiledStageExec.map")
        elif kind == "agg":
            self._jit_step = self._site(
                self._agg_spec_body, label="CompiledStageExec.step",
                donate_argnums=(1, 2))
            self._jit_step_exact = self._site(
                self._agg_exact_body,
                label="CompiledStageExec.step_exact")
        else:  # join_agg
            self._jit_sizing = self._site(
                self._sizing_body, label="CompiledStageExec.sizing")
            self._jit_step = self._site(
                self._ja_spec_body,
                label="CompiledStageExec.probe_step",
                static_argnums=(5, 6, 7), donate_argnums=(3, 4))
            self._jit_step_exact = self._site(
                self._ja_exact_body,
                label="CompiledStageExec.probe_step_exact",
                static_argnums=(3, 4, 5))
        _note(stages_fused=1, ops_fused=len(self._absorbed))

    # -- TpuExec surface ---------------------------------------------------
    @property
    def output_schema(self) -> Schema:
        return self._terminal.output_schema

    def additional_metrics(self):
        # computeAggTime keeps the surface the absorbed AggregateExec
        # used to report (inclusive of the source drive, the agg's own
        # convention) so metric-keyed tooling survives fusion; map
        # stages register it too (zero) — the declaration must stay
        # self-independent (docs-lint contract)
        return (AGG_TIME,) + GATHER_METRICS + DISPATCH_METRICS

    @property
    def output_grouped_by(self):
        # the absorbed chain's links are intact, so the terminal op's
        # contract (e.g. the inner join's key-grouped emission feeding
        # a downstream group-by) reads straight through
        return self._terminal.output_grouped_by

    @property
    def consumes_encoded(self) -> bool:
        # a map stage can run on dictionary-encoded inputs (ISSUE 18)
        # exactly when every absorbed operator could individually —
        # the fused body runs the same columnar_eval/compaction those
        # operators would. agg/join_agg stages fold values into
        # aggregate state, so they need materialized inputs. No
        # encoded-ness entry is folded into the plan fingerprint:
        # DictionaryColumn and its decoded form are DIFFERENT pytree
        # structures, so jit keys the compiled program on the actual
        # input encoding already — the SCAN_ENCODED conf digest entry
        # only separates plans whose EXECS were built under different
        # gate values.
        if self._kind != "map":
            return False
        return all(op.consumes_encoded for op in self._absorbed)

    def _fingerprint_extras(self):
        term_fp = self._terminal.plan_fingerprint()
        if term_fp is None:
            return None
        return (self._kind, term_fp)

    def node_description(self) -> str:
        ops = "+".join(type(op).__name__ for op in self._absorbed)
        return f"CompiledStageExec[{self._kind}: {ops}]"

    @property
    def _stage_label(self) -> str:
        return f"{self._kind}:" + \
            "+".join(type(op).__name__ for op in self._absorbed)

    # -- engagement / fallback --------------------------------------------
    def _stage_engaged(self) -> bool:
        """Per-execution gate: an open `device_dispatch` breaker (PR 5)
        demotes this stage to per-operator execution until its
        cooldown/probe closes it; a healthy consult notes the
        engagement so classified-transient failures of this attempt
        count against the domain."""
        from . import lifecycle
        if not lifecycle.breaker_allows("device_dispatch"):
            return False
        lifecycle.engage_domain("device_dispatch")
        return True

    def _drive_fallback(self):
        _note(fallbacks=1)
        yield from self._terminal.execute()

    def internal_execute(self):
        if not self._stage_engaged():
            yield from self._drive_fallback()
            return
        disp = self.metrics[NUM_DISPATCHES]
        d0 = disp.value
        t0 = time.perf_counter_ns()
        #: [input batches, donated bytes] updated LIVE by the drive
        #: below — a consumer abandoning the stream early (a limit)
        #: must still see the true counts in the stage_fused event
        live = self._live_stats = [0, 0]
        if self._kind == "map":
            gen = self._execute_map()
        elif self._kind == "agg":
            gen = self._execute_agg()
        else:
            gen = self._execute_join_agg()
        fell_back = False
        try:
            for item in gen:
                if item is _FALLBACK:
                    # empty-input corner: the per-op chain owns the
                    # empty-aggregate semantics — re-drive it (sources
                    # are exhausted-empty, so this is cheap and exact)
                    fell_back = True
                    yield from self._drive_fallback()
                    return
                yield item
        finally:
            n_in, donated = live
            if not fell_back:
                # one gather_stats per execution (the wired-exec
                # convention): the fused probe/compaction gathers
                # reconcile with the stage's numGathers metric
                self._gather_track.emit_event(type(self).__name__,
                                              self._op_id)
                wall = time.perf_counter_ns() - t0
                if self._kind != "map":
                    self.metrics[AGG_TIME].add(wall)
                d = disp.value - d0
                _note(executions=1, batches=n_in, dispatches=d)
                from ..obs import events as obs_events
                obs_events.emit(
                    "stage_fused", stage=self._kind,
                    label=self._stage_label, ops=len(self._absorbed),
                    batches=n_in, dispatches=d, donated_bytes=donated,
                    wall_ns=time.perf_counter_ns() - t0)

    # -- map stage ---------------------------------------------------------
    def _map_body(self, batch: ColumnarBatch):
        """PURE traced body (stage-governance rule): every projection
        evaluated, every filter ANDed into ONE mask, ONE compaction at
        the end of each output path. Expand fans out: all projections
        of one input batch emit from this single program."""
        from ..ops.basic import compact_columns
        from .basic import eval_projection
        outs: List[ColumnarBatch] = []

        def run(cur, mask, steps):
            for i, step in enumerate(steps):
                if step[0] == "filter":
                    pred = step[1].columnar_eval(cur)
                    m = pred.data & pred.validity
                    mask = m if mask is None else (mask & m)
                elif step[0] == "project":
                    cur = eval_projection(step[1], cur, step[2])
                else:  # expand: fan out over its projections
                    for bound in step[1]:
                        nxt = eval_projection(bound, cur, step[2])
                        run(nxt, mask, steps[i + 1:])
                    return
            if mask is None:
                outs.append(cur)
            else:
                cols, n = compact_columns(cur.columns, mask,
                                          cur.num_rows)
                outs.append(ColumnarBatch(cols, n, cur.schema))

        run(batch, None, self._steps)
        return tuple(outs)

    def _execute_map(self):
        from ..memory.retry import split_in_half_by_rows, with_retry
        from ..memory.spillable import SpillableBatch
        live = self._live_stats
        n_in = 0
        for batch in self.children[0].execute():
            n_in += 1
            live[0] = n_in
            sp = SpillableBatch.from_batch(batch)
            try:
                def run(s):
                    b = s.get_batch()
                    try:
                        with self.batch_harness(
                                gather_shape=("map", b.capacity),
                                fault_point="device.dispatch",
                                fault_key=f"stage:map:{n_in}"):
                            return self._jit_map(b)
                    finally:
                        s.release()
                for outs in with_retry(
                        sp, run, split_policy=split_in_half_by_rows):
                    for out in outs:
                        yield out
            finally:
                sp.close()

    # -- agg stage ---------------------------------------------------------
    def _agg_spec_body(self, batch, state, flag):
        return self._agg._streaming_step(batch, state, flag)

    def _agg_exact_body(self, batch):
        part = self._agg._fused_update_exact(batch)
        ev = None if self._agg.mode == "partial" \
            else self._agg._evaluate(part)
        return part, ev

    def _fresh_state(self):
        """Fresh (never the agg's cached) initial state: the fused
        step DONATES the carried state, and donating a cached buffer
        would invalidate it for the next execution on backends that
        honor donation."""
        import jax.numpy as jnp
        from ..columnar.batch import empty_batch
        return (empty_batch(self._agg._buffer_schema,
                            capacity=self._agg._small_cap()),
                jnp.asarray(False))

    def _spec_allowed(self) -> bool:
        """Speculate with the masked buckets? Not where this stage's plan
        shape tripped before: it takes the exact tier in its first pass."""
        from .speculation import speculation_allowed
        agg = self._agg
        return agg._masked_ok and agg._spec_enabled \
            and speculation_allowed(self.plan_fingerprint())

    def _step_spanned(self, run, sp, exact=False):
        """One source batch's step of the stage's group-by: the span the
        per-operator group-by opens (`agg.update`, phase `group-agg`), so
        that `groupby_ms` reads for a fused group-by too."""
        from ..memory.retry import split_in_half_by_rows, with_retry
        from ..obs import op_span
        if exact:
            from .aggregate import note_many_groups
            note_many_groups(self.plan_fingerprint())
        with op_span("agg.update", phase="group-agg"):
            return list(with_retry(sp, run,
                                   split_policy=split_in_half_by_rows))

    def _execute_agg(self):
        """The fused group-by over the source's one stream, or, as the
        per-operator one (`AggregateExec.internal_execute`), once a
        partition where the source hands out partitions on chips of their
        own and the group-by is not `complete`."""
        from .aggregate import _note as note_agg
        from .exchange import onto_one_chip, partition_streams
        note_agg(executions=1)
        streams = [onto_one_chip(self.children[0].execute())] \
            if self._agg.mode == "complete" \
            else partition_streams(self.children[0])
        saw = False
        for stream in streams:
            for out in self._agg_over(stream):
                saw = True
                yield out
        if not saw:
            yield _FALLBACK

    def _agg_over(self, stream):
        """One stream's group-by; nothing where the stream is empty."""
        from ..memory.spillable import SpillableBatch
        from .speculation import current_scope
        agg = self._agg
        live = self._live_stats
        spec = self._spec_allowed()
        saw = False
        n_in = live[0]
        if spec:
            state, flag = self._fresh_state()
            ev = None
            for batch in stream:
                saw = True
                n_in += 1
                live[0] = n_in
                live[1] = _nbytes_of((state, flag))
                sp = SpillableBatch.from_batch(batch)
                box = [state, flag, None]
                try:
                    def run(s):
                        b = s.get_batch()
                        try:
                            with self.batch_harness(
                                    gather_shape=("agg", b.capacity),
                                    fault_point="device.dispatch",
                                    fault_key=f"stage:agg:{n_in}"):
                                return self._jit_step(b, box[0], box[1])
                        finally:
                            s.release()
                    for out in self._step_spanned(run, sp):
                        box[0], box[1], box[2] = out
                finally:
                    sp.close()
                state, flag, ev = box
            if not saw:
                return
            scope = current_scope()
            if scope is not None:
                scope.record(flag, owner=self.plan_fingerprint())
            if agg.mode == "partial":
                yield state
            else:
                yield (ev if ev is not None
                       else agg._jit_evaluate(state))
        else:
            parts: List = []
            n_parts = 0
            last_ev = None
            for batch in stream:
                saw = True
                n_in += 1
                live[0] = n_in
                batch = agg._tight_input(batch, self.plan_fingerprint())
                sp = SpillableBatch.from_batch(batch)
                try:
                    def run(s):
                        b = s.get_batch()
                        try:
                            with self.batch_harness(
                                    gather_shape=("agg", b.capacity),
                                    fault_point="device.dispatch",
                                    fault_key=f"stage:agg:{n_in}"):
                                return self._jit_step_exact(b)
                        finally:
                            s.release()
                    for part, ev in self._step_spanned(run, sp, True):
                        # the agg's own shrink + MERGE_FAN_IN window:
                        # live partials stay bounded under a forced-
                        # spill budget, exactly like the per-op drive
                        agg._absorb_partial(parts, part)
                        n_parts += 1
                        last_ev = ev
                finally:
                    sp.close()
            if not saw:
                for p in parts:
                    p.close()
                return
            yield self._finish_exact(
                parts, last_ev if n_parts == 1 else None)

    def _finish_exact(self, parts, last_ev):
        """Exact-tier tail: a single partial was already evaluated
        in-program (the N=1 steady state: one dispatch total); several
        delegate to the absorbed aggregate's own merge machinery —
        byte-identical to the per-operator merge path."""
        agg = self._agg
        if len(parts) == 1:
            only = parts[0]
            merged = only.get_batch()
            only.release()
            only.close()
            if agg.mode == "partial":
                return merged
            return last_ev if last_ev is not None \
                else agg._jit_evaluate(merged)
        merged = agg._merge_all(parts)
        return merged if agg.mode == "partial" \
            else agg._jit_evaluate(merged)

    # -- join_agg stage ----------------------------------------------------
    def _sizing_body(self, build_batch, stream_batch):
        """Cold-path sizing program: build table + probe counts + the
        exact byte needs, ONE dispatch (the table is re-derived inside
        the first fused step — sizing runs once per size-cache miss,
        not per batch)."""
        table = self._join._build_kernel(build_batch)
        _lo, _counts, _sk, total, needs = \
            self._join._counts_kernel(table, stream_batch)
        return total, needs

    def _probe_in_stage(self, table, build_batch, stream_batch,
                        cand_cap, s_caps, b_caps):
        """Traced: counts + probe + emit, plus the speculative-sizing
        overflow flag (the join's _probe_one contract, in-program)."""
        import jax.numpy as jnp
        lo, counts, skey_cols, total, needs = \
            self._join._counts_kernel(table, stream_batch)
        zeros = jnp.zeros((table.capacity,), jnp.bool_)
        out, _bm = self._join._probe_kernel(
            table, build_batch, stream_batch, (lo, counts, skey_cols),
            zeros, cand_cap, s_caps, b_caps)
        flag = total > cand_cap
        s_needs, b_needs = needs
        for need, cap in zip(
                list(s_needs) + list(b_needs),
                [c for c in s_caps if c is not None]
                + [c for c in b_caps if c is not None]):
            flag = flag | (need > cap)
        return out, flag

    def _ja_spec_body(self, table, build_batch, stream_batch, state,
                      flag, cand_cap, s_caps, b_caps):
        if table is None:
            table = self._join._build_kernel(build_batch)
        out, size_flag = self._probe_in_stage(
            table, build_batch, stream_batch, cand_cap, s_caps, b_caps)
        state, flag, ev = self._agg._streaming_step(out, state, flag)
        return table, state, flag, ev, size_flag

    def _ja_exact_body(self, table, build_batch, stream_batch,
                       cand_cap, s_caps, b_caps):
        if table is None:
            table = self._join._build_kernel(build_batch)
        out, size_flag = self._probe_in_stage(
            table, build_batch, stream_batch, cand_cap, s_caps, b_caps)
        part = self._agg._fused_update_exact(out)
        ev = None if self._agg.mode == "partial" \
            else self._agg._evaluate(part)
        return table, part, ev, size_flag

    def _sizing(self, build_batch, stream_batch):
        """Host half of the join's speculative sizing contract: warm
        shape -> cached static caps, overflow checked by a device flag
        inside the fused program (recorded with the speculation scope);
        cold shape (or no scope) -> ONE sizing dispatch + exact caps.
        Bounded staleness (the join's SPEC_REFRESH contract, ADVICE
        r4): after SPEC_REFRESH warm uses the entry expires and the
        next probe re-measures FRESH — no monotone max — so one
        pathological batch cannot inflate the plan shape's buckets for
        the process lifetime. Returns ((cand_cap, s_caps, b_caps),
        warm)."""
        import jax
        from ..columnar.column import bucket_capacity
        from .joins import HashJoinExec, _byte_cap_tuple
        from .speculation import speculation_allowed
        key = (stream_batch.capacity, build_batch.capacity)
        cached = self._size_cache.get(key)
        known = cached is not None
        if cached is not None and speculation_allowed():
            cached[3] += 1
            if cached[3] > HashJoinExec.SPEC_REFRESH:
                del self._size_cache[key]
                cached = None
            else:
                return (cached[0], cached[1], cached[2]), True
        if known:
            _note(size_cache_refreshes=1)
        else:
            _note(size_cache_misses=1)
        from ..obs import op_span
        with op_span("join.sizing", phase="device-wait"):
            total_dev, needs_dev = self._jit_sizing(build_batch,
                                                    stream_batch)
            total, (s_needs, b_needs) = host_read(
                "stage.candidates", (total_dev, needs_dev))
        cand_cap = bucket_capacity(max(int(total), 1))
        s_caps = _byte_cap_tuple(stream_batch.columns, s_needs)
        b_caps = _byte_cap_tuple(build_batch.columns, b_needs)
        if cached is not None:
            # keep buckets monotone so steady state stays compiled
            oc, os_, ob = cached[0], cached[1], cached[2]
            cand_cap = max(cand_cap, oc)
            s_caps = tuple(None if c is None else max(c, o)
                           for c, o in zip(s_caps, os_))
            b_caps = tuple(None if c is None else max(c, o)
                           for c, o in zip(b_caps, ob))
        self._size_cache[key] = [cand_cap, s_caps, b_caps, 0]
        return (cand_cap, s_caps, b_caps), False

    def _execute_join_agg(self):
        from ..columnar.batch import empty_batch
        from ..memory.spillable import SpillableBatch
        from .aggregate import _note as note_agg
        from .coalesce import concat_batches
        from .speculation import current_scope
        join, agg = self._join, self._agg
        bi = 1 if join.build_side == "right" else 0
        build_child, stream_child = self.children[bi], \
            self.children[1 - bi]
        from ..obs import op_span
        with op_span("join.build", phase="join-build"):
            batches = list(build_child.execute())
            if batches:
                build_batch = concat_batches(batches,
                                             build_child.output_schema)
            else:
                build_batch = empty_batch(build_child.output_schema)
        spec = self._spec_allowed()
        note_agg(executions=1)
        table = None
        state = flag = ev = None
        size_flags: List = []
        parts: List = []
        n_parts = 0
        last_ev = None
        if spec:
            state, flag = self._fresh_state()
        saw = False
        n_in = 0
        live = self._live_stats
        scope = current_scope()
        for stream_batch in stream_child.execute():
            saw = True
            n_in += 1
            live[0] = n_in
            (cand_cap, s_caps, b_caps), warm = \
                self._sizing(build_batch, stream_batch)
            sp = SpillableBatch.from_batch(stream_batch)
            try:
                if spec:
                    live[1] = _nbytes_of((state, flag))
                    box = [table, state, flag, None]

                    def run(s):
                        b = s.get_batch()
                        try:
                            with self.batch_harness(
                                    gather_shape=(
                                        "join_agg", b.capacity,
                                        build_batch.capacity, cand_cap,
                                        s_caps, b_caps),
                                    fault_point="device.dispatch",
                                    fault_key=f"stage:join:{n_in}"):
                                return self._jit_step(
                                    box[0], build_batch, b, box[1],
                                    box[2], cand_cap, s_caps, b_caps)
                        finally:
                            s.release()
                    for out in self._step_spanned(run, sp):
                        box[0], box[1], box[2], box[3], size_flag = out
                        size_flags.append(size_flag)
                    table, state, flag, ev = box
                else:
                    def run(s):
                        b = s.get_batch()
                        try:
                            with self.batch_harness(
                                    gather_shape=(
                                        "join_agg", b.capacity,
                                        build_batch.capacity, cand_cap,
                                        s_caps, b_caps),
                                    fault_point="device.dispatch",
                                    fault_key=f"stage:join:{n_in}"):
                                return self._jit_step_exact(
                                    table, build_batch, b, cand_cap,
                                    s_caps, b_caps)
                        finally:
                            s.release()
                    for tbl, part, pev, size_flag in self._step_spanned(
                            run, sp, True):
                        table = tbl
                        # bounded accumulation: the agg's shrink +
                        # MERGE_FAN_IN window (forced-spill parity)
                        agg._absorb_partial(parts, part)
                        n_parts += 1
                        last_ev = pev
                        if warm and scope is not None:
                            scope.record(size_flag)
            finally:
                sp.close()
        if not saw:
            for p in parts:
                p.close()
            yield _FALLBACK
            return
        if spec:
            if scope is not None:
                # the buckets' overflow under this stage's fingerprint (a
                # trip is remembered: `_spec_allowed`); the join's stale
                # sizes apart from it, they say nothing about the shape
                scope.record(flag, owner=self.plan_fingerprint())
                for size_flag in size_flags:
                    scope.record(size_flag)
            if agg.mode == "partial":
                yield state
            else:
                yield (ev if ev is not None
                       else agg._jit_evaluate(state))
        else:
            yield self._finish_exact(
                parts, last_ev if n_parts == 1 else None)


#: sentinel: the fused drive hit a corner the per-op chain owns
_FALLBACK = object()


# ---------------------------------------------------------------------------
# the stage planner
# ---------------------------------------------------------------------------

def compile_stages(root: TpuExec, conf=None) -> TpuExec:
    """Rewrite a converted TpuExec tree: whitelisted chains become
    CompiledStageExec nodes; everything else is untouched. The no-op
    path (conf off) returns `root` as-is."""
    from ..config import STAGE_FUSION_ENABLED, active_conf
    conf = conf if conf is not None else active_conf()
    if not conf.get(STAGE_FUSION_ENABLED):
        return root
    return _rewrite(root)


def _rewrite(node: TpuExec) -> TpuExec:
    stage = _try_stage(node)
    if stage is None and getattr(node, "_fused_steps", None):
        # an aggregate that runs its filter/project chain inline as a row
        # mask without forming a stage (the lane tier's): it drives its
        # `_source`, so the rewrite goes on from there, and the chain it
        # absorbed never becomes a map stage as well
        src = node._source
        new = _rewrite(src)
        if new is not src:
            node._source = new
            last = _chain_between(node, src)[-1]
            last.children = type(last.children)([new])
        return node
    target = stage if stage is not None else node
    kids = list(target.children)
    changed = False
    for i, c in enumerate(kids):
        new = _rewrite(c)
        if new is not c:
            kids[i] = new
            changed = True
            # an absorbing aggregate's streaming source may bypass the
            # children chain — keep it pointing at the live node
            if getattr(target, "_source", None) is c:
                target._source = new
    if changed:
        target.children = kids if isinstance(target.children, list) \
            else type(target.children)(kids)
    return target


def _agg_eligible(agg) -> bool:
    from ..config import FUSION_ENABLED, active_conf
    return (agg.mode in ("complete", "partial") and agg._masked_ok
            and active_conf().get(FUSION_ENABLED))


def _join_eligible(join) -> bool:
    from .joins import INNER
    # inner only: no build flags, no stream-preserved tails — the
    # probe's one-output-batch-per-stream-batch dataflow the fused
    # program composes with the aggregate update
    return join.join_type == INNER and not join._need_build_flags


def _try_stage(node: TpuExec) -> Optional[CompiledStageExec]:
    from .aggregate import AggregateExec
    from .basic import ExpandExec, FilterExec, ProjectExec
    from .joins import HashJoinExec
    if isinstance(node, CompiledStageExec):
        return None
    if isinstance(node, AggregateExec) and _agg_eligible(node):
        src = node._source
        if isinstance(src, HashJoinExec) and _join_eligible(src):
            return CompiledStageExec(
                "join_agg", absorbed=[node] + _chain_between(node, src)
                + [src], sources=list(src.children), join=src, agg=node)
        if node._fused_steps:
            # a REAL chain (filter/project absorbed); a bare group-by
            # is already one program per batch — wrapping it would
            # only rename its profile row
            return CompiledStageExec(
                "agg", absorbed=[node] + _chain_between(node, src),
                sources=[src], agg=node)
        return None
    if isinstance(node, (FilterExec, ProjectExec, ExpandExec)):
        chain = [node]
        cur = node
        while True:
            child = cur.children[0]
            if isinstance(child, (FilterExec, ProjectExec, ExpandExec)):
                chain.append(child)
                cur = child
            else:
                break
        if len(chain) >= 2:
            return CompiledStageExec("map", absorbed=chain,
                                     sources=[cur.children[0]])
    return None


def _chain_between(agg, src) -> List[TpuExec]:
    """The operator nodes the aggregate absorbed between itself and
    its streaming source (for stage description/accounting)."""
    out = []
    cur = agg.children[0] if agg.children else None
    while cur is not None and cur is not src:
        out.append(cur)
        cur = cur.children[0] if cur.children else None
    return out
