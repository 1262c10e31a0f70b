"""TpuExec — base of the columnar operator tree (reference GpuExec,
sql-plugin/.../GpuExec.scala:365 `doExecuteColumnar`; metric registry at
GpuExec.scala:49-116 with ESSENTIAL/MODERATE/DEBUG levels).

Operators form a tree; `execute()` returns an iterator of ColumnarBatch.
Each operator's device work is jax-traced per batch *shape bucket*, so a
pipeline of execs compiles into a small set of XLA programs reused across
batches. Host-side control (iteration, spill, retry, coalesce decisions)
stays in Python exactly where the reference keeps it in Scala.
"""

from __future__ import annotations

import itertools
import time
from contextlib import contextmanager, nullcontext
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

from ..columnar.batch import ColumnarBatch
from ..types import Schema

# the same three-level scale as obs/events.py (the single name->int
# parser lives there: events.parse_level)
ESSENTIAL = 0
MODERATE = 1
DEBUG = 2


def metrics_level_from_conf(conf=None) -> int:
    """spark.rapids.sql.metrics.level as an int (unknown → MODERATE),
    the visibility cut for all_metrics()/last_query_metrics()
    (reference GpuExec.scala:36-47)."""
    from ..config import METRICS_LEVEL, active_conf
    from ..obs.events import parse_level
    conf = conf if conf is not None else active_conf()
    return parse_level(conf.get(METRICS_LEVEL))


class TpuMetric:
    """Accumulating operator metric (reference GpuMetric).

    Device-produced values (e.g. a traced row count) are accumulated as
    device scalars and only materialized when the metric is READ. A d2h
    sync in the steady-state batch loop costs orders of magnitude more
    than the kernels themselves (the analog of a cudaStreamSynchronize
    per batch), so `add_device` must never block.
    """

    __slots__ = ("name", "level", "_value", "_pending")

    def __init__(self, name: str, level: int = MODERATE):
        self.name = name
        self.level = level
        self._value = 0
        self._pending: List = []

    def add(self, v):
        self._value += v

    def add_device(self, scalar):
        """Accumulate a device scalar lazily (no sync until read)."""
        self._pending.append(scalar)

    @property
    def value(self):
        if self._pending:
            import jax.numpy as jnp
            pending, self._pending = self._pending, []
            # one stacked transfer, not one round trip per scalar
            self._value += int(jnp.sum(jnp.stack(
                [jnp.asarray(s).astype(jnp.int64) for s in pending])))
        return self._value

    @value.setter
    def value(self, v):
        self._pending = []
        self._value = v

    def ns_timer(self):
        return _NsTimer(self)


class _NsTimer:
    def __init__(self, metric: TpuMetric):
        self.metric = metric

    def __enter__(self):
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.metric.add(time.perf_counter_ns() - self._t0)


# canonical metric names (reference GpuMetric companion, GpuExec.scala:49-96)
NUM_OUTPUT_ROWS = "numOutputRows"
NUM_OUTPUT_BATCHES = "numOutputBatches"
NUM_INPUT_ROWS = "numInputRows"
NUM_INPUT_BATCHES = "numInputBatches"
OP_TIME = "opTime"
SORT_TIME = "sortTime"
AGG_TIME = "computeAggTime"
CONCAT_TIME = "concatTime"
JOIN_TIME = "joinTime"
BUILD_TIME = "buildTime"
PEAK_DEVICE_MEMORY = "peakDevMemory"
NUM_TASKS_FALL_BACKED = "numTasksFallBacked"
SPILL_TIME = "spillTime"
PARTITION_SIZE = "dataSize"
SHUFFLE_WRITE_TIME = "shuffleWriteTime"
SHUFFLE_READ_TIME = "shuffleReadTime"
SHUFFLE_PACK_TIME = "shufflePackTimeNs"
BROADCAST_TIME = "broadcastTime"
PIPELINE_WAIT = "pipelineWaitNs"
PIPELINE_FULL_WAIT = "pipelineFullWaitNs"
PIPELINE_WALL = "pipelineWallNs"
NUM_GATHERS = "numGathers"
GATHER_TIME = "gatherTimeNs"
NUM_UPLOADS = "numUploads"
UPLOAD_PACK_TIME = "uploadPackTimeNs"
NUM_DISPATCHES = "numDispatches"
COMPILE_TIME = "compileTimeNs"

#: the closed set of metric names execs may register — one name, one
#: meaning, exactly like the reference's GpuMetric companion object.
#: tests/test_docs_lint.py asserts every additional_metrics() entry
#: resolves here, so a typo'd or duplicate-meaning name fails tier-1.
CANONICAL_METRICS = frozenset({
    NUM_OUTPUT_ROWS, NUM_OUTPUT_BATCHES, NUM_INPUT_ROWS, NUM_INPUT_BATCHES,
    OP_TIME, SORT_TIME, AGG_TIME, CONCAT_TIME, JOIN_TIME, BUILD_TIME,
    PEAK_DEVICE_MEMORY, NUM_TASKS_FALL_BACKED, SPILL_TIME, PARTITION_SIZE,
    SHUFFLE_WRITE_TIME, SHUFFLE_READ_TIME, SHUFFLE_PACK_TIME,
    BROADCAST_TIME,
    PIPELINE_WAIT, PIPELINE_FULL_WAIT, PIPELINE_WALL,
    NUM_GATHERS, GATHER_TIME,
    NUM_UPLOADS, UPLOAD_PACK_TIME,
    NUM_DISPATCHES, COMPILE_TIME,
})

#: per-operator instance ids for event/span attribution (two
#: AggregateExecs in one plan stay distinguishable in the event log)
_OP_IDS = itertools.count(1)

#: an additional_metrics() entry: a bare canonical name (MODERATE) or
#: (name, level)
MetricSpec = Union[str, Tuple[str, int]]

#: the metric triple every exec that runs a pipelined() input stage
#: registers (include in additional_metrics(); bind with
#: TpuExec.pipeline_stage)
PIPELINE_STAGE_METRICS = ((PIPELINE_WAIT, MODERATE),
                          (PIPELINE_FULL_WAIT, MODERATE),
                          (PIPELINE_WALL, MODERATE))

#: the metric pair every gather-engine-wired exec registers (include in
#: additional_metrics(); bind with ops.gather.GatherTracker): the
#: structural count of materializing row gathers per execution and the
#: wall-ns of the gather-bearing kernel dispatches
GATHER_METRICS = ((NUM_GATHERS, MODERATE), (GATHER_TIME, MODERATE))

#: the metric pair every upload-engine-wired exec registers (include in
#: additional_metrics(); attributed via columnar.upload.metric_sink /
#: promote_stream): batch uploads this execution dispatched and the
#: wall-ns spent packing + transferring them
UPLOAD_METRICS = ((NUM_UPLOADS, MODERATE), (UPLOAD_PACK_TIME, MODERATE))

#: the metric pair every dispatch-ledger-wired exec registers (include
#: in additional_metrics(); bound by building the exec's jit sites with
#: obs.dispatch.instrument(owner=self), or via dispatch.metric_scope
#: for module-level program sites): program dispatches this exec issued
#: and the wall-ns its fresh traces spent compiling (ISSUE 13 — the
#: per-stage dispatches/batch baseline whole-stage compilation answers
#: to). Dispatches are counted at CALL time, so jit cache hits replay
#: identical counts on repeated executions.
DISPATCH_METRICS = ((NUM_DISPATCHES, MODERATE), (COMPILE_TIME, MODERATE))


class TpuExec:
    """Base columnar operator."""

    #: ISSUE 18 (encoded execution): True when this exec's kernels accept
    #: DictionaryColumn inputs from its children (code-space predicates,
    #: encoded-key joins, pass-through projections). Execs override it —
    #: usually with an eligibility walk over their bound expressions
    #: (expr/predicates.encoded_safe_predicate) — and the default False
    #: guarantees an operator never silently misreads the encoded layout:
    #: its children materialize at the batch boundary instead.
    consumes_encoded: bool = False

    #: stamped by the PARENT's execute() before this exec's first batch is
    #: pulled (child iterators start lazily): whether encoded columns may
    #: cross this exec's output boundary. The root of a plan is never
    #: stamped, so root output always materializes (the late-
    #: materialization seam — results are byte-identical with the lane
    #: off).
    _encoded_ok_for_parent: bool = False

    def __init__(self, *children: "TpuExec"):
        self.children: List[TpuExec] = list(children)
        self._op_id = next(_OP_IDS)
        self.metrics: Dict[str, TpuMetric] = {}
        for name in (NUM_OUTPUT_ROWS, NUM_OUTPUT_BATCHES):
            self.metrics[name] = TpuMetric(name, ESSENTIAL)
        self.metrics[OP_TIME] = TpuMetric(OP_TIME, MODERATE)
        for spec in self.additional_metrics():
            name, level = spec if isinstance(spec, tuple) \
                else (spec, MODERATE)
            self.metrics[name] = TpuMetric(name, level)

    # -- subclass surface --------------------------------------------------
    @property
    def output_schema(self) -> Schema:
        raise NotImplementedError(type(self).__name__)

    def additional_metrics(self) -> Sequence[MetricSpec]:
        return ()

    @property
    def output_grouped_by(self):
        """Grouping contract of this exec's output batches, or None.

        A tuple of frozensets of output column names: within every
        emitted batch, rows carrying equal values for (one representative
        of each class) are CONTIGUOUS, and the columns inside one class
        are pairwise equal per row (e.g. the two sides of an equi-join
        key). A downstream group-by whose keys pick a representative from
        every class (and nothing else) may skip its sort
        (ops/aggregate.groupby_aggregate pre_grouped)."""
        return None

    @property
    def runs_own_pipeline_stage(self) -> bool:
        """True when this exec's execute() already drives a pipelined()
        producer stage of its own. A consumer that would wrap its input
        in another stage (e.g. CoalesceBatchesExec) skips it then —
        stacking two stages on one edge doubles threads and live
        prefetched batches for zero extra overlap. Wrapper execs that
        delegate execution to a child should forward the child's value."""
        return False

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        raise NotImplementedError(type(self).__name__)

    def _fingerprint_extras(self):
        """Semantic parameters of THIS node beyond its class, output
        schema and children — everything a trace of its programs
        depends on (bound expressions, modes, captured conf knobs).
        Returning None opts the subtree out of the plan-fingerprint
        program cache (the safe default: an exec whose trace semantics
        are not fully captured here must never share compiled programs
        across instances)."""
        return None

    def plan_fingerprint(self) -> Optional[str]:
        """Canonical plan-subtree fingerprint (ISSUE 14): equal
        fingerprints promise byte-identical traces, so the process-wide
        program cache (obs/dispatch.py) may hand a later collect()'s
        rebuilt exec the programs an identical earlier plan already
        compiled — and the stage compiler keys CompiledStageExec
        programs (and, later, ROADMAP 5's sub-plan result cache) off
        the same digest. Combines per-node semantics
        (_fingerprint_extras), the output schema, every child's
        fingerprint, the backend platform and the trace-affecting conf
        digest. None = some node in the subtree opted out (or the
        stage.fusion gate is off) — callers fall back to per-instance
        program sites. Memoized per instance: compute it only after
        the node's semantic fields are final."""
        memo = self.__dict__.get("_plan_fp", False)
        if memo is not False:
            return memo
        fp = None
        try:
            extras = self._fingerprint_extras()
            if extras is not None:
                from .stage_compiler import fingerprint_node
                fp = fingerprint_node(self, extras)
        except Exception:  # noqa: BLE001 — fingerprinting is an
            fp = None      # optimization; never fail plan build
        self.__dict__["_plan_fp"] = fp
        return fp

    def _site(self, fn, label: str, key_salt=None, **jit_kwargs):
        """Build one of this exec's program sites through the dispatch
        chokepoint, keyed by the plan fingerprint when available — a
        semantically identical exec in a later collect() then reuses
        the SAME compiled programs (zero fresh traces, the PR 13
        per-collect-recompile finding closed). `key_salt`
        disambiguates several sites sharing one label on one exec
        (ExpandExec's per-projection programs): without it the cache
        would hand every projection the FIRST one's program."""
        from ..obs.dispatch import instrument
        fp = self.plan_fingerprint()
        key = None if fp is None else \
            (fp if key_salt is None else (fp, key_salt))
        return instrument(fn, label=label, owner=self, cache_key=key,
                          **jit_kwargs)

    def batch_harness(self, gather_shape=None, fault_point=None,
                      fault_key=None, metric_scope: bool = False):
        """THE per-batch stage-boundary governance harness (ISSUE 14).

        Compute bodies handed to the dispatch chokepoint must stay PURE
        traced dataflow (the `stage-governance` analyzer rule): the
        per-batch governance hooks — gather accounting, chaos fault
        points, module-site dispatch metric attribution — bind HERE,
        around the one program call, at the stage boundary. Lifecycle
        cancellation ticks already live at the TpuExec._drive batch
        boundary, and breaker engagement is noted at trace time where a
        domain is consulted, so the PR 5/6 contracts hold at stage
        granularity. Returns a context manager; plain per-op paths and
        CompiledStageExec route through the same helper so every wired
        boundary changes together."""
        scopes = []
        if fault_point is not None:
            from .. import faults
            faults.check(fault_point, key=fault_key)
        if gather_shape is not None:
            tracker = getattr(self, "_gather_track", None)
            if tracker is not None:
                scopes.append(tracker.observe(gather_shape))
        if metric_scope:
            from ..obs import dispatch as obs_dispatch
            scopes.append(obs_dispatch.metric_scope(
                self.metrics[NUM_DISPATCHES],
                self.metrics[COMPILE_TIME]))
        if not scopes:
            return nullcontext()
        if len(scopes) == 1:
            return scopes[0]

        @contextmanager
        def _stacked():
            with scopes[0], scopes[1]:
                yield
        return _stacked()

    def pipeline_stage(self, source, label: str, depth=None):
        """The one way an exec wraps an input in a pipelined() stage:
        binds this operator's three PIPELINE_STAGE_METRICS (which its
        additional_metrics() must register) and tags the stage label
        with the op id. Callers drive the returned stage inside
        try/finally with stage.close() — close/metric conventions live
        here so all wired boundaries change together."""
        from .pipeline import pipelined
        return pipelined(source, depth=depth,
                         label=f"{label}-{self._op_id}",
                         wait_metric=self.metrics[PIPELINE_WAIT],
                         full_metric=self.metrics[PIPELINE_FULL_WAIT],
                         wall_metric=self.metrics[PIPELINE_WALL])

    # -- public ------------------------------------------------------------
    def execute(self) -> Iterator[ColumnarBatch]:
        """Final wrapper (reference GpuExec.doExecuteColumnar:365): counts
        output rows/batches around the operator's own iterator, with an
        xprof trace annotation per batch step (the reference's NVTX
        range; shows operator names over their XLA ops in timelines).

        With the event log enabled (spark.rapids.tpu.eventLog.enabled)
        this is also the operator span source: one `op_open` when the
        iterator starts, one `op_batch` per step (wall-ns around the
        pull, so INCLUSIVE of child time — the pull model's analog of
        the reference's NVTX range nesting), and one `op_close` carrying
        the cumulative totals when it finishes (or is abandoned by a
        limit). Disabled mode pays exactly one active_bus() check."""
        from ..obs import events as obs_events
        rows = self.metrics[NUM_OUTPUT_ROWS]
        batches = self.metrics[NUM_OUTPUT_BATCHES]
        name = type(self).__name__
        # retain last outputs ONLY when failure dumping is configured —
        # otherwise each operator would pin one device batch for the
        # whole query, stealing memory the spill machinery counts as free
        try:
            from ..config import DEBUG_DUMP_PATH, active_conf
            dump_enabled = bool(active_conf().get(DEBUG_DUMP_PATH))
        except Exception:  # noqa: BLE001 — conf unavailable early
            dump_enabled = False
        # encoded-execution stamping (ISSUE 18): children learn whether
        # THIS exec's kernels can consume their encoded columns before
        # their first batch is pulled (internal_execute below builds the
        # child iterators lazily); an unstamped/False child materializes
        # at its own yield boundary in _drive
        for c in self.children:
            c._encoded_ok_for_parent = self.consumes_encoded
        it = self.internal_execute()
        bus = obs_events.active_bus()
        # lifecycle governor (ISSUE 6): the ONE batch-boundary
        # cancellation hook for every operator — outside a governed
        # query (tests/bench driving exec trees directly) qctx is None
        # and each batch pays exactly this pointer check; inside one,
        # tick() checks the deadline/cancel token every
        # query.cancelCheckBatches batches and raises
        # QueryCancelledError at the boundary
        from . import lifecycle
        qctx = lifecycle.current_context()
        try:
            yield from self._drive(it, bus, qctx, name, rows, batches,
                                   dump_enabled)
        finally:
            # synchronous teardown (ISSUE 6): when an exception (a
            # cancellation tick, a downstream operator error) unwinds
            # THROUGH this frame, the internal iterator below us may be
            # left suspended — closing it here runs its try/finally
            # chain NOW (pipeline stages join their producer threads,
            # staged spillables close), instead of whenever GC drops
            # the suspended frames. Exhausted iterators close as a
            # no-op, so the steady state is unchanged.
            close = getattr(it, "close", None)
            if close is not None:
                close()

    def _drive(self, it, bus, qctx, name, rows, batches, dump_enabled):
        from ..columnar.encoded import materialize_batch
        from ..obs import events as obs_events
        from ..utils.tracing import annotate_op
        # late materialization (ISSUE 18): when the parent's kernels
        # cannot consume encoded columns, decode them HERE — once, at the
        # batch boundary, through the gather engine — instead of letting
        # them reach code that would misread the layout. Identity (one
        # isinstance scan) for batches with no encoded columns.
        decode = not self._encoded_ok_for_parent
        if bus is None:
            # fast path: bit-identical to the pre-obs loop
            while True:
                if qctx is not None:
                    qctx.tick()
                with annotate_op(name):
                    try:
                        batch = next(it)
                    except StopIteration:
                        return
                    except Exception:
                        self._dump_failure_inputs(name)
                        raise
                    if decode:
                        batch = materialize_batch(batch, seam="boundary")
                batches.add(1)
                if batch._host_rows is not None:
                    rows.add(batch._host_rows)
                else:
                    rows.add_device(batch.num_rows)
                if qctx is not None:
                    # live-introspection progress (ISSUE 11): current
                    # operator + root-output batch/row counts; host row
                    # counts only — never a device sync
                    qctx.note_batch(name, self._op_id, batch._host_rows)
                if dump_enabled:
                    self._last_output = batch
                yield batch
        # instrumented path
        bus.emit("op_open", op=name, op_id=self._op_id)
        # snapshot so op_close reports THIS execution's rows, not the
        # metric's lifetime total — bench reuses one plan object across
        # iterations, and profile_report sums rows across closes
        try:
            rows_at_open = rows.value
        except Exception:  # noqa: BLE001
            rows_at_open = None
        # dispatch plane (ISSUE 13): wired execs carry DISPATCH_METRICS
        # — snapshot them so one dispatch_stats record per execution
        # reports per-execution deltas (the gather_stats convention)
        disp = self.metrics.get(NUM_DISPATCHES)
        comp = self.metrics.get(COMPILE_TIME)
        disp_at_open = disp.value if disp is not None else None
        comp_at_open = comp.value if comp is not None else 0
        total_ns = 0
        nbatches = 0
        emit_batches = bus.level >= obs_events.DEBUG
        try:
            while True:
                if qctx is not None:
                    qctx.tick()
                t0 = time.perf_counter_ns()
                with annotate_op(name):
                    try:
                        batch = next(it)
                    except StopIteration:
                        return
                    except Exception:
                        self._dump_failure_inputs(name)
                        bus.emit("op_error", op=name, op_id=self._op_id)
                        raise
                    if decode:
                        batch = materialize_batch(batch, seam="boundary")
                step_ns = time.perf_counter_ns() - t0
                total_ns += step_ns
                nbatches += 1
                batches.add(1)
                if batch._host_rows is not None:
                    rows.add(batch._host_rows)
                else:
                    rows.add_device(batch.num_rows)
                if qctx is not None:
                    qctx.note_batch(name, self._op_id, batch._host_rows)
                if emit_batches:
                    # device_size_bytes() walks the whole pytree — only
                    # pay it when the DEBUG-level record will be kept
                    bus.emit("op_batch", op=name, op_id=self._op_id,
                             wall_ns=step_ns, rows=batch._host_rows,
                             bytes=batch.device_size_bytes())
                if dump_enabled:
                    self._last_output = batch
                yield batch
        finally:
            # reading the metric materializes pending device counts (one
            # stacked transfer, query-end only); the open-snapshot delta
            # makes op_close.rows per-execution, and on a fresh plan it
            # reconciles exactly with last_query_metrics() totals
            try:
                out_rows = rows.value - rows_at_open \
                    if rows_at_open is not None else None
            except Exception:  # noqa: BLE001 — close is best-effort
                out_rows = None
            bus.emit("op_close", op=name, op_id=self._op_id,
                     wall_ns=total_ns, batches=nbatches, rows=out_rows)
            if disp_at_open is not None \
                    and disp.value > disp_at_open:
                bus.emit("dispatch_stats", op=name, op_id=self._op_id,
                         dispatches=disp.value - disp_at_open,
                         compile_ns=(comp.value - comp_at_open
                                     if comp is not None else 0),
                         batches=nbatches)

    #: most recent batch this operator yielded (= a child's view of its
    #: input); consumed by the failure dump below
    _last_output: "ColumnarBatch" = None

    def _dump_failure_inputs(self, name: str) -> None:
        """On operator failure, dump the children's last-yielded batches —
        the failing operator's actual inputs (reference DumpUtils dump-
        failing-batches hooks) — plus the REAL active exception's
        traceback. Conf-gated; never masks the error."""
        try:
            import sys

            from ..config import DEBUG_DUMP_PATH, active_conf
            if not active_conf().get(DEBUG_DUMP_PATH):
                return
            from ..utils.dump import dump_on_error
            scope = dump_on_error(name)
            for c in self.children:
                if c._last_output is not None:
                    scope.observe(c._last_output)
            # called from the operator's except block: sys.exc_info() IS
            # the failure being dumped
            scope.__exit__(*sys.exc_info())
        except Exception:  # noqa: BLE001 — dumping is best-effort
            pass

    @property
    def child(self) -> "TpuExec":
        assert len(self.children) == 1, type(self).__name__
        return self.children[0]

    def collect(self) -> List[tuple]:
        """Materialize results. Opens a speculation scope: aggregates may
        run their fast masked-bucket tier and flag overflow on device; the
        flag costs one extra host read here, and a trip re-runs the plan
        with every operator on its exact tier (span `plan.rerun`, counted
        in `exec/aggregate.counters()`). An aggregate whose flag tripped is
        remembered by plan fingerprint and does not speculate again
        (`speculation.known_to_trip`): the re-run is paid once a shape."""
        from ..obs import op_span
        from .speculation import force_exact, speculation_scope

        # late materialization (ISSUE 18): collect consumes root batches
        # through to_pylist -> fetch_batch_host, which decodes encoded
        # columns at the "output" seam — let them flow there instead of
        # double-decoding at the root's own _drive boundary
        self._encoded_ok_for_parent = True

        def run() -> List[tuple]:
            out: List[tuple] = []
            for batch in self.execute():
                out.extend(batch.to_pylist())
            return out

        with speculation_scope() as scope:
            out = run()
            # the flag's host read waits for the device like a fetch
            with op_span("result.fetch", phase="device-wait"):
                tripped = scope.tripped()
            if tripped:
                from . import aggregate
                aggregate._note(spec_trips=1, plan_reruns=1)
                with op_span("plan.rerun", phase="plan-rerun"), \
                        force_exact():
                    out = run()
        return out

    def tree_string(self, indent: int = 0) -> str:
        lines = ["  " * indent + self.node_description()]
        for c in self.children:
            lines.append(c.tree_string(indent + 1))
        return "\n".join(lines)

    def node_description(self) -> str:
        return type(self).__name__

    def all_metrics(self, level: Optional[int] = None) -> Dict[str, int]:
        """Flat per-operator metric values, filtered to entries at or
        below `level` (None = the spark.rapids.sql.metrics.level conf) —
        the reference's ESSENTIAL/MODERATE/DEBUG visibility cut
        (GpuExec.scala:36-47). Pass DEBUG explicitly to see everything."""
        if level is None:
            level = metrics_level_from_conf()
        out = {}
        def walk(node, path, label):
            for name, m in node.metrics.items():
                if m.level <= level:
                    out[f"{path}{label}.{name}"] = m.value
            for i, c in enumerate(node.children):
                # the child ordinal disambiguates same-class siblings
                # (self-joins): without it both sides collide on one
                # key and one side's metrics silently vanish
                walk(c, f"{path}{label}/", f"{type(c).__name__}[{i}]")
        walk(self, "", type(self).__name__)
        return out
