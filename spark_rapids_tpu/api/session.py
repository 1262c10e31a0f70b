"""TpuSession + DataFrame — the engine's user surface. The reference keeps
PySpark's API and swaps the physical plan underneath (SQLPlugin +
GpuOverrides); standalone, this session IS the query entry, but the flow
is identical: build a logical plan, run it through TpuOverrides
(wrap -> tag -> convert), execute the TpuExec tree."""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

from ..columnar.batch import ColumnarBatch
from ..config import RapidsConf, set_active_conf
from ..expr.aggexprs import AggregateFunction
from ..expr.core import Expression, col, lit, output_name
from ..plan import logical as L
from ..plan.overrides import TpuOverrides
from ..types import Schema


class _InMemorySource:
    def __init__(self, batches: List[ColumnarBatch], schema: Schema):
        self._batches = batches
        self.schema = schema

    def batches(self):
        return list(self._batches)

    def estimated_size_bytes(self) -> int:
        return sum(b.device_size_bytes() for b in self._batches)

    def estimated_num_rows(self) -> int:
        return sum(b.num_rows_host for b in self._batches)


def conf_mesh_devices(conf: RapidsConf) -> Optional[int]:
    """The chips a session's conf asks to span: under
    `spark.rapids.shuffle.mode=ICI` the shuffle is the resident mesh
    all-to-all, so the deployment's `spark.rapids.sql.shuffle.partitions`
    partitions are its chips; None under any other mode."""
    from ..config import SHUFFLE_MODE, SHUFFLE_PARTITIONS
    if conf.get(SHUFFLE_MODE).upper() != "ICI":
        return None
    return conf.get(SHUFFLE_PARTITIONS)


class TpuSession:
    def __init__(self, conf: Optional[Dict] = None,
                 mesh_devices: Optional[int] = None, mesh=None):
        """mesh_devices/mesh: enable distributed planning — group-bys and
        equi-joins compile to partial → ICI all-to-all exchange → final
        SPMD stages over the device mesh (exec/exchange.py). Without
        either, the conf decides: `spark.rapids.shuffle.mode=ICI` spans a
        mesh of `spark.rapids.sql.shuffle.partitions` chips, one partition
        a chip (`conf_mesh_devices`); any other mode plans the
        single-partition plan (no exchange nodes)."""
        from .. import faults
        from ..columnar import upload
        from ..obs import dispatch as obs_dispatch
        from ..obs import events as obs_events
        from ..obs import history as obs_history
        from ..obs import telemetry
        from ..parallel.mesh import device_mesh, set_active_mesh
        self.conf = RapidsConf(conf or {})
        set_active_conf(self.conf)
        obs_events.configure(self.conf)
        telemetry.configure(self.conf)
        obs_dispatch.configure(self.conf)
        obs_history.configure(self.conf)
        faults.configure(self.conf)
        # pre-size the upload staging pool's bucket ladder from
        # batchSizeBytes (ISSUE 14 satellite): steady-state scans hit
        # zero grow-on-miss staging allocations
        upload.configure(self.conf)
        if mesh is None and mesh_devices is None:
            mesh_devices = conf_mesh_devices(self.conf)
        if mesh is None and mesh_devices is not None:
            mesh = device_mesh(mesh_devices)
        self.mesh = mesh
        set_active_mesh(mesh)
        #: what the LAST collect() on this session left for its metric
        #: roll-up and profile, built on first read
        #: (exec/task_metrics.QueryHarvest; reference GpuTaskMetrics)
        self._last_harvest = None
        #: lifecycle-governor ownership token: every governed collect
        #: registers its QueryContext under it, so cancel_query() (from
        #: any thread) can find and cancel THIS session's queries
        self._lifecycle_owner = object()

    def cancel_query(self) -> int:
        """Cooperatively cancel every query this session is currently
        running (exec/lifecycle.py): their cancellation tokens are set,
        each blocked or computing thread raises QueryCancelledError at
        its next batch boundary / wait-loop poll, and the queries
        unwind through their normal try/finally chains — no leaked
        pipeline/spill threads, settled budget and catalog counters.
        Returns the number of queries cancelled (0 = none running)."""
        from ..exec import lifecycle
        return lifecycle.cancel_owner(self._lifecycle_owner)

    def health(self) -> Dict:
        """Engine health surface (exec/lifecycle.py): degradation
        circuit-breaker states per fault domain, governed-query count,
        the cumulative lifecycle counters (cancellations, breaker
        trips, partition-granular vs whole-plan recoveries), the
        workload governor's admission surface — queue depth, admitted
        count, queued/admitted/shed/quota-spill counters
        (exec/workload.py) — the telemetry registry's state + newest
        sample (obs/telemetry.py), and the dispatch ledger's program
        counters with the worst compile-cost programs
        (obs/dispatch.py)."""
        from ..exec import lifecycle
        from ..obs import dispatch, telemetry
        from ..obs import stats as obs_stats
        from ..parallel import heartbeat
        out = lifecycle.health()
        out["telemetry"] = telemetry.health_section()
        out["dispatch"] = dispatch.health_section()
        # peer liveness registry (ISSUE 20): live/dead peers, lifetime
        # purges and blacklisted slots — {"enabled": False} in the
        # default single-process session (no installed manager)
        out["peers"] = heartbeat.health_section()
        # per-priority-class wall-clock percentiles over the telemetry
        # registry's latency ring (ISSUE 17) — {"enabled": False} when
        # telemetry is off
        out["slo"] = telemetry.slo_section()
        # skew pressure + adaptive decisions (ISSUE 19): recent
        # per-exchange max/median ratios and the replanner's decision
        # counters, so operators see what the measured-statistics
        # control plane did without reading the event log
        out["stats"] = obs_stats.health_section()
        return out

    def active_queries(self) -> List[Dict]:
        """Live engine introspection (ISSUE 11): one row per in-flight
        governed query — phase (queued / admitted / executing /
        retrying), the operator currently yielding batches, root-output
        batches/rows produced so far, elapsed and deadline-remaining
        ms, task attempt number, spill count/bytes the query
        experienced, and (under the workload governor) its quota
        used/granted. Assembled lock-light from lifecycle/workload/
        catalog state; `mine` marks the queries this session drives
        (the surface is engine-wide, like health()). Empty when nothing
        is running."""
        from ..exec import lifecycle
        return lifecycle.active_queries(owner=self._lifecycle_owner)

    def last_query_metrics(self):
        """Task-level metrics of the most recent DataFrame.collect():
        semaphore wait, OOM-retry counts, spill volumes (per-query
        deltas) plus per-operator metric sums — the engine's
        GpuTaskMetrics surface (GpuTaskMetrics.scala:81-103). Honors
        spark.rapids.sql.metrics.level (GpuExec.scala:36-47). Built on
        the first call after the collect (its reads of device row counts
        are host syncs the collect itself does not pay), then cached."""
        h = self._last_harvest
        return h.metrics() if h is not None else None

    def last_query_profile(self):
        """QueryProfile of the most recent DataFrame.collect(): the
        executed plan tree annotated with per-operator metrics, with
        `.text()` (explain-with-metrics, the Spark-SQL-UI analog),
        `.to_json()` and `.top_operators()` renderers (obs/profile.py).
        None before the first collect. Built on first call, like
        `last_query_metrics()`."""
        h = self._last_harvest
        return h.profile() if h is not None else None

    # -- ingestion ---------------------------------------------------------
    def from_pydict(self, data: Dict, schema: Schema,
                    batch_rows: Optional[int] = None) -> "DataFrame":
        n = len(next(iter(data.values()))) if data else 0
        rows = batch_rows or max(n, 1)
        batches = []
        for s in range(0, max(n, 1), rows):
            chunk = {k: v[s:s + rows] for k, v in data.items()}
            batches.append(ColumnarBatch.from_pydict(chunk, schema))
        return self._df(L.LogicalScan(_InMemorySource(batches, schema)))

    def from_arrow(self, table) -> "DataFrame":
        batch = ColumnarBatch.from_arrow(table)
        return self._df(L.LogicalScan(
            _InMemorySource([batch], batch.schema)))

    def from_batches(self, batches: Sequence[ColumnarBatch],
                     schema: Schema) -> "DataFrame":
        return self._df(L.LogicalScan(_InMemorySource(list(batches), schema)))

    def range(self, start: int, end: Optional[int] = None,
              step: int = 1) -> "DataFrame":
        if end is None:
            start, end = 0, start
        return self._df(L.LogicalRange(start, end, step))

    def read_parquet(self, path) -> "DataFrame":
        from ..io.parquet import ParquetSource
        return self._df(L.LogicalScan(ParquetSource(path, self.conf)))

    def read_csv(self, path, schema: Optional[Schema] = None,
                 header: bool = True, **options) -> "DataFrame":
        from ..io.csv import CsvSource
        return self._df(L.LogicalScan(CsvSource(path, self.conf,
                                                schema=schema,
                                                header=header, **options)))

    def read_json(self, path, schema: Optional[Schema] = None,
                  **options) -> "DataFrame":
        from ..io.json import JsonSource
        return self._df(L.LogicalScan(JsonSource(path, self.conf,
                                                 schema=schema, **options)))

    def read_orc(self, path, columns=None) -> "DataFrame":
        from ..io.orc import OrcSource
        return self._df(L.LogicalScan(OrcSource(path, self.conf,
                                                columns=columns)))

    def read_iceberg(self, path, snapshot_id=None) -> "DataFrame":
        from ..io.iceberg import IcebergSource
        return self._df(L.LogicalScan(IcebergSource(path, self.conf,
                                                    snapshot_id)))

    def read_hive_text(self, path, schema, **options) -> "DataFrame":
        from ..io.hivetext import HiveTextSource
        return self._df(L.LogicalScan(HiveTextSource(path, schema,
                                                     self.conf, **options)))

    def read_delta(self, path, version=None) -> "DataFrame":
        from ..delta import read_delta
        return read_delta(self, path, version)

    def read_avro(self, path, **options) -> "DataFrame":
        from ..io.avro import AvroSource
        return self._df(L.LogicalScan(AvroSource(path, self.conf,
                                                 **options)))

    def _df(self, plan: L.LogicalPlan) -> "DataFrame":
        return DataFrame(plan, self)


def _to_expr(x) -> Expression:
    if isinstance(x, Expression):
        return x
    if isinstance(x, str):
        return col(x)
    return lit(x)


class DataFrame:
    def __init__(self, plan: L.LogicalPlan, session: TpuSession):
        self._plan = plan
        self.session = session

    @property
    def schema(self) -> Schema:
        return self._plan.schema

    @property
    def columns(self) -> List[str]:
        return list(self.schema.names)

    # -- transformations ---------------------------------------------------
    def select(self, *exprs) -> "DataFrame":
        return self._with(L.LogicalProject([_to_expr(e) for e in exprs],
                                           self._plan))

    def with_column(self, name: str, expr) -> "DataFrame":
        exprs = [col(n) for n in self.columns if n != name]
        exprs.append(_to_expr(expr).alias(name))
        return self._with(L.LogicalProject(exprs, self._plan))

    def filter(self, condition) -> "DataFrame":
        return self._with(L.LogicalFilter(_to_expr(condition), self._plan))

    where = filter

    def group_by(self, *keys) -> "GroupedData":
        return GroupedData([_to_expr(k) for k in keys], self)

    groupBy = group_by

    def map_in_pandas(self, fn, schema) -> "DataFrame":
        """Spark df.mapInPandas(fn, schema): fn(iterator of pandas
        DataFrames) -> iterator of DataFrames (reference
        GpuMapInBatchExec.scala)."""
        return self._with(L.LogicalMapInBatch(fn, _to_schema(schema),
                                              self._plan))

    mapInPandas = map_in_pandas

    def window_in_pandas(self, partition_by, *wins) -> "DataFrame":
        """Whole-partition pandas window UDFs: each win is (fn, name,
        result_type, input columns...); fn(series...) -> scalar broadcast
        over its partition (reference GpuWindowInPandasExecBase)."""
        parts = [_to_expr(p) for p in (
            partition_by if isinstance(partition_by, (list, tuple))
            else [partition_by])]
        return self._with(L.LogicalWindowInPandas(
            parts, _named_pandas_fns(wins), self._plan))

    def agg(self, *aggs: Tuple[AggregateFunction, str]) -> "DataFrame":
        return GroupedData([], self).agg(*aggs)

    def join(self, other: "DataFrame", on=None, how: str = "inner",
             left_on=None, right_on=None, condition=None) -> "DataFrame":
        if on is not None:
            names = [on] if isinstance(on, str) else list(on)
            if how not in ("left_semi", "left_anti", "existence"):
                # USING-join semantics (Spark): ONE output column per key.
                # Rename the right keys, join, project the dup away; the
                # surviving key is left's (right's for right_outer,
                # coalesced for full_outer).
                return self._using_join(other, names, how, condition)
            lkeys = [col(n) for n in names]
            rkeys = [col(n) for n in names]
        elif left_on is not None:
            lk = [left_on] if not isinstance(left_on, (list, tuple)) else left_on
            rk = [right_on] if not isinstance(right_on, (list, tuple)) else right_on
            lkeys = [_to_expr(k) for k in lk]
            rkeys = [_to_expr(k) for k in rk]
        else:
            lkeys, rkeys = [], []
        return self._with(L.LogicalJoin(self._plan, other._plan, lkeys,
                                        rkeys, how, condition))

    def _using_join(self, other: "DataFrame", names: List[str], how: str,
                    condition) -> "DataFrame":
        from ..expr.conditional import Coalesce
        tmp = {n: f"__using_r_{n}" for n in names}
        rproj = other.select(*[col(n).alias(tmp[n]) if n in tmp else col(n)
                               for n in other.columns])
        joined = L.LogicalJoin(self._plan, rproj._plan,
                               [col(n) for n in names],
                               [col(tmp[n]) for n in names], how, condition)
        out: List[Expression] = []
        for n in names:
            if how == "right_outer":
                out.append(col(tmp[n]).alias(n))
            elif how == "full_outer":
                out.append(Coalesce(col(n), col(tmp[n])).alias(n))
            else:
                out.append(col(n))
        out += [col(n) for n in self.columns if n not in names]
        out += [col(n) for n in other.columns if n not in names]
        return self._with(L.LogicalProject(out, joined))

    def sort(self, *orders) -> "DataFrame":
        norm = []
        for o in orders:
            if isinstance(o, tuple):
                e = _to_expr(o[0])
                norm.append((e,) + tuple(o[1:]))
            else:
                norm.append((_to_expr(o), True))
        return self._with(L.LogicalSort(norm, self._plan))

    order_by = sort
    orderBy = sort

    def limit(self, n: int, offset: int = 0) -> "DataFrame":
        if isinstance(self._plan, L.LogicalSort) and self._plan.limit is None:
            # sort+limit collapses to TopN (reference GpuTopN, limit.scala:351)
            return self._with(L.LogicalSort(self._plan.orders,
                                            self._plan.children[0],
                                            limit=n, offset=offset))
        return self._with(L.LogicalLimit(n, self._plan, offset))

    def with_windows(self, *window_exprs) -> "DataFrame":
        """Append window-function columns: (WindowExpression, name) pairs
        (the pyspark F.xxx().over(w) surface)."""
        named = []
        for i, we in enumerate(window_exprs):
            if isinstance(we, tuple):
                named.append(we)
            else:
                named.append((we, f"{we.fn.name}_{i}"))
        return self._with(L.LogicalWindow(named, self._plan))

    def explode(self, column, alias: str = "col",
                outer: bool = False) -> "DataFrame":
        """One output row per array element; empty/null arrays drop the
        row (outer=True keeps it with a null element). PySpark's
        select(explode(c)) surface, keeping the other columns."""
        return self._with(L.LogicalGenerate(_to_expr(column), self._plan,
                                            outer=outer, elem_name=alias))

    def posexplode(self, column, alias: str = "col", pos_name: str = "pos",
                   outer: bool = False) -> "DataFrame":
        return self._with(L.LogicalGenerate(_to_expr(column), self._plan,
                                            outer=outer, position=True,
                                            elem_name=alias,
                                            pos_name=pos_name))

    def union(self, other: "DataFrame") -> "DataFrame":
        return self._with(L.LogicalUnion(self._plan, other._plan))

    def distinct(self) -> "DataFrame":
        return self._with(L.LogicalAggregate(
            [col(n) for n in self.columns], [], self._plan))

    def repartition(self, n_partitions: int) -> "DataFrame":
        """Round-robin repartition through the host shuffle (Spark
        df.repartition(n); reference GpuRoundRobinPartitioning)."""
        return self._with(L.LogicalRepartition(n_partitions, self._plan,
                                               mode="roundrobin"))

    def coalesce(self, n_partitions: int = 1) -> "DataFrame":
        """Collapse to a single partition (Spark df.coalesce(1);
        reference GpuSinglePartitioning)."""
        assert n_partitions == 1, "only coalesce(1) is supported"
        return self._with(L.LogicalRepartition(1, self._plan,
                                               mode="single"))

    def sample(self, fraction: float, seed: int = 42) -> "DataFrame":
        """Bernoulli sample (Spark df.sample; reference GpuSampleExec)."""
        return self._with(L.LogicalSample(fraction, seed, self._plan))

    def cache(self) -> "DataFrame":
        """Materialize-once columnar cache (reference
        ParquetCachedBatchSerializer / GpuInMemoryTableScanExec): the
        first action on the returned frame runs this plan and stores
        compressed host frames; later actions re-scan the cache. Call
        `.unpersist()` on the returned frame to drop it."""
        from ..exec.cache import CachedRelation
        rel = CachedRelation(self._exec, self.schema)
        out = self._with(L.LogicalScan(rel))
        out._cached_relation = rel
        return out

    def unpersist(self) -> "DataFrame":
        rel = getattr(self, "_cached_relation", None)
        if rel is not None:
            rel.unpersist()
        return self

    # -- actions -----------------------------------------------------------
    def _exec(self):
        from .. import faults
        from ..columnar import upload
        from ..obs import dispatch as obs_dispatch
        from ..obs import events as obs_events
        from ..obs import history as obs_history
        from ..obs import telemetry
        from ..parallel.mesh import set_active_mesh
        set_active_conf(self.session.conf)
        set_active_mesh(self.session.mesh)
        obs_events.configure(self.session.conf)
        telemetry.configure(self.session.conf)
        obs_dispatch.configure(self.session.conf)
        obs_history.configure(self.session.conf)
        faults.configure(self.session.conf)
        upload.configure(self.session.conf)
        return TpuOverrides(self.session.conf).apply(self._plan)

    def collect(self) -> List[tuple]:
        """Materialize results, with task-level re-execution (ISSUE 4):
        a transient failure — an injected/real device error outside the
        OOM lane, a checksum-quarantined spill file or shuffle block, a
        dying IO path past its bounded retries — discards the attempt
        and re-runs the whole plan from the sources, up to
        spark.rapids.tpu.task.maxAttempts times. Every attempt rebuilds
        its exec tree in _collect_once, so attempts share no state.

        Lifecycle governor (ISSUE 6): the whole drive — including every
        retry attempt and its backoff — runs under one QueryContext, so
        spark.rapids.tpu.query.timeoutMs bounds the query's total
        wall-clock and TpuSession.cancel_query() can unwind it
        cooperatively from another thread.

        Workload governor (ISSUE 7): with
        spark.rapids.tpu.workload.enabled the query is admitted through
        the process-wide fair admission queue first — inside the
        governed scope, so the deadline spans queue wait and
        cancel_query() dequeues a queued query (phase admission-wait).
        A shed arrival (queue full / admission timeout / known-degraded
        device) raises QueryAdmissionError fast."""
        import time as _time

        from ..config import PHASES_ENABLED
        from ..exec import lifecycle, workload
        from ..exec.task_retry import with_task_retry
        from ..obs import history as obs_history
        from ..obs import phase as obs_phase
        with lifecycle.governed(self.session.conf,
                                owner=self.session._lifecycle_owner) as ctx:
            # wall-clock phase attribution (ISSUE 17): the ledger spans
            # the WHOLE governed drive — admission wait, every retry
            # attempt and its backoff — so sum(phases) == query wall
            if self.session.conf.get(PHASES_ENABLED):
                obs_phase.attach(ctx)
            # progress watchdog (ISSUE 20): armed only when
            # stall.timeoutMs > 0, after the ledger (its query_stalled
            # event reads the dominant phase mid-flight); stopped in
            # the same finally chain that closes the query books
            from ..exec import speculation_shield
            watchdog = speculation_shield.watchdog_for(
                ctx, self.session.conf)
            # history capsule (ISSUE 17): default-off = this one
            # pointer check; the counter snapshot is read only when a
            # store is actually installed
            store = obs_history.active_store()
            before = obs_history.process_counters() \
                if store is not None else None
            if store is not None:
                # a query failing before its harvest must not write the
                # PREVIOUS query's plan/metrics into its capsule
                self.session._last_harvest = None
            t0 = _time.perf_counter_ns()
            ok = False
            try:
                with workload.admitted(self.session.conf, ctx):
                    out = with_task_retry(
                        lambda attempt: self._collect_once(),
                        conf=self.session.conf)
                    ok = True
                    return out
            finally:
                if watchdog is not None:
                    watchdog.stop()
                self._finish_query(ctx, ok, store, before,
                                   _time.perf_counter_ns() - t0)

    def _finish_query(self, ctx, ok, store, before, fallback_wall_ns):
        """Query-end observability (ISSUE 17), inside collect's finally
        chain — close the phase ledger, emit the `query_phases` event,
        feed the SLO latency ring, append the history capsule. Must
        never raise (it would mask the query's real exception)."""
        from ..config import WORKLOAD_PRIORITY
        from ..exec.workload import PRIORITIES
        from ..obs import events as obs_events
        from ..obs import history as obs_history
        from ..obs import telemetry
        try:
            priority = str(self.session.conf.get(
                WORKLOAD_PRIORITY)).strip().lower()
            if priority not in PRIORITIES:
                priority = "interactive"
            ledger = getattr(ctx, "phase_ledger", None)
            phases = None
            wall_ns = fallback_wall_ns
            if ledger is not None:
                ledger.finish()
                wall_ns = ledger.wall_ns
                phases = ledger.snapshot()
                # events-plane id (the final attempt's query_scope),
                # NOT ctx.ctx_id: the two counters drift after any
                # retry, and the log must join on one id space
                obs_events.emit(
                    "query_phases",
                    query=getattr(ctx, "events_qid", None) or ctx.ctx_id,
                    ok=ok, wall_ns=wall_ns, attempts=ctx.attempt_no,
                    priority=priority, phases=phases)
            if ok:
                # only completed queries feed the SLO percentiles: a
                # shed/failed arrival returns in microseconds and would
                # drag p50 down, under-reporting real latency
                telemetry.note_query_latency(priority, wall_ns)
            if store is not None:
                profile = self.session.last_query_profile()
                deltas = obs_history.counters_delta(
                    before, obs_history.process_counters())
                mesh = self.session.mesh
                store.append(obs_history.build_capsule(
                    query_id=ctx.ctx_id,
                    mesh_devices=int(mesh.devices.size)
                    if mesh is not None else 1,
                    fingerprint=getattr(profile, "fingerprint", None),
                    ok=ok, priority=priority, attempts=ctx.attempt_no,
                    wall_ns=wall_ns, phases=phases,
                    stats=ctx.runtime_stats,
                    summary=self.session.last_query_metrics(),
                    deltas=deltas))
        except Exception:  # noqa: BLE001 — observability never masks
            pass

    def _collect_once(self) -> List[tuple]:
        import time as _time

        from ..exec import lifecycle
        from ..exec.task_metrics import QueryHarvest, query_snapshot
        from ..obs import events as obs_events
        from ..obs import op_span
        from ..obs.stats import RuntimeStats
        with obs_events.query_scope() as qid:
            # conversion inside the scope: plan_fallback / plan_not_on_tpu
            # events must carry this query's id
            with op_span("session.plan", phase="plan"):
                plan = self._exec()
            # runtime statistics + live progress (ISSUE 11): a fresh
            # RuntimeStats per attempt (a failed attempt's partial
            # distributions must not pollute the retry's), and the root
            # op id so note_batch counts only real query output
            ctx = lifecycle.current_context()
            stats = RuntimeStats()
            if ctx is not None:
                ctx.runtime_stats = stats
                ctx.root_op_id = plan._op_id
                # query_phases (emitted after the scope closes) must
                # carry the same id as this attempt's query_start/
                # query_end so the event log joins per query
                ctx.events_qid = qid
            before = query_snapshot()
            obs_events.emit("query_start", root=type(plan).__name__)
            t0 = _time.perf_counter_ns()
            ok = False
            try:
                out = plan.collect()
                ok = True
                return out
            finally:
                # metrics are harvested even on failure: a half-run
                # query's spill/retry spend is exactly what an operator
                # debugging it wants to see. Only host values are taken
                # here; the roll-up waits for its first reader
                try:
                    self.session._last_harvest = QueryHarvest(
                        plan, before, statistics=stats,
                        phases=ctx.phase_ledger
                        if ctx is not None else None)
                except Exception:  # noqa: BLE001 — must never mask
                    pass
                obs_events.emit(
                    "query_end", root=type(plan).__name__, ok=ok,
                    wall_ns=_time.perf_counter_ns() - t0)

    def to_arrow(self):
        import pyarrow as pa
        tables = [b.to_arrow() for b in self._exec().execute()]
        if not tables:
            from ..types import to_arrow as t2a
            return pa.table({f.name: pa.array([], t2a(f.data_type))
                             for f in self.schema.fields})
        return pa.concat_tables(tables)

    def to_pydict(self) -> Dict:
        t = self.to_arrow()
        return {name: t.column(name).to_pylist() for name in t.column_names}

    def to_jax(self) -> Dict:
        """ML handoff (reference ColumnarRdd / spark-rapids-ml bridge):
        materialize the query DEVICE-RESIDENT as a dict of
        {name: (data, validity)} jnp arrays, trimmed to the row count —
        zero host round trip, ready to feed a JAX model. Fixed-width
        columns only (strings need tokenization first)."""
        batches = list(self._exec().execute())
        out: Dict = {}
        from ..exec.coalesce import concat_batches
        from ..columnar.batch import empty_batch
        if not batches:
            merged = empty_batch(self.schema)
        elif len(batches) == 1:
            merged = batches[0]
        else:
            merged = concat_batches(batches, self.schema)
        n = merged.num_rows_host
        for f, c in zip(self.schema.fields, merged.columns):
            assert f.data_type.is_fixed_width, \
                f"to_jax needs fixed-width columns, {f.name} is " \
                f"{f.data_type.simple_name()}"
            out[f.name] = (c.data[:n], c.validity[:n])
        return out

    def count(self) -> int:
        from ..expr.aggexprs import Count
        rows = self._with(L.LogicalAggregate([], [(Count(), "count")],
                                             self._plan)).collect()
        return rows[0][0]

    def explain(self) -> str:
        return TpuOverrides(self.session.conf).explain(self._plan)

    def logical_plan(self) -> L.LogicalPlan:
        return self._plan

    def write_parquet(self, path, partition_by: Optional[Sequence[str]] = None):
        from ..io.parquet import write_parquet
        write_parquet(self, path, partition_by=partition_by)

    def write_csv(self, path, header: bool = True, delimiter: str = ","):
        from ..io.csv import write_csv
        write_csv(self, path, header=header, delimiter=delimiter)

    def write_json(self, path):
        from ..io.json import write_json
        write_json(self, path)

    def write_orc(self, path):
        from ..io.orc import write_orc
        write_orc(self, path)

    def write_avro(self, path, codec: str = "deflate"):
        from ..io.avro import write_avro
        write_avro(self, path, codec=codec)

    def write_delta(self, path, mode: str = "append",
                    partition_by: Optional[Sequence[str]] = None):
        from ..delta import write_delta
        write_delta(self, path, mode=mode, partition_by=partition_by)

    def write_iceberg(self, path, mode: str = "append"):
        from ..io.iceberg import write_iceberg
        write_iceberg(self, path, mode=mode)

    def write_hive_text(self, path, **options):
        from ..io.hivetext import write_hive_text
        write_hive_text(self, path, **options)

    def _with(self, plan: L.LogicalPlan) -> "DataFrame":
        return DataFrame(plan, self.session)


def _to_schema(schema) -> Schema:
    assert isinstance(schema, Schema), \
        "pandas UDF output schema must be a Schema"
    return schema


def _named_pandas_fns(specs):
    """Normalize (fn, name, result_type, inputs...) pandas-UDF specs: the
    inputs may be varargs or one list/tuple."""
    named = []
    for fn, name, rt, *ins in specs:
        exprs = [_to_expr(e) for e in
                 (ins[0] if len(ins) == 1
                  and isinstance(ins[0], (list, tuple)) else ins)]
        named.append((fn, name, rt, exprs))
    return named


class CoGroupedData:
    def __init__(self, left: "GroupedData", right: "GroupedData"):
        self.left = left
        self.right = right

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """fn(left_group_df, right_group_df) -> DataFrame per key in
        either input (reference GpuFlatMapCoGroupsInPandasExec)."""
        return self.left.df._with(L.LogicalCoGroupedMapInPandas(
            self.left.keys, self.right.keys, fn, _to_schema(schema),
            self.left.df._plan, self.right.df._plan))

    applyInPandas = apply_in_pandas


class GroupedData:
    def __init__(self, keys: List[Expression], df: DataFrame):
        self.keys = keys
        self.df = df

    def apply_in_pandas(self, fn, schema) -> DataFrame:
        """Spark df.groupBy(...).applyInPandas(fn, schema): fn receives
        each group as a pandas DataFrame and returns a DataFrame matching
        `schema` (reference GpuFlatMapGroupsInPandasExec.scala:79)."""
        return self.df._with(L.LogicalGroupedMapInPandas(
            self.keys, fn, _to_schema(schema), self.df._plan))

    applyInPandas = apply_in_pandas

    def agg_in_pandas(self, *aggs) -> DataFrame:
        """Grouped pandas aggregates: each agg is (fn, name, result_type,
        input columns/exprs...); fn receives one pandas Series per input
        and returns a scalar (reference GpuAggregateInPandasExec)."""
        key_names = [getattr(k, "name", f"key_{i}")
                     for i, k in enumerate(self.keys)]
        return self.df._with(L.LogicalAggregateInPandas(
            self.keys, key_names, _named_pandas_fns(aggs), self.df._plan))

    def cogroup(self, other: "GroupedData") -> "CoGroupedData":
        """Spark df.groupBy(k).cogroup(other.groupBy(k))."""
        return CoGroupedData(self, other)

    def agg(self, *aggs) -> DataFrame:
        named: List[Tuple[AggregateFunction, str]] = []
        for i, a in enumerate(aggs):
            if isinstance(a, tuple):
                named.append(a)
            else:
                assert isinstance(a, AggregateFunction), a
                default = f"{a.name}({', '.join(map(repr, a.inputs))})" \
                    if a.inputs else f"{a.name}(*)"
                named.append((a, default))
        return self.df._with(L.LogicalAggregate(self.keys, named,
                                                self.df._plan))
