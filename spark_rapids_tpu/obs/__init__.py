"""Structured observability — the engine's analog of the reference's
GpuMetric/GpuTaskMetrics/NVTX stack joined into one subsystem (ISSUE 2):

  * `events` — process-wide JSONL event bus (query begin/end, operator
    spans, semaphore waits, spills, OOM retries, plan fallbacks,
    exchange volumes), gated by the
    spark.rapids.tpu.eventLog.{enabled,dir,level} confs and costing one
    pointer check per batch when disabled.
  * `span` — op_span(name, phase=, metric=): the ONE span primitive —
    one context manager that writes the host event into the profiler's
    trace (TraceAnnotation: the device trace's clock, any thread),
    accrues the block's exclusive time to a wall-clock phase, bumps a
    TpuMetric, and appends a DEBUG `span` event record. The ingest
    path's boundaries open it: `session.plan` (phase `plan`),
    `scan.decode` (`scan-decode`), `upload.pack` / `upload.put`
    (`upload`), `result.fetch` (`device-wait`).
  * `phase` — the closed wall-clock phase ledger under `op_span`
    (ISSUE 17): `sum(phases) == wall_ns` per governed query.
  * `profile` — QueryProfile: the executed plan tree annotated with
    per-operator metrics, with text (explain-with-metrics) and JSON
    renderers plus `.statistics()`; surfaced as
    TpuSession.last_query_profile().
  * `stats` — runtime statistics collection (ISSUE 11): per-exchange
    map-output/partition row+byte distributions as log2 histograms,
    exact per-partition totals and skew summaries, carried per query
    on the governing QueryContext (`stats.current()`) — the data plane
    the AQE loop (ROADMAP 4) replans from.
  * `telemetry` — live metrics registry + sampler (ISSUE 11): per-owner
    HBM attribution, link bytes, queue/semaphore/breaker/spill gauges
    in bounded ring-buffer series, flushed as telemetry_sample events;
    gated by spark.rapids.tpu.telemetry.{enabled,intervalMs,historySize}.
  * `dispatch` — the jit dispatch ledger (ISSUE 13): every engine
    program dispatch routes through `dispatch.instrument`, recording
    per stable program key (label x arg-shape bucket x platform) the
    dispatch count, first-trace vs cache-hit split, trace/compile cost
    and donated/retained bytes; emits `program_compile` per fresh trace
    and `recompile_storm` on shape-bucket churn. The whole-stage-
    compilation baseline (ROADMAP 2) reads
    QueryProfile.dispatch_summary() on top of it. Each program record
    names the XLA `module` it carries in a device trace, and
    `dispatch.module_labels()` maps module -> labels, so device time
    joins to the engine's own names.

Render an event-log file with tools/profile_report.py (`--format json`
for the machine-readable summary) and telemetry samples with
tools/telemetry_export.py (Prometheus text format).
"""

from . import events  # noqa: F401
from .profile import QueryProfile  # noqa: F401
from .span import op_span  # noqa: F401
