"""op_span — the engine's ONE span primitive (the NvtxWithMetrics analog,
reference NvtxWithMetrics.scala: one object that IS both the NVTX range
and the metric scope), on the profiler's clock.

One context manager:
  * opens a jax.profiler.TraceAnnotation, so the span is a host event in
    the profiler's own trace — on the device trace's clock, on whatever
    thread it runs — and an idle gap of the chip can be named by it,
  * with `phase=`, accrues the block's EXCLUSIVE time to that wall-clock
    phase (obs/phase.py: child time subtracted, global counters always,
    the query's PhaseLedger when one is attached; a span on a
    non-driving thread folds into the pipeline-stall budget),
  * appends a `span` event record (DEBUG level) to the event bus when
    logging is enabled.

Timing and accumulation happen even when the body raises — a failed
span's time is exactly what an operator debugging it wants attributed
(same try/finally discipline as TpuMetric.ns_timer).

The span names the engine opens (docs/observability.md has the table):
`session.plan`, `scan.decode`, `upload.pack`, `upload.put`,
`result.fetch`, `exchange.map_write`, `exchange.ici_round`, and
`sync.<site>` around every device-to-host read
(`obs.dispatch.host_read`).
`utils.tracing.annotate_op` stays as the annotation-only form for the
per-batch operator loop (exec/base._drive).
"""

from __future__ import annotations

import contextlib
import time
from typing import Any, Iterator, Optional

from ..utils.tracing import annotate_op
from . import events
from . import phase as obs_phase


@contextlib.contextmanager
def op_span(name: str, *, phase: Optional[str] = None,
            **fields: Any) -> Iterator[None]:
    bus = events.active_bus()
    t0 = time.perf_counter_ns()
    ok = True
    try:
        with annotate_op(name), \
                (obs_phase.span(phase) if phase is not None
                 else contextlib.nullcontext()):
            yield
    except BaseException:
        ok = False
        raise
    finally:
        if bus is not None:
            bus.emit("span", op=name, wall_ns=time.perf_counter_ns() - t0,
                     ok=ok, **fields)
