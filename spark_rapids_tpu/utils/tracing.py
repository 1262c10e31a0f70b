"""Trace annotation — the engine's cheapest xprof surface (reference
analog: the NVTX ranges in GpuExec; SURVEY §5).

`annotate_op(name)` is a jax.profiler.TraceAnnotation around each
operator's per-batch device work, so xprof timelines show engine-level
operator names (ProjectExec, AggregateExec, ...) over the XLA ops they
launched — the TPU equivalent of the reference's NVTX ranges in Nsight.
It is the annotation-only form, for the per-batch loop of
exec/base._drive; every other timed region opens
`spark_rapids_tpu.obs.op_span`, which adds phase, metric and event
accounting to the same annotation. A whole trace is taken from outside
the engine (`jax.profiler`; the benchmark's `lib/trace.capture`).
"""

from __future__ import annotations

import contextlib
from typing import Iterator


@contextlib.contextmanager
def annotate_op(name: str) -> Iterator[None]:
    """Named trace annotation (no-op cost when no trace is active)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield
