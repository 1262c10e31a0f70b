"""Task-level metrics roll-up — reference GpuTaskMetrics
(GpuTaskMetrics.scala:81-103: semWaitTimeNs, retryCount,
splitAndRetryCount, spill/readSpill sizes accumulated per task and
published into Spark task metrics).

Standalone, a "task" is one driven query: `query_snapshot()` captures
the process-global accumulators (admission-semaphore wait, OOM-retry
counters, spill volumes) before execution, and `query_summary()` diffs
them after and rolls the per-operator metric registries of the executed
TpuExec tree into one flat per-query dict. The session API surfaces it
as `TpuSession.last_query_metrics()` after every `DataFrame.collect()`
(ISSUE 1 satellite, VERDICT Missing #8). A collect leaves a
`QueryHarvest` (ISSUE 40): the host values of the query's end, with the
roll-up — whose reads of pending device row counts are host syncs —
built on the first call of either accessor, so a collect nobody
inspects pays none of them.

Shape of the summary:
- task-scoped globals (diffed):  semWaitTimeNs, retryCount,
  splitAndRetryCount, spilledDeviceBytes, spilledHostBytes
- per-metric sums over the operator tree:  total.<metricName>
- per-operator breakdown:  ops.<Path>.<metricName>  (same addressing as
  TpuExec.all_metrics)
"""

from __future__ import annotations

from typing import Dict, Optional

from .base import TpuExec, metrics_level_from_conf


def query_snapshot() -> Dict[str, int]:
    """Process-global accumulators BEFORE a query, for delta-ing."""
    from ..memory.catalog import buffer_catalog
    from ..memory.retry import task_retry_counts
    from ..memory.semaphore import tpu_semaphore
    retry, split_retry = task_retry_counts()
    cat = buffer_catalog()
    return {
        "semWaitTimeNs": tpu_semaphore().total_wait_ns,
        "retryCount": retry,
        "splitAndRetryCount": split_retry,
        "spilledDeviceBytes": cat.spilled_device_bytes,
        "spilledHostBytes": cat.spilled_host_bytes,
    }


def query_summary(root: TpuExec,
                  before: Dict[str, int] | None = None,
                  after: Dict[str, int] | None = None,
                  level: Optional[int] = None) -> Dict[str, int]:
    """Roll one executed plan's metrics into a per-query summary.

    `before`: a query_snapshot() taken before execution; the summary
    reports the DELTA of each global accumulator (what THIS query spent,
    the analog of per-task attribution in GpuTaskMetrics). Without it
    the raw running totals are reported. `after`: the snapshot of the
    query's end (None = now); `level`: the metrics level (None = the
    conf's).
    """
    after = query_snapshot() if after is None else after
    out: Dict[str, int] = {}
    for k, v in after.items():
        out[k] = v - (before or {}).get(k, 0)

    per_op = root.all_metrics(level)
    totals: Dict[str, int] = {}
    for path, value in per_op.items():
        name = path.rsplit(".", 1)[1]
        totals[name] = totals.get(name, 0) + value
    for name in sorted(totals):
        out[f"total.{name}"] = totals[name]
    for path in sorted(per_op):
        out[f"ops.{path}"] = per_op[path]
    return out


def _tree_metrics(node: TpuExec):
    yield from node.metrics.values()
    for c in node.children:
        yield from _tree_metrics(c)


class QueryHarvest:
    """What one collect() leaves for `last_query_metrics()` /
    `last_query_profile()`: the accumulator snapshots of the query's
    start and end, the metrics level, statistics and phase ledger, every
    operator metric as it stood at the end (its host value and the device
    scalars pending on it, none of them read), and the executed plan. The
    roll-up is built from those on the first call of either accessor and
    cached (the plan is then let go), so it reads what the query's end
    held even where the unwind of a failed query adds to a metric later.
    A build that raises reads as None: observability never masks."""

    __slots__ = ("_plan", "_before", "_after", "_level", "_stats",
                 "_phases", "_ends", "_metrics", "_profile")

    def __init__(self, plan: TpuExec, before: Dict[str, int], *,
                 statistics=None, phases=None):
        self._plan = plan
        self._ends = [(m, m._value, list(m._pending))
                      for m in _tree_metrics(plan)]
        self._before = before
        self._after = query_snapshot()
        self._level = metrics_level_from_conf()
        self._stats = statistics
        self._phases = phases
        self._metrics = None
        self._profile = None

    def _build(self) -> None:
        plan, self._plan = self._plan, None
        if plan is None:
            return
        from ..obs.profile import QueryProfile
        for m, value, pending in self._ends:
            m._value, m._pending = value, pending
        self._ends = None
        try:
            self._metrics = query_summary(plan, self._before, self._after,
                                          self._level)
            self._profile = QueryProfile(plan, self._metrics,
                                         level=self._level,
                                         statistics=self._stats,
                                         phases=self._phases)
        except Exception:  # noqa: BLE001 — must never mask
            self._metrics = self._profile = None

    def metrics(self) -> Optional[Dict[str, int]]:
        self._build()
        return self._metrics

    def profile(self):
        self._build()
        return self._profile
