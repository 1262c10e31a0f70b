"""The gather engine (ISSUE 8): one accounting chokepoint for every
materializing row gather in the engine.

Three jobs:

1. **One entry point** — `gather_rows` is the packed row gather every
   exec calls: the XLA formulation (ops/rowpack.py), recorded.

2. **Structural accounting** — every routed gather records (count,
   packed, bytes-moved estimate) into a thread-local recorder while a
   wired exec's `GatherTracker.observe` scope is active. Recording
   happens at TRACE time (the calls live inside jit programs); the
   tracker memoizes the structural counts per static program key and
   replays them on cache hits, so the per-iteration `numGathers` /
   `gatherTimeNs` metrics stay exact under jit caching. This is what
   the gather-count regression test asserts (counts, not timing —
   CPU-runnable).

3. **Batch-level helper** — `gather_batch_columns` is the ONE
   implementation of "gather a batch of columns by an index map":
   fixed-width columns ride a single packed row gather, varlen/nested
   columns keep the per-column path. The join emit, the filter/output
   compaction (ops/basic.compact_columns) and the window sort
   permutation all route through it, so the gather-count drop is
   engine-wide.
"""

from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import List, Optional, Sequence

import jax.numpy as jnp

__all__ = [
    "GatherStats", "GatherTracker", "gather_rows", "gather_lane_matrix",
    "gather_batch_columns", "record", "recording", "counters",
]

_tls = threading.local()


class GatherStats:
    """Structural gather totals: number of materializing gathers, how
    many rode a packed (multi-column) row gather, and the estimated
    bytes moved."""

    __slots__ = ("count", "packed_count", "bytes")

    def __init__(self, count: int = 0, packed_count: int = 0,
                 nbytes: int = 0):
        self.count = count
        self.packed_count = packed_count
        self.bytes = nbytes

    def add(self, other: "GatherStats") -> None:
        self.count += other.count
        self.packed_count += other.packed_count
        self.bytes += other.bytes

    def copy(self) -> "GatherStats":
        return GatherStats(self.count, self.packed_count, self.bytes)

    def delta(self, since: "GatherStats") -> "GatherStats":
        return GatherStats(self.count - since.count,
                           self.packed_count - since.packed_count,
                           self.bytes - since.bytes)


#: process-cumulative totals (bench.py embeds per-record deltas)
_proc = GatherStats()
_proc_lock = threading.Lock()


def counters() -> dict:
    with _proc_lock:
        return {"count": _proc.count, "packed_count": _proc.packed_count,
                "bytes": _proc.bytes}


def record(n: int = 1, packed: bool = False, nbytes: int = 0) -> None:
    """Note a routed gather on the active recorder (one pointer check
    when no wired exec is observing)."""
    rec = getattr(_tls, "rec", None)
    if rec is None:
        return
    rec.count += n
    if packed:
        rec.packed_count += n
    rec.bytes += nbytes


@contextmanager
def recording():
    """Collect structural gather counts for the enclosed region (the
    tracker's trace-time capture; also used directly by tests)."""
    prev = getattr(_tls, "rec", None)
    rec = GatherStats()
    _tls.rec = rec
    try:
        yield rec
    finally:
        _tls.rec = prev


class GatherTracker:
    """Per-exec gather accounting: wraps the exec's gather-bearing
    kernel dispatches, memoizing trace-time structural counts per
    static program key so jit cache hits replay the same counts.

    `numGathers` adds the structural count per dispatch; `gatherTimeNs`
    adds the dispatch wall-ns (the gather-bearing region, inclusive of
    the program's non-gather work — counts are the structural signal,
    time is the profile hint). `emit_event` writes one `gather_stats`
    event per exec execution with the totals since the last emission.
    """

    def __init__(self, num_metric=None, time_metric=None):
        self._num = num_metric
        self._time = time_metric
        self._memo = {}
        self._total = GatherStats()
        self._emitted = GatherStats()

    @contextmanager
    def observe(self, key):
        prev = getattr(_tls, "rec", None)
        rec = GatherStats()
        _tls.rec = rec
        t0 = time.perf_counter_ns()
        try:
            yield
        finally:
            _tls.rec = prev
            dt = time.perf_counter_ns() - t0
            if rec.count:
                # a trace happened inside: refresh the structural memo
                self._memo[key] = rec.copy()
            stats = self._memo.get(key)
            if stats is not None and stats.count:
                self._total.add(stats)
                with _proc_lock:
                    _proc.add(stats)
                if self._num is not None:
                    self._num.add(stats.count)
                if self._time is not None:
                    self._time.add(dt)

    def emit_event(self, op: str, op_id) -> None:
        """One `gather_stats` event with this exec's totals since the
        last emission (called at operator-iterator close, the
        pipeline-event convention)."""
        delta = self._total.delta(self._emitted)
        if not delta.count:
            return
        self._emitted = self._total.copy()
        from ..obs import events as obs_events
        if obs_events.active_bus() is None:
            return
        obs_events.emit("gather_stats", op=op, op_id=op_id,
                        count=delta.count, packed=delta.packed_count,
                        bytes=delta.bytes)


# ---------------------------------------------------------------------------
# routed primitives
# ---------------------------------------------------------------------------


def gather_rows(plan, imat, fmat, idx):
    """Packed row gather (rowpack.gather_rows, recorded)."""
    from .rowpack import gather_rows as _xla_gather_rows
    lanes = int(imat.shape[1]) + (2 * int(fmat.shape[1])
                                  if fmat is not None else 0)
    record(1, packed=True, nbytes=int(idx.shape[0]) * lanes * 4)
    return _xla_gather_rows(plan, imat, fmat, idx)


def gather_lane_matrix(mat, idx):
    """Row gather of a small index-lane matrix (the join emit's ONE
    index materialization): rows out of range read row 0 — callers mask
    by their own selection predicate."""
    cap = mat.shape[0]
    record(1, packed=True,
           nbytes=int(idx.shape[0]) * int(mat.shape[1]) * 4)
    in_range = (idx >= 0) & (idx < cap)
    safe = jnp.where(in_range, idx, 0)
    return mat[safe]


def gather_batch_columns(columns: Sequence, idx, num_rows=None,
                         byte_caps: Optional[Sequence] = None,
                         out_valid=None) -> List:
    """Gather a batch's columns by an int32 index map: fixed-width
    columns via ONE packed row gather, varlen/nested via the per-column
    path. `num_rows` masks output slots >= num_rows; `out_valid` masks
    by predicate; indices already -1-masked pass neither."""
    from .basic import active_mask, gather_column
    from .rowpack import pack_rows, split_packable, unpack_rows
    caps = byte_caps or (None,) * len(columns)
    midx = idx
    if num_rows is not None:
        midx = jnp.where(active_mask(num_rows, idx.shape[0]), idx, -1)
    elif out_valid is not None:
        midx = jnp.where(out_valid, idx, -1)
    out: List = [None] * len(columns)
    p_idx, o_idx = split_packable(columns)
    if len(p_idx) > 1:
        plan, imat, fmat = pack_rows([columns[i] for i in p_idx])
        gi, gf = gather_rows(plan, imat, fmat, midx)
        for j, c in zip(p_idx, unpack_rows(plan, gi, gf)):
            out[j] = c
    else:
        o_idx = sorted(p_idx + o_idx)
    for j in o_idx:
        out[j] = gather_column(columns[j], midx, out_byte_capacity=caps[j])
    return out
