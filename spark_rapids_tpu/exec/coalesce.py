"""Batch coalescing — reference GpuCoalesceBatches.scala:875 /
AbstractGpuCoalesceIterator:250. Concatenates small batches up to the target
batch size (spark.rapids.sql.batchSizeBytes) so downstream kernels run at
MXU-friendly sizes. Pending input is held as SpillableBatch so the coalesce
window never pins more HBM than the catalog allows."""

from __future__ import annotations

from typing import Iterator, List, Optional

import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch
from ..columnar.column import bucket_capacity
from ..config import active_conf
from ..memory.retry import with_retry_no_split
from ..memory.spillable import SpillableBatch
from ..ops.basic import concat_columns, sanitize
from ..types import Schema
from ..obs import dispatch as obs_dispatch
from ..obs.dispatch import instrument
from . import adaptive
from .base import (COMPILE_TIME, CONCAT_TIME, DEBUG, DISPATCH_METRICS,
                   NUM_DISPATCHES, NUM_INPUT_BATCHES, NUM_INPUT_ROWS,
                   PIPELINE_STAGE_METRICS, TpuExec)


from functools import partial


@partial(instrument, label="coalesce.concat_pair",
         static_argnums=(2,))
def _concat_pair(a: ColumnarBatch, b: ColumnarBatch, cap: int
                 ) -> ColumnarBatch:
    cols = [concat_columns(ca, cb, a.num_rows, b.num_rows, cap)
            for ca, cb in zip(a.columns, b.columns)]
    return ColumnarBatch(cols, a.num_rows + b.num_rows, a.schema)


def concat_batches(batches: List[ColumnarBatch], schema: Schema
                   ) -> ColumnarBatch:
    """Concatenate active rows of all batches into one batch whose capacity
    is the bucket of the total. Tree-shaped pairwise reduction: each row is
    copied O(log k) times instead of the O(k) of a left fold, and each
    round runs ONE compiled concat program per capacity-shape pair (jit
    cache keyed on shapes + static out capacity). A pair's fixed-width
    lanes are two contiguous block copies at a traced row offset
    (ops.basic.concat_columns), so a round costs its bytes, not its rows.
    Known host row counts take the exact lane: the output bucket is sized
    to the rows and may be smaller than an input's capacity."""
    assert batches
    level = batches
    while len(level) > 1:
        nxt_level = []
        for i in range(0, len(level) - 1, 2):
            a, b = level[i], level[i + 1]
            if a._host_rows is not None and b._host_rows is not None:
                # exact: tight output bucket from known row counts
                rows = a._host_rows + b._host_rows
                cap = bucket_capacity(rows)
                out = _concat_pair(a, b, cap)
                nxt_level.append(ColumnarBatch(out.columns, rows, schema))
            else:
                # device row counts: don't sync — bucket by capacities
                cap = bucket_capacity(a.capacity + b.capacity)
                out = _concat_pair(a, b, cap)
                nxt_level.append(ColumnarBatch(out.columns, out.num_rows,
                                               schema))
        if len(level) % 2:
            nxt_level.append(level[-1])
        level = nxt_level
    return level[0]


class CoalesceBatchesExec(TpuExec):
    def __init__(self, child: TpuExec, target_bytes: Optional[int] = None):
        super().__init__(child)
        self.target_bytes = target_bytes or active_conf().batch_size_bytes

    #: dictionary-encoded batches flow through untouched on the
    #: single-batch path; a real multi-batch concat materializes first
    #: inside flush() — per-batch dictionaries differ, and
    #: concat_columns requires one shared payload
    consumes_encoded = True

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def additional_metrics(self):
        return (CONCAT_TIME, (NUM_INPUT_ROWS, DEBUG),
                (NUM_INPUT_BATCHES, DEBUG)) + PIPELINE_STAGE_METRICS \
            + DISPATCH_METRICS

    def _fingerprint_extras(self):
        # its concat program is a module-level site (process-cached
        # already); the extras exist so PARENT subtrees stay cacheable
        return (self.target_bytes,)

    @property
    def runs_own_pipeline_stage(self) -> bool:
        # wraps its input in a stage of its own — or, when the child
        # already runs one, that stage feeds this exec directly: either
        # way the output edge is covered and a consumer must not stack
        # another stage on it
        return True

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        in_rows = self.metrics[NUM_INPUT_ROWS]
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        concat_time = self.metrics[CONCAT_TIME]
        pending: List[SpillableBatch] = []
        pending_bytes = 0

        def flush() -> Optional[ColumnarBatch]:
            nonlocal pending, pending_bytes
            if not pending:
                return None
            # the concat program is a module-level dispatch site: the
            # metric scope attributes its dispatches to this exec
            with concat_time.ns_timer(), obs_dispatch.metric_scope(
                    self.metrics[NUM_DISPATCHES],
                    self.metrics[COMPILE_TIME]):
                spillables, pending = pending, []
                pending_bytes = 0
                def do(items):
                    batches = [s.get_batch() for s in items]
                    try:
                        if len(batches) > 1:
                            from ..columnar.encoded import \
                                materialize_batch
                            batches = [materialize_batch(b, seam="concat")
                                       for b in batches]
                        return concat_batches(batches, self.output_schema)
                    finally:
                        for s in items:
                            s.release()
                try:
                    return with_retry_no_split(spillables, do)
                finally:
                    # close on BOTH paths: an exhausted retry must not
                    # leave the swapped-out set registered in the
                    # catalog (the outer finally only sees `pending`)
                    for s in spillables:
                        s.close()

        # pipelined input (ISSUE 3): upstream compute of batch N+1 runs
        # on the producer thread while this operator accumulates /
        # concatenates batch N — unless the child already runs its own
        # stage (TpuExec.runs_own_pipeline_stage): stacking a second one
        # on the same edge would double threads and live prefetched
        # device batches for zero extra overlap.
        depth = 0 if self.child.runs_own_pipeline_stage else None
        stage = self.pipeline_stage(self.child.execute(), "coalesce",
                                    depth=depth)
        try:
            for batch in stage:
                in_batches.add(1)
                if batch._host_rows is not None:
                    in_rows.add(batch._host_rows)
                else:
                    in_rows.add_device(batch.num_rows)
                size = batch.device_size_bytes()
                # OOM-feedback right-sizing (ISSUE 19): a with_retry
                # SPLIT earlier in this query shrank the governed batch
                # target — honor it here so later batches stop
                # re-triggering the retry lane. One context-pointer
                # read per batch, no conf access.
                target = self.target_bytes
                override = adaptive.batch_target_override()
                if override is not None and override < target:
                    target = override
                if pending and pending_bytes + size > target:
                    yield flush()
                pending.append(SpillableBatch.from_batch(batch))
                pending_bytes += size
                if pending_bytes >= target:
                    yield flush()
            tail = flush()
            if tail is not None:
                yield tail
        finally:
            stage.close()
            for s in pending:
                s.close()
