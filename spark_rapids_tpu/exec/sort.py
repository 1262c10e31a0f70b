"""SortExec — reference GpuSortExec.scala:86 (per-batch sort) +
GpuOutOfCoreSortIterator:281 (spill-backed merge) + GpuTopN (limit.scala:351).

TPU shape: each input batch sorts with one lax.sort over order-key lanes.
Small merges concatenate all runs and re-sort (XLA sort on mostly-sorted
lanes is cheap). Big merges go out-of-core: runs stay spilled; a streamed
k-way merge keeps only MERGE_FAN_IN chunk heads device-resident, emits
every row that is provably globally final (lexicographically <= the
smallest not-yet-loaded key, compared on the sort's own order-key lanes),
and spills intermediate runs between passes — device footprint is bounded
by fan-in × chunk size regardless of input size.
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch
from ..columnar.column import (MIN_BUCKET, Column, StringColumn,
                               bucket_capacity)
from ..expr.core import BoundReference, Expression, resolve
from ..memory.retry import split_in_half_by_rows, with_retry, with_retry_no_split
from ..memory.spillable import SpillableBatch
from ..ops.basic import active_mask, slice_rows
from ..obs.dispatch import host_read
from ..obs.span import op_span
from ..ops.sort import (
    SortOrder, first_rows, lexsort_permutation, order_key_lanes,
    packed_key_lanes, sort_batch_columns, string_key_bytes,
    string_words_for,
)
from ..types import Schema
from .base import (DEBUG, DISPATCH_METRICS, GATHER_METRICS, GATHER_TIME,
                   NUM_GATHERS, NUM_INPUT_BATCHES, SORT_TIME, TpuExec)
from .coalesce import concat_batches


def _lex_leq(lanes: List, bound: List):
    """Per-row: lane tuple <= bound tuple (lexicographic, device)."""
    less = jnp.zeros(lanes[0].shape, jnp.bool_)
    eq = jnp.ones(lanes[0].shape, jnp.bool_)
    for lane, b in zip(lanes, bound):
        less = less | (eq & (lane < b))
        eq = eq & (lane == b)
    return less | eq


def _lex_less_scalar(a: List, b: List):
    less = jnp.asarray(False)
    eq = jnp.asarray(True)
    for x, y in zip(a, b):
        less = less | (eq & (x < y))
        eq = eq & (x == y)
    return less


def resolve_sort_orders(orders: Sequence, schema: Schema) -> List[SortOrder]:
    """Accepts SortOrder (ordinal-based) or (Expression, asc, nulls_first)."""
    out = []
    for o in orders:
        if isinstance(o, SortOrder):
            out.append(o)
            continue
        expr, asc, nf = (o + (None,))[:3] if isinstance(o, tuple) else (o, True, None)
        bound = resolve(expr, schema)
        assert isinstance(bound, BoundReference), \
            "planner must pre-project computed sort keys"
        out.append(SortOrder(bound.ordinal, asc, nf))
    return out


class SortExec(TpuExec):
    def __init__(self, orders: Sequence, child: TpuExec,
                 limit: Optional[int] = None):
        super().__init__(child)
        self.orders = resolve_sort_orders(orders, child.output_schema)
        self.limit = limit
        # one compiled sort program per (capacity bucket, key bytes);
        # the site is plan-fingerprint cached (ISSUE 14) so a rebuilt
        # identical plan reuses it across collects
        self._jit_sort = self._site(self._sort_kernel,
                                    label="SortExec.sort",
                                    static_argnums=(1,))
        # round 8: fixed-width columns ride INSIDE lax.sort as packed
        # lanes, so numGathers here counts only the varlen columns'
        # permutation gathers — the structural proof the sort path needs
        # no row gathers for fixed-width batches
        from ..ops.gather import GatherTracker
        self._gather_track = GatherTracker(self.metrics[NUM_GATHERS],
                                           self.metrics[GATHER_TIME])

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def additional_metrics(self):
        return (SORT_TIME, (NUM_INPUT_BATCHES, DEBUG)) + GATHER_METRICS \
            + DISPATCH_METRICS

    def _fingerprint_extras(self):
        return (tuple((o.ordinal, o.ascending, o.nulls_first)
                      for o in self.orders), self.limit)

    def _string_words(self, batch: ColumnarBatch) -> int:
        return string_words_for(batch.columns,
                                [o.ordinal for o in self.orders])

    def _sort_kernel(self, batch: ColumnarBatch,
                     key_bytes: int) -> ColumnarBatch:
        """The batch sorted; under a limit, its first `limit` rows in the
        limit's own capacity bucket (the slice is part of the program).
        Where that bucket is smaller than the batch's only those rows are
        materialized: found by selection where the limit fits the smallest
        bucket (no sort at all), else read off the sort's permutation."""
        n = batch.num_rows if self.limit is None \
            else jnp.minimum(batch.num_rows, jnp.int32(self.limit))
        out_cap = batch.capacity if self.limit is None \
            else bucket_capacity(self.limit)
        if out_cap < batch.capacity:
            from ..ops.gather import gather_batch_columns
            lanes = packed_key_lanes(batch.columns, self.orders,
                                     batch.num_rows, batch.capacity,
                                     key_bytes)
            rows = first_rows(lanes, batch.capacity, self.limit, out_cap) \
                if out_cap == MIN_BUCKET \
                else lexsort_permutation(lanes, batch.capacity)[:out_cap]
            cols = gather_batch_columns(batch.columns, rows, num_rows=n)
            return ColumnarBatch(cols, n, batch.schema)
        cols, _ = sort_batch_columns(batch.columns, self.orders,
                                     batch.num_rows, batch.capacity,
                                     key_bytes)
        if self.limit is not None:
            from ..ops.basic import sanitize
            cols = [sanitize(c, n) for c in cols]
        return ColumnarBatch(cols, n, batch.schema)

    def _sort_one(self, batch: ColumnarBatch) -> ColumnarBatch:
        with op_span("sort.result", phase="sort"):
            # the key lanes follow the keys' MEASURED width (one host
            # sync): down to one byte a key as well as up
            key_bytes = string_key_bytes(batch.columns,
                                         [o.ordinal for o in self.orders])
            with self._gather_track.observe((batch.capacity, key_bytes)):
                out = self._jit_sort(batch, key_bytes)
        if self.limit is None:
            out = ColumnarBatch(out.columns, batch.num_rows, batch.schema,
                                batch._host_rows)
        return out

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        try:
            yield from self._execute_sort()
        finally:
            self._gather_track.emit_event(type(self).__name__,
                                          self._op_id)

    def _execute_sort(self) -> Iterator[ColumnarBatch]:
        sort_time = self.metrics[SORT_TIME]
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        runs: List[SpillableBatch] = []
        with sort_time.ns_timer():
            for batch in self.child.execute():
                in_batches.add(1)
                spillable = SpillableBatch.from_batch(batch)
                try:
                    for sorted_batch in with_retry(
                            spillable, self._sort_spillable,
                            split_policy=split_in_half_by_rows):
                        runs.append(SpillableBatch.from_batch(sorted_batch))
                finally:
                    spillable.close()
            if not runs:
                return
            if len(runs) == 1:
                only = runs[0]
                batch = only.get_batch()
                only.release()
                only.close()
                yield batch
                return
            from ..config import SORT_OOC_ENABLED, active_conf
            if (self.limit is None and len(runs) > self.MERGE_FAN_IN
                    and active_conf().get(SORT_OOC_ENABLED)):
                # big merge: bounded-memory streamed k-way merge over
                # spilled runs (GpuOutOfCoreSortIterator analog)
                yield from self._merge_out_of_core([[r] for r in runs])
                return
            # small merge: concat all runs, one final sort; with_retry
            # splits the merge set under OOM
            yield self._merge(runs)

    def _sort_spillable(self, s: SpillableBatch) -> ColumnarBatch:
        batch = s.get_batch()
        try:
            return self._sort_one(batch)
        finally:
            s.release()

    def _merge(self, runs: List[SpillableBatch]) -> ColumnarBatch:
        from .exchange import onto_one_chip

        def do(items):
            batches = [s.get_batch() for s in items]
            try:
                # the runs of partitions on chips of their own (under a
                # limit, each one's first rows) meet on one chip
                merged = concat_batches(list(onto_one_chip(batches)),
                                        self.output_schema)
                return self._sort_one(merged)
            finally:
                for s in items:
                    s.release()
        try:
            return with_retry_no_split(runs, do)
        finally:
            for s in runs:
                s.close()

    #: runs merged per streaming pass; device footprint is bounded by
    #: ~2 × MERGE_FAN_IN × chunk capacity
    MERGE_FAN_IN = 8

    def _merge_out_of_core(self, run_lists: List[List[SpillableBatch]]
                           ) -> Iterator[ColumnarBatch]:
        """Multi-pass streamed merge: each pass merges groups of
        MERGE_FAN_IN runs, spilling the merged chunks; the final pass
        streams directly to the consumer."""
        fan = self.MERGE_FAN_IN
        live: List[List[SpillableBatch]] = run_lists
        nxt: List[List[SpillableBatch]] = []
        try:
            while len(live) > fan:
                nxt = []
                for g in range(0, len(live), fan):
                    group = live[g:g + fan]
                    if len(group) == 1:
                        nxt.append(group[0])
                        continue
                    merged = [SpillableBatch.from_batch(b)
                              for b in self._stream_merge(group)]
                    nxt.append(merged)
                live, nxt = nxt, []
            if len(live) == 1:
                for s in list(live[0]):
                    b = s.get_batch()
                    s.release()
                    s.close()
                    live[0].pop(0)
                    yield b
                return
            yield from self._stream_merge(live)
        finally:
            # error or early consumer abandonment: close everything left —
            # the current pass's inputs AND any merged runs already
            # produced into the next pass
            for r in live + nxt:
                for s in r:
                    s.close()

    def _stream_merge(self, group: List[List[SpillableBatch]]
                      ) -> Iterator[ColumnarBatch]:
        """Streamed k-way merge of sorted chunked runs.

        Invariant: a row may be emitted once it is lexicographically <=
        the loaded maximum of every run that still has unloaded chunks —
        any future row of run r is >= r's loaded max. Each head keeps its
        unemittable suffix device-resident; exhausted heads refill from
        their spilled queue. One small host sync (per-head emit counts)
        per loaded chunk."""
        # consume the caller's run lists IN PLACE so an abandoned or
        # failed merge leaves exactly the unconsumed spillables for the
        # caller's finally-close
        queues = group
        heads: List[Optional[ColumnarBatch]] = [None] * len(queues)
        # emitted chunks re-split to the input chunk bucket so chunk size
        # stays constant across merge passes (the memory bound depends on
        # it: footprint <= ~2 × fan-in × chunk)
        from ..columnar.column import bucket_capacity as _bc
        chunk_cap = max((_bc(max(int(s.num_rows), 1))
                         for q in queues for s in q), default=0) or 128

        def emit(batch: ColumnarBatch) -> Iterator[ColumnarBatch]:
            n = batch.num_rows_host
            if n <= chunk_cap:
                yield batch
                return
            for start in range(0, n, chunk_cap):
                m = min(chunk_cap, n - start)
                cols = [slice_rows(c, jnp.int32(start), jnp.int32(m),
                                   chunk_cap) for c in batch.columns]
                yield ColumnarBatch(cols, m, batch.schema)

        # per-head lane cache: lanes only recompute when a head changes
        # (refill/slice) or the global string-word width grows — unchanged
        # heads are byte-identical across rounds (review finding r1)
        lane_cache: dict = {}
        words_cache: dict = {}
        words = 1
        while True:
            for i, q in enumerate(queues):
                if heads[i] is None and q:
                    s = q.pop(0)
                    heads[i] = s.get_batch()
                    s.release()
                    s.close()
                    lane_cache.pop(i, None)
                    words_cache[i] = self._string_words(heads[i])
            live = [i for i, h in enumerate(heads) if h is not None]
            if not live:
                return
            constrainers = [i for i in live if queues[i]]
            if not constrainers:
                # everything is loaded: final merge of the remaining heads
                batches = [heads[i] for i in live]
                merged = concat_batches(batches, self.output_schema) \
                    if len(batches) > 1 else batches[0]
                yield from emit(self._sort_one(merged))
                return

            new_words = max(words_cache[i] for i in live)
            if new_words > words:
                lane_cache.clear()  # lane widths must agree across heads
                words = new_words
            for i in live:
                if i not in lane_cache:
                    lane_cache[i] = order_key_lanes(
                        heads[i].columns, self.orders, heads[i].num_rows,
                        heads[i].capacity, words)[1:]  # drop activity lane
            # bound: lexicographic min of constrainer heads' last rows
            bound = None
            for i in constrainers:
                h = heads[i]
                idx = jnp.clip(h.num_rows - 1, 0, h.capacity - 1)
                b = [lane[idx] for lane in lane_cache[i]]
                if bound is None:
                    bound = b
                else:
                    take = _lex_less_scalar(b, bound)
                    bound = [jnp.where(take, x, y)
                             for x, y in zip(b, bound)]

            emit_parts: List[ColumnarBatch] = []
            counts = []
            for i in live:
                h = heads[i]
                leq = _lex_leq(lane_cache[i], bound) \
                    & active_mask(h.num_rows, h.capacity)
                counts.append(jnp.sum(leq.astype(jnp.int32)))
            fetched = [int(c) for c in host_read("sort.counts", counts)]
            for i, cnt in zip(live, fetched):
                h = heads[i]
                n = h.num_rows_host
                if cnt > 0:
                    cols = [slice_rows(c, jnp.int32(0), jnp.int32(cnt),
                                       bucket_capacity(max(cnt, 1)))
                            for c in h.columns]
                    emit_parts.append(ColumnarBatch(cols, cnt, h.schema))
                if cnt >= n:
                    heads[i] = None  # fully emitted: refill next round
                    lane_cache.pop(i, None)
                elif cnt > 0:
                    rest = n - cnt
                    cols = [slice_rows(c, jnp.int32(cnt), jnp.int32(rest),
                                       bucket_capacity(max(rest, 1)))
                            for c in h.columns]
                    heads[i] = ColumnarBatch(cols, rest, h.schema)
                    lane_cache.pop(i, None)
            if emit_parts:
                merged = concat_batches(emit_parts, self.output_schema) \
                    if len(emit_parts) > 1 else emit_parts[0]
                yield from emit(self._sort_one(merged))

    def node_description(self):
        lim = f", limit={self.limit}" if self.limit is not None else ""
        return f"SortExec[{self.orders}{lim}]"


class TopNExec(SortExec):
    """GpuTopN (limit.scala:351): sort+limit per batch, merge keeps `limit`."""

    def __init__(self, limit: int, orders: Sequence, child: TpuExec,
                 offset: int = 0):
        super().__init__(orders, child, limit=limit + offset)
        self.offset = offset

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        for batch in super().internal_execute():
            if self.offset:
                n = max(0, batch.num_rows_host - self.offset)
                cols = [slice_rows(c, jnp.int32(self.offset), jnp.int32(n),
                                   batch.capacity) for c in batch.columns]
                batch = ColumnarBatch(cols, n, batch.schema)
            yield batch


class PartitionWiseSortExec(TpuExec):
    """Per-partition sort over a range exchange: the child yields one
    batch STREAM per partition (execute_partitions) in ascending bound
    order, so sorting each partition independently yields a GLOBALLY
    sorted stream (the reference's distributed sort: GpuRangePartitioner
    bounds + per-partition GpuSortExec). One inner SortExec is reused so
    compiled sort programs cache across partitions."""

    def __init__(self, orders: Sequence, child: TpuExec):
        super().__init__(child)
        from .basic import InMemoryScanExec
        self._scan = InMemoryScanExec([], child.output_schema)
        self._sort = SortExec(orders, self._scan)

    @property
    def output_schema(self) -> Schema:
        return self.child.output_schema

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        # partition boundaries come from execute_partitions (round 5:
        # exchanges stream a partition as MULTIPLE pieces — flat batches
        # no longer delimit partitions)
        for gen in self.child.execute_partitions():
            self._scan._batches = list(gen)
            yield from self._sort.execute()

    def node_description(self):
        return "PartitionWiseSortExec"
