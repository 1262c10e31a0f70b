"""WindowExec — reference GpuWindowExec.scala:146 and its specializations
(running, double-pass, bounded, unbounded-to-unbounded). One exec here:
every frame shape lowers to segmented scans over partition-sorted rows
(ops/window.py), so the reference's four execution strategies collapse
into one compiled program per window-expression set.

Frame coverage: ROWS frames with any bounds (sum/count/avg via prefix
differences; min/max via the sparse-table sliding-extrema kernel,
ops/window.bounded_min_max); RANGE frames support the default (UNBOUNDED
PRECEDING..CURRENT ROW with ties) shape. Whole input is windowed as one
concatenated batch — partition-boundary batching rides the out-of-core
sort work.
"""

from __future__ import annotations

from typing import Iterator, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column
from ..expr.core import Expression
from ..expr.windowexprs import (
    DenseRank, FirstValue, Lag, LastValue, Rank, RowNumber, WindowAgg,
    WindowExpression, WindowFrame,
)
from ..ops.basic import active_mask, gather_column, sanitize
from ..ops.sort import (
    SortOrder, group_segment_ids, order_key_lanes, sort_permutation,
    string_words_for,
)
from ..ops.window import (
    bounded_min_max, lag_lead, rank_dense_rank, row_number, running_min_max,
    segment_ends, segment_starts, whole_partition_broadcast,
    windowed_sum_count,
)
from ..types import DoubleType, IntegerType, LongType, Schema, StructField
from ..obs.dispatch import instrument
from .base import (DISPATCH_METRICS, GATHER_METRICS, GATHER_TIME,
                   NUM_GATHERS, OP_TIME,
                   TpuExec)
from .basic import bind_projection, eval_projection, projection_schema
from .coalesce import concat_batches
from .sort import resolve_sort_orders


class _StreamSourceExec(TpuExec):
    """Leaf yielding batches from a generator (keeps the window's sort
    input streaming instead of materialized)."""

    def __init__(self, schema: Schema, gen):
        super().__init__()
        self._schema = schema
        self._gen = gen

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        yield from self._gen


class WindowExec(TpuExec):
    def __init__(self, window_exprs: Sequence[Tuple[WindowExpression, str]],
                 child: TpuExec):
        super().__init__(child)
        self.window_exprs = list(window_exprs)
        in_schema = child.output_schema
        # all specs must share partition/order in one exec (the planner
        # splits differing specs into stacked WindowExecs, like Spark)
        spec0 = self.window_exprs[0][0].spec
        for we, _ in self.window_exprs:
            assert we.spec.partition_by == spec0.partition_by
            assert we.spec.order_by == spec0.order_by
        self.spec = spec0

        # pre-projection: child cols + partition keys + order keys + inputs
        from ..expr.core import col
        self._pre_exprs: List[Expression] = [col(n) for n in in_schema.names]
        self._n_child = len(in_schema.fields)
        self._part_slots = []
        for e in self.spec.partition_by:
            self._part_slots.append(len(self._pre_exprs))
            self._pre_exprs.append(e.alias(f"_wpart{len(self._part_slots)}"))
        self._order_slots = []
        self._order_dirs = []
        for o in self.spec.order_by:
            e, asc = o[0], o[1] if len(o) > 1 else True
            nf = o[2] if len(o) > 2 else None
            self._order_slots.append(len(self._pre_exprs))
            self._order_dirs.append((asc, nf))
            self._pre_exprs.append(e.alias(f"_word{len(self._order_slots)}"))
        self._input_slots: List[List[int]] = []
        for we, _ in self.window_exprs:
            slots = []
            for e in we.fn.inputs:
                slots.append(len(self._pre_exprs))
                self._pre_exprs.append(e.alias(f"_win{len(self._pre_exprs)}"))
            self._input_slots.append(slots)
        self._pre_bound = bind_projection(self._pre_exprs, in_schema)
        self._pre_schema = projection_schema(self._pre_exprs, in_schema)
        self._jit_window = instrument(self._window_kernel,
                                      label="WindowExec.window",
                                      owner=self, static_argnums=(1,))
        from ..ops.gather import GatherTracker
        self._gather_track = GatherTracker(self.metrics[NUM_GATHERS],
                                           self.metrics[GATHER_TIME])
        self._jit_lps = None
        self._jit_fpl = None
        self._jit_carry_update = None
        self._jit_pre = instrument(
            lambda b: eval_projection(self._pre_bound, b,
                                      self._pre_schema),
            label="WindowExec.pre_project", owner=self)

    @property
    def output_schema(self) -> Schema:
        fields = list(self.child.output_schema.fields)
        for i, (we, name) in enumerate(self.window_exprs):
            in_types = [self._pre_schema.fields[s].data_type
                        for s in self._input_slots[i]]
            fields.append(StructField(name, we.fn.result_type(in_types)))
        return Schema(tuple(fields))

    def additional_metrics(self):
        return GATHER_METRICS + DISPATCH_METRICS

    def _dispatch_window(self, batch: ColumnarBatch, words: int
                         ) -> ColumnarBatch:
        """The one gather-tracked window-kernel dispatch point."""
        with self._gather_track.observe((batch.capacity, words)):
            return self._jit_window(batch, words)

    # -- kernel ------------------------------------------------------------
    def _window_kernel(self, batch: ColumnarBatch, words: int
                       ) -> ColumnarBatch:
        cap = batch.capacity
        n = batch.num_rows
        part_cols = [batch.columns[s] for s in self._part_slots]
        order_cols = [batch.columns[s] for s in self._order_slots]

        orders = [SortOrder(s) for s in self._part_slots] + [
            SortOrder(s, asc, nf) for s, (asc, nf)
            in zip(self._order_slots, self._order_dirs)]
        perm = sort_permutation(batch.columns, orders, n, cap, 8 * words)
        # round 8: the partition-sort permutation moves the whole batch
        # through the gather engine — ONE packed row gather for the
        # fixed-width columns instead of one gather per column
        from ..ops.gather import gather_batch_columns
        sorted_cols = gather_batch_columns(batch.columns, perm)
        sorted_parts = [sorted_cols[s] for s in self._part_slots]
        sorted_orders = [sorted_cols[s] for s in self._order_slots]

        if self._part_slots:
            seg, _ = group_segment_ids(sorted_parts, n, cap, words)
        else:
            act = active_mask(n, cap)
            seg = jnp.where(act, 0, cap)

        # order-key boundary mask (first row of each distinct order key)
        if self._order_slots:
            lanes = order_key_lanes(
                sorted_orders, [SortOrder(i) for i in range(len(sorted_orders))],
                n, cap, words)[1:]
            ob = jnp.zeros((cap,), jnp.bool_)
            for lane in lanes:
                ob = ob | (lane != jnp.roll(lane, 1))
            ob = ob.at[0].set(True)
            # per-row last index of its order group (for RANGE-with-ties)
            gid = jnp.cumsum((ob | jnp.concatenate(
                [jnp.ones(1, jnp.bool_), seg[1:] != seg[:-1]])).astype(jnp.int32)) - 1
            gid = jnp.where(active_mask(n, cap), gid, cap)
            positions = jnp.arange(cap, dtype=jnp.int32)
            glast = jax.ops.segment_max(positions, gid, num_segments=cap)
            group_last = jnp.clip(glast[jnp.clip(gid, 0, cap - 1)], 0, cap - 1)
        else:
            ob = None
            group_last = None

        out_cols = list(sorted_cols[: self._n_child])
        out_schema = self.output_schema
        for i, (we, name) in enumerate(self.window_exprs):
            fn = we.fn
            res_type = out_schema.fields[self._n_child + i].data_type
            ins = [sorted_cols[s] for s in self._input_slots[i]]
            col = self._eval_fn(fn, we.spec.frame, ins, seg, ob, group_last,
                                n, cap, res_type, sorted_orders)
            out_cols.append(sanitize(col, n))
        return ColumnarBatch(out_cols, n, out_schema)

    def _eval_fn(self, fn, frame, ins, seg, order_boundary, group_last,
                 n, cap, res_type, sorted_orders=()) -> Column:
        ones = jnp.ones((cap,), jnp.bool_)
        if isinstance(fn, RowNumber):
            return Column(row_number(seg, n, cap), ones, res_type)
        if isinstance(fn, DenseRank):
            _, dense = rank_dense_rank(order_boundary, seg, n, cap)
            return Column(dense, ones, res_type)
        if isinstance(fn, Rank):
            rank, _ = rank_dense_rank(order_boundary, seg, n, cap)
            return Column(rank, ones, res_type)
        if isinstance(fn, Lag):  # covers Lead (negated offset)
            out, same_seg = lag_lead(ins[0], seg, n, cap, fn.offset)
            if fn.default is not None:
                # default only where the offset row does NOT exist; an
                # existing-but-null offset row stays NULL (Spark)
                fill = jnp.full((cap,), fn.default, out.data.dtype)
                data = jnp.where(same_seg, out.data, fill)
                valid = out.validity | ~same_seg
                return Column(data, valid, res_type)
            return out
        if isinstance(fn, LastValue):
            idx = group_last if group_last is not None \
                else segment_ends(seg, cap)
            return gather_column(ins[0], idx)
        if isinstance(fn, FirstValue):
            return gather_column(ins[0], segment_starts(seg, cap))
        assert isinstance(fn, WindowAgg), fn
        # frame resolution: default = RANGE UNBOUNDED..CURRENT (with ties)
        # when ordered, whole partition otherwise
        range_ties = frame.kind == "default" and self._order_slots
        if frame.kind == "default":
            preceding, following = (None, 0) if self._order_slots \
                else (None, None)
        else:
            preceding, following = frame.preceding, frame.following

        values = ins[0] if ins else None
        if frame.kind == "range" and not (preceding is None
                                          and following is None):
            # bounded RANGE frame: value-offset bounds over the single
            # numeric order key (Spark's analyzer enforces exactly one)
            assert len(self._order_slots) == 1, \
                "bounded RANGE frame requires exactly one order expression"
            from ..ops.window import (range_frame_bounds, range_min_max,
                                      range_sum_count)
            asc, nf = self._order_dirs[0]
            if nf is None:
                nf = asc  # Spark default: asc => nulls first
            lo, hi = range_frame_bounds(sorted_orders[0], seg, n, cap,
                                        preceding, following, asc, nf)
            if fn.op in ("sum", "count", "avg"):
                if values is None:
                    data = jnp.ones((cap,), jnp.int64)
                    valid = active_mask(n, cap)
                else:
                    data, valid = values.data, values.validity
                s, c = range_sum_count(data, valid, seg, n, cap, lo, hi)
                if fn.op == "count":
                    return Column(c.astype(jnp.int64), ones, res_type)
                if fn.op == "avg":
                    ok = c > 0
                    d = s.astype(jnp.float64) / jnp.where(ok, c, 1)
                    return Column(jnp.where(ok, d, 0.0), ok, res_type)
                return Column(s.astype(res_type.jnp_dtype), c > 0, res_type)
            assert fn.op in ("min", "max"), fn.op
            data, valid = range_min_max(values.data, values.validity, n,
                                        cap, lo, hi, fn.op == "max")
            return Column(data.astype(values.data.dtype), valid, res_type)
        if fn.op in ("sum", "count", "avg"):
            if values is None:
                data = jnp.ones((cap,), jnp.int64)
                valid = active_mask(n, cap)
            else:
                data, valid = values.data, values.validity
            s, c = windowed_sum_count(data, valid, seg, n, cap,
                                      preceding, following)
            if range_ties and group_last is not None:
                s = s[group_last]
                c = c[group_last]
            if fn.op == "count":
                return Column(c.astype(jnp.int64), ones, res_type)
            if fn.op == "avg":
                ok = c > 0
                d = s.astype(jnp.float64) / jnp.where(ok, c, 1)
                return Column(jnp.where(ok, d, 0.0), ok, res_type)
            return Column(s.astype(res_type.jnp_dtype), c > 0, res_type)
        # min/max
        if preceding is None and following is None:
            neutral_is_max = fn.op == "max"
            # whole partition: segment reduce + broadcast
            from .aggregate import groupby_aggregate  # reuse reduce machinery
            red_fn = jax.ops.segment_max if fn.op == "max" \
                else jax.ops.segment_min
            vals = values.data
            if jnp.issubdtype(vals.dtype, jnp.floating):
                neutral = jnp.full((), -jnp.inf if fn.op == "max" else jnp.inf,
                                   vals.dtype)
            else:
                info = jnp.iinfo(vals.dtype)
                neutral = jnp.full((), info.min if fn.op == "max"
                                   else info.max, vals.dtype)
            act = active_mask(n, cap)
            v = jnp.where(values.validity & act, vals, neutral)
            red = red_fn(v, seg, num_segments=cap)
            cnt = jax.ops.segment_sum((values.validity & act).astype(jnp.int32),
                                      seg, num_segments=cap)
            data = whole_partition_broadcast(red, seg, cap)
            valid = whole_partition_broadcast(cnt, seg, cap) > 0
            return Column(data, valid, res_type)
        if preceding is None and following == 0:
            data, valid = running_min_max(values.data, values.validity, seg,
                                          n, cap, fn.op == "max")
            if range_ties and group_last is not None:
                data = data[group_last]
                valid = valid[group_last]
            return Column(data.astype(values.data.dtype), valid, res_type)
        # bounded frames: sparse-table sliding extrema (reference
        # GpuBatchedBoundedWindowExec.scala:220)
        data, valid = bounded_min_max(values.data, values.validity, seg,
                                      n, cap, preceding, following,
                                      fn.op == "max")
        return Column(data.astype(values.data.dtype), valid, res_type)

    # -- giant-partition two-pass (reference
    # GpuUnboundedToUnboundedAggWindowExec.scala:1155) ---------------------
    # When one partition outgrows the chunk budget AND every window
    # expression is a whole-partition aggregate, hold only tiny carry
    # STATE (sum/count/min/max scalars) plus spillable row pieces; pass 2
    # replays the pieces appending the broadcast final values. Peak device
    # memory = one chunk, not the partition.
    TWO_PASS_THRESHOLD_ROWS = 1 << 21

    def _whole_partition_aggs(self):
        """(op, input slot or None) per expr if EVERY window expression is
        a whole-partition numeric aggregate, else None."""
        out = []
        for i, (we, _) in enumerate(self.window_exprs):
            fn = we.fn
            if not isinstance(fn, WindowAgg) or fn.op not in (
                    "sum", "count", "avg", "min", "max"):
                return None
            fr = we.spec.frame
            whole = (fr.kind == "default" and not self._order_slots) or \
                (fr.kind in ("rows", "range") and fr.preceding is None
                 and fr.following is None)
            if not whole:
                return None
            slots = self._input_slots[i]
            if slots:
                from ..columnar.column import Column as _C
                ft = self._pre_schema.fields[slots[0]].data_type
                from ..types import (ByteType, DoubleType, FloatType,
                                     IntegerType, LongType, ShortType)
                if not isinstance(ft, (ByteType, ShortType, IntegerType,
                                       LongType, FloatType, DoubleType)):
                    return None
            out.append((fn.op, slots[0] if slots else None))
        return out

    class _PartitionCarry:
        """Running whole-partition aggregate state + spilled row pieces
        for ONE partition streaming through multiple chunks."""

        def __init__(self, exec_, aggs):
            self._exec = exec_
            self._aggs = aggs
            self._pieces: List = []
            self._state = None  # per-agg (sum, cnt, mn, mx) device scalars
            # the compiled update kernel lives on the exec (aggs are fixed
            # per exec), so successive giant partitions share it
            if getattr(exec_, "_jit_carry_update", None) is None:
                exec_._jit_carry_update = instrument(
                    self._update_kernel,
                    label="WindowExec.carry_update", owner=exec_)
            self._jit_update = exec_._jit_carry_update

        def _update_kernel(self, batch: ColumnarBatch, state):
            out = []
            act = active_mask(batch.num_rows, batch.capacity)
            for (op, slot), st in zip(self._aggs, state):
                s, c, mn, mx = st
                if slot is None:
                    c = c + jnp.sum(act, dtype=jnp.int64)
                    out.append((s, c, mn, mx))
                    continue
                col = batch.columns[slot]
                valid = col.validity & act
                # widen BEFORE the where: an i64 sentinel stuffed into an
                # i32 lane truncates to -1/0 and poisons the extrema
                if jnp.issubdtype(col.data.dtype, jnp.floating):
                    v = col.data.astype(jnp.float64)
                    lo_sent, hi_sent = jnp.inf, -jnp.inf
                else:
                    v = col.data.astype(jnp.int64)
                    info = jnp.iinfo(jnp.int64)
                    lo_sent, hi_sent = info.max, info.min
                s = s + jnp.sum(jnp.where(valid, v, jnp.zeros((), v.dtype)))
                c = c + jnp.sum(valid, dtype=jnp.int64)
                mn = jnp.minimum(mn, jnp.min(jnp.where(valid, v, lo_sent)))
                mx = jnp.maximum(mx, jnp.max(jnp.where(valid, v, hi_sent)))
                out.append((s, c, mn, mx))
            return tuple(out)

        def _zero_state(self, batch: ColumnarBatch):
            st = []
            for op, slot in self._aggs:
                flt = slot is not None and jnp.issubdtype(
                    batch.columns[slot].data.dtype, jnp.floating)
                s = jnp.float64(0.0) if flt else jnp.int64(0)
                mn = jnp.float64(jnp.inf) if flt \
                    else jnp.int64(jnp.iinfo(jnp.int64).max)
                mx = jnp.float64(-jnp.inf) if flt \
                    else jnp.int64(jnp.iinfo(jnp.int64).min)
                st.append((s, jnp.int64(0), mn, mx))
            return tuple(st)

        def add(self, piece: ColumnarBatch):
            from ..memory.spillable import SpillableBatch
            if self._state is None:
                self._state = self._zero_state(piece)
            self._state = self._jit_update(piece, self._state)
            self._pieces.append(SpillableBatch.from_batch(piece))

        def finalize(self) -> Iterator[ColumnarBatch]:
            ex = self._exec
            out_schema = ex.output_schema
            n_child = ex._n_child
            state = self._state
            for sp in self._pieces:
                piece = sp.get_batch()
                cap = piece.capacity
                n = piece.num_rows
                act = active_mask(n, cap)
                cols = list(piece.columns[:n_child])
                for i, ((op, slot), st) in enumerate(
                        zip(self._aggs, state)):
                    s, c, mn, mx = st
                    rt = out_schema.fields[n_child + i].data_type
                    if op == "count":
                        data, ok = jnp.broadcast_to(c, (cap,)), \
                            jnp.broadcast_to(jnp.bool_(True), (cap,))
                    elif op == "avg":
                        d = s.astype(jnp.float64) / jnp.maximum(c, 1)
                        data = jnp.broadcast_to(d, (cap,))
                        ok = jnp.broadcast_to(c > 0, (cap,))
                    elif op == "sum":
                        data = jnp.broadcast_to(
                            s.astype(rt.jnp_dtype), (cap,))
                        ok = jnp.broadcast_to(c > 0, (cap,))
                    else:
                        v = mn if op == "min" else mx
                        data = jnp.broadcast_to(
                            v.astype(rt.jnp_dtype), (cap,))
                        ok = jnp.broadcast_to(c > 0, (cap,))
                    cols.append(sanitize(
                        Column(data, ok & act, rt), n))
                yield ColumnarBatch(cols, n, out_schema)
                sp.release()
                sp.close()
            self._pieces = []

    def _part_key_match(self, columns, words: int, ref_cols, ref_idx):
        """(cap,) bool: row's partition key equals ref_cols' key at
        ref_idx. ref_cols holds ONE column per partition slot (possibly
        the same batch's columns). Shared by the last-partition split and
        the carry-continuation check — the string-lane gotchas (exact
        prefix lanes at `words`; null rows compare by validity alone, the
        underlying bytes may be arbitrary) live in exactly one place."""
        from ..columnar.column import StringColumn
        from ..ops.sort import _numeric_order_key, string_prefix_lanes
        from ..ops.strings import string_lengths

        cap = columns[self._part_slots[0]].capacity if self._part_slots \
            else 0
        same = jnp.ones((cap,), jnp.bool_)
        for c, r in zip((columns[s] for s in self._part_slots), ref_cols):
            if isinstance(c, StringColumn):
                for lane, rlane in zip(string_prefix_lanes(c, words),
                                       string_prefix_lanes(r, words)):
                    lane = jnp.where(c.validity, lane, 0)
                    rlane = jnp.where(r.validity, rlane, 0)
                    same = same & (lane == rlane[ref_idx])
                lens = jnp.where(c.validity, string_lengths(c), 0)
                rlens = jnp.where(r.validity, string_lengths(r), 0)
                same = same & (lens == rlens[ref_idx])
                same = same & (c.validity == r.validity[ref_idx])
            else:
                from ..ops.sort import numeric_order_lanes
                for lane, rlane in zip(numeric_order_lanes(c),
                                       numeric_order_lanes(r)):
                    lane = jnp.where(c.validity, lane,
                                     jnp.zeros((), lane.dtype))
                    rlane = jnp.where(r.validity, rlane,
                                      jnp.zeros((), rlane.dtype))
                    same = same & (lane == rlane[ref_idx])
                same = same & (c.validity == r.validity[ref_idx])
        return same

    def _first_partition_len(self, batch: ColumnarBatch, words: int,
                             ref_cols) -> int:
        """Host int: number of leading rows whose partition key equals the
        CARRY partition's key (ref_cols, one 1-row column per partition
        slot) — NOT the batch's own first key, which would fold a fresh
        partition into the carry when a chunk boundary lands exactly on
        the giant partition's end."""
        if self._jit_fpl is None:
            def fpl(b: ColumnarBatch, w: int, refs):
                n = b.num_rows
                cap = b.capacity
                same = self._part_key_match(b.columns, w, refs, 0)
                act = active_mask(n, cap)
                idx = jnp.arange(cap, dtype=jnp.int32)
                nm = jnp.min(jnp.where(act & ~same, idx, cap))
                return jnp.minimum(nm, n)

            self._jit_fpl = instrument(fpl,
                                       label="WindowExec.first_part_len",
                                       owner=self, static_argnums=(1,))
        return int(self._jit_fpl(batch, words, ref_cols))

    # -- drive -------------------------------------------------------------
    def _last_partition_start(self, batch: ColumnarBatch,
                              words: int) -> int:
        """Host int: index of the first row of the LAST partition key in
        a (partition, order)-sorted batch. One tiny device sync per
        chunk — the price of partition-aligned batching."""
        if self._jit_lps is None:
            def lps(b: ColumnarBatch, w: int):
                n = b.num_rows
                cap = b.capacity
                last = jnp.clip(n - 1, 0, cap - 1)
                same = self._part_key_match(
                    b.columns, w, [b.columns[s] for s in self._part_slots],
                    last)
                act = active_mask(n, cap)
                # first index i such that rows i..n-1 all match the last
                # key: max over non-matching active rows + 1
                idx = jnp.arange(cap, dtype=jnp.int32)
                nm = jnp.max(jnp.where(act & ~same, idx, -1))
                return nm + 1

            self._jit_lps = instrument(lps,
                                       label="WindowExec.last_part_start",
                                       owner=self, static_argnums=(1,))
        return int(self._jit_lps(batch, words))

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        try:
            yield from self._execute_window()
        finally:
            self._gather_track.emit_event(type(self).__name__,
                                          self._op_id)

    def _execute_window(self) -> Iterator[ColumnarBatch]:
        """Partition-aware batched drive (replaces the r2 concat-all):
        the pre-projected input streams through the out-of-core sort on
        (partition, order) keys; each sorted chunk is windowed
        independently after holding back its final (possibly incomplete)
        partition, which is prepended to the next chunk. Memory peak =
        sort budget + largest single partition (the reference's
        GpuBatchedBoundedWindowExec/GpuRunningWindowExec bound the same
        way). Without partition keys the whole input is one partition
        and degrades to a single batch, as before."""
        from ..columnar.column import bucket_capacity
        from ..ops.basic import slice_rows
        from .sort import SortExec

        with self.metrics[OP_TIME].ns_timer():
            source = _StreamSourceExec(
                self._pre_schema,
                (self._jit_pre(b) for b in self.child.execute()))
            if not self._part_slots:
                batches = list(source.execute())
                if not batches:
                    return
                merged = concat_batches(batches, self._pre_schema)
                words = string_words_for(
                    merged.columns, self._part_slots + self._order_slots)
                yield self._dispatch_window(merged, words)
                return

            orders = [SortOrder(s) for s in self._part_slots] + [
                SortOrder(s, asc, nf) for s, (asc, nf)
                in zip(self._order_slots, self._order_dirs)]
            sorter = SortExec(orders, source)
            held: ColumnarBatch = None
            carry = None
            two_pass_aggs = self._whole_partition_aggs()
            saw = False
            for chunk in sorter.execute():
                saw = True
                if carry is not None:
                    # an active giant partition: rows continuing it fold
                    # into the carry state; the first foreign key closes it
                    cw = string_words_for(
                        chunk.columns, self._part_slots + self._order_slots)
                    cw = max(cw, carry_words)
                    flen = self._first_partition_len(chunk, cw, carry_ref)
                    nch = chunk.num_rows_host
                    if flen >= nch:
                        carry.add(chunk)
                        continue
                    if flen > 0:
                        hcap = bucket_capacity(max(flen, 1))
                        carry.add(ColumnarBatch(
                            [slice_rows(c, jnp.int32(0), jnp.int32(flen),
                                        hcap) for c in chunk.columns],
                            flen, self._pre_schema))
                    yield from carry.finalize()
                    carry = None
                    rest_n = nch - flen
                    rcap = bucket_capacity(max(rest_n, 1))
                    chunk = ColumnarBatch(
                        [slice_rows(c, jnp.int32(flen), jnp.int32(rest_n),
                                    rcap) for c in chunk.columns],
                        rest_n, self._pre_schema)
                if held is not None and held.num_rows_host > 0:
                    cur = concat_batches([held, chunk], self._pre_schema)
                else:
                    cur = chunk
                n = cur.num_rows_host
                cur_words = string_words_for(
                    cur.columns, self._part_slots + self._order_slots)
                split = self._last_partition_start(cur, cur_words)
                if split <= 0:
                    # one giant partition so far: switch to carry state if
                    # every expression is a whole-partition aggregate,
                    # else keep growing (concat fallback)
                    if two_pass_aggs is not None and \
                            n > self.TWO_PASS_THRESHOLD_ROWS:
                        carry = self._PartitionCarry(self, two_pass_aggs)
                        carry.add(cur)
                        # 1-row reference key identifying the carried
                        # partition (continuation checks compare against
                        # THIS, not an incoming chunk's own first row)
                        carry_ref = [
                            slice_rows(cur.columns[s], jnp.int32(0),
                                       jnp.int32(1), bucket_capacity(1))
                            for s in self._part_slots]
                        carry_words = cur_words
                        held = None
                    else:
                        held = cur
                    continue
                ready_cap = bucket_capacity(max(split, 1))
                ready = ColumnarBatch(
                    [slice_rows(c, jnp.int32(0), jnp.int32(split),
                                ready_cap) for c in cur.columns],
                    split, self._pre_schema)
                tail_n = n - split
                tail_cap = bucket_capacity(max(tail_n, 1))
                held = ColumnarBatch(
                    [slice_rows(c, jnp.int32(split), jnp.int32(tail_n),
                                tail_cap) for c in cur.columns],
                    tail_n, self._pre_schema)
                # cur_words stays exact for the prefix slice: reuse it
                # instead of paying a second measuring sync per chunk
                yield self._dispatch_window(ready, cur_words)
            if not saw:
                return
            if carry is not None:
                yield from carry.finalize()
            elif held is not None and held.num_rows_host > 0:
                words = string_words_for(
                    held.columns, self._part_slots + self._order_slots)
                yield self._dispatch_window(held, words)
