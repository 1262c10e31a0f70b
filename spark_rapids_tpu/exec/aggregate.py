"""HashAggregateExec — reference GpuHashAggregateExec
(GpuAggregateExec.scala:1711) + GpuMergeAggregateIterator:711 rebuilt around
the sort-based segment-reduce kernel (ops/aggregate.py).

Flow (complete mode):
  1. per input batch: pre-project [group keys..., agg inputs...]
  2. update group-by -> batch of [keys..., buffer cols...] (first-pass agg)
  3. aggregated batches accumulate as SpillableBatch
  4. merge: concat + re-aggregate with merge ops (reference
     tryMergeAggregatedBatches:803; our kernel IS the sort fallback :909,
     so the two reference paths collapse into one here)
  5. evaluate buffers -> output projection

`partial` mode stops after 4 and emits keys+buffers (feeds a shuffle);
`final` consumes keys+buffers batches and runs 4-5. This mirrors Spark's
partial/final split so distributed aggregation reuses the same exec.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column, StringColumn
from ..expr.aggexprs import AggregateFunction
from ..expr.core import Expression, output_name, resolve
from ..memory.retry import (
    TpuSplitAndRetryOOM, split_in_half_by_rows, with_retry,
)
from ..memory.spillable import SpillableBatch
from ..ops.aggregate import groupby_aggregate, groupby_aggregate_hash
from ..ops.basic import active_mask, sanitize
from ..ops.sort import string_words_for
from ..types import DataType, LongType, Schema, StructField
from ..obs.dispatch import instrument
from ..obs.span import op_span
from .base import (AGG_TIME, CONCAT_TIME, DEBUG, DISPATCH_METRICS,
                   NUM_INPUT_BATCHES, NUM_INPUT_ROWS, TpuExec)
from .basic import bind_projection, eval_projection
from .coalesce import concat_batches


# ---------------------------------------------------------------------------
# process counters of the exact drive (the chaos-delta pattern, as
# `stage_compiler.counters()`)
# ---------------------------------------------------------------------------

_COUNTER_LOCK = threading.Lock()
_COUNTERS = {
    # drives of a group-by's exact tier or of a fused stage's group-by: one
    # a query (two where the plan ran a second time)
    "executions": 0,
    # batches (source batches, and merges of partials) that took the hash
    # path: `update_hash` / `merge_hash` at its first tier (the lane tier
    # where it applies, else 2 rounds) and one host read of `leftover`
    "hash_updates": 0,
    # of those, the ones whose 2 rounds left keys over and that went on to
    # 6 rounds: a second program and a second host sync
    "hash_round_retries": 0,
    # of those, the ones 6 rounds did not resolve either and that fell to
    # the exact sort path
    "exact_fallbacks": 0,
    # of `hash_updates`, the ones the lane tier resolved: string keys as
    # packed fixed-width lanes through the masked-bucket kernel, before
    # any hash round
    "lane_updates": 0,
    # the ones whose schema allows the lane tier and whose measured keys
    # were too wide for it (the hash rounds ran as without it)
    "lane_declines": 0,
    # source batches that took the exact tier (masked buckets with the sort
    # path behind them, one program) in the FIRST pass of their plan: the
    # plan shape is known to overflow the buckets
    # (`speculation.known_to_trip`), so it did not speculate
    "many_group_updates": 0,
    # queries whose speculation flag tripped (`TpuExec.collect`: masked
    # buckets overflowed, or a join's cached sizes were stale)
    "spec_trips": 0,
    # of those, the ones that ran their whole plan a second time, every
    # operator on its exact tier: all of them; a plan shape known to trip
    # does not speculate again, so neither moves in its later queries
    "plan_reruns": 0,
    # the ones the lane tier ran on and left keys over (more distinct keys
    # than its slots): they went on to the hash rounds
    "lane_leftovers": 0,
}

#: what a tier that left keys over counts, by its key in the tiers' sites
_LEFTOVER_COUNTER = {"lanes": "lane_leftovers", 2: "hash_round_retries",
                     6: "exact_fallbacks"}

#: the lane tier takes string keys up to this many bytes, and as many key
#: lane columns as `masked_group_assignment`'s packed stats word holds
LANE_KEY_BYTES = 16
LANE_KEY_COLUMNS = 16


def _masked_type(dt: DataType) -> bool:
    """A column type the masked-bucket kernels reduce (and order as ONE
    static lane): fixed-width, not decimal128."""
    from ..types import (ArrayType, BinaryType, DecimalType, StringType,
                         StructType)
    return not (isinstance(dt, (StringType, BinaryType, StructType,
                                ArrayType))
                or (isinstance(dt, DecimalType) and dt.is_decimal128))


def _note(**deltas) -> None:
    with _COUNTER_LOCK:
        for k, v in deltas.items():
            _COUNTERS[k] += v


def note_many_groups(fingerprint: Optional[str]) -> None:
    """Count one source batch's exact update of a plan shape that is known
    to overflow the masked buckets (the fused stages count theirs here
    too)."""
    from .speculation import known_to_trip
    if known_to_trip(fingerprint):
        _note(many_group_updates=1)


def counters() -> Dict[str, int]:
    """Process-cumulative counters of the hash group-by's tiers."""
    with _COUNTER_LOCK:
        return dict(_COUNTERS)


def reset_aggregate_counters() -> None:
    with _COUNTER_LOCK:
        for k in _COUNTERS:
            _COUNTERS[k] = 0


@partial(instrument, label="aggregate.shrink_batch",
         static_argnums=(1, 2))
def _shrink_batch(batch: ColumnarBatch, cap: int,
                  byte_caps: Optional[Tuple[Optional[int], ...]] = None
                  ) -> ColumnarBatch:
    """Move the active prefix into a smaller capacity bucket: aggregated
    partials carry few groups in huge input-sized buckets; merging at input
    size would sort mostly-padding (the dominant waste in a groupby).
    `byte_caps` (one a column, None where it is not a string) shrinks the
    string columns' byte buckets too: a gather of a string column costs by
    its byte bucket, whatever its rows."""
    from ..ops.basic import gather_column
    idx = jnp.arange(cap, dtype=jnp.int32)
    live = idx < batch.num_rows
    cols = [gather_column(c, idx, live,
                          byte_caps[i] if byte_caps is not None else None)
            for i, c in enumerate(batch.columns)]
    return ColumnarBatch(cols, batch.num_rows, batch.schema)


@partial(instrument, label="aggregate.partial_size")
def _partial_size(batch: ColumnarBatch):
    """[rows, bytes of each string column's active prefix]: what a tight
    bucket for a partial needs, in one array for one host read."""
    return jnp.stack([batch.num_rows.astype(jnp.int32)] + [
        c.offsets[batch.num_rows] for c in batch.columns
        if isinstance(c, StringColumn)])


def _tight_partial(out: ColumnarBatch, sizes) -> ColumnarBatch:
    """`out` in the bucket its groups need (`sizes`: `_partial_size` on the
    host). The hash tiers' output keeps its INPUT's capacity and byte
    buckets (four groups of Q1 in 8,388,608 rows): everything after it, the
    merge, the evaluation and the result sort, would work, and compile, at
    that size."""
    from ..columnar.column import bucket_capacity
    rows = int(sizes[0])
    cap = bucket_capacity(max(rows, 1))
    nbytes = iter(int(b) for b in sizes[1:])
    byte_caps = tuple(
        bucket_capacity(max(next(nbytes), 1))
        if isinstance(c, StringColumn) else None for c in out.columns)
    held = tuple(c.byte_capacity if isinstance(c, StringColumn) else None
                 for c in out.columns)
    cols = out.columns if (cap, byte_caps) == (out.capacity, held) else \
        _shrink_batch(out, cap, byte_caps).columns
    return ColumnarBatch(cols, rows, out.schema)


def _result_column(data, valid, dtype) -> Column:
    """Aggregate result (data, valid) -> Column; decimal128 sums arrive
    as (hi, lo) limb tuples and build a Decimal128Column (or fold back
    to one limb when the buffer type fits 18 digits)."""
    import jax.numpy as jnp

    from ..columnar.column import Decimal128Column
    from ..types import DecimalType
    if isinstance(data, tuple):
        hi, lo = data
        if isinstance(dtype, DecimalType) and dtype.is_decimal128:
            return Decimal128Column.from_limbs(hi, lo, valid, dtype)
        from ..ops import decimal128 as D
        bound = 10 ** min(dtype.precision, 18)
        ok = D.fits_i64(hi, lo) & (lo < bound) & (lo > -bound)
        valid = valid & ok
        return Column(jnp.where(valid, lo, 0), valid, dtype)
    return Column(data.astype(dtype.jnp_dtype), valid, dtype)


class AggregateExec(TpuExec):
    def __init__(self, group_exprs: Sequence[Expression],
                 aggregates: Sequence[Tuple[AggregateFunction, str]],
                 child: TpuExec, mode: str = "complete",
                 input_types: Optional[List[List["DataType"]]] = None):
        """input_types: per-aggregate original INPUT types, passed by the
        planner to final-mode instances so result types (e.g. decimal sum
        precision) match the single-stage plan instead of being derived
        from the widened buffer types (ADVICE r3 #3)."""
        super().__init__(child)
        assert mode in ("complete", "partial", "final")
        self._final_input_types = input_types
        self.mode = mode
        self.group_exprs = list(group_exprs)
        self.aggregates = list(aggregates)
        in_schema = child.output_schema

        from ..config import (
            AGG_GROUP_SLOTS, AGG_ROUNDS, AGG_SPECULATIVE, FUSION_ENABLED,
            active_conf,
        )
        conf = active_conf()
        self._slots = max(8, min(64, conf.get(AGG_GROUP_SLOTS)))
        self._rounds = max(1, conf.get(AGG_ROUNDS))
        self._spec_enabled = conf.get(AGG_SPECULATIVE)

        self._fusion_enabled = conf.get(FUSION_ENABLED)
        self._fused_steps: list = []
        self._source: TpuExec = child

        if mode == "final":
            # input is keys+buffers produced by a partial instance; the
            # planner's input_types hint restores original result types
            self._key_count = len(group_exprs)
            self._input_types = input_types
            self._buffer_schema = in_schema
        else:
            # pre-projection: keys then the union of agg inputs
            self._pre_exprs = list(self.group_exprs)
            self._input_slots: List[List[int]] = []
            for fn, _ in self.aggregates:
                slots = []
                for e in fn.inputs:
                    slot = len(self._pre_exprs)
                    self._pre_exprs.append(e.alias(f"_aggin{slot}"))
                    slots.append(slot)
                self._input_slots.append(slots)
            self._pre_bound = bind_projection(self._pre_exprs, in_schema)
            from .basic import projection_schema
            self._pre_schema = projection_schema(self._pre_exprs, in_schema)
            self._key_count = len(group_exprs)
            self._input_types = [
                [self._pre_schema.fields[s].data_type for s in slots]
                for slots in self._input_slots]
            self._buffer_schema = self._make_buffer_schema()

        # whole-stage fusion: inline upstream filter/project chains into
        # this operator's program (one XLA program per source batch; the
        # reference's analog is whole-stage codegen — XLA is the codegen).
        # Only for the masked tier: the string tiers consume child batches.
        if self._fusion_enabled and mode != "final" and self._masked_ok:
            steps, node = [], child
            while hasattr(node, "fused_step"):
                steps.append(node.fused_step())
                node = node.child
            self._fused_steps = list(reversed(steps))
            self._source = node

        # round 5: when the child contract (output_grouped_by) already
        # groups rows by this aggregate's keys — e.g. the inner join's
        # key-grouped emission — the exact tier skips its batch sort
        self._pre_grouped = mode != "final" and self._input_pre_grouped()
        self._initial_state_cache = None

        # program sites, built LAST (ISSUE 14): the plan fingerprint
        # the site cache keys on must see the final semantic fields
        # (fused steps, pre-grouped contract) — a site
        # built earlier would fingerprint a half-constructed node.
        # Compiled-kernel jit caches key on capacity bucket + string
        # words; the site cache keys whole instances across collects.
        self._jit_update = self._site(self._update_batch,
                                      label="AggregateExec.update",
                                      static_argnums=(1,))
        self._jit_merge = self._site(self._merge_batch,
                                     label="AggregateExec.merge",
                                     static_argnums=(1,))
        # hash-path tiers: where the schema allows it (`_lane_ok`) the
        # lane tier first (short string keys as packed lanes through the
        # masked-bucket kernel: no scatter, no hash round), then the cheap
        # 2-round hash, the 6-round escalation for mid-cardinality, and
        # the exact sort as the last resort
        self._jit_update_hash = {
            r: self._site(partial(self._update_batch, hash_path=True,
                                  hash_rounds=r),
                          label="AggregateExec.update_hash", key_salt=r)
            for r in (2, 6)}
        self._jit_merge_hash = {
            r: self._site(partial(self._merge_batch, hash_path=True,
                                  hash_rounds=r),
                          label="AggregateExec.merge_hash", key_salt=r)
            for r in (2, 6)}
        if self._lane_ok:
            self._jit_update_hash["lanes"] = self._site(
                self._lane_update, label="AggregateExec.update_hash",
                key_salt="lanes", static_argnums=(1,))
            self._jit_merge_hash["lanes"] = self._site(
                self._lane_merge, label="AggregateExec.merge_hash",
                key_salt="lanes", static_argnums=(1,))
        # sync-free exact merge: masked buckets + in-program sort fallback
        self._jit_merge_auto = self._site(
            partial(self._merge_batch, auto_path=True),
            label="AggregateExec.merge_auto")
        self._jit_pre = self._site(self._pre_project,
                                   label="AggregateExec.pre_project")
        self._jit_concat_merge = self._site(
            self._concat_merge_pair,
            label="AggregateExec.concat_merge", static_argnums=(2,))
        # streaming speculative kernel: fused steps + masked-bucket update
        # + fold into the O(1) device state — ONE program per source batch
        self._jit_step_spec = self._site(
            self._streaming_step,
            label="AggregateExec.streaming_step")
        self._jit_step_exact = self._site(
            self._fused_update_exact,
            label="AggregateExec.fused_update_exact")
        self._jit_evaluate = self._site(self._evaluate,
                                        label="AggregateExec.evaluate")

    def _fingerprint_extras(self):
        # semantic_key throughout, NOT repr: repr is display-only and
        # omits non-child parameters (a percentile's percentage, a
        # first()'s ignore_nulls) — a lossy key hands one aggregate
        # another's compiled programs (caught live)
        from .stage_compiler import schema_sig
        exprs = list(self.group_exprs) + [
            e for fn, _ in self.aggregates for e in fn.inputs]
        for s in self._fused_steps:
            exprs.extend(s[1] if s[0] == "project" else [s[1]])
        if not all(e.deterministic for e in exprs):
            return None  # see ProjectExec._fingerprint_extras

        def step_key(s):
            if s[0] == "filter":
                return ("filter", s[1].semantic_key())
            return ("project",
                    tuple(b.semantic_key() for b in s[1]),
                    schema_sig(s[2]))

        return (self.mode,
                tuple(e.semantic_key() for e in self.group_exprs),
                tuple((fn.semantic_key(), name)
                      for fn, name in self.aggregates),
                repr(self._final_input_types),
                self._slots, self._rounds, self._spec_enabled,
                self._fusion_enabled,
                tuple(step_key(s) for s in self._fused_steps),
                self._pre_grouped)

    def _input_pre_grouped(self) -> bool:
        from ..expr.core import UnresolvedAttribute
        hint = self.children[0].output_grouped_by
        if not hint or not self.group_exprs:
            return False
        names = set()
        for e in self.group_exprs:
            if not isinstance(e, UnresolvedAttribute):
                return False
            names.add(e.name)
        all_names = set().union(*hint)
        # every key must belong to a grouping class, and every class must
        # be represented (otherwise joint-tuple contiguity doesn't hold)
        return names <= all_names and all(cls & names for cls in hint)

    # -- schemas -----------------------------------------------------------
    def _make_buffer_schema(self) -> Schema:
        fields = list(self._pre_schema.fields[: self._key_count])
        for i, (fn, name) in enumerate(self.aggregates):
            for j, bt in enumerate(fn.buffer_types(self._input_types[i])):
                fields.append(StructField(f"{name}#buf{j}", bt, True))
        return Schema(tuple(fields))

    @property
    def output_schema(self) -> Schema:
        if self.mode == "partial":
            return self._buffer_schema
        key_fields = list(self._buffer_schema.fields[: self._key_count])
        agg_fields = []
        bufs = self._buffer_schema.fields[self._key_count:]
        # result types: derive from buffer types for final mode
        pos = 0
        for i, (fn, name) in enumerate(self.aggregates):
            n_buf = len(fn.merge_ops())
            if self._input_types is not None:
                rt = fn.result_type(self._input_types[i])
            else:  # final mode: derive from buffer types explicitly
                rt = fn.result_type_from_buffer(
                    [f.data_type for f in bufs[pos:pos + n_buf]])
            agg_fields.append(StructField(name, rt))
            pos += n_buf
        return Schema(tuple(key_fields + agg_fields))

    def additional_metrics(self):
        return (AGG_TIME, CONCAT_TIME, (NUM_INPUT_ROWS, DEBUG),
                (NUM_INPUT_BATCHES, DEBUG)) + DISPATCH_METRICS

    # -- kernels -----------------------------------------------------------
    def _pre_project(self, batch: ColumnarBatch) -> ColumnarBatch:
        return eval_projection(self._pre_bound, batch, self._pre_schema)

    def _update_inputs(self, batch: ColumnarBatch):
        keys = list(batch.columns[: self._key_count])
        agg_inputs = []
        for i, (fn, _) in enumerate(self.aggregates):
            for (op, slot) in fn.update_ops():
                col = batch.columns[self._input_slots[i][slot]] \
                    if slot is not None else None
                agg_inputs.append((op, col))
        return keys, agg_inputs

    def _merge_inputs(self, batch: ColumnarBatch):
        keys = list(batch.columns[: self._key_count])
        agg_inputs = []
        pos = self._key_count
        for fn, _ in self.aggregates:
            for op in fn.merge_ops():
                agg_inputs.append((op, batch.columns[pos]))
                pos += 1
        return keys, agg_inputs

    def _update_batch(self, batch: ColumnarBatch, words: int = 4,
                      hash_path: bool = False, hash_rounds: int = 2,
                      auto_path: bool = False, row_mask=None):
        """First-pass aggregation of one pre-projected batch."""
        keys, agg_inputs = self._update_inputs(batch)
        return self._run_groupby(keys, agg_inputs, batch,
                                 self._buffer_schema, words, hash_path,
                                 hash_rounds, auto_path, row_mask,
                                 is_update=True)

    def _merge_batch(self, batch: ColumnarBatch, words: int = 4,
                     hash_path: bool = False, hash_rounds: int = 2,
                     auto_path: bool = False, row_mask=None):
        """Re-aggregate a keys+buffers batch with merge ops."""
        keys, agg_inputs = self._merge_inputs(batch)
        return self._run_groupby(keys, agg_inputs, batch,
                                 self._buffer_schema, words, hash_path,
                                 hash_rounds, auto_path, row_mask)

    # -- fused + speculative streaming kernels -----------------------------
    def _apply_fused(self, batch: ColumnarBatch):
        """Traced: run the inlined filter/project chain. Filters become a
        row MASK (no compaction gather — gathers are slow on TPU; masked
        reductions ignore dead rows for free)."""
        mask = None
        cur = batch
        for step in self._fused_steps:
            if step[0] == "filter":
                pred = step[1].columnar_eval(cur)
                m = pred.data & pred.validity
                mask = m if mask is None else (mask & m)
            else:
                _, bound, schema = step
                cur = eval_projection(bound, cur, schema)
        return cur, mask

    def _fused_update_exact(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Exact tier, one program: fused steps -> pre-project -> masked
        bucket group-by with in-program lax.cond sort fallback."""
        assert self.mode != "final", "final mode merges via _merge_jitted"
        cur, mask = self._apply_fused(batch)
        pre = eval_projection(self._pre_bound, cur, self._pre_schema)
        return self._update_batch(pre, auto_path=True, row_mask=mask)

    def _small_cap(self) -> int:
        from ..columnar.column import bucket_capacity
        return bucket_capacity(self._slots * self._rounds)

    def _build_small_batch(self, out_keys, results, num_groups
                           ) -> ColumnarBatch:
        cols = list(out_keys)
        buf_fields = self._buffer_schema.fields[self._key_count:]
        for r, f in zip(results, buf_fields):
            data, valid = r[1]
            cols.append(_result_column(data, valid, f.data_type))
        return ColumnarBatch(cols, num_groups, self._buffer_schema)

    def _streaming_step(self, batch: ColumnarBatch, state: ColumnarBatch,
                        flag):
        """Speculative tier, ONE program per source batch: fused steps ->
        masked-bucket update into a SMALL partial -> fold into the O(1)
        running state. Overflow/collision leftovers only raise the device
        flag; the plan re-runs exactly if it ever trips (speculation.py)."""
        from ..ops.basic import concat_columns
        from ..ops.maskedagg import masked_groupby, masked_reduce
        out_cap = self._small_cap()

        if self.mode == "final":
            cur, mask = batch, None
            keys, agg_inputs = self._merge_inputs(batch)
        else:
            cur, mask = self._apply_fused(batch)
            pre = eval_projection(self._pre_bound, cur, self._pre_schema)
            keys, agg_inputs = self._update_inputs(pre)
            cur = pre

        if not keys:
            results = [("raw", r) for r in masked_reduce(
                agg_inputs, cur.num_rows, mask, out_cap)]
            part = self._build_small_batch([], results, jnp.int32(1))
        else:
            out_keys, results, num_groups, leftover = masked_groupby(
                keys, agg_inputs, cur.num_rows, cur.capacity, mask,
                self._slots, self._rounds)
            flag = flag | leftover
            part = self._build_small_batch(out_keys, results, num_groups)

        # fold: concat state + part, re-aggregate with merge ops
        cat_cap = 2 * out_cap
        cols = [concat_columns(a, b, state.num_rows, part.num_rows, cat_cap)
                for a, b in zip(state.columns, part.columns)]
        both = ColumnarBatch(cols, state.num_rows + part.num_rows,
                             self._buffer_schema)
        mkeys, minputs = self._merge_inputs(both)
        if not mkeys:
            mres = [("raw", r) for r in masked_reduce(
                minputs, both.num_rows, None, out_cap)]
            new_state = self._build_small_batch([], mres, jnp.int32(1))
        else:
            mk, mres, mgroups, mleft = masked_groupby(
                mkeys, minputs, both.num_rows, cat_cap, None,
                self._slots, self._rounds)
            flag = flag | mleft
            new_state = self._build_small_batch(mk, mres, mgroups)
        # evaluate the (tiny) state inside the SAME program: the final
        # result is then a step output and no separate evaluate program
        # has to launch (per-program launch latency: not measured on
        # this installation)
        ev = None if self.mode == "partial" else self._evaluate(new_state)
        return new_state, flag, ev

    def _initial_state(self) -> ColumnarBatch:
        """Empty small state (built once; reused across executions)."""
        if self._initial_state_cache is None:
            from ..columnar.batch import empty_batch
            self._initial_state_cache = (
                empty_batch(self._buffer_schema, capacity=self._small_cap()),
                jnp.asarray(False))
        return self._initial_state_cache

    def _concat_merge_pair(self, a: ColumnarBatch, b: ColumnarBatch,
                           cap: int) -> ColumnarBatch:
        """Device-only merge of two keys+buffers partials: concat into one
        `cap`-capacity batch, then re-aggregate with merge ops. Output
        groups <= a_groups + b_groups <= cap always, so this is exact with
        no host involvement."""
        from ..ops.basic import concat_columns
        cols = [concat_columns(ca, cb, a.num_rows, b.num_rows, cap)
                for ca, cb in zip(a.columns, b.columns)]
        both = ColumnarBatch(cols, a.num_rows + b.num_rows,
                             self._buffer_schema)
        return self._merge_batch(both, auto_path=True)

    def _lane_groupby(self, keys, agg_inputs, batch: ColumnarBatch,
                      key_bytes: int):
        """The lane tier's program: (partial in the SMALL bucket, leftover).
        Where `leftover` is set the partial is not an answer."""
        from ..ops.maskedagg import masked_groupby_lanes
        out_keys, results, num_groups, leftover = masked_groupby_lanes(
            keys, agg_inputs, batch.num_rows, batch.capacity, key_bytes,
            self._slots, self._rounds)
        return self._build_small_batch(out_keys, results, num_groups), \
            leftover

    def _lane_update(self, batch: ColumnarBatch, key_bytes: int):
        return self._lane_groupby(*self._update_inputs(batch), batch,
                                  key_bytes)

    def _lane_merge(self, batch: ColumnarBatch, key_bytes: int):
        return self._lane_groupby(*self._merge_inputs(batch), batch,
                                  key_bytes)

    def _run_groupby(self, keys, agg_inputs, batch, out_schema, words: int,
                     hash_path: bool = False, hash_rounds: int = 2,
                     auto_path: bool = False, row_mask=None,
                     is_update: bool = False):
        from ..ops.maskedagg import masked_groupby_exact, masked_reduce
        cap = batch.capacity
        if not keys:
            if any(op.startswith(("collect", "psketch"))
                   for op, _ in agg_inputs):
                # grand collect_list/set: one-row array outputs
                from ..ops.aggregate import collect_all
                cols = []
                fields = out_schema.fields
                plain = [(op, c) for op, c in agg_inputs
                         if not op.startswith(("collect", "psketch"))]
                plain_res = iter(masked_reduce(
                    plain, batch.num_rows, row_mask, cap)) if plain else \
                    iter(())
                for (op, c), f in zip(agg_inputs, fields):
                    if op.startswith(("collect", "psketch")):
                        cols.append(collect_all(op, c, batch.num_rows, cap))
                    else:
                        data, valid = next(plain_res)
                        cols.append(_result_column(data, valid,
                                                   f.data_type))
                out = ColumnarBatch(cols, 1, out_schema)
                return (out, jnp.asarray(False)) if hash_path else out
            # a count(*)-only aggregate has no input columns at all; give
            # the one-row output a real capacity bucket. Scatter-free
            # masked reductions (scatters are the slowest TPU op family).
            out_cap = 128
            results = masked_reduce(agg_inputs, batch.num_rows,
                                    row_mask, out_cap)
            cols = []
            fields = out_schema.fields
            for (data, valid), f in zip(results, fields):
                cols.append(_result_column(data, valid, f.data_type))
            out = ColumnarBatch(cols, 1, out_schema)
            return (out, jnp.asarray(False)) if hash_path else out
        leftover = None
        if auto_path:
            out_keys, results, num_groups = masked_groupby_exact(
                keys, agg_inputs, batch.num_rows, cap, row_mask,
                string_words=words, group_slots=self._slots,
                rounds=self._rounds)
        elif hash_path:
            out_keys, results, num_groups, leftover = groupby_aggregate_hash(
                keys, agg_inputs, batch.num_rows, cap, rounds=hash_rounds)
        else:
            # pre_grouped only holds for SOURCE batches (the child's
            # grouping contract); merge inputs are concatenated partials
            out_keys, results, num_groups = groupby_aggregate(
                keys, agg_inputs, batch.num_rows, cap, words,
                pre_grouped=self._pre_grouped and is_update)
        cols = list(out_keys)
        buf_fields = out_schema.fields[self._key_count:]
        for r, f in zip(results, buf_fields):
            if r[0] == "col":
                cols.append(r[1])
            else:
                data, valid = r[1]
                cols.append(_result_column(data, valid, f.data_type))
        out = ColumnarBatch(cols, num_groups, out_schema)
        return (out, leftover) if hash_path else out

    def _evaluate(self, batch: ColumnarBatch) -> ColumnarBatch:
        """Final projection buffers -> results."""
        out_schema = self.output_schema
        cols = list(batch.columns[: self._key_count])
        pos = self._key_count
        for i, (fn, _) in enumerate(self.aggregates):
            n_buf = len(fn.merge_ops())
            bufs = list(batch.columns[pos: pos + n_buf])
            input_types = self._input_types[i] if self._input_types else \
                [b.dtype for b in bufs]
            col = fn.evaluate(bufs, input_types)
            cols.append(sanitize(col, batch.num_rows))
            pos += n_buf
        return ColumnarBatch(cols, batch.num_rows, out_schema,
                             batch._host_rows)

    # -- drive -------------------------------------------------------------

    #: merge this many partials device-side before one amortized host sync
    #: shrinks the running result into a tight capacity bucket
    MERGE_FAN_IN = 8

    #: exact-tier partials at or above this capacity are shrunk eagerly
    #: (one host sync each) instead of holding full-size buckets in HBM
    SHRINK_THRESHOLD_CAP = 1 << 16

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        from .speculation import speculation_allowed
        if (self._masked_ok and self._spec_enabled
                and speculation_allowed(self.plan_fingerprint())):
            yield from self._execute_speculative()
            return
        yield from self._execute_exact()

    def _execute_speculative(self) -> Iterator[ColumnarBatch]:
        """Streaming speculative drive: ONE program per source batch folds
        into an O(1)-size device state; the overflow flag is recorded with
        the active speculation scope and never read here."""
        from .speculation import current_scope
        agg_time = self.metrics[AGG_TIME]
        in_rows = self.metrics[NUM_INPUT_ROWS]
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        state, flag = self._initial_state()
        evaluated = None
        saw_input = False
        with agg_time.ns_timer():
            for batch in self._source.execute():
                in_batches.add(1)
                if batch._host_rows is not None:
                    in_rows.add(batch._host_rows)
                else:
                    in_rows.add_device(batch.num_rows)
                saw_input = True
                spillable = SpillableBatch.from_batch(batch)
                box = [state, flag, None]
                try:
                    def run(s: SpillableBatch):
                        b = s.get_batch()
                        try:
                            return self._jit_step_spec(b, box[0], box[1])
                        finally:
                            s.release()
                    for out in with_retry(spillable, run,
                                          split_policy=split_in_half_by_rows):
                        box[0], box[1], box[2] = out
                finally:
                    spillable.close()
                state, flag, evaluated = box
        if not saw_input:
            if self.group_exprs or self.mode == "partial":
                return  # no output rows (matches the exact path)
            # grand aggregate over empty input still emits one row
            from ..columnar.batch import empty_batch
            src_schema = (self._buffer_schema if self.mode == "final"
                          else self._source.output_schema)
            state, flag, evaluated = self._jit_step_spec(
                empty_batch(src_schema), state, flag)
        scope = current_scope()
        if scope is not None:
            scope.record(flag, owner=self.plan_fingerprint())
        if self.mode == "partial":
            yield state
        else:
            # the last step already evaluated its state in-program
            yield evaluated if evaluated is not None \
                else self._jit_evaluate(state)

    def _tight_input(self, batch: ColumnarBatch,
                     fingerprint: Optional[str]) -> ColumnarBatch:
        """A sparse source batch of a plan shape known to overflow the
        masked buckets, moved into a tight bucket before its exact update.
        The update will take the sort path, whose cost follows the batch's
        capacity, not its rows, and a join hands on its candidate bucket
        (Q3: 30,500 rows in 1,048,576 slots, 0.143 s a step; PERF.md
        section 6, PR 38). ONE host read (`_partial_size`: the rows, and
        the bytes of the string columns it carries), only of a bucket from
        `SHRINK_THRESHOLD_CAP` up, and a move only where the rows fill
        less than a quarter of it."""
        from .speculation import known_to_trip
        if batch.capacity < self.SHRINK_THRESHOLD_CAP \
                or not known_to_trip(fingerprint):
            return batch
        from ..columnar.column import bucket_capacity
        sizes = jax.device_get(_partial_size(batch))
        if 4 * bucket_capacity(max(int(sizes[0]), 1)) > batch.capacity:
            return batch
        return _tight_partial(batch, sizes)

    def _absorb_partial(self, aggregated: List[SpillableBatch],
                        out: ColumnarBatch) -> None:
        """Partial-accumulation discipline shared by the per-op exact
        drive and the fused stage's exact flavor (ISSUE 14): eager
        shrink of big partials past SHRINK_THRESHOLD_CAP, then
        MERGE_FAN_IN windowing so live partials stay BOUNDED — a
        forced-spill budget survives an arbitrarily long stream."""
        if (out.capacity >= self.SHRINK_THRESHOLD_CAP
                and aggregated):
            # the FIRST partial is held unshrunken: for the
            # (common) single-batch pipeline the shrink's
            # d2h sync buys nothing
            # — one full-size partial costs what the input
            # batch already cost, and it is spillable
            # big-batch partials keep the input capacity
            # (groups are usually few): pay ONE host sync
            # to shrink rather than hold MERGE_FAN_IN
            # full-size partials in HBM
            from ..columnar.column import bucket_capacity
            rows = out.num_rows_host
            small = bucket_capacity(max(rows, 1))
            if small < out.capacity:
                shrunk = _shrink_batch(out, small)
                out = ColumnarBatch(shrunk.columns, rows,
                                    out.schema)
        aggregated.append(SpillableBatch.from_batch(out))
        if len(aggregated) >= self.MERGE_FAN_IN:
            # bound live partials: merge the window device-side,
            # then ONE host sync shrinks the result into a tight
            # bucket (amortized over MERGE_FAN_IN batches).
            merged = self._merge_all(list(aggregated))
            from ..columnar.column import bucket_capacity
            rows = merged.num_rows_host
            small_cap = bucket_capacity(max(rows, 1))
            if small_cap < merged.capacity:
                shrunk = _shrink_batch(merged, small_cap)
                merged = ColumnarBatch(shrunk.columns, rows,
                                       merged.schema)
            aggregated[:] = [SpillableBatch.from_batch(merged)]

    def _execute_exact(self) -> Iterator[ColumnarBatch]:
        agg_time = self.metrics[AGG_TIME]
        in_rows = self.metrics[NUM_INPUT_ROWS]
        in_batches = self.metrics[NUM_INPUT_BATCHES]
        aggregated: List[SpillableBatch] = []
        _note(executions=1)

        with agg_time.ns_timer():
            first_pass = self._merge_jitted if self.mode == "final" \
                else self._update_spanned
            for batch in self._source.execute():
                in_batches.add(1)
                if batch._host_rows is not None:
                    in_rows.add(batch._host_rows)
                else:
                    in_rows.add_device(batch.num_rows)
                spillable = SpillableBatch.from_batch(batch)
                try:
                    for out in with_retry(spillable,
                                          self._spill_wrap(first_pass),
                                          split_policy=split_in_half_by_rows):
                        self._absorb_partial(aggregated, out)
                finally:
                    spillable.close()

            if not aggregated:
                if not self.group_exprs and self.mode != "partial":
                    # grand aggregate over empty input: one row (count=0 ...)
                    from .basic import InMemoryScanExec
                    from ..columnar.batch import empty_batch
                    empty = empty_batch(self._pre_schema
                                        if self.mode != "final"
                                        else self._buffer_schema)
                    merged = self._update_batch(empty) \
                        if self.mode != "final" else self._merge_batch(empty)
                    yield self._jit_evaluate(merged)
                return

            if len(aggregated) == 1:
                # a single partial already has unique keys: no merge needed
                only = aggregated[0]
                merged = only.get_batch()
                only.release()
                only.close()
            else:
                merged = self._merge_all(aggregated)
            if self.mode == "partial":
                yield merged
            else:
                yield self._jit_evaluate(merged)

    def _key_words(self, batch: ColumnarBatch) -> int:
        """String-lane width for exact key ordering (host sync, pre-jit)."""
        return string_words_for(batch.columns, range(self._key_count))

    @property
    def _hash_path_ok(self) -> bool:
        """Hash group-by handles everything except ordering aggs (min/max)
        over strings — those need sort lanes. Both update and merge passes
        see them as min/max over a string buffer, so checking the buffer
        schema covers every mode."""
        from ..types import ArrayType, BinaryType, StringType
        pos = self._key_count
        for fn, _ in self.aggregates:
            for op in fn.merge_ops():
                bt = self._buffer_schema.fields[pos].data_type
                if op in ("min", "max") and isinstance(
                        bt, (StringType, BinaryType)):
                    return False
                if isinstance(bt, ArrayType):  # collect_* need sort order
                    return False
                pos += 1
        return True

    @property
    def _masked_ok(self) -> bool:
        """True when the masked-bucket kernels apply: every key and buffer
        column is fixed-width (strings have no static order lanes for the
        in-program exact fallback and no masked min/max encoding; string
        KEYS beside such buffers take the hash tiers' lane tier,
        `_lane_ok`)."""
        return all(_masked_type(f.data_type)
                   for f in self._buffer_schema.fields)

    @property
    def _lane_ok(self) -> bool:
        """True when the hash tiers start with the lane tier: the masked
        kernel sums every BUFFER, and what keeps the group-by off it
        (`_masked_ok`) is only STRING keys, which ride as packed lanes
        where the batch's keys measure short enough (`_lane_key_bytes`)."""
        from ..types import StringType
        fields = self._buffer_schema.fields
        return (not self._masked_ok
                and all(_masked_type(f.data_type)
                        for f in fields[self._key_count:])
                and all(_masked_type(f.data_type)
                        or isinstance(f.data_type, StringType)
                        for f in fields[: self._key_count]))

    def _lane_key_bytes(self, batch: ColumnarBatch) -> Optional[int]:
        """The static key width the lane tier runs this batch at (one host
        read of the keys' longest string, a power of two), or None where
        the keys are too wide for it."""
        from ..ops.maskedagg import key_lane_count
        from ..ops.sort import string_key_bytes
        key_bytes = string_key_bytes(batch.columns, range(self._key_count))
        lanes = sum(key_lane_count(f.data_type, key_bytes)
                    for f in self._buffer_schema.fields[: self._key_count])
        if key_bytes > LANE_KEY_BYTES or lanes > LANE_KEY_COLUMNS:
            return None
        return key_bytes

    @property
    def _sync_free(self) -> bool:
        return self._masked_ok

    def _update_spanned(self, batch: ColumnarBatch) -> ColumnarBatch:
        with op_span("agg.update", phase="group-agg"):
            return self._update_and_aggregate(batch)

    def _update_and_aggregate(self, batch: ColumnarBatch) -> ColumnarBatch:
        if self._masked_ok:
            # one program: fused steps + masked buckets + lax.cond exact
            # sort fallback; the host never reads any flag (no round trip)
            note_many_groups(self.plan_fingerprint())
            return self._jit_step_exact(
                self._tight_input(batch, self.plan_fingerprint()))
        pre = self._jit_pre(batch)
        if self._hash_path_ok:
            out = self._hash_tiers(self._jit_update_hash, pre)
            if out is not None:
                return out
            # unresolved hash collisions: exact sort fallback (reference
            # duality: hash primary, sort fallback)
        return self._jit_update(pre, self._key_words(pre))

    def _hash_tiers(self, sites, batch: ColumnarBatch
                    ) -> Optional[ColumnarBatch]:
        """The hash path's tiers, counted: the lane tier where the schema
        and the measured keys allow it, then 2 hash rounds, then 6, each
        where the one before left keys over (each reads `leftover` on the
        host, and with it the partial's size, so that it leaves in a tight
        bucket); None where 6 left some too and the caller falls to the
        sort path."""
        _note(hash_updates=1)
        tiers = [(r, sites[r]) for r in (2, 6)]
        if "lanes" in sites:
            key_bytes = self._lane_key_bytes(batch)
            if key_bytes is None:
                _note(lane_declines=1)
            else:
                tiers.insert(0, ("lanes",
                                 lambda b: sites["lanes"](b, key_bytes)))
        for tier, program in tiers:
            out, leftover = program(batch)
            # one host read: the flag, and what a tight bucket needs
            leftover, sizes = jax.device_get((leftover, _partial_size(out)))
            if not leftover:
                if tier == "lanes":
                    _note(lane_updates=1)
                return _tight_partial(out, sizes)
            _note(**{_LEFTOVER_COUNTER[tier]: 1})
        return None

    def _merge_jitted(self, batch: ColumnarBatch) -> ColumnarBatch:
        if self._masked_ok:
            return self._jit_merge_auto(batch)
        if self._hash_path_ok:
            out = self._hash_tiers(self._jit_merge_hash, batch)
            if out is not None:
                return out
        return self._jit_merge(batch, self._key_words(batch))

    def _spill_wrap(self, fn):
        def run(s: SpillableBatch):
            b = s.get_batch()
            try:
                return fn(b)
            finally:
                s.release()
        return run

    def _merge_all(self, aggregated: List[SpillableBatch]) -> ColumnarBatch:
        """Concat + re-aggregate; under OOM the retry framework splits the
        set of partial batches and re-merges the halves (always correct:
        merge ops are associative & commutative)."""
        with op_span("agg.merge", phase="group-agg"):
            return self._merge_all_spanned(aggregated)

    def _merge_all_spanned(self, aggregated: List[SpillableBatch]
                           ) -> ColumnarBatch:
        extra_owned: List[SpillableBatch] = []

        def split_set(items: List[SpillableBatch]):
            if len(items) < 2:
                halves = split_in_half_by_rows(items[0])
                extra_owned.extend(halves)
                return [[h] for h in halves]
            half = len(items) // 2
            return [items[:half], items[half:]]

        def do(items: List[SpillableBatch]) -> ColumnarBatch:
            batches = [s.get_batch() for s in items]
            try:
                if self._sync_free:
                    return self._tree_merge_device(batches)
                merged = concat_batches(batches, self._buffer_schema)
                return self._merge_jitted(merged)
            finally:
                for s in items:
                    s.release()

        try:
            outs = list(with_retry(aggregated, do, split_policy=split_set))
        finally:
            for s in aggregated + extra_owned:
                s.close()
        if len(outs) == 1:
            return outs[0]
        # split path produced several partials: re-merge them
        spill = [SpillableBatch.from_batch(b) for b in outs]
        return self._merge_all(spill)

    def _tree_merge_device(self, batches: List[ColumnarBatch]
                           ) -> ColumnarBatch:
        """Pairwise device-only merge: every level concats pairs into the
        capacity bucket of the pair and re-aggregates — no host syncs, no
        row-count reads. Peak capacity is the bucket of the total, same as
        the concat-all path, but each level shrinks live groups."""
        from ..columnar.column import bucket_capacity
        level = list(batches)
        while len(level) > 1:
            nxt: List[ColumnarBatch] = []
            for i in range(0, len(level) - 1, 2):
                a, b = level[i], level[i + 1]
                cap = bucket_capacity(a.capacity + b.capacity)
                nxt.append(self._jit_concat_merge(a, b, cap))
            if len(level) % 2:
                nxt.append(level[-1])
            level = nxt
        return level[0]

    def node_description(self):
        aggs = ", ".join(f"{fn!r} AS {name}" for fn, name in self.aggregates)
        return (f"AggregateExec[{self.mode}, keys={self.group_exprs!r}, "
                f"aggs=[{aggs}]]")
