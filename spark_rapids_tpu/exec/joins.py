"""Join execs — reference GpuHashJoin
(org/apache/spark/sql/rapids/execution/GpuHashJoin.scala:994, doJoin:1103),
GpuShuffledHashJoinExec, GpuBroadcastHashJoinExecBase,
GpuBroadcastNestedLoopJoinExecBase, ExistenceJoin.

One HashJoinExec covers broadcast & shuffled hash joins: in this engine a
"broadcast" build side is simply an already-materialized child (the
broadcast exchange keeps it device-resident), so both reference execs share
this operator, parameterized by build side. The probe pipeline is the
gather-map kernel stack in ops/join.py; per stream batch there is exactly
one host sync (candidate count -> capacity bucket), everything else stays
in compiled XLA.

Join-type support: inner, left/right/full outer, left semi, left anti,
cross (via NestedLoopJoinExec), existence. Extra non-equi conditions
evaluate over candidate pairs and AND into the verified mask — the analog
of the reference's AST-compiled join conditions (AstUtil.scala).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column, StringColumn, bucket_capacity
from ..columnar.encoded import DictionaryColumn
from ..expr.core import Expression, resolve
from ..memory.spillable import SpillableBatch
from ..obs.dispatch import host_read
from ..ops.basic import active_mask, compaction_order, gather_column
from ..ops.strings import string_equal
from ..ops.join import (
    BuildTable, cross_pairs, expand_candidates, gather_column_indices,
    inner_gather_maps, matched_flags, outer_extend_maps,
    probe_ranges, unmatched_indices, verify_pairs,
)
from ..types import BooleanType, Schema, StructField
from .base import (BUILD_TIME, DEBUG, DISPATCH_METRICS, GATHER_METRICS,
                   GATHER_TIME,
                   JOIN_TIME, NUM_GATHERS, NUM_INPUT_BATCHES, TpuExec)
from .basic import bind_projection, eval_projection, projection_schema
from .coalesce import concat_batches

INNER, LEFT_OUTER, RIGHT_OUTER, FULL_OUTER = "inner", "left_outer", \
    "right_outer", "full_outer"
LEFT_SEMI, LEFT_ANTI, EXISTENCE, CROSS = "left_semi", "left_anti", \
    "existence", "cross"


def _gather_batch(columns: Sequence[Column], idx, n,
                  byte_caps: Optional[Tuple] = None) -> List[Column]:
    """byte_caps: per-column static output byte bucket (None entries keep
    the input bucket). Joins DUPLICATE rows, so string columns must size
    their output byte bucket from the measured join byte need — the input
    bucket silently truncates payloads once output bytes exceed it.

    Fixed-width columns ride ONE packed row gather (XLA's per-gather
    loop cost dwarfs its per-byte cost on v5e), varlen columns keep the
    per-column path — both routed through the gather engine
    (ops/gather.gather_batch_columns) so the structural numGathers
    accounting covers every join emit."""
    from ..ops.gather import gather_batch_columns
    return gather_batch_columns(columns, idx, num_rows=n,
                                byte_caps=byte_caps)


def _is_varsize(c: Column) -> bool:
    from ..columnar.column import ArrayColumn
    return isinstance(c, (StringColumn, ArrayColumn))


def _var_lengths(c: Column):
    """Per-row payload size of a variable-size column: bytes for strings,
    elements for arrays."""
    from ..columnar.column import ArrayColumn
    from ..ops.collection import array_lengths
    from ..ops.strings import string_lengths
    if isinstance(c, ArrayColumn):
        return array_lengths(c)
    return string_lengths(c)


def _string_byte_needs(stream_columns, counts, act, range_sizes):
    """Exact output payload requirement per variable-size column of the
    join (string bytes / array elements), all on device — fetched together
    with the candidate total in the one host sync per stream batch.

    Stream side: row i is emitted count_i times (candidates) plus at most
    once more (outer-unmatched tail). Build side: candidate payload is the
    size of each row's sorted-order range [lo, lo+count), which
    `probe_ranges` read with the range (`range_sizes`)."""
    cnt = counts.astype(jnp.int64)
    stream_needs = []
    for c in stream_columns:
        if _is_varsize(c):
            lens = jnp.where(act, _var_lengths(c), 0).astype(jnp.int64)
            stream_needs.append(jnp.sum(cnt * lens) + jnp.sum(lens))
    build_needs = [jnp.sum(r.astype(jnp.int64)) for r in range_sizes]
    return tuple(stream_needs), tuple(build_needs)


def _byte_cap_tuple(columns, needs) -> Tuple:
    """Static per-column payload buckets from fetched needs (None = keep
    the input bucket for fixed-width columns)."""
    it = iter(needs)
    return tuple(bucket_capacity(max(int(next(it)), 8))
                 if _is_varsize(c) else None for c in columns)


class HashJoinExec(TpuExec):
    # speculative sizing-cache entries expire after this many uses so a
    # pathological batch cannot inflate candidate caps forever
    SPEC_REFRESH = 512

    def __init__(self, left: TpuExec, right: TpuExec,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str = INNER,
                 build_side: str = "right",
                 condition: Optional[Expression] = None,
                 exists_name: str = "exists"):
        super().__init__(left, right)
        assert build_side in ("left", "right")
        self.join_type = join_type
        self.build_side = build_side
        self.condition = condition
        self.exists_name = exists_name
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        # semi/anti/existence joins that preserve the stream side require
        # build == non-preserved side; the planner guarantees this.
        if join_type in (LEFT_SEMI, LEFT_ANTI, EXISTENCE):
            assert build_side == "right"
        # (stream_cap, build_cap) -> (cand_cap, s_caps, b_caps): lets a
        # speculation scope skip the per-batch sizing sync (round 4)
        self._size_cache = {}
        # structural gather accounting (round 8): counts the probe's
        # materializing row gathers per iteration into numGathers /
        # gatherTimeNs (trace-time counts memoized per program key)
        from ..ops.gather import GatherTracker
        self._gather_track = GatherTracker(self.metrics[NUM_GATHERS],
                                           self.metrics[GATHER_TIME])
        # per-shape speculative-use counters driving cap decay (round 5)
        self._spec_uses = {}
        # round 5: absorb child Filters into the probe/build kernels as
        # key-validity masks — an invalid key never matches, so for join
        # shapes that emit ONLY matched rows from that side the filter's
        # compaction (sort + gather, ~40 ms per 2M-row batch on v5e) is
        # pure overhead. Build side: safe whenever unmatched build rows
        # are never emitted; stream side: inner/semi only (outer/anti
        # emit unmatched stream rows, which must already be filtered).
        from .basic import FilterExec
        self._stream_filter = None
        self._build_filter = None
        stream_idx = 0 if build_side == "right" else 1
        build_idx = 1 - stream_idx
        kids = list(self.children)
        if join_type in (INNER, LEFT_SEMI):
            preds = []
            while isinstance(kids[stream_idx], FilterExec):
                preds.append(kids[stream_idx]._bound)
                kids[stream_idx] = kids[stream_idx].child
            if preds:
                self._stream_filter = preds
        if not self._need_build_flags:
            preds = []
            while isinstance(kids[build_idx], FilterExec):
                preds.append(kids[build_idx]._bound)
                kids[build_idx] = kids[build_idx].child
            if preds:
                self._build_filter = preds
        self.children = tuple(kids)
        # compiled phases, built AFTER filter absorption (ISSUE 14):
        # the plan fingerprint keying the program-site cache must see
        # the final children + absorbed predicates. counts is sized by
        # the stream bucket; the probe body by stream + candidate
        # buckets (static per shape).
        self._jit_build = self._site(self._build_kernel,
                                     label="HashJoinExec.build")
        self._jit_counts = self._site(self._counts_kernel,
                                      label="HashJoinExec.counts")
        self._jit_probe = self._site(self._probe_kernel,
                                     label="HashJoinExec.probe",
                                     static_argnums=(5, 6, 7))

    @property
    def consumes_encoded(self) -> bool:
        """Encoded inputs are fine when every key is a bare reference
        (the probe byte-compares through the dictionary spans and the
        bucket hash precomputes the dictionary's hashes once — ISSUE
        18) or string-reference-free, and the absorbed filters plus the
        residual condition pass the code-space walk."""
        from ..expr.predicates import (encoded_safe_predicate,
                                       encoded_safe_projection)
        try:
            lb = [resolve(e, self.left_schema) for e in self.left_keys]
            rb = [resolve(e, self.right_schema) for e in self.right_keys]
        except Exception:  # noqa: BLE001 — unresolvable = conservative
            return False
        if not all(encoded_safe_projection(e) for e in lb + rb):
            return False
        for preds in (self._stream_filter, self._build_filter):
            if preds and not all(encoded_safe_predicate(p) for p in preds):
                return False
        if self.condition is not None:
            pair = Schema(tuple(self.left_schema.fields)
                          + tuple(self.right_schema.fields))
            try:
                cond = resolve(self.condition, pair)
            except Exception:  # noqa: BLE001
                return False
            if not encoded_safe_predicate(cond):
                return False
        return True

    def _fingerprint_extras(self):
        # semantic_key, NOT repr (repr omits non-child expression
        # parameters — the program-cache soundness contract).
        # Non-deterministic expressions (a UDF predicate absorbed as a
        # stream/build filter keys per-INSTANCE by id, recyclable
        # after GC) opt the subtree out — see ProjectExec.
        exprs = list(self.left_keys) + list(self.right_keys) \
            + list(self._stream_filter or ()) \
            + list(self._build_filter or ())
        if self.condition is not None:
            exprs.append(self.condition)
        if not all(e.deterministic for e in exprs):
            return None

        def keys(es):
            return None if es is None else \
                tuple(e.semantic_key() for e in es)
        return (self.join_type, self.build_side, keys(self.left_keys),
                keys(self.right_keys),
                None if self.condition is None
                else self.condition.semantic_key(),
                self.exists_name,
                keys(self._stream_filter), keys(self._build_filter))

    # -- schema ------------------------------------------------------------
    @property
    def left_schema(self) -> Schema:
        return self.children[0].output_schema

    @property
    def right_schema(self) -> Schema:
        return self.children[1].output_schema

    @property
    def output_schema(self) -> Schema:
        if self.join_type in (LEFT_SEMI, LEFT_ANTI):
            return self.left_schema
        if self.join_type == EXISTENCE:
            return Schema(tuple(self.left_schema.fields) +
                          (StructField(self.exists_name, BooleanType(), False),))
        lf = [StructField(f.name, f.data_type,
                          f.nullable or self.join_type in (RIGHT_OUTER, FULL_OUTER))
              for f in self.left_schema.fields]
        rf = [StructField(f.name, f.data_type,
                          f.nullable or self.join_type in (LEFT_OUTER, FULL_OUTER))
              for f in self.right_schema.fields]
        return Schema(tuple(lf + rf))

    def additional_metrics(self):
        return (BUILD_TIME, JOIN_TIME, (NUM_INPUT_BATCHES, DEBUG)) \
            + GATHER_METRICS + DISPATCH_METRICS

    @property
    def output_grouped_by(self):
        """INNER-join output batches are emitted key-grouped (the pair
        compaction carries the packed key lanes — see _probe_kernel); one
        equivalence class per key pair, since left key == right key on
        every emitted row."""
        if self.join_type != INNER:
            return None
        out_names = [f.name for f in self.output_schema.fields]
        classes = []
        for lk, rk in zip(self.left_keys, self.right_keys):
            for e, sch in ((lk, self.left_schema), (rk, self.right_schema)):
                try:
                    dt = resolve(e, sch).data_type
                except (KeyError, TypeError):
                    return None
                from ..types import DecimalType
                if not dt.is_fixed_width or isinstance(dt, DecimalType):
                    # string/decimal keys are not in the packed lanes
                    return None
            names = set()
            for e in (lk, rk):
                n = getattr(e, "name", None)
                if n and out_names.count(n) == 1:
                    names.add(n)
            if not names:
                return None  # an unnamed key: grouping not expressible
            classes.append(frozenset(names))
        return tuple(classes)

    @staticmethod
    def _filter_mask(preds, batch: ColumnarBatch):
        keep = None
        for p in preds:
            c = p.columnar_eval(batch)
            k = c.data & c.validity  # Spark: null predicate rows drop
            keep = k if keep is None else (keep & k)
        return keep

    @staticmethod
    def _mask_keys(key_cols, keep):
        """AND an absorbed-filter mask into key validity (invalid keys
        never match; dropped rows vanish from matched-only outputs)."""
        from ..columnar.column import (ArrayColumn, MapColumn,
                                       StringColumn, StructColumn)
        out = []
        for c in key_cols:
            v = c.validity & keep
            if isinstance(c, DictionaryColumn):
                out.append(DictionaryColumn(c.codes, c.dict_data,
                                            c.dict_offsets, v, c.dtype))
            elif isinstance(c, StringColumn):
                out.append(StringColumn(c.data, c.offsets, v, c.dtype))
            elif isinstance(c, StructColumn):
                out.append(type(c)(c.children, v, c.dtype))
            elif isinstance(c, MapColumn):
                out.append(MapColumn(c.keys, c.values, c.offsets, v,
                                     c.dtype))
            elif isinstance(c, ArrayColumn):
                out.append(ArrayColumn(c.child, c.offsets, v, c.dtype))
            else:
                out.append(Column(c.data, v, c.dtype))
        return out

    # -- build -------------------------------------------------------------
    def _build_kernel(self, batch: ColumnarBatch) -> BuildTable:
        build_child = self.children[1] if self.build_side == "right" \
            else self.children[0]
        keys = self.right_keys if self.build_side == "right" else self.left_keys
        bound = bind_projection(keys, build_child.output_schema)
        key_cols = [e.columnar_eval(batch) for e in bound]
        if self._build_filter is not None:
            key_cols = self._mask_keys(
                key_cols, self._filter_mask(self._build_filter, batch))
        return BuildTable.build(key_cols, list(batch.columns),
                                batch.num_rows, batch.capacity)

    def _build(self) -> Tuple[BuildTable, ColumnarBatch]:
        build_child = self.children[1] if self.build_side == "right" \
            else self.children[0]
        from ..obs import op_span
        # the span the fused join stage opens around its build child's
        # drain: `join_build_ms` reads a per-operator join's too
        with self.metrics[BUILD_TIME].ns_timer(), \
                op_span("join.build", phase="join-build"):
            batches = list(build_child.execute())
            if len(batches) > 1:
                # distinct per-batch dictionaries cannot concatenate
                # shape-stably (ops/basic.concat_columns asserts) —
                # decode first; a single-batch build side (the common
                # broadcast shape) stays encoded end-to-end
                from ..columnar.encoded import materialize_batch
                batches = [materialize_batch(b, seam="concat")
                           for b in batches]
            if batches:
                batch = concat_batches(batches, build_child.output_schema)
            else:
                from ..columnar.batch import empty_batch
                batch = empty_batch(build_child.output_schema)
            return self._jit_build(batch), batch

    @property
    def _need_build_flags(self) -> bool:
        jt, bs = self.join_type, self.build_side
        return ((jt in (RIGHT_OUTER, FULL_OUTER) and bs == "right")
                or (jt in (LEFT_OUTER, FULL_OUTER) and bs == "left"))

    # -- probe -------------------------------------------------------------
    def internal_execute(self) -> Iterator[ColumnarBatch]:
        build, build_batch = self._build()
        stream_child = self.children[0] if self.build_side == "right" \
            else self.children[1]
        build_matched = jnp.zeros((build.capacity,), jnp.bool_)

        join_time = self.metrics[JOIN_TIME]
        try:
            for stream_batch in stream_child.execute():
                with join_time.ns_timer():
                    out, build_matched = self._probe_one(
                        build, build_batch, stream_batch, build_matched)
                if out is not None:
                    yield out

            if self._need_build_flags:
                with join_time.ns_timer():
                    yield self._emit_build_unmatched(build, build_batch,
                                                     build_matched)
        finally:
            # one gather_stats event per execution (the pipeline-event
            # convention): reconciles with the numGathers metric and
            # the op_close batch count
            self._gather_track.emit_event(type(self).__name__,
                                          self._op_id)

    def _counts_kernel(self, build: BuildTable, stream_batch: ColumnarBatch):
        stream_child = self.children[0] if self.build_side == "right" \
            else self.children[1]
        stream_keys = self.left_keys if self.build_side == "right" \
            else self.right_keys
        bound = bind_projection(stream_keys, stream_child.output_schema)
        skey_cols = [e.columnar_eval(stream_batch) for e in bound]
        if self._stream_filter is not None:
            skey_cols = self._mask_keys(
                skey_cols,
                self._filter_mask(self._stream_filter, stream_batch))
        lo, counts, _, range_sizes = probe_ranges(
            build, skey_cols, stream_batch.num_rows, stream_batch.capacity)
        act = active_mask(stream_batch.num_rows, stream_batch.capacity)
        needs = _string_byte_needs(stream_batch.columns, counts, act,
                                   range_sizes)
        return lo, counts, skey_cols, jnp.sum(counts.astype(jnp.int64)), needs

    def _probe_kernel(self, build: BuildTable, build_batch: ColumnarBatch,
                      stream_batch: ColumnarBatch, lo_counts, build_matched,
                      cand_cap: int, s_caps: Tuple = (), b_caps: Tuple = ()):
        """Packed-row probe (round 4): the build side's fixed-width
        keys+payload live in ONE sorted u32 matrix (+ f64 matrix), so the
        whole candidate-verify-compact-emit pipeline is a handful of row
        gathers instead of 2 gathers per column (reference JoinGatherer
        gathers; measured ~20x on the q3 shape, tools/exp_gather.py).

        Gather elimination (round 8): the payload is deferred to ONE
        output-level packed gather per side after compaction — the
        candidate level touches only key lanes. Per iteration the emit
        is one index materialization + one packed payload gather per
        side, counted structurally by the gather engine (ops/gather)
        into the numGathers metric."""
        from ..ops import gather as G
        from ..ops.rowpack import pack_rows, unpack_rows
        lo, counts, skey_cols = lo_counts
        s_caps = s_caps or (None,) * len(stream_batch.columns)
        b_caps = b_caps or (None,) * len(build.payload)
        scap = stream_batch.capacity

        (plan_k, kmat_b, kfmat_b, plan_p, pmat_b, pfmat_b,
         kpi, ppi, poi) = build.pack

        s_idx, b_pos, total_dev = expand_candidates(lo, counts, cand_cap)
        pair_valid = s_idx >= 0
        b_pos_m = jnp.where(pair_valid, b_pos, -1)

        # --- verify: keys packable on BOTH sides compare via
        # KEY-ONLY candidate-level row gathers (the payload no
        # longer rides them), the rest via the per-column path ---
        from ..ops.rowpack import is_packable
        kpi_pos = {ki: pos for pos, ki in enumerate(kpi)}
        pk = [ki for ki in kpi if is_packable(skey_cols[ki])]

        # sorted position -> original build row; only needed for
        # varlen columns, fallback keys and residual conditions
        need_b_row = bool(poi) or self.condition is not None or \
            len(pk) < len(skey_cols)
        b_row = gather_column_indices(build.perm, b_pos_m) \
            if need_b_row else None
        ok = pair_valid
        ki_c = kf_c = None
        if pk:
            ki_c, kf_c = G.gather_rows(plan_k, kmat_b, kfmat_b, b_pos_m)
            bk_cand = unpack_rows(plan_k, ki_c, kf_c,
                                  only=[kpi_pos[ki] for ki in pk])
            plan_sk, imat_sk, fmat_sk = pack_rows(
                [skey_cols[ki] for ki in pk])
            ski_c, skf_c = G.gather_rows(
                plan_sk, imat_sk, fmat_sk,
                jnp.where(pair_valid, s_idx, -1))
            sk_cand = unpack_rows(plan_sk, ski_c, skf_c)
            for b, s in zip(bk_cand, sk_cand):
                ok = ok & (b.data == s.data) & b.validity & s.validity
        pk_set = set(pk)
        for ki in range(len(skey_cols)):
            if ki in pk_set:
                continue
            bk = build.key_cols[ki]
            sk = skey_cols[ki]
            if isinstance(bk, DictionaryColumn) or \
                    isinstance(sk, DictionaryColumn):
                # encoded key (ISSUE 18): byte-compare through
                # spans into the ORIGINAL buffers — no decode, and
                # no materialized candidate gather (whose byte
                # bucket a join fan-out overflows)
                from ..columnar.encoded import bytes_equal_at
                ok = ok & bytes_equal_at(
                    bk, b_row, sk,
                    jnp.where(pair_valid, s_idx, -1))
                continue
            b = gather_column(bk, b_row)
            s = gather_column(sk, jnp.where(pair_valid, s_idx, -1))
            if isinstance(bk, StringColumn):
                eq = string_equal(b, s)
                ok = ok & eq.data & eq.validity
            else:
                from ..columnar.column import Decimal128Column
                if isinstance(bk, Decimal128Column):
                    # two-limb equality (round 5: decimal128 keys)
                    ok = ok & (b.hi.data == s.hi.data) \
                        & (b.lo.data == s.lo.data) \
                        & b.validity & s.validity
                else:
                    ok = ok & (b.data == s.data) \
                        & b.validity & s.validity
        verified = ok
        if self.condition is not None:
            verified = verified & self._eval_condition(
                stream_batch, build_batch, s_idx, b_row, cand_cap,
                s_caps, b_caps)

        jt, bs = self.join_type, self.build_side
        stream_preserved = (jt == LEFT_OUTER and bs == "right") or \
            (jt == RIGHT_OUTER and bs == "left") or jt == FULL_OUTER

        if self._need_build_flags:
            # flags live in SORTED build space; translated once at
            # _emit_build_unmatched
            build_matched = build_matched | matched_flags(
                verified, b_pos_m, build.capacity)

        if jt in (LEFT_SEMI, LEFT_ANTI, EXISTENCE):
            smatched = matched_flags(verified, s_idx, scap)
            if jt == EXISTENCE:
                flag = Column(smatched, jnp.ones((scap,), jnp.bool_),
                              BooleanType())
                cols = list(stream_batch.columns) + [flag]
                return (ColumnarBatch(cols, stream_batch.num_rows,
                                      self.output_schema), build_matched)
            keep = smatched if jt == LEFT_SEMI else ~smatched
            perm, n = compaction_order(keep, stream_batch.num_rows)
            cols = _gather_batch(stream_batch.columns, perm, n)
            return ColumnarBatch(cols, n, self.output_schema), build_matched

        # --- compact verified pairs ---
        # (pk == kpi whenever every key is fixed-width, the same
        # condition output_grouped_by promises grouping under)
        grouped_emit = jt == INNER and len(kpi) == len(skey_cols) \
            and len(pk) == len(kpi)
        if grouped_emit:
            # key-grouped emission (round 5): carry the packed build-key
            # lanes as extra sort keys so equal join keys land contiguous
            # in the output — a downstream group-by on the join keys then
            # skips its own sort (output_grouped_by). Extra sort lanes
            # are ~free on v5e (docs/perf.md r5). Key LANES, not b_pos:
            # the build table is hash-sorted, so two distinct keys
            # sharing a 64-bit hash could interleave by position.
            act_c = active_mask(total_dev, cand_cap)
            kflag = verified & act_c
            # key lanes from the candidate-level KEY pack (already
            # gathered for the verify above)
            nvl = plan_k.n_valid_lanes
            klanes = []
            for pos in range(len(kpi)):
                kind, lane = plan_k.kinds[pos]
                if kind == "f64":
                    klanes.append(kf_c[:, lane])
                elif kind == "w2":
                    klanes.append(ki_c[:, nvl + lane])
                    klanes.append(ki_c[:, nvl + lane + 1])
                else:
                    klanes.append(ki_c[:, nvl + lane])
            iota_c = jnp.arange(cand_cap, dtype=jnp.int32)
            res = jax.lax.sort(
                ((~kflag).astype(jnp.uint32), *klanes, iota_c),
                num_keys=2 + len(klanes))
            perm_c = res[-1]
            n_pairs = jnp.sum(kflag, dtype=jnp.int32)
        else:
            perm_c, n_pairs = compaction_order(verified, total_dev)
        # compact ONLY the 2-3 index lanes (round 8, BOTH tiers); the
        # full-width payload gather happens ONCE, at output level, below
        lanes = [s_idx, b_pos_m] + ([b_row] if need_b_row else [])
        lane_mat = jnp.stack(lanes, axis=1)

        if stream_preserved:
            smatched = matched_flags(verified, s_idx, scap)
            un_idx, n_un = unmatched_indices(smatched, stream_batch.num_rows,
                                             scap)
            out_cap = bucket_capacity(cand_cap + scap)
            n_out = n_pairs + n_un
            i = jnp.arange(out_cap, dtype=jnp.int32)
            from_pairs = i < n_pairs
            perm_pad = jnp.concatenate(
                [perm_c, jnp.full((out_cap - cand_cap,), cand_cap,
                                  jnp.int32)]) if out_cap > cand_cap \
                else perm_c
            bsel = jnp.where(from_pairs, perm_pad, -1)
            tail = (~from_pairs) & (i < n_out)
            # shift the unmatched tail to start at n_pairs with a roll
            # (two dynamic slices) instead of a full-width index gather
            un_pad = jnp.concatenate(
                [un_idx, jnp.full((out_cap - scap,), -1, jnp.int32)]) \
                if out_cap > scap else un_idx[:out_cap]
            un_part = jnp.roll(un_pad, n_pairs)
        else:
            out_cap = cand_cap
            n_out = n_pairs
            i = jnp.arange(out_cap, dtype=jnp.int32)
            from_pairs = i < n_pairs
            bsel = jnp.where(from_pairs, perm_c, -1)
            tail = None
            un_part = None

        # ONE index materialization: the compacted selection reads only
        # the index lanes; out-of-range bsel rows read row 0 and are
        # masked by from_pairs
        g = G.gather_lane_matrix(lane_mat, bsel)
        s_map = jnp.where(from_pairs, g[:, 0], -1)
        if tail is not None:
            s_map = jnp.where(tail, un_part, s_map)
        b_pos_out = jnp.where(from_pairs, g[:, 1], -1)
        b_map = jnp.where(from_pairs, g[:, 2], -1) if need_b_row else None

        # build-side output columns: ONE output-level packed payload
        # gather — only SURVIVING pairs move the full payload width
        # (before round 8 the XLA tier paid it at candidate level and
        # again at output level); varlen columns ride b_map
        bcols: List[Optional[Column]] = [None] * len(build.payload)
        if ppi:
            pmat_out, pfmat_out = G.gather_rows(plan_p, pmat_b, pfmat_b,
                                                b_pos_out)
            for j, c in zip(ppi, unpack_rows(plan_p, pmat_out,
                                             pfmat_out)):
                bcols[j] = c
        for j in poi:
            bcols[j] = gather_column(build.payload[j], b_map,
                                     out_byte_capacity=b_caps[j])
        # stream-side output columns: one packed row gather by s_map
        scols = _gather_batch(stream_batch.columns, s_map, n_out, s_caps)
        bcols_f = [c for c in bcols if c is not None]
        left_cols = scols if self.build_side == "right" else bcols_f
        right_cols = bcols_f if self.build_side == "right" else scols
        return (ColumnarBatch(left_cols + right_cols, n_out,
                              self.output_schema), build_matched)

    def _probe_one(self, build: BuildTable, build_batch: ColumnarBatch,
                   stream_batch: ColumnarBatch, build_matched):
        from .speculation import current_scope, speculation_allowed
        lo, counts, skey_cols, total_dev, needs_dev = \
            self._jit_counts(build, stream_batch)
        key = (stream_batch.capacity, build.capacity)
        cached = self._size_cache.get(key)
        if cached is not None and speculation_allowed():
            # Bounded-staleness refresh (ADVICE/VERDICT r4): caps grew
            # monotonically, so one pathological batch used to inflate
            # every later probe of the shape forever. After SPEC_REFRESH
            # SPECULATIVE uses (the measured branch re-syncs exact needs
            # anyway) the entry expires and the next probe re-measures
            # FRESH (no monotone max), letting caps shrink back; stable
            # workloads re-derive the same bucket sizes so the compiled
            # kernel is reused.
            self._spec_uses[key] = self._spec_uses.get(key, 0) + 1
            if self._spec_uses[key] > self.SPEC_REFRESH:
                del self._size_cache[key]
                self._spec_uses[key] = 0
                cached = None
        if cached is not None and speculation_allowed():
            # speculative sizing (round 4): reuse the last buckets for this
            # shape and record a device overflow flag with the scope
            # instead of paying a host round trip per stream
            # batch; a tripped scope re-runs the plan exactly (the same
            # optimistic-then-redo contract as the masked-bucket
            # aggregate, exec/speculation.py)
            cand_cap, s_caps, b_caps = cached
            flag = total_dev > cand_cap
            s_needs, b_needs = needs_dev
            # the zip below pairs byte-needs with caps positionally; if
            # _string_byte_needs and _byte_cap_tuple ever drift in column
            # order/count a silent mis-pairing could fail to trip the flag
            # and ship truncated payloads — guard the lengths
            assert len(list(s_needs)) == sum(c is not None for c in s_caps), \
                (len(list(s_needs)), s_caps)
            assert len(list(b_needs)) == sum(c is not None for c in b_caps), \
                (len(list(b_needs)), b_caps)
            for need, cap in zip(list(s_needs) + list(b_needs),
                                 [c for c in s_caps if c is not None]
                                 + [c for c in b_caps if c is not None]):
                flag = flag | (need > cap)
            current_scope().record(flag)
        else:
            # ONE host sync per stream batch sizes the candidate bucket AND
            # the string byte buckets (exact measured needs, no truncation)
            total, (s_needs, b_needs) = host_read("join.candidates",
                                                  (total_dev, needs_dev))
            cand_cap = bucket_capacity(max(int(total), 1))
            s_caps = _byte_cap_tuple(stream_batch.columns, s_needs)
            b_caps = _byte_cap_tuple(build.payload, b_needs)
            if cached is not None:
                # keep buckets monotone so steady state stays compiled
                oc, os_, ob = cached
                cand_cap = max(cand_cap, oc)
                s_caps = tuple(None if c is None else max(c, o)
                               for c, o in zip(s_caps, os_))
                b_caps = tuple(None if c is None else max(c, o)
                               for c, o in zip(b_caps, ob))
            self._size_cache[key] = (cand_cap, s_caps, b_caps)
        with self._gather_track.observe(
                (stream_batch.capacity, build.capacity, cand_cap,
                 s_caps, b_caps)):
            return self._jit_probe(build, build_batch, stream_batch,
                                   (lo, counts, skey_cols), build_matched,
                                   cand_cap, s_caps, b_caps)

    def _emit_build_unmatched(self, build: BuildTable,
                              build_batch: ColumnarBatch, build_matched):
        with self._gather_track.observe(("unmatched", build.capacity)):
            return self._emit_build_unmatched_inner(build, build_batch,
                                                    build_matched)

    def _emit_build_unmatched_inner(self, build: BuildTable,
                                    build_batch: ColumnarBatch,
                                    build_matched):
        # probe flags live in SORTED build space; translate to original
        # rows once per join (perm is a permutation, so the scatter is
        # exact)
        matched_orig = jnp.zeros((build.capacity,), jnp.int32).at[
            build.perm].max(build_matched.astype(jnp.int32)) > 0
        un_idx, n_un = unmatched_indices(matched_orig, build.num_rows,
                                         build.capacity)
        bcols = _gather_batch(build.payload, un_idx, n_un)
        stream_schema = self.left_schema if self.build_side == "right" \
            else self.right_schema
        null_map = jnp.full((build.capacity,), -1, jnp.int32)
        stream_child = self.children[0] if self.build_side == "right" \
            else self.children[1]
        from ..columnar.batch import empty_batch
        nulls = empty_batch(stream_schema, capacity=build.capacity)
        scols = [gather_column(c, null_map) for c in nulls.columns]
        left_cols = scols if self.build_side == "right" else bcols
        right_cols = bcols if self.build_side == "right" else scols
        return ColumnarBatch(left_cols + right_cols, n_un, self.output_schema)

    def _eval_condition(self, stream_batch, build_batch, s_idx, b_row,
                        cand_cap: int, s_caps: Tuple = (),
                        b_caps: Tuple = ()):
        """Evaluate the residual condition over candidate pairs: build a
        pair batch of gathered left+right columns in output order."""
        s_caps = s_caps or (None,) * len(stream_batch.columns)
        b_caps = b_caps or (None,) * len(build_batch.columns)
        scols = [gather_column(c, s_idx, out_byte_capacity=bc)
                 for c, bc in zip(stream_batch.columns, s_caps)]
        bcols = [gather_column(c, b_row, out_byte_capacity=bc)
                 for c, bc in zip(build_batch.columns, b_caps)]
        left_cols = scols if self.build_side == "right" else bcols
        right_cols = bcols if self.build_side == "right" else scols
        lf = list(self.left_schema.fields)
        rf = list(self.right_schema.fields)
        pair_schema = Schema(tuple(lf + rf))
        pair = ColumnarBatch(left_cols + right_cols,
                             jnp.int32(cand_cap), pair_schema)
        bound = resolve(self.condition, pair_schema)
        pred = bound.columnar_eval(pair)
        return pred.data & pred.validity

    def node_description(self):
        return (f"HashJoinExec[{self.join_type}, build={self.build_side}, "
                f"lkeys={self.left_keys!r}, rkeys={self.right_keys!r}]")


class NestedLoopJoinExec(TpuExec):
    """Broadcast nested-loop / cartesian product join (reference
    GpuBroadcastNestedLoopJoinExecBase, GpuCartesianProductExec): all pairs
    in chunks, residual condition filters. Supports inner/cross and
    stream-preserved outer/semi/anti with build == right."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 join_type: str = CROSS,
                 condition: Optional[Expression] = None,
                 chunk_rows: int = 1 << 16):
        super().__init__(left, right)
        self.join_type = join_type
        self.condition = condition
        self.chunk_rows = chunk_rows
        assert join_type in (INNER, CROSS, LEFT_OUTER, LEFT_SEMI, LEFT_ANTI,
                             EXISTENCE)

    @property
    def output_schema(self) -> Schema:
        if self.join_type in (LEFT_SEMI, LEFT_ANTI):
            return self.children[0].output_schema
        if self.join_type == EXISTENCE:
            return Schema(tuple(self.children[0].output_schema.fields) +
                          (StructField("exists", BooleanType(), False),))
        lf = list(self.children[0].output_schema.fields)
        rf = [StructField(f.name, f.data_type,
                          f.nullable or self.join_type == LEFT_OUTER)
              for f in self.children[1].output_schema.fields]
        return Schema(tuple(lf + rf))

    @staticmethod
    def _max_lens(batch: ColumnarBatch, n_rows: int) -> List[Optional[int]]:
        """Max string byte length per column (None for fixed-width); ONE
        host sync per batch (stacked fetch), hoisted out of the chunk
        loop."""
        from ..ops.strings import string_lengths
        maxes = []
        for c in batch.columns:
            if isinstance(c, StringColumn):
                act = jnp.arange(c.capacity, dtype=jnp.int32) < n_rows
                maxes.append(jnp.max(jnp.where(act, string_lengths(c), 0)))
        if not maxes:
            return [None] * len(batch.columns)
        fetched = iter(host_read("join.key_max", jnp.stack(maxes)).tolist())
        return [int(next(fetched)) if isinstance(c, StringColumn) else None
                for c in batch.columns]

    @staticmethod
    def _chunk_byte_caps(max_lens: List[Optional[int]], chunk_cap: int
                         ) -> Tuple:
        """Cross joins duplicate every row: size each string column's
        output byte bucket from its max row length × chunk capacity (the
        input bucket truncates once duplicated bytes exceed it)."""
        return tuple(None if ml is None
                     else bucket_capacity(max(chunk_cap * ml, 8))
                     for ml in max_lens)

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        right_batches = list(self.children[1].execute())
        if right_batches:
            build = concat_batches(right_batches,
                                   self.children[1].output_schema)
        else:
            from ..columnar.batch import empty_batch
            build = empty_batch(self.children[1].output_schema)
        b_rows = build.num_rows_host
        b_lens = self._max_lens(build, b_rows)

        for stream in self.children[0].execute():
            s_rows = stream.num_rows_host
            s_lens = self._max_lens(stream, s_rows)
            total = s_rows * b_rows
            jt = self.join_type
            smatched = jnp.zeros((stream.capacity,), jnp.bool_)
            start = 0
            while start < total:
                chunk = min(self.chunk_rows, total - start)
                cap = bucket_capacity(max(chunk, 1))
                # the capacity bucket may exceed the nominal chunk; emit a
                # full bucket's worth and advance by what was emitted
                chunk = min(total - start, cap)
                s_idx, b_idx, n = cross_pairs(
                    jnp.int32(s_rows), jnp.int32(b_rows), jnp.int32(start), cap)
                s_caps = self._chunk_byte_caps(s_lens, cap)
                b_caps = self._chunk_byte_caps(b_lens, cap)
                verified = (s_idx >= 0)
                if self.condition is not None:
                    verified = verified & self._condition_mask(
                        stream, build, s_idx, b_idx, cap, s_caps, b_caps)
                if jt in (LEFT_SEMI, LEFT_ANTI, EXISTENCE, LEFT_OUTER):
                    smatched = smatched | matched_flags(
                        verified, s_idx, stream.capacity)
                if jt in (INNER, CROSS, LEFT_OUTER):
                    s_map, b_map, n_pairs = inner_gather_maps(
                        verified, s_idx, b_idx, n)
                    scols = _gather_batch(stream.columns, s_map, n_pairs,
                                          s_caps)
                    bcols = _gather_batch(build.columns, b_map, n_pairs,
                                          b_caps)
                    yield ColumnarBatch(scols + bcols, n_pairs,
                                        self.output_schema)
                start += chunk
            # stream-preserved tails
            if jt == LEFT_OUTER:
                un_idx, n_un = unmatched_indices(smatched, stream.num_rows,
                                                 stream.capacity)
                scols = _gather_batch(stream.columns, un_idx, n_un)
                null_map = jnp.full((stream.capacity,), -1, jnp.int32)
                bcols = [gather_column(c, null_map) for c in build.columns]
                yield ColumnarBatch(scols + bcols, n_un, self.output_schema)
            elif jt in (LEFT_SEMI, LEFT_ANTI):
                keep = smatched if jt == LEFT_SEMI else ~smatched
                perm, n_keep = compaction_order(keep, stream.num_rows)
                cols = [gather_column(
                    c, jnp.where(active_mask(n_keep, stream.capacity), perm, -1))
                    for c in stream.columns]
                yield ColumnarBatch(cols, n_keep, self.output_schema)
            elif jt == EXISTENCE:
                flag = Column(smatched, jnp.ones((stream.capacity,), jnp.bool_),
                              BooleanType())
                yield ColumnarBatch(list(stream.columns) + [flag],
                                    stream.num_rows, self.output_schema)

    def _condition_mask(self, stream, build, s_idx, b_idx, cap: int,
                        s_caps: Tuple = (), b_caps: Tuple = ()):
        s_caps = s_caps or (None,) * len(stream.columns)
        b_caps = b_caps or (None,) * len(build.columns)
        scols = [gather_column(c, s_idx, out_byte_capacity=bc)
                 for c, bc in zip(stream.columns, s_caps)]
        bcols = [gather_column(c, b_idx, out_byte_capacity=bc)
                 for c, bc in zip(build.columns, b_caps)]
        pair_schema = Schema(tuple(self.children[0].output_schema.fields) +
                             tuple(self.children[1].output_schema.fields))
        pair = ColumnarBatch(scols + bcols, jnp.int32(cap), pair_schema)
        bound = resolve(self.condition, pair_schema)
        pred = bound.columnar_eval(pair)
        return pred.data & pred.validity


class AdaptiveJoinExec(TpuExec):
    """AQE-lite join (VERDICT r2 item 10): when plan-time size estimation
    returns unknown, materialize the build side FIRST (a hash join would
    anyway), measure its real padded device bytes with no host sync, and
    pick the strategy at runtime — broadcast-style single-build when it
    fits the broadcast threshold, sub-partitioned when it exceeds the
    sub-partition threshold (MULTITHREADED mode), plain hash join
    otherwise. The reference reaches the same decision through AQE
    query-stage statistics; standalone, the exec measures its own child."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 left_keys: Sequence[Expression],
                 right_keys: Sequence[Expression],
                 join_type: str, condition: Optional[Expression],
                 conf):
        super().__init__(left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.condition = condition
        self._conf = conf
        # schema comes from the plain-shape join (all strategies agree)
        from .basic import InMemoryScanExec
        self._template = HashJoinExec(
            InMemoryScanExec([], left.output_schema),
            InMemoryScanExec([], right.output_schema),
            left_keys, right_keys, join_type, condition=condition)

    @property
    def output_schema(self) -> Schema:
        return self._template.output_schema

    def _fingerprint_extras(self):
        # what a trace of the operators ABOVE can depend on: the join's
        # semantics (the plain-shape join's extras), not the strategy this
        # execution picks; the join it builds at run time keys its own
        # programs by its own fingerprint (`_SpillableScanExec`)
        extras = self._template._fingerprint_extras()
        return None if extras is None else ("adaptive",) + extras

    def _materialize(self, side: TpuExec):
        """Drain a side into SPILLABLE batches + its padded byte size
        (reference GpuShuffledSymmetricHashJoinExec holds both sides
        spillable while deciding)."""
        sps, size = [], 0
        for b in side.execute():
            size += b.device_size_bytes()
            sps.append(SpillableBatch.from_batch(b))
        return sps, size

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        from ..config import (BROADCAST_SIZE_THRESHOLD,
                              JOIN_SUBPARTITION_THRESHOLD, SHUFFLE_MODE,
                              SHUFFLE_PARTITIONS)
        thr_b = self._conf.get(BROADCAST_SIZE_THRESHOLD)
        thr_sub = self._conf.get(JOIN_SUBPARTITION_THRESHOLD)
        multithreaded = self._conf.get(SHUFFLE_MODE).upper() \
            == "MULTITHREADED"
        left, right = self.children
        # quota-aware broadcast demotion (ISSUE 19 decision 2): the
        # measured build side must also fit the adaptive cap — the
        # tighter of adaptive.autoBroadcastMaxBytes and the admitting
        # ticket's workload quota share. A single-build plan whose
        # build MEASURES over the cap demotes to the sub-partitioned
        # strategy BEFORE the first OOM retry fires.
        from . import adaptive
        from ..config import ADAPTIVE_ENABLED
        cap_basis = None
        if self._conf.get(ADAPTIVE_ENABLED) and adaptive.consult(
                self._conf, op=type(self).__name__, op_id=self._op_id):
            cap_basis = adaptive.demote_cap(self._conf)
        r_sps, size_r = self._materialize(right)
        r_scan = _SpillableScanExec(r_sps, right.output_schema, right)
        swappable = self.join_type == "inner" and not self.condition
        demoted = False
        if thr_b >= 0 and size_r <= thr_b:
            if cap_basis is not None and size_r > cap_basis[0]:
                demoted = True
                adaptive.note_demote(
                    "broadcast_demote", op=type(self).__name__,
                    op_id=self._op_id, measured_bytes=size_r,
                    threshold=cap_basis[0], basis=cap_basis[1],
                    planned="build_right")
            else:
                # small build: stream the left side straight through
                self._measured = (None, size_r)
                self._choice = "build_right"
                join: TpuExec = HashJoinExec(
                    left, r_scan, self.left_keys, self.right_keys,
                    self.join_type, build_side="right",
                    condition=self.condition)
                yield from join.execute()
                return
        # symmetric: hold BOTH sides spillable, measure, decide. The left
        # side is held as it is UNDER the filters at its top (a filter
        # keeps its input's capacity, so the padded bytes measured are the
        # same), and the filters go back over the replay: the join built
        # below absorbs them as a key mask where its shape allows, as the
        # small-build path above does, in place of a compaction of every
        # row before the join (PERF.md section 6, PR 38)
        from .basic import FilterExec
        l_base, conditions = left, []
        while isinstance(l_base, FilterExec):
            conditions.append(l_base.condition)
            l_base = l_base.child
        l_sps, size_l = self._materialize(l_base)
        l_scan = _SpillableScanExec(l_sps, l_base.output_schema, l_base)
        for condition in reversed(conditions):
            l_scan = FilterExec(condition, l_scan)
        self._measured = (size_l, size_r)
        # the side that would actually be BUILT must fit: only inner
        # joins without a condition may swap build sides
        build_size = min(size_l, size_r) if swappable else size_r
        # a demoted join sub-partitions when the to-be-built side still
        # exceeds the cap; the effective threshold is the tighter of
        # the static conf and the measured cap
        over_cap = (demoted and cap_basis is not None
                    and build_size > cap_basis[0])
        eff_sub = thr_sub
        if over_cap:
            eff_sub = cap_basis[0] if thr_sub < 0 \
                else min(thr_sub, cap_basis[0])
        if multithreaded and ((thr_sub >= 0 and build_size > thr_sub)
                              or over_cap):
            from .exchange import (HostShuffleExchangeExec,
                                   ShuffledHashJoinExec)
            # size k from the side that will actually be BUILT (build is
            # forced right for non-swappable joins — ADVICE r3 #4)
            k = min(256, max(self._conf.get(SHUFFLE_PARTITIONS),
                             -(-build_size // max(eff_sub, 1))))
            lex = HostShuffleExchangeExec(self.left_keys, l_scan,
                                          int(k), self._conf)
            rex = HostShuffleExchangeExec(self.right_keys, r_scan, int(k),
                                          self._conf)
            self._choice = "subpartition"
            join = ShuffledHashJoinExec(
                lex, rex, self.left_keys, self.right_keys,
                self.join_type, condition=self.condition)
        else:
            # build the measured-smaller side (runtime build-side choice;
            # only swap when semantics allow)
            build_left = swappable and size_l < size_r
            self._choice = "build_left" if build_left else "build_right"
            join = HashJoinExec(
                l_scan, r_scan, self.left_keys, self.right_keys,
                self.join_type,
                build_side="left" if build_left else "right",
                condition=self.condition)
        yield from join.execute()

    @property
    def child(self) -> TpuExec:
        return self.children[0]

    def node_description(self):
        return f"AdaptiveJoinExec {self.join_type}"


class _SpillableScanExec(TpuExec):
    """Leaf replaying spillable batches (unspilling on demand); each
    batch releases its pin after the downstream consumes it. It stands in
    a plan for `stands_for`, the exec whose output it replays, and takes
    its fingerprint from it: the join built over it at run time then keys
    its programs like a planned one, and a later query compiles nothing."""

    def __init__(self, sps, schema: Schema,
                 stands_for: Optional[TpuExec] = None):
        super().__init__()
        self._sps = sps
        self._schema = schema
        self._stands_for = stands_for

    def _fingerprint_extras(self):
        fp = self._stands_for.plan_fingerprint() \
            if self._stands_for is not None else None
        return None if fp is None else ("replay", fp)

    @property
    def output_schema(self) -> Schema:
        return self._schema

    def internal_execute(self) -> Iterator[ColumnarBatch]:
        # single-consumption scan: handles free eagerly as consumed
        for sp in self._sps:
            b = sp.get_batch()
            sp.release()
            sp.close()
            yield b


