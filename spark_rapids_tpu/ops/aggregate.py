"""Group-by aggregation kernels — device core of GpuHashAggregateExec
(reference GpuAggregateExec.scala:1711 over cuDF groupby).

TPU-first: no device hash table. XLA's native sort is fast and static-shaped,
so group-by is sort-based end to end: order-key lanes (ops/sort.py) -> stable
sort -> segment boundaries -> `jax.ops.segment_*` reductions. This is the
same shape the reference falls back to when hash-merge can't fit
(buildSortFallbackIterator, GpuAggregateExec.scala:909) — on TPU it is the
primary path because segment reductions vectorize perfectly and never
collide. num_groups rides as a device scalar; the output keeps the input
capacity bucket (num_groups <= num_rows), so merge passes re-run the SAME
compiled kernel.

Null semantics follow Spark: nulls are excluded from sum/min/max/avg/count
(sum of an all-null group is null); count(*) counts rows; GROUP BY treats
nulls as equal.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.column import Column, StringColumn
from ..types import DataType, DoubleType, LongType
from .basic import active_mask, gather_column, sanitize
from .sort import (
    SortOrder, group_segment_ids, sort_permutation, string_words_for,
)

#: aggregate op names understood by the kernel. first/last skip nulls
#: (ignoreNulls=True); first_any/last_any take the first/last row
#: regardless of null (Spark's default ignoreNulls=False). collect/
#: collect_set build list results (collect_merge flattens partials).
AGG_OPS = ("sum", "count", "count_star", "min", "max", "first", "last",
           "first_any", "last_any", "any_value", "sum_sq", "collect",
           "collect_set", "collect_merge")


def collect_all(op: str, col: Column, num_rows, capacity: int) -> "Column":
    """Grand-aggregate (no group keys) collect_list/collect_set: ONE row
    holding every valid value (deduped for sets)."""
    from ..columnar.column import ArrayColumn
    from ..types import ArrayType
    from .basic import compaction_order
    from .strings import _rebuild_offsets

    act = active_mask(num_rows, capacity)
    if op.startswith("psketch"):
        # grand approx_percentile: one segment covering every active row
        seg = jnp.where(act, 0, jnp.int32(capacity))
        positions = jnp.arange(capacity, dtype=jnp.int32)
        group_act = jnp.zeros(capacity, jnp.bool_).at[0].set(True)
        return _collect_group(op, col, seg, act, capacity, positions,
                              group_act)
    if op == "collect_merge":
        assert isinstance(col, ArrayColumn)
        from .collection import array_lengths
        lens = jnp.where(act & col.validity, array_lengths(col), 0)
        total = jnp.sum(lens)
        counts = jnp.zeros(capacity, jnp.int32).at[0].set(
            total.astype(jnp.int32))
        offsets = _rebuild_offsets(counts)
        valid = jnp.zeros(capacity, jnp.bool_).at[0].set(True)
        return ArrayColumn(col.child, offsets, valid, col.dtype)
    keep = act & col.validity
    if op == "collect_set":
        keep = keep & _first_occurrence(
            col, jnp.where(keep, 0, 1).astype(jnp.int32), keep, capacity)
    total = jnp.sum(keep.astype(jnp.int32))
    counts = jnp.zeros(capacity, jnp.int32).at[0].set(
            total.astype(jnp.int32))
    offsets = _rebuild_offsets(counts)
    perm, n_kept = compaction_order(keep, jnp.int32(capacity))
    child = gather_column(col, perm, active_mask(n_kept, capacity))
    valid = jnp.zeros(capacity, jnp.bool_).at[0].set(True)
    return ArrayColumn(child, offsets, valid, ArrayType(col.dtype))


def _dedup_value_lanes(col: Column):
    """Fixed-width dedup sort lanes with Spark equality semantics: -0.0
    equals 0.0 and NaN equals NaN. Floats go through the arithmetic bit
    reconstruction — bitcasts FROM f64 do not compile on TPU."""
    data = col.data
    if data.dtype == jnp.bool_:
        data = data.astype(jnp.int8)
    if jnp.issubdtype(data.dtype, jnp.floating):
        from .f64bits import f64_bits
        d = data.astype(jnp.float64)
        d = jnp.where(d == 0.0, 0.0, d)           # -0.0 -> 0.0
        d = jnp.where(jnp.isnan(d), jnp.float64(jnp.nan), d)  # one NaN
        return [f64_bits(d)]
    return [data]


def _first_occurrence(col: Column, group_key, keep, capacity: int):
    """Mask of the first kept row of each (group_key, value) pair —
    the dedup primitive behind collect_set. The dropped-row sentinel is
    far above any group id (group ids may exceed `capacity` when the
    group domain is the parent batch of a child buffer)."""
    from .sort import _split_u64_lanes
    lanes = _split_u64_lanes(_dedup_value_lanes(col))
    iota = jnp.arange(capacity, dtype=jnp.int32)
    big = jnp.int32(1 << 30)
    gk = jnp.where(keep, group_key, big).astype(jnp.int32)
    sorted_out = jax.lax.sort(tuple([gk] + lanes + [iota]),
                              num_keys=1 + len(lanes))
    sgk, sperm = sorted_out[0], sorted_out[-1]
    slanes = sorted_out[1:-1]
    diff = sgk[1:] != sgk[:-1]
    for sl in slanes:
        diff = diff | (sl[1:] != sl[:-1])
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), diff])
    return jnp.zeros(capacity, jnp.bool_).at[sperm].set(
        first & (sgk < big))


@dataclass(frozen=True)
class AggSpec:
    """One physical aggregate: op over an input ordinal (-1 for count_star)."""
    op: str
    ordinal: int = -1

    def __post_init__(self):
        assert self.op in AGG_OPS, self.op


def _segment_reduce(op: str, values, validity, seg, capacity: int, positions):
    """One aggregate over presorted segments. Returns (data, validity)."""
    num_segments = capacity
    valid_i = validity.astype(jnp.int32)
    counts = jax.ops.segment_sum(valid_i, seg, num_segments=num_segments)
    has_any = counts > 0
    if op == "count":
        return counts.astype(jnp.int64), jnp.ones((capacity,), jnp.bool_)
    if op == "count_star":
        ones = jnp.ones_like(seg, jnp.int32)
        c = jax.ops.segment_sum(ones, seg, num_segments=num_segments)
        return c.astype(jnp.int64), jnp.ones((capacity,), jnp.bool_)
    if op in ("sum", "sum_sq"):
        v = values.astype(jnp.float64) if jnp.issubdtype(values.dtype, jnp.floating) \
            else values.astype(jnp.int64)
        if op == "sum_sq":
            v = v * v
        v = jnp.where(validity, v, jnp.zeros((), v.dtype))
        s = jax.ops.segment_sum(v, seg, num_segments=num_segments)
        return s, has_any
    if op in ("min", "max"):
        fn = jax.ops.segment_min if op == "min" else jax.ops.segment_max
        if jnp.issubdtype(values.dtype, jnp.floating):
            sub = jnp.inf if op == "min" else -jnp.inf
            neutral = jnp.full((), sub, values.dtype)
        elif values.dtype == jnp.bool_:
            values = values.astype(jnp.int8)
            neutral = jnp.int8(1 if op == "min" else 0)
        else:
            info = jnp.iinfo(values.dtype)
            neutral = jnp.full((), info.max if op == "min" else info.min,
                               values.dtype)
        v = jnp.where(validity, values, neutral)
        r = fn(v, seg, num_segments=num_segments)
        return r, has_any
    if op in ("first", "last", "any_value"):
        # ignoreNulls=True: value at the smallest (first) / largest (last)
        # position holding a VALID row
        big = jnp.int32(capacity)
        if op == "last":
            p = jnp.where(validity, positions, -1)
            pick = jax.ops.segment_max(p, seg, num_segments=num_segments)
        else:
            p = jnp.where(validity, positions, big)
            pick = jax.ops.segment_min(p, seg, num_segments=num_segments)
        ok = (pick >= 0) & (pick < capacity)
        safe = jnp.clip(pick, 0, capacity - 1)
        return values[safe], ok & has_any
    if op in ("first_any", "last_any"):
        # ignoreNulls=False (Spark default): first/last row regardless of
        # null; the result is null when that row's value is null
        if op == "last_any":
            pick = jax.ops.segment_max(positions, seg,
                                       num_segments=num_segments)
        else:
            pick = jax.ops.segment_min(positions, seg,
                                       num_segments=num_segments)
        ok = (pick >= 0) & (pick < capacity)
        safe = jnp.clip(pick, 0, capacity - 1)
        return values[safe], ok & validity[safe]
    raise AssertionError(op)


def _collect_group(op: str, g: Column, seg, act, capacity: int, positions,
                   group_act) -> Column:
    """collect_list/collect_set update + merge over key-sorted rows
    (reference GpuCollectList/GpuCollectSet, aggregate functions over
    cuDF lists; here the sorted layout makes the list column literally
    the compacted values with group-boundary offsets).

    'collect': values of each group in row order, nulls dropped.
    'collect_set': additionally dedup within the group (element order
    unspecified, as in Spark). 'collect_merge': flatten the per-row
    lists of each group (the merge of partial collect buffers)."""
    from ..columnar.column import ArrayColumn
    from ..types import ArrayType
    from .strings import _rebuild_offsets

    if op == "collect_merge":
        assert isinstance(g, ArrayColumn), g
        # g is key-sorted: each group's row lists are contiguous, so the
        # gathered child IS the flattened result; offsets accumulate the
        # per-group totals
        from .collection import array_lengths
        lens = jnp.where(act & g.validity, array_lengths(g), 0)
        counts = jax.ops.segment_sum(lens, seg, num_segments=capacity)
        offsets = _rebuild_offsets(jnp.where(group_act, counts, 0))
        return ArrayColumn(g.child, offsets, group_act, g.dtype)

    if op.startswith("psketch_merge"):
        # bounded approx_percentile: merge partial sketches
        # ([values..., n] rows) of each group — decode per-element
        # weights from the PRE-flatten row structure, flatten like
        # collect_merge, then resample to K (ops/percentile.sketch_merge)
        k = int(op.split(":")[1])
        assert isinstance(g, ArrayColumn), g
        from .collection import array_lengths
        from .percentile import sketch_merge
        cap = capacity
        rowlen = array_lengths(g)
        ccap = g.child.capacity
        epos = jnp.arange(ccap, dtype=jnp.int32)
        prow = jnp.clip(jnp.searchsorted(g.offsets, epos, side="right")
                        .astype(jnp.int32) - 1, 0, cap - 1)
        last_idx = jnp.clip(g.offsets[1:] - 1, 0, ccap - 1)
        counts_row = jnp.where(rowlen > 0, g.child.data[last_idx], 0.0)
        lens_row = jnp.maximum(rowlen - 1, 0)
        pos_in_row = epos - g.offsets[prow]
        is_count_elem = pos_in_row == (rowlen[prow] - 1)
        row_lens_e = jnp.where(is_count_elem, 0.0,
                               lens_row[prow].astype(jnp.float64))
        row_counts_e = counts_row[prow].astype(jnp.float64)
        lens = jnp.where(act & g.validity, rowlen, 0)
        counts = jax.ops.segment_sum(lens, seg, num_segments=capacity)
        offsets = _rebuild_offsets(jnp.where(group_act, counts, 0))
        flat = ArrayColumn(g.child, offsets, group_act, g.dtype)
        return sketch_merge(flat, row_lens_e, row_counts_e, k)

    if op.startswith("psketch"):
        # bounded approx_percentile update: collect the group's raw
        # values then compress to the K-point sketch encoding
        k = int(op.split(":")[1])
        collected = _collect_group("collect", g, seg, act, capacity,
                                   positions, group_act)
        from .percentile import sketch_compress
        return sketch_compress(collected, k)

    keep = act & g.validity  # Spark: collect_* drop nulls
    if op == "collect_set":
        # dedup: first kept occurrence of each (segment, value)
        keep = keep & _first_occurrence(g, seg, keep, capacity)
    counts = jax.ops.segment_sum(keep.astype(jnp.int32), seg,
                                 num_segments=capacity)
    offsets = _rebuild_offsets(jnp.where(group_act, counts, 0))
    from .basic import compaction_order as _co
    perm2, n_kept = _co(keep, jnp.int32(capacity))
    child = gather_column(g, perm2, active_mask(n_kept, capacity))
    return ArrayColumn(child, offsets, group_act, ArrayType(g.dtype))


def groupby_aggregate(key_columns: Sequence[Column],
                      agg_inputs: Sequence[Tuple[str, Optional[Column]]],
                      num_rows, capacity: int,
                      string_words: int,
                      pre_grouped: bool = False,
                      ) -> Tuple[List[Column], List[Tuple[jnp.ndarray, jnp.ndarray]], jnp.ndarray]:
    """Sort-based group-by over one batch.

    agg_inputs: list of (op, input Column or None for count_star).
    Returns (grouped key columns, [(agg data, agg validity)], num_groups).
    All outputs have the input capacity; rows >= num_groups are inactive.

    pre_grouped: the caller guarantees equal keys are already CONTIGUOUS
    (e.g. the inner join's key-grouped emission, exec/joins.py) — the
    batch sort is skipped entirely; segment detection works on adjacency
    and never needed a total order.
    """
    all_cols = list(key_columns) + [c for _, c in agg_inputs
                                    if c is not None]
    if pre_grouped:
        sorted_all = list(all_cols)
    else:
        orders = [SortOrder(i) for i in range(len(key_columns))]
        # ONE sort carries keys AND agg inputs as packed lanes (round 4):
        # the old per-column gather-by-permutation cost ~26 ms per column
        from .sort import sort_batch_columns
        sorted_all, _ = sort_batch_columns(all_cols, orders, num_rows,
                                           capacity, 8 * string_words)
    sorted_keys = sorted_all[: len(key_columns)]
    sorted_in = sorted_all[len(key_columns):]
    seg, num_groups = group_segment_ids(sorted_keys, num_rows, capacity,
                                        string_words)
    act = active_mask(num_rows, capacity)
    positions = jnp.arange(capacity, dtype=jnp.int32)
    group_act = active_mask(num_groups, capacity)

    # -- prefix-difference tier (round 4, reworked round 5) ---------------
    # Over SORTED segments, sum/count collapse to SEGMENT-LOCAL inclusive
    # cumsums read at each group's LAST row. jax.ops.segment_sum is a
    # scatter-add (~163 ms for 2M f64 on v5e); this path has no scatters.
    # Segment-local scans (associative_scan with a segment-reset combine)
    # keep float sums numerically sound — a global cumsum difference
    # loses tiny groups sorted after large-magnitude ones to catastrophic
    # cancellation (ADVICE r4) — and the group totals come back via ONE
    # packed row gather at group-last positions instead of carrying every
    # prefix lane through the boundary-compaction sort.
    from ..types import DecimalType

    def prefixable(op, g):
        if op in ("count", "count_star"):
            return True
        if op in ("sum", "sum_sq"):
            return g is not None and not isinstance(g, StringColumn) \
                and not isinstance(g.dtype, DecimalType)
        return False

    in_it = iter(sorted_in)
    per_agg_inputs: List[Optional[Column]] = []
    for op, col in agg_inputs:
        per_agg_inputs.append(next(in_it) if col is not None else None)

    first_flag = ((seg != jnp.roll(seg, 1)) | (positions == 0)) & act
    scan_lanes: List[jnp.ndarray] = []
    agg_lane: dict = {}
    for i, (op, _) in enumerate(agg_inputs):
        g = per_agg_inputs[i]
        if not prefixable(op, g):
            continue
        if op == "count_star":
            # active rows sort first, so group size falls out of the
            # first-row positions alone
            agg_lane[i] = ("pos", None, None)
            continue
        valid_c = (g.validity & act).astype(jnp.int32)
        vlane = len(scan_lanes)
        scan_lanes.append(valid_c)
        if op == "count":
            agg_lane[i] = ("count", vlane, None)
            continue
        v = g.data.astype(jnp.float64) \
            if jnp.issubdtype(g.data.dtype, jnp.floating) \
            else g.data.astype(jnp.int64)
        if op == "sum_sq":
            v = v * v
        v = jnp.where(g.validity & act, v, jnp.zeros((), v.dtype))
        slane = len(scan_lanes)
        scan_lanes.append(v)
        agg_lane[i] = ("sum", vlane, slane)

    # ONE fused segment-reset scan over every lane: incl[j] = sum of the
    # lane within j's segment up to and including j
    if scan_lanes:
        def _comb(a, b):
            af, bf = a[-1], b[-1]
            out = tuple(jnp.where(bf, bv, av + bv)
                        for av, bv in zip(a[:-1], b[:-1]))
            return out + (af | bf,)

        scanned = jax.lax.associative_scan(
            _comb, tuple(scan_lanes) + (first_flag,))[:-1]
    else:
        scanned = ()

    # boundary compaction: a group's first row goes to the slot of its
    # (dense, sorted) segment id with one scatter of the positions, and the
    # packed key lanes are read there with one row gather. (A stable sort
    # carried them until PR 38: each of its operands cost the chip's
    # compiler tens of seconds.)
    from .rowpack import pack_rows, split_packable, unpack_rows
    first_pos = jnp.full((capacity,), capacity, jnp.int32).at[
        jnp.where(first_flag, seg, capacity)].set(positions, mode="drop")
    first_pos = jnp.where(group_act, first_pos, capacity)
    kp_idx, ko_idx = split_packable(sorted_keys)
    if kp_idx:
        kplan, kimat, kfmat = pack_rows([sorted_keys[i] for i in kp_idx])
        first_safe = jnp.clip(first_pos, 0, capacity - 1)
        s_imat = kimat[first_safe]
        s_fmat = kfmat[first_safe] if kfmat is not None else None

    last_group = positions == (num_groups - 1)

    # per-group LAST row: ONE stacked-matrix gather per dtype class reads
    # every group total (per-lane gathers cost ~26 ms each on v5e; an
    # (N, L) matrix gather is ~13 ms total)
    if scan_lanes:
        last_pos = jnp.where(last_group, num_rows - 1,
                             jnp.roll(first_pos, -1) - 1)
        last_safe = jnp.clip(jnp.where(group_act, last_pos, 0), 0,
                             capacity - 1)
        ilanes: List[jnp.ndarray] = []
        flanes: List[jnp.ndarray] = []
        lane_slot = []
        for lane in scanned:
            if lane.dtype == jnp.float64:
                lane_slot.append(("f", len(flanes)))
                flanes.append(lane)
            elif lane.dtype == jnp.int64:
                pair = jax.lax.bitcast_convert_type(lane, jnp.uint32)
                lane_slot.append(("w2", len(ilanes)))
                ilanes.append(pair[:, 0])
                ilanes.append(pair[:, 1])
            else:
                lane_slot.append(("w1", len(ilanes)))
                ilanes.append(jax.lax.bitcast_convert_type(
                    lane.astype(jnp.int32), jnp.uint32))
        gi = jnp.stack(ilanes, axis=1)[last_safe] if ilanes else None
        gf = jnp.stack(flanes, axis=1)[last_safe] if flanes else None
        lane_vals = []
        for kind, j in lane_slot:
            if kind == "f":
                lane_vals.append(gf[:, j])
            elif kind == "w2":
                pair = jnp.stack([gi[:, j], gi[:, j + 1]], axis=1)
                lane_vals.append(
                    jax.lax.bitcast_convert_type(pair, jnp.int64))
            else:
                lane_vals.append(jax.lax.bitcast_convert_type(
                    gi[:, j], jnp.int32))
    else:
        lane_vals = []

    results = []
    for i, (op, col) in enumerate(agg_inputs):
        if i in agg_lane:
            kind, vlane, slane = agg_lane[i]
            if kind == "pos":
                nxt = jnp.where(last_group, num_rows, jnp.roll(first_pos, -1))
                data = jnp.where(group_act, (nxt - first_pos), 0) \
                    .astype(jnp.int64)
                valid = group_act
            elif kind == "count":
                data = jnp.where(group_act, lane_vals[vlane], 0) \
                    .astype(jnp.int64)
                valid = group_act
            else:
                data = jnp.where(group_act, lane_vals[slane],
                                 jnp.zeros((), lane_vals[slane].dtype))
                valid = (lane_vals[vlane] > 0) & group_act
            results.append(("raw", (data, valid)))
            continue
        if col is None:
            data, valid = _segment_reduce("count_star", positions,
                                          act, seg, capacity, positions)
        else:
            g = per_agg_inputs[i]
            if op in ("collect", "collect_set", "collect_merge") \
                    or op.startswith("psketch"):
                results.append(("col", _collect_group(
                    op, g, seg, act, capacity, positions, group_act)))
                continue
            if isinstance(g, StringColumn):
                if op in ("min", "max", "first", "last", "first_any",
                          "last_any", "any_value"):
                    # order strings via their sort lanes; pick the row index
                    # then gather the string (exact given string_words).
                    from .sort import string_prefix_lanes
                    lanes = string_prefix_lanes(g, string_words)
                    valid = g.validity
                    pickpos = _pick_string_pos(op, lanes, valid, seg,
                                               capacity, positions)
                    ok = (pickpos >= 0) & (pickpos < capacity)
                    safe = jnp.clip(pickpos, 0, capacity - 1)
                    out = gather_column(g, safe, out_valid=ok & group_act)
                    results.append(("col", out))
                    continue
                raise NotImplementedError(f"string agg {op}")
            if op == "sum" and isinstance(g.dtype, DecimalType):
                from .decimal128 import decimal_segment_sum
                (rh, rl), has = decimal_segment_sum(g, g.validity, seg,
                                                    capacity)
                valid = has & group_act
                data = (jnp.where(group_act, rh, 0),
                        jnp.where(group_act, rl, 0))
                results.append(("raw", (data, valid)))
                continue
            data, valid = _segment_reduce(op, g.data, g.validity, seg,
                                          capacity, positions)
        valid = valid & group_act
        data = jnp.where(group_act, data, jnp.zeros((), data.dtype))
        results.append(("raw", (data, valid)))

    # representative key per group: first row of each segment, taken from
    # the compaction's carried key lanes (packable) or gathered (varlen)
    out_keys: List[Optional[Column]] = [None] * len(key_columns)
    if kp_idx:
        for j, c in zip(kp_idx, unpack_rows(kplan, s_imat, s_fmat)):
            from ..columnar.column import Column as _C
            out_keys[j] = _C(jnp.where(group_act, c.data,
                                       jnp.zeros((), c.data.dtype)),
                             c.validity & group_act, c.dtype)
    if ko_idx:
        safe = jnp.clip(first_pos, 0, capacity - 1)
        for j in ko_idx:
            c = sorted_keys[j]
            out_keys[j] = gather_column(
                c, safe, out_valid=c.validity[safe] & group_act)
    return list(out_keys), results, num_groups


def _pick_string_pos(op, lanes, valid, seg, capacity, positions):
    """Position of the min/max/first/last string per segment using its
    uint64 prefix lanes + position as the final tiebreaker."""
    if op in ("first", "any_value"):
        p = jnp.where(valid, positions, capacity)
        return jax.ops.segment_min(p, seg, num_segments=capacity)
    if op == "last":
        p = jnp.where(valid, positions, -1)
        return jax.ops.segment_max(p, seg, num_segments=capacity)
    if op == "first_any":  # ignoreNulls=False: position regardless of null
        return jax.ops.segment_min(positions, seg, num_segments=capacity)
    if op == "last_any":
        return jax.ops.segment_max(positions, seg, num_segments=capacity)
    # min/max over lexicographic lanes: sort rows by (seg, lanes) and take
    # the first/last row of each segment — reuse lax.sort for exactness.
    key_lanes = [seg.astype(jnp.uint32)]
    for lane in lanes:
        lane = jnp.where(valid, lane, jnp.zeros((), lane.dtype))
        if op == "max":
            lane = ~lane
        # invalid rows must lose: push them after all valid rows
        key_lanes.append(lane)
    # nulls excluded: make invalid rows sort last inside the segment
    key_lanes.insert(1, (~valid).astype(jnp.uint32))
    out = jax.lax.sort(tuple(key_lanes) + (positions,),
                       num_keys=len(key_lanes))
    sorted_pos = out[-1]
    sorted_seg = seg[sorted_pos]
    # index (in this ordering) of each segment's first VALID row, then map
    # back to the original row position; capacity => "no valid row".
    first_idx = jax.ops.segment_min(
        jnp.where(valid[sorted_pos],
                  jnp.arange(capacity, dtype=jnp.int32),
                  jnp.int32(capacity)),
        sorted_seg, num_segments=capacity)
    ok = first_idx < capacity
    safe = jnp.clip(first_idx, 0, capacity - 1)
    return jnp.where(ok, sorted_pos[safe], jnp.int32(capacity))


def groupby_aggregate_hash(key_columns: Sequence[Column],
                           agg_inputs: Sequence[Tuple[str, Optional[Column]]],
                           num_rows, capacity: int, rounds: int = 2,
                           ):
    """Hash-path group-by (ops/hashagg.py): no sort; returns the same
    (keys, results, num_groups) plus a `leftover` device flag the exec
    must host-check — True means unresolved collisions and the caller
    must re-run the exact sort-based kernel instead.

    Not supported here: min/max over string inputs (they need ordering
    lanes; the exec routes those plans to the sort path statically).
    """
    from .hashagg import hash_group_assignment

    seg_slots, rep_row, leftover = hash_group_assignment(
        key_columns, num_rows, capacity, rounds)
    keys, results, num_groups = _aggregate_with_assignment(
        key_columns, agg_inputs, num_rows, capacity, rounds,
        seg_slots, rep_row)
    return keys, results, num_groups, leftover


def _aggregate_with_assignment(key_columns, agg_inputs, num_rows,
                               capacity: int, rounds: int,
                               seg_slots, rep_row):
    """Aggregate over a precomputed hash group assignment."""
    from .hashagg import dense_group_ids

    seg, group_rep, num_groups = dense_group_ids(seg_slots, rep_row,
                                                 capacity, rounds)
    act = active_mask(num_rows, capacity)
    positions = jnp.arange(capacity, dtype=jnp.int32)
    group_act = active_mask(num_groups, capacity)

    results = []
    for op, col in agg_inputs:
        if col is None:
            data, valid = _segment_reduce("count_star", positions, act, seg,
                                          capacity, positions)
        else:
            if isinstance(col, StringColumn):
                if op in ("first", "last", "first_any", "last_any",
                          "any_value"):
                    valid = col.validity
                    if op == "last":
                        p = jnp.where(valid, positions, -1)
                        pick = jax.ops.segment_max(p, seg,
                                                   num_segments=capacity)
                    elif op == "last_any":
                        pick = jax.ops.segment_max(positions, seg,
                                                   num_segments=capacity)
                    elif op == "first_any":
                        pick = jax.ops.segment_min(positions, seg,
                                                   num_segments=capacity)
                    else:
                        p = jnp.where(valid, positions, capacity)
                        pick = jax.ops.segment_min(p, seg,
                                                   num_segments=capacity)
                    ok = (pick >= 0) & (pick < capacity)
                    safe = jnp.clip(pick, 0, capacity - 1)
                    out_valid = ok & group_act
                    if op in ("first_any", "last_any"):
                        out_valid = out_valid & valid[safe]
                    out = gather_column(col, safe, out_valid=out_valid)
                    results.append(("col", out))
                    continue
                raise NotImplementedError(
                    f"string agg {op} requires the sort path")
            from ..types import DecimalType
            if op == "sum" and isinstance(col.dtype, DecimalType):
                from .decimal128 import decimal_segment_sum
                (rh, rl), has = decimal_segment_sum(
                    col, col.validity & act, seg, capacity)
                valid = has & group_act
                data = (jnp.where(group_act, rh, 0),
                        jnp.where(group_act, rl, 0))
                results.append(("raw", (data, valid)))
                continue
            data, valid = _segment_reduce(op, col.data, col.validity & act,
                                          seg, capacity, positions)
        valid = valid & group_act
        data = jnp.where(group_act, data, jnp.zeros((), data.dtype))
        results.append(("raw", (data, valid)))

    out_keys = [gather_column(c, jnp.clip(group_rep, 0, capacity - 1),
                              out_valid=(group_rep < capacity)
                              & c.validity[jnp.clip(group_rep, 0,
                                                    capacity - 1)])
                for c in key_columns]
    return out_keys, results, num_groups
