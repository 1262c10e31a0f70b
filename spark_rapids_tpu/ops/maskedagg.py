"""Masked-bucket group-by — the engine's primary aggregation kernel.

Reference analog: cuDF's hash group-by under GpuHashAggregateExec
(GpuAggregateExec.scala:1711). The TPU rebuild CANNOT use a hash table:
measured on v5e, XLA scatter/segment ops cost ~15ms per 1M rows (they
serialize), while masked full-array reductions FUSE into a handful of HBM
passes regardless of how many of them read the same input. So grouping is
built entirely from masked reductions:

  round r in [0, R):                              (R static, default 2)
    bucket b = mix_r(keys) mod G                  (G static, <= 64)
    per key column: masked min/max of its order-bits over each bucket
      -> bucket is CLEAN iff every key column is constant across it
         (min == max, and not a null/value mix) — an EXACT uniformity
         proof, no row gathers, no scatters
    clean buckets resolve ALL their rows to slot r*G + b; their key value
      is the min (== max) itself, decoded from order bits
    dirty buckets retry with a different mix next round
  leftover = any row still unresolved after R rounds (cardinality greater
  than the slot table or adversarial collisions)

Aggregates are masked reductions per slot (sum/count/min/max/first/last),
slots compact to a dense prefix with one tiny (R*G)-element pass, and the
whole thing — bucket assignment, uniformity proof, reductions — fuses with
the upstream filter/project into ONE XLA program with ZERO host syncs.

`leftover` handling is the caller's choice: speculate (emit the small
partial + device flag; plan-level retry re-runs exact if it ever trips —
exec/speculation.py), wrap in lax.cond with the exact sort-based kernel
(masked_groupby_exact), or read the flag on the host and run another kernel
(the hash group-by's lane tier, exec/aggregate._hash_tiers).

Keys and buffers are fixed-width columns. A STRING key no longer than 16
bytes is one too: its length and its zero-padded bytes, packed into 32-bit
lanes (`string_key_lanes`; the width is measured by the caller, a static
argument), equal exactly when the strings are. `masked_groupby_lanes` hands
such keys to the same assignment and spells the result's few key strings
back from the slots' lanes: no scatter, no row gather and no compare by
bytes over the source rows (the hash update those replace spent 20 s on
8M rows for four groups on v5e; PERF.md, PR 36). String BUFFERS (min / max
/ first of a string) stay with the hash and sort tiers.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.column import Column, StringColumn, bucket_capacity
from ..types import DataType
from .basic import active_mask, compact_columns
from .sort import _numeric_order_key


def _unorder_bits(u, dtype: DataType):
    """Invert ops/sort._numeric_order_key: order-bits lane -> value."""
    jdt = jnp.dtype(dtype.jnp_dtype)
    if jdt == jnp.bool_:
        return u.astype(jnp.bool_)
    if jnp.issubdtype(jdt, jnp.floating):
        bits_dt = jnp.uint64 if jdt == jnp.float64 else jnp.uint32
        sign = jnp.ones((), bits_dt) << (8 * jnp.dtype(bits_dt).itemsize - 1)
        was_neg = (u & sign) == 0
        bits = jnp.where(was_neg, ~u, u ^ sign)
        val = jax.lax.bitcast_convert_type(
            bits, jnp.float64 if jdt == jnp.float64 else jnp.float32)
        return val.astype(jdt)
    if jnp.issubdtype(jdt, jnp.signedinteger):
        bits = 8 * jnp.dtype(jdt).itemsize
        flipped = u ^ (jnp.ones((), u.dtype) << (bits - 1))
        return jax.lax.bitcast_convert_type(flipped, jdt)
    return u.astype(jdt)


def _mix32(h, salt: int):
    """Cheap murmur3-finalizer mixing (internal bucketing only — Spark-parity
    hashing lives in ops/hashing.py and is ~10x costlier)."""
    h = h ^ jnp.uint32(salt)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _bucket_hash(key_cols: Sequence[Column], salt: int, capacity: int):
    h = jnp.full((capacity,), jnp.uint32(0x9E3779B9))
    for c in key_cols:
        lane = _numeric_order_key(c)
        if lane.dtype in (jnp.uint64, jnp.int64):
            lo = lane.astype(jnp.uint32)
            hi = (lane >> jnp.uint64(32)).astype(jnp.uint32)
            h = _mix32(h ^ lo, salt)
            h = _mix32(h ^ hi, salt + 0x51)
        else:
            h = _mix32(h ^ lane.astype(jnp.uint32), salt)
        h = _mix32(h ^ c.validity.astype(jnp.uint32), salt + 0xA3)
    return h


def masked_group_assignment(key_cols: Sequence[Column], num_rows,
                            capacity: int, row_mask=None,
                            group_slots: int = 32, rounds: int = 2):
    """Scatter-free exact group assignment.

    Returns (seg (capacity,) int32 in [0, R*G) or sentinel R*G;
    slot_occupied (R*G,) bool; slot key values+validity per key column;
    leftover device bool).
    """
    G, R = group_slots, rounds
    assert G <= 64, "bitmask lookup supports at most 64 buckets per round"
    mask_dt = jnp.uint32 if G <= 32 else jnp.uint64
    cap = capacity
    act = active_mask(num_rows, cap)
    if row_mask is not None:
        act = act & row_mask
    unresolved = act
    sentinel = R * G
    seg = jnp.full((cap,), sentinel, jnp.int32)
    slot_occ: List[jnp.ndarray] = []
    slot_keys: List[List[Tuple[jnp.ndarray, jnp.ndarray]]] = []  # per round

    g_iota = jnp.arange(G, dtype=jnp.int32)
    one = jnp.ones((), mask_dt)
    lanes = [_numeric_order_key(c) for c in key_cols]

    # one u32 per row packing (valid?2:1) << 2ci for every key column:
    # a single OR-reduction then yields any_valid/any_null per column AND
    # bucket occupancy, replacing 2*n_cols+1 boolean sweep-reductions
    # (the sweeps are VPU-compute-bound, so reduction count is the cost).
    # More than 16 key columns exceed the u32 code word: those queries
    # keep the per-column boolean reductions.
    packed_stats = len(key_cols) <= 16
    if packed_stats:
        base_code = jnp.zeros((cap,), jnp.uint32)
        for ci, c in enumerate(key_cols):
            bits_ci = jnp.where(c.validity, jnp.uint32(2), jnp.uint32(1))
            base_code = base_code | (bits_ci << jnp.uint32(2 * ci))

    def _round(r: int, unresolved):
        """One bucketing round: per-bucket stats as axis-0 reductions over
        an on-the-fly (cap, G) comparison tensor. XLA fuses the broadcast
        compare into the reduce without materializing cap*G elements, and
        one such reduce is dramatically cheaper than G independent masked
        reductions (measured on v5e: 32x4 separate reductions lower to
        serial per-bucket passes; the 2-D form is a single tiled sweep)."""
        h = _bucket_hash(key_cols, 0x2545F491 + r * 0x9E37, cap)
        b = (h % jnp.uint32(G)).astype(jnp.int32)
        bm = b[:, None] == g_iota[None, :]            # (cap, G) on the fly
        un2 = unresolved[:, None] & bm
        if packed_stats:
            code = jax.lax.reduce(
                jnp.where(un2, base_code[:, None], jnp.uint32(0)),
                jnp.uint32(0), jax.lax.bitwise_or, (0,))  # (G,) stats
        clean = jnp.ones((G,), jnp.bool_)
        mins_cols, avail_cols = [], []
        for ci, (c, lane) in enumerate(zip(key_cols, lanes)):
            neutral_min = jnp.full((), jnp.iinfo(lane.dtype).max,
                                   lane.dtype)
            mv = un2 & c.validity[:, None]
            mn = jnp.min(jnp.where(mv, lane[:, None], neutral_min), axis=0)
            mx = jnp.max(jnp.where(mv, lane[:, None],
                                   jnp.zeros((), lane.dtype)), axis=0)
            if packed_stats:
                any_valid = ((code >> jnp.uint32(2 * ci + 1)) & 1) != 0
                any_null = ((code >> jnp.uint32(2 * ci)) & 1) != 0
            else:
                any_valid = jnp.any(mv, axis=0)
                any_null = jnp.any(un2 & ~c.validity[:, None], axis=0)
            clean = clean & ~(any_valid & any_null) & \
                (~any_valid | (mn == mx))
            mins_cols.append(mn)
            avail_cols.append(any_valid)
        occupied = (code != 0) if packed_stats else jnp.any(un2, axis=0)
        resolved_bucket = clean & occupied
        # rows stay unresolved exactly when their bucket is occupied and
        # dirty, so "any row left" is a G-element reduce, not a cap one
        dirty = jnp.any(occupied & ~clean)
        # branchless per-row lookup: clean buckets as a bitmask scalar
        bits = jnp.sum(jnp.where(resolved_bucket,
                                 one << g_iota.astype(mask_dt), 0))
        row_clean = ((bits >> b.astype(mask_dt)) & one) != 0
        resolved = unresolved & row_clean
        return b, resolved_bucket, resolved, dirty, tuple(mins_cols), \
            tuple(avail_cols)

    dirty = None
    for r in range(R):
        if r == 0:
            b, resolved_bucket, resolved, dirty, mins_cols, avail_cols = \
                _round(0, unresolved)
        else:
            # later rounds only matter when earlier rounds left rows
            # unresolved; the common case (low-cardinality keys) resolves
            # everything in round 1, so skip the whole sweep on device
            def _dead(_):
                return (jnp.zeros((cap,), jnp.int32),
                        jnp.zeros((G,), jnp.bool_),
                        jnp.zeros((cap,), jnp.bool_),
                        jnp.bool_(False),
                        tuple(jnp.zeros((G,), ln.dtype) for ln in lanes),
                        tuple(jnp.zeros((G,), jnp.bool_)
                              for _ in key_cols))

            b, resolved_bucket, resolved, dirty, mins_cols, avail_cols = \
                jax.lax.cond(dirty,
                             lambda _, _r=r, _u=unresolved: _round(_r, _u),
                             _dead, None)
        keys_r: List[Tuple[jnp.ndarray, jnp.ndarray]] = [
            (mins_cols[ci], avail_cols[ci]) for ci in range(len(key_cols))]
        seg = jnp.where(resolved, r * G + b, seg)
        unresolved = unresolved & ~resolved
        slot_occ.append(resolved_bucket)
        slot_keys.append(keys_r)

    # rows left after the final round == final round had a dirty bucket
    leftover = dirty
    occ = jnp.concatenate(slot_occ)  # (R*G,)
    # per key column: (R*G,) order-bits + validity across rounds
    key_slots = []
    for ci, c in enumerate(key_cols):
        bits = jnp.concatenate([slot_keys[r][ci][0] for r in range(R)])
        valid = jnp.concatenate([slot_keys[r][ci][1] for r in range(R)])
        key_slots.append((bits, valid))
    return seg, occ, key_slots, leftover


def _slot_sweep(agg_inputs, seg, positions, capacity: int, n_slots: int,
                G: int, R: int, occ, skip=None):
    """All aggregates over all slots, skipping the slots past the first G
    on device when no group resolved after round 1 (the common
    low-cardinality case pays for G slots, not R*G). `skip` (a device
    bool, or None) skips the sweep altogether and returns zeros: for a
    caller that throws the result away where keys were left over."""

    def sweep(S: int):
        si = jnp.arange(S, dtype=jnp.int32)[None, :]
        m = seg[:, None] == si
        has_map = _packed_has(agg_inputs, m)
        outs = []
        for i, (op, col) in enumerate(agg_inputs):
            svals, svalid = _slot_reduce_all(op, seg, col, positions,
                                             capacity, S, m=m,
                                             has=has_map.get(i))
            if S < n_slots:
                def _pad(a):
                    return jnp.concatenate(
                        [a, jnp.zeros((n_slots - S,), a.dtype)])
                svals = tuple(_pad(x) for x in svals) \
                    if isinstance(svals, tuple) else _pad(svals)
                svalid = jnp.concatenate(
                    [svalid, jnp.zeros((n_slots - S,), jnp.bool_)])
            outs.append((svals, svalid))
        return tuple(outs)

    def swept(_=None):
        if R > 1 and agg_inputs:
            return jax.lax.cond(jnp.any(occ[G:]), lambda _: sweep(n_slots),
                                lambda _: sweep(G), None)
        return sweep(n_slots)

    if skip is None or not agg_inputs:
        return swept()
    zeros = jax.tree_util.tree_map(
        lambda a: jnp.zeros(a.shape, a.dtype), jax.eval_shape(swept))
    return jax.lax.cond(skip, lambda _: zeros, swept, None)


def _decimal_limbs(col: Column):
    """(hi, lo) int64 lanes of a decimal column (either tier)."""
    from ..columnar.column import Decimal128Column
    from . import decimal128 as D
    if isinstance(col, Decimal128Column):
        return col.hi.data, col.lo.data
    return D.from_i64(col.data.astype(jnp.int64))


def _packed_has(agg_inputs, m) -> dict:
    """One OR-reduction computing per-slot 'any valid row' for every
    aggregate that needs it (bit i of a packed u32 per row), replacing one
    boolean sweep-reduction per aggregate. Returns {agg_index: (S,) bool}."""
    need = [i for i, (op, c) in enumerate(agg_inputs)
            if op in ("sum", "sum_sq", "min", "max") and c is not None]
    if not need or len(need) > 32:
        return {}
    cap = agg_inputs[need[0]][1].capacity
    base = jnp.zeros((cap,), jnp.uint32)
    for k, i in enumerate(need):
        base = base | (agg_inputs[i][1].validity.astype(jnp.uint32)
                       << jnp.uint32(k))
    packed = jax.lax.reduce(
        jnp.where(m, base[:, None], jnp.uint32(0)),
        jnp.uint32(0), jax.lax.bitwise_or, (0,))
    return {i: ((packed >> jnp.uint32(k)) & 1) != 0
            for k, i in enumerate(need)}


def _slot_reduce_all(op: str, seg, col: Optional[Column], positions,
                     capacity: int, n_slots: int, m=None, has=None):
    """One aggregate over ALL slots at once: an axis-0 reduction over the
    on-the-fly (capacity, n_slots) segment-membership tensor. Returns
    ((n_slots,) values, (n_slots,) valid). Equivalent to n_slots calls of
    _slot_reduce but a single fused sweep on device."""
    if m is None:
        si = jnp.arange(n_slots, dtype=jnp.int32)[None, :]
        m = seg[:, None] == si                  # (cap, S) on the fly
    ones_s = jnp.ones((n_slots,), jnp.bool_)
    if op == "count_star":
        # i32 accumulation (a batch cannot exceed 2^31 rows), widened to
        # Spark's LONG count after the reduce — i64 lanes are emulated
        return (jnp.sum(m, axis=0, dtype=jnp.int32).astype(jnp.int64),
                ones_s)
    v = m & col.validity[:, None]
    if op == "count":
        return (jnp.sum(v, axis=0, dtype=jnp.int32).astype(jnp.int64),
                ones_s)
    if has is None:
        has = jnp.any(v, axis=0)
    if op in ("sum", "sum_sq"):
        from ..types import DecimalType
        if op == "sum" and isinstance(col.dtype, DecimalType):
            # exact 128-bit decimal sum: eight u16-limb lanes summed in
            # int64, recombined mod 2^128 (ops/decimal128.py)
            from . import decimal128 as D
            h, l = _decimal_limbs(col)
            sums = [jnp.sum(jnp.where(v, lane[:, None], jnp.int64(0)),
                            axis=0)
                    for lane in D.limb16_lanes(h, l)]
            negs = jnp.sum(v & (h < 0)[:, None], axis=0,
                           dtype=jnp.int64)
            rh, rl, over = D.combine_limb_sums_checked(sums, negs)
            any_sat = jnp.any(v & D.is_saturated(h, l)[:, None], axis=0)
            rh, rl = D.saturate_sum(rh, rl, over, any_sat)
            return (rh, rl), has
        data = col.data
        acc = data.astype(jnp.float64) \
            if jnp.issubdtype(data.dtype, jnp.floating) \
            else data.astype(jnp.int64)
        if op == "sum_sq":
            acc = acc * acc
        z = jnp.zeros((), acc.dtype)
        return jnp.sum(jnp.where(v, acc[:, None], z), axis=0), has
    if op in ("min", "max"):
        data = col.data
        if jnp.issubdtype(data.dtype, jnp.floating):
            neutral = jnp.full((), jnp.inf if op == "min" else -jnp.inf,
                               data.dtype)
        elif data.dtype == jnp.bool_:
            data = data.astype(jnp.int8)
            neutral = jnp.int8(1 if op == "min" else 0)
        else:
            info = jnp.iinfo(data.dtype)
            neutral = jnp.full((), info.max if op == "min" else info.min,
                               data.dtype)
        fn = jnp.min if op == "min" else jnp.max
        return fn(jnp.where(v, data[:, None], neutral), axis=0), has
    if op in ("first", "last", "any_value", "first_any", "last_any"):
        pick_mask = m if op in ("first_any", "last_any") else v
        if op in ("last", "last_any"):
            pick = jnp.max(jnp.where(pick_mask, positions[:, None], -1),
                           axis=0)
        else:
            pick = jnp.min(jnp.where(pick_mask, positions[:, None],
                                     capacity), axis=0)
        ok = (pick >= 0) & (pick < capacity)
        safe = jnp.clip(pick, 0, capacity - 1)
        vals = col.data[safe]                    # (S,)-sized gather
        if op in ("first_any", "last_any"):
            ok = ok & col.validity[safe]
        return vals, ok
    raise AssertionError(op)


def _slot_reduce(op: str, m, col: Optional[Column], positions,
                 capacity: int):
    """One aggregate over one row mask: a masked full-array reduction."""
    if op == "count_star":
        return jnp.sum(m, dtype=jnp.int64), jnp.bool_(True)
    v = col.validity & m
    if op == "count":
        return jnp.sum(v, dtype=jnp.int64), jnp.bool_(True)
    has = jnp.any(v)
    if op in ("sum", "sum_sq"):
        from ..types import DecimalType
        if op == "sum" and isinstance(col.dtype, DecimalType):
            from . import decimal128 as D
            h, l = _decimal_limbs(col)
            sums = [jnp.sum(jnp.where(v, lane, jnp.int64(0)))
                    for lane in D.limb16_lanes(h, l)]
            negs = jnp.sum(v & (h < 0), dtype=jnp.int64)[None]
            rh, rl, over = D.combine_limb_sums_checked(
                [s[None] for s in sums], negs)  # (1,)-shaped limb pair
            any_sat = jnp.any(v & D.is_saturated(h, l))[None]
            rh, rl = D.saturate_sum(rh, rl, over, any_sat)
            return (rh, rl), has
        data = col.data
        acc = data.astype(jnp.float64) \
            if jnp.issubdtype(data.dtype, jnp.floating) \
            else data.astype(jnp.int64)
        if op == "sum_sq":
            acc = acc * acc
        return jnp.sum(jnp.where(v, acc, jnp.zeros((), acc.dtype))), has
    if op in ("min", "max"):
        data = col.data
        if jnp.issubdtype(data.dtype, jnp.floating):
            neutral = jnp.full((), jnp.inf if op == "min" else -jnp.inf,
                               data.dtype)
        elif data.dtype == jnp.bool_:
            data = data.astype(jnp.int8)
            neutral = jnp.int8(1 if op == "min" else 0)
        else:
            info = jnp.iinfo(data.dtype)
            neutral = jnp.full((), info.max if op == "min" else info.min,
                               data.dtype)
        fn = jnp.min if op == "min" else jnp.max
        return fn(jnp.where(v, data, neutral)), has
    if op in ("first", "last", "any_value"):
        if op == "last":
            pick = jnp.max(jnp.where(v, positions, -1))
        else:
            pick = jnp.min(jnp.where(v, positions, capacity))
        ok = (pick >= 0) & (pick < capacity)
        return col.data[jnp.clip(pick, 0, capacity - 1)], ok
    if op in ("first_any", "last_any"):
        # ignoreNulls=False: pick over ACTIVE rows regardless of null
        if op == "last_any":
            pick = jnp.max(jnp.where(m, positions, -1))
        else:
            pick = jnp.min(jnp.where(m, positions, capacity))
        ok = (pick >= 0) & (pick < capacity)
        safe = jnp.clip(pick, 0, capacity - 1)
        return col.data[safe], ok & col.validity[safe]
    raise AssertionError(op)


def masked_groupby(key_columns: Sequence[Column],
                   agg_inputs: Sequence[Tuple[str, Optional[Column]]],
                   num_rows, capacity: int, row_mask=None,
                   group_slots: int = 32, rounds: int = 2,
                   sweep_unless_leftover: bool = False):
    """Group-by into a SMALL output bucket (capacity bucket_capacity(R*G)).

    Returns (out_keys, tagged results, num_groups, leftover). When
    `leftover` is True the output is INCOMPLETE (rows of dirty buckets are
    dropped) — the caller must either lax.cond to an exact kernel or run
    under a speculation scope that re-executes the plan exactly; with
    `sweep_unless_leftover` the aggregates are then not computed at all
    (zeros), for a caller that reads the flag and discards the output.
    Fixed-width keys and buffers only — callers gate on schema; short
    string KEYS come in as lanes through `masked_groupby_lanes`.
    """
    G, R = group_slots, rounds
    n_slots = R * G
    out_cap = bucket_capacity(n_slots)
    seg, occ, key_slots, leftover = masked_group_assignment(
        key_columns, num_rows, capacity, row_mask, G, R)
    act = active_mask(num_rows, capacity)
    if row_mask is not None:
        act = act & row_mask
    positions = jnp.arange(capacity, dtype=jnp.int32)

    # dense ids for occupied slots (tiny arrays)
    dense = jnp.cumsum(occ.astype(jnp.int32)) - 1
    num_groups = jnp.sum(occ, dtype=jnp.int32)
    target = jnp.where(occ, dense, out_cap)  # scatter position per slot

    def _place(vals, valids):
        """(R*G,) slot arrays -> dense-prefix (out_cap,) arrays.
        vals may be a (hi, lo) limb tuple (decimal128 sums)."""
        if isinstance(vals, tuple):
            d = tuple(jnp.zeros((out_cap,), x.dtype).at[target].set(
                x, mode="drop") for x in vals)
        else:
            d = jnp.zeros((out_cap,), vals.dtype).at[target].set(
                vals, mode="drop")
        v = jnp.zeros((out_cap,), jnp.bool_).at[target].set(
            valids & occ, mode="drop")
        return d, v

    for op, col in agg_inputs:
        if isinstance(col, StringColumn):
            raise NotImplementedError(
                "string buffers take the sort/hash tiers")

    sweeps = _slot_sweep(agg_inputs, seg, positions, capacity, n_slots,
                         G, R, occ,
                         skip=leftover if sweep_unless_leftover else None)

    results = []
    for svals, svalid in sweeps:
        data, valid = _place(svals, svalid)
        results.append(("raw", (data, valid)))

    out_keys = []
    for (bits, valid), c in zip(key_slots, key_columns):
        vals = _unorder_bits(bits, c.dtype)
        data, v = _place(vals, valid)
        data = jnp.where(v, data, jnp.zeros((), data.dtype))
        out_keys.append(Column(data, v, c.dtype))
    return out_keys, results, num_groups, leftover


def _string_lane_widths(key_bytes: int) -> List[int]:
    """Bit widths of a short string key's fields: its length in bytes
    (0..key_bytes), then its zero-padded bytes as `_string_key_fields`
    cuts them (one sub-word field under four bytes, else whole words)."""
    step = min(key_bytes, 4)
    return [key_bytes.bit_length()] + [8 * step] * (key_bytes // step)


def key_lane_count(dtype: DataType, key_bytes: int) -> int:
    """How many key columns `masked_groupby_lanes` hands the assignment
    for one key of this type: a string's packed lanes, else the column."""
    from ..types import StringType
    if not isinstance(dtype, StringType):
        return 1
    return -(-sum(_string_lane_widths(key_bytes)) // 32)


def string_key_lanes(col: StringColumn, key_bytes: int) -> List[Column]:
    """A string key of at most `key_bytes` bytes as fixed-width key columns.

    Such a string IS its length and its zero-padded bytes: two are equal
    exactly when those are (so "a" and "a\0" differ, by length), packed
    most significant first into as few 32-bit lanes as they fill (CHAR(1):
    9 bits, one lane; 16 bytes: five). The lanes keep the column's
    validity, so NULL stays apart from ""; a NULL row's lanes are zero."""
    from ..types import INT
    from .sort import _pack_fields, _string_key_fields
    from .strings import string_lengths
    fields = [(string_lengths(col).astype(jnp.uint32),
               _string_lane_widths(key_bytes)[0])] \
        + _string_key_fields(col, key_bytes)
    return [Column(jax.lax.bitcast_convert_type(
                jnp.where(col.validity, lane, jnp.uint32(0)), jnp.int32),
                   col.validity, INT)
            for lane in _pack_fields(fields)]


def string_from_key_lanes(lanes: Sequence[Column], key_bytes: int,
                          dtype: DataType) -> StringColumn:
    """Inverse of `string_key_lanes`, for the few rows of a result: the
    string column those lanes spell (no row of the source is read)."""
    from .sort import _unpack_fields
    from .strings import string_from_padded
    widths = _string_lane_widths(key_bytes)
    length, *words = _unpack_fields(
        [jax.lax.bitcast_convert_type(c.data, jnp.uint32) for c in lanes],
        widths)
    # a word's bytes, most significant first
    padded = jnp.stack(
        [(w >> jnp.uint32(shift)).astype(jnp.uint8)
         for w, bits in zip(words, widths[1:])
         for shift in range(bits - 8, -8, -8)], axis=1)
    return string_from_padded(length.astype(jnp.int32), padded,
                              lanes[0].validity, dtype)


def masked_groupby_lanes(key_columns: Sequence[Column],
                         agg_inputs: Sequence[Tuple[str, Optional[Column]]],
                         num_rows, capacity: int, key_bytes: int,
                         group_slots: int = 32, rounds: int = 2):
    """`masked_groupby` for keys of which some are strings no longer than
    `key_bytes` bytes (measured by the caller: `sort.string_key_bytes`):
    each rides as `string_key_lanes`, and the string key columns of the
    SMALL result are spelt back from the slots' lanes. No scatter, no row
    gather and no compare by bytes over the source rows beyond the
    `key_bytes` byte gathers that read a key. The aggregates are swept
    only where no key was left over: the caller reads `leftover` and runs
    another kernel where it is set."""
    spans, lanes = [], []
    for c in key_columns:
        own = string_key_lanes(c, key_bytes) \
            if isinstance(c, StringColumn) else [c]
        spans.append(len(own))
        lanes.extend(own)
    out_lanes, results, num_groups, leftover = masked_groupby(
        lanes, agg_inputs, num_rows, capacity, None, group_slots, rounds,
        sweep_unless_leftover=True)
    out_keys, at = [], 0
    for c, n in zip(key_columns, spans):
        own = out_lanes[at:at + n]
        at += n
        out_keys.append(string_from_key_lanes(own, key_bytes, c.dtype)
                        if isinstance(c, StringColumn) else own[0])
    return out_keys, results, num_groups, leftover


def masked_groupby_exact(key_columns: Sequence[Column],
                         agg_inputs: Sequence[Tuple[str, Optional[Column]]],
                         num_rows, capacity: int, row_mask=None,
                         string_words: int = 1,
                         group_slots: int = 32, rounds: int = 2):
    """Exact full-capacity group-by with zero host syncs: masked-bucket fast
    path, lax.cond into the exact sort-based kernel for the (rare) leftover
    case. Output capacity == input capacity so both branches agree."""
    from .aggregate import groupby_aggregate

    seg, occ, key_slots, leftover = masked_group_assignment(
        key_columns, num_rows, capacity, row_mask, group_slots, rounds)
    act = active_mask(num_rows, capacity)
    if row_mask is not None:
        act = act & row_mask
    positions = jnp.arange(capacity, dtype=jnp.int32)
    G, R = group_slots, rounds
    n_slots = R * G

    def fast_branch(_):
        dense = jnp.cumsum(occ.astype(jnp.int32)) - 1
        num_groups = jnp.sum(occ, dtype=jnp.int32)
        target = jnp.where(occ, dense, capacity)

        def place(vals, valids):
            if isinstance(vals, tuple):
                d = tuple(jnp.zeros((capacity,), x.dtype).at[target].set(
                    x, mode="drop") for x in vals)
            else:
                d = jnp.zeros((capacity,), vals.dtype).at[target].set(
                    vals, mode="drop")
            v = jnp.zeros((capacity,), jnp.bool_).at[target].set(
                valids & occ, mode="drop")
            return d, v

        sweeps = _slot_sweep(agg_inputs, seg, positions, capacity,
                             n_slots, G, R, occ)
        res = [place(svals, svalid) for svals, svalid in sweeps]
        keys = []
        for (bits, valid), c in zip(key_slots, key_columns):
            vals = _unorder_bits(bits, c.dtype)
            d, v = place(vals, valid)
            keys.append(Column(jnp.where(v, d, jnp.zeros((), d.dtype)),
                               v, c.dtype))
        return tuple(keys), tuple(res), num_groups

    def sort_branch(_):
        if row_mask is None:
            cols = list(key_columns) + [c for _, c in agg_inputs
                                        if c is not None]
            n = num_rows
            kc = key_columns
            ai = agg_inputs
        else:
            # the exact path needs the packed-prefix invariant: compact
            all_cols = list(key_columns) + [c for _, c in agg_inputs
                                            if c is not None]
            packed, n = compact_columns(all_cols, row_mask, num_rows)
            kc = list(packed[: len(key_columns)])
            rest = list(packed[len(key_columns):])
            ai = []
            it = iter(rest)
            for op, c in agg_inputs:
                ai.append((op, next(it) if c is not None else None))
        keys, results, num_groups = groupby_aggregate(
            kc, ai, n, capacity, string_words)
        return (tuple(keys),
                tuple(r[1] for r in results),  # all ("raw", _) by gating
                num_groups)

    keys, plain, num_groups = jax.lax.cond(
        leftover, sort_branch, fast_branch, None)
    tagged = [("raw", p) for p in plain]
    return list(keys), tagged, num_groups


def masked_reduce(agg_inputs: Sequence[Tuple[str, Optional[Column]]],
                  num_rows, row_mask=None, out_capacity: int = 128):
    """Grand aggregate (no GROUP BY), scatter-free: one masked full-array
    reduction per aggregate, one active output row at out_capacity.

    Capacity is derived per input column (a count(*)-only aggregate has NO
    input columns at all — its count is just num_rows/the mask popcount)."""
    act1 = active_mask(jnp.int32(1), out_capacity)
    out = []
    for op, col in agg_inputs:
        if col is None and row_mask is None:
            # count(*) with no filter mask: the row count IS the answer
            val = jnp.asarray(num_rows).astype(jnp.int64)
            ok = jnp.bool_(True)
        else:
            cap = col.capacity if col is not None else row_mask.shape[0]
            act = active_mask(num_rows, cap)
            if row_mask is not None:
                act = act & row_mask
            positions = jnp.arange(cap, dtype=jnp.int32)
            val, ok = _slot_reduce(op, act, col, positions, cap)
        if isinstance(val, tuple):  # decimal128 (hi, lo) limbs
            data = tuple(
                jnp.where(act1, jnp.zeros((out_capacity,), x.dtype)
                          .at[0].set(x.reshape(())), jnp.int64(0))
                for x in val)
        else:
            data = jnp.zeros((out_capacity,), val.dtype).at[0].set(val)
            data = jnp.where(act1, data, jnp.zeros((), val.dtype))
        valid = act1 & ok
        out.append((data, valid))
    return out
