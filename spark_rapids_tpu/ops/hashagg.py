"""Hash-based group assignment — the TPU answer to cuDF's hash group-by
(the reference's primary aggregation path; sort-based is its fallback,
GpuAggregateExec.scala:909 — same duality here).

No open addressing / probing loops (serial, XLA-hostile). Instead,
*collision-verified scatter*: R static rounds, each round r
  1. bucket b = xxhash64(keys, seed=r) mod capacity
  2. representative per bucket = min row index (one scatter-min)
  3. rows whose keys EQUAL their bucket's representative key resolve to
     that bucket (vectorized gather + compare; hash collisions between
     distinct keys simply fail the compare)
  4. unresolved rows go to round r+1 with a different seed
All equal keys share a bucket every round, so each distinct key resolves
as a whole group the first round its bucket isn't contested. After R
rounds a `leftover` flag reports unresolved rows; the exec checks it on
the host (one sync) and falls back to the exact sort-based kernel — rare
in practice for cardinality << capacity, and for cardinality ~ capacity
the sort path is the right algorithm anyway.

Cost: O(R·n) scatters/gathers/compares, no O(n log n) sort, no
data-dependent shapes: the kernel for mid-cardinality aggregations and for
keys the masked-bucket kernel cannot take. On a TPU every one of those
scatters and row gathers serializes, and a string key is compared byte by
byte behind a per-byte search (`string_equal`): 20 s for 8M rows and four
groups on v5e (PERF.md, PR 35). So the exec runs these rounds SECOND where
it can: string keys that measure at most 16 bytes go first through the
masked-bucket kernel as packed lanes (ops/maskedagg.masked_groupby_lanes,
exec/aggregate._hash_tiers), and come here where that leaves keys over
(more distinct keys than its slots), where the keys are wider, or where a
buffer is a string.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.column import Column, StringColumn
from .basic import active_mask, compaction_order, gather_column
from .hashing import xxhash64_batch
from .strings import string_equal

#: static number of re-hash rounds before the sort fallback
DEFAULT_ROUNDS = 2


def _keys_equal_rows(key_cols: Sequence[Column], idx_a, idx_b):
    """Null-aware GROUP BY equality between row idx_a[i] and idx_b[i]:
    null == null, values compare exactly."""
    eq = None
    for col in key_cols:
        a = gather_column(col, idx_a)
        b = gather_column(col, idx_b)
        if isinstance(col, StringColumn):
            s = string_equal(a, b)
            val_eq = s.data & s.validity
        else:
            val_eq = a.data == b.data
        both_null = (~a.validity) & (~b.validity)
        both_valid = a.validity & b.validity
        this_eq = both_null | (both_valid & val_eq)
        eq = this_eq if eq is None else (eq & this_eq)
    return eq if eq is not None else jnp.ones_like(idx_a, jnp.bool_)


def hash_group_assignment(key_cols: Sequence[Column], num_rows,
                          capacity: int, rounds: int = DEFAULT_ROUNDS):
    """Assign group slots without sorting.

    Returns (seg (capacity,) int32 in [0, rounds*capacity) or the sentinel
    rounds*capacity for unresolved/inactive rows,
    rep_row (rounds*capacity,) int32: representative source row per slot
    (or capacity when the slot is empty),
    leftover: device bool scalar — True iff some active row stayed
    unresolved and the caller must use the sort fallback).
    """
    cap = capacity
    iota = jnp.arange(cap, dtype=jnp.int32)
    act = active_mask(num_rows, cap)
    # include validity in the hash so null keys get their own bucket chain
    remaining = act
    seg = jnp.full((cap,), rounds * cap, jnp.int32)
    rep_rows: List[jnp.ndarray] = []
    for r in range(rounds):
        h = xxhash64_batch(list(key_cols), seed=0x9E3779B9 + r)
        h_u = jax.lax.bitcast_convert_type(h, jnp.uint64)
        bucket = (h_u % jnp.uint64(cap)).astype(jnp.int32)
        # scatter-min row index into contested buckets (only remaining rows)
        rep = jnp.full((cap,), cap, jnp.int32)
        rep = rep.at[jnp.where(remaining, bucket, cap)].min(iota, mode="drop")
        my_rep = rep[bucket]
        same = _keys_equal_rows(key_cols, iota,
                                jnp.clip(my_rep, 0, cap - 1))
        resolved = remaining & (my_rep < cap) & same
        seg = jnp.where(resolved, r * cap + bucket, seg)
        # a slot's representative is only real if the rep row resolved INTO
        # this slot (rep row always matches itself, so rep<cap => resolved)
        rep_rows.append(rep)
        remaining = remaining & ~resolved
    leftover = jnp.any(remaining)
    # a slot's rep row always resolves into that slot (it compares equal to
    # itself), so rep < cap is exactly "slot occupied"
    rep_row = jnp.concatenate(rep_rows)
    return seg, rep_row, leftover


def dense_group_ids(seg, rep_row, capacity: int, rounds: int):
    """Compact occupied slots into dense ids [0, num_groups).

    Returns (dense_seg (capacity,) int32 with sentinel capacity for
    unresolved rows, group_rep (capacity,) int32 source row per dense
    group, num_groups)."""
    n_slots = rounds * capacity
    occupied = rep_row < capacity
    # dense id per slot: prefix count of occupied slots
    pos = jnp.cumsum(occupied.astype(jnp.int32)) - 1
    num_groups = jnp.sum(occupied, dtype=jnp.int32)
    slot_to_dense = jnp.where(occupied, pos, capacity)
    safe_seg = jnp.clip(seg, 0, n_slots - 1)
    dense_seg = jnp.where(seg < n_slots, slot_to_dense[safe_seg], capacity)
    # group_rep in dense order: scatter rep rows to their dense position
    group_rep = jnp.full((capacity,), capacity, jnp.int32)
    group_rep = group_rep.at[jnp.where(occupied, pos, capacity)].set(
        rep_row, mode="drop")
    return dense_seg, group_rep, num_groups
