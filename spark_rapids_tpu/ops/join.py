"""Equi-join gather-map kernels — device core of GpuHashJoin / JoinGatherer
(reference org/apache/spark/sql/rapids/execution/GpuHashJoin.scala:994,
JoinGatherer.scala).

TPU-first: no device hash table with collision chains. The build side is
sorted by a 64-bit key hash (xxhash64, already Spark-exact in ops/hashing);
each stream row finds its hash-equal candidate range with two searchsorteds;
candidates expand into (stream, build) index pairs; a vectorized *verify*
pass compares the real key columns (so hash collisions cost a false
candidate, never a wrong row); compaction drops mismatches. All steps are
static-shape XLA; the only host sync is choosing the candidate-capacity
bucket from the total match count — the analog of the reference sizing its
gather maps from cuDF's join row count.

Join-type semantics (Spark):
  * equi-keys never match null keys (IS NOT DISTINCT FROM is handled by the
    planner rewriting to a null-safe wrapper before reaching here);
  * left outer emits unmatched stream rows with build side null (build_idx
    == -1 -> gather_column yields invalid rows);
  * semi/anti/existence reduce to the per-stream-row matched flag.
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.column import (
    ArrayColumn, Column, StringColumn, bucket_capacity,
)
from .basic import active_mask, compaction_order, gather_column
from .hashing import murmur3_batch
# row gathers in this module route through ops.gather (numGathers
# accounting) — do NOT import the raw rowpack.gather_rows here
from .rowpack import pack_rows, split_packable
from .strings import string_equal

JOIN_HASH_SEED = 0x5370_6172  # arbitrary fixed seed, 'Spar'
JOIN_HASH_SEED2 = 0x85EB_CA6B


def join_hash_pair(key_cols: Sequence[Column], lo_too: bool = True):
    """Internal join bucket hash: two independent murmur3 passes (u32 VPU
    ops only). xxhash64's emulated 64-bit arithmetic measured ~120 ms per
    2M i64 keys on v5e vs ~10 ms for murmur3 lanes (round 4); the join
    never needs Spark-exact hashing here — collisions only cost a false
    candidate that the exact key-verify pass drops."""
    h_hi = jax.lax.bitcast_convert_type(
        murmur3_batch(list(key_cols), seed=JOIN_HASH_SEED), jnp.uint32)
    if not lo_too:
        return h_hi, None
    h_lo = jax.lax.bitcast_convert_type(
        murmur3_batch(list(key_cols), seed=JOIN_HASH_SEED2), jnp.uint32)
    return h_hi, h_lo


def _keys_valid(key_cols: Sequence[Column], num_rows, capacity: int):
    v = active_mask(num_rows, capacity)
    for c in key_cols:
        v = v & c.validity
    return v


def _bucket_bits(capacity: int) -> int:
    """Static bucket-count exponent: ~2 slots per build row, capped so
    the offsets table stays small."""
    return min(21, max(10, (capacity - 1).bit_length() + 1))


class BuildTable:
    """Hash-bucketed build side: the TPU analog of the cuDF hash table
    the reference builds once and probes per stream batch. Rows sort by
    the u32 hash pair (u32 sort keys are ~5x cheaper than emulated u64 on
    v5e) and a top-B-bits bucket offsets table replaces binary search:
    probing is two tiny table gathers instead of 2 x 19 emulated-u64
    searchsorted rounds (measured: ~1.05 s per 2M probes). Bucket-mates
    with unequal keys are filtered by the existing exact key-verify pass,
    so correctness never depends on hash-range tightness. A registered
    pytree so the whole build phase jits and the probe phase takes it as
    a traced argument."""

    def __init__(self, bucket_table, perm, valid_count, num_rows,
                 key_cols: Sequence[Column], payload: Sequence[Column],
                 capacity: int, pair_table=None, pack=None):
        self.bucket_table = bucket_table  # (2^B + 1,) int32 offsets
        self.perm = perm  # sorted position -> original build row
        self.valid_count = valid_count
        self.num_rows = num_rows
        self.key_cols = list(key_cols)
        self.payload = list(payload)
        self.capacity = capacity
        # (2^B, 2 + k) int32 per bucket: [lo, hi) and, for each of the k
        # variable-size payload columns (payload order), the bytes or
        # elements the range holds, which size the join's output buckets:
        # ONE row gather per probe instead of two offset-table gathers
        # (round 4) and 2k i64 prefix-sum gathers (ISSUE 31)
        self.pair_table = pair_table
        # (plan_k, kmat_sorted, kfmat_sorted, plan_p, pmat_sorted,
        #  pfmat_sorted, key_pack_idx, payload_pack_idx,
        #  payload_other_idx): fixed-width KEYS and PAYLOAD packed into
        #  SEPARATE u32 (+ f64) matrices in SORTED hash order (round 8:
        #  the probe's verify gathers only the key pack at candidate
        #  level; the payload pack is gathered ONCE, at output level,
        #  after compaction — the gather-elimination contract asserted
        #  by the structural numGathers tests)
        self.pack = pack

    @staticmethod
    def build(key_cols: Sequence[Column], payload: Sequence[Column],
              num_rows, capacity: int) -> "BuildTable":
        from .strings import string_lengths
        valid = _keys_valid(key_cols, num_rows, capacity)
        # invalid/inactive rows: push to the end with the max hash AND keep
        # them out of every candidate range via the valid-count boundary.
        h_hi, h_lo = join_hash_pair(key_cols)
        big32 = jnp.uint32(0xFFFF_FFFF)
        k_hi = jnp.where(valid, h_hi, big32)
        k_lo = jnp.where(valid, h_lo, big32)
        iota = jnp.arange(capacity, dtype=jnp.int32)
        sorted_hi, _, _, perm = jax.lax.sort(
            (k_hi, k_lo, (~valid).astype(jnp.int8), iota), num_keys=3)
        valid_count = jnp.sum(valid, dtype=jnp.int32)
        # top-B-bits bucket offsets over the sorted order
        B = _bucket_bits(capacity)
        n_buckets = 1 << B
        sorted_bucket = (sorted_hi >> jnp.uint32(32 - B)).astype(jnp.int32)
        in_valid = iota < valid_count
        seg = jnp.where(in_valid, sorted_bucket, n_buckets)
        counts = jax.ops.segment_sum(
            jnp.ones((capacity,), jnp.int32), seg,
            num_segments=n_buckets + 1)[:n_buckets]
        bucket_table = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(counts, dtype=jnp.int32)])
        # one row per bucket: [lo, hi) and, per variable-size payload
        # column, the payload its range holds, from the prefix sum of the
        # row sizes in sorted order (a column's offsets are i32, so a
        # range's size fits one i32 lane). A probe sizes its output from
        # the SAME row gather that finds its range: gathering i64 prefix
        # sums at both ends of every stream row's range instead was four
        # u32 gathers of the stream's width, one of them from a table the
        # compiler left in HBM whose time differed from process to
        # process (TPC-H Q14, ISSUE 31)
        lanes = [bucket_table[:-1], bucket_table[1:]]
        for c in payload:
            if isinstance(c, (StringColumn, ArrayColumn)):
                if isinstance(c, ArrayColumn):
                    lens = c.offsets[1:] - c.offsets[:-1]
                else:
                    lens = string_lengths(c)
                sorted_lens = jnp.where(iota < valid_count, lens[perm], 0)
                prefix = jnp.concatenate(
                    [jnp.zeros((1,), jnp.int32),
                     jnp.cumsum(sorted_lens, dtype=jnp.int32)])
                lanes.append(prefix[bucket_table[1:]]
                             - prefix[bucket_table[:-1]])
        pair_table = jnp.stack(lanes, axis=1)
        # pack fixed-width keys and payload into SEPARATE sorted-order
        # matrices (round 8): the key pack serves the candidate-level
        # verify, the payload pack is gathered once at output level.
        # The permutes route through the gather engine like every
        # materializing row gather.
        from .gather import gather_rows as routed_gather_rows
        key_pack_idx, _ = split_packable(key_cols)
        payload_pack_idx, payload_other_idx = split_packable(payload)
        plan_k, kmat, kfmat = pack_rows([key_cols[i]
                                         for i in key_pack_idx])
        plan_p, pmat, pfmat = pack_rows([payload[i]
                                         for i in payload_pack_idx])
        kmat_s, kfmat_s = routed_gather_rows(plan_k, kmat, kfmat, perm) \
            if key_pack_idx else (kmat, kfmat)
        pmat_s, pfmat_s = routed_gather_rows(plan_p, pmat, pfmat, perm) \
            if payload_pack_idx else (pmat, pfmat)
        pack = (plan_k, kmat_s, kfmat_s, plan_p, pmat_s, pfmat_s,
                tuple(key_pack_idx), tuple(payload_pack_idx),
                tuple(payload_other_idx))
        return BuildTable(bucket_table, perm, valid_count,
                          num_rows, key_cols, payload, capacity,
                          pair_table, pack)


def _bt_flatten(bt: BuildTable):
    (plan_k, kmat_s, kfmat_s, plan_p, pmat_s, pfmat_s,
     kpi, ppi, poi) = bt.pack
    return ((bt.bucket_table, bt.perm, bt.valid_count, bt.num_rows,
             tuple(bt.key_cols), tuple(bt.payload),
             bt.pair_table, kmat_s, kfmat_s, pmat_s, pfmat_s),
            (bt.capacity, plan_k, plan_p, kpi, ppi, poi))


def _bt_unflatten(aux, children):
    capacity, plan_k, plan_p, kpi, ppi, poi = aux
    (bucket_table, perm, valid_count, num_rows, key_cols, payload,
     pair_table, kmat_s, kfmat_s, pmat_s, pfmat_s) = children
    return BuildTable(bucket_table, perm, valid_count, num_rows,
                      list(key_cols), list(payload), capacity,
                      pair_table,
                      (plan_k, kmat_s, kfmat_s, plan_p, pmat_s, pfmat_s,
                       kpi, ppi, poi))


jax.tree_util.register_pytree_node(BuildTable, _bt_flatten, _bt_unflatten)


def probe_counts(build: BuildTable, stream_keys: Sequence[Column],
                 stream_rows, stream_cap: int):
    """Per-stream-row candidate range (lo, hi) in the bucketed build
    table: two offset-table gathers; bucket-mates with different keys
    are dropped by the key-verify pass downstream."""
    return probe_ranges(build, stream_keys, stream_rows, stream_cap)[:3]


def probe_ranges(build: BuildTable, stream_keys: Sequence[Column],
                 stream_rows, stream_cap: int):
    """`probe_counts` plus, per variable-size payload column of the
    build side, each stream row's candidate payload size (i32; 0 for a
    row without candidates): (lo, counts, valid, range_sizes). ONE row
    gather of the pair table finds it all."""
    valid = _keys_valid(stream_keys, stream_rows, stream_cap)
    h_hi, _ = join_hash_pair(stream_keys, lo_too=False)
    B = _bucket_bits(build.capacity)
    b = (h_hi >> jnp.uint32(32 - B)).astype(jnp.int32)
    pair = build.pair_table[b]
    lo = pair[:, 0]
    hi = jnp.minimum(pair[:, 1], build.valid_count)
    # buckets hold valid rows only, so hi <= valid_count and the range's
    # size is the bucket's
    sizes = tuple(jnp.where(valid, pair[:, 2 + i], 0)
                  for i in range(pair.shape[1] - 2))
    lo = jnp.minimum(lo, hi)
    counts = jnp.where(valid, hi - lo, 0)
    return lo, counts, valid, sizes


def expand_candidates(lo, counts, out_capacity: int):
    """Flatten candidate ranges into (stream_idx, build_pos) pairs.

    out_capacity >= total candidates (host-chosen bucket). Pair i belongs to
    the stream row whose cumulative count interval contains i.

    Formulation (round 4): interval starts scatter their OWNER ROW INDEX
    (disjoint targets by construction), a cummax forward-fills (row index
    is monotone along the flat order), and one 2-lane row gather fetches
    (lo, start) to turn flat positions into build positions. The old i32
    searchsorted was ~21 binary-search rounds, each a full-width gather —
    ~10x this formulation's cost on v5e (tools/exp_join_parts.py).

    Overflow discipline: the i32 prefix sums are exact whenever the true
    candidate total < 2^31; the total itself is accumulated in int64 (a
    cheap reduce), so skew past 2^31 is still detected by the caller's
    sizing/overflow checks (review finding r1) and served by the int64
    searchsorted fallback.
    """
    total = jnp.sum(counts.astype(jnp.int64)) if counts.shape[0] \
        else jnp.int64(0)
    if counts.shape[0] and out_capacity < (1 << 31):
        # range starts carry their owner row (disjoint by construction)
        start = jnp.cumsum(counts) - counts     # exclusive prefix, i32
        pos = jnp.where(counts > 0, jnp.minimum(start, out_capacity),
                        out_capacity)
        j = jnp.arange(counts.shape[0], dtype=jnp.int32)
        seg = jnp.zeros((out_capacity,), jnp.int32).at[pos].max(
            j, mode="drop")
        ls = jnp.stack([lo, start], axis=1)
        row_f = jax.lax.cummax(seg)
        g = ls[row_f]                       # one 2-lane row gather
        i = jnp.arange(out_capacity, dtype=jnp.int32)
        in_range = i.astype(jnp.int64) < total
        stream_idx = jnp.where(in_range, row_f, -1)
        build_pos = g[:, 0] + (i - g[:, 1])
        return stream_idx, build_pos, total
    cum = jnp.cumsum(counts.astype(jnp.int64))  # inclusive
    i = jnp.arange(out_capacity, dtype=jnp.int64)
    stream_idx = jnp.searchsorted(cum, i, side="right").astype(jnp.int32)
    in_range = i < total
    safe_stream = jnp.clip(stream_idx, 0, max(counts.shape[0] - 1, 0))
    before = cum[safe_stream] - counts[safe_stream]
    # (i - before) < per-row count <= capacity, so the int64->int32 narrowing
    # is safe after the subtraction
    build_pos = lo[safe_stream] + (i - before).astype(jnp.int32)
    return jnp.where(in_range, safe_stream, -1), build_pos, total


def verify_pairs(build: BuildTable, stream_keys: Sequence[Column],
                 stream_idx, build_pos, pair_valid):
    """Exact key equality per candidate pair (null-safe: nulls never match,
    but null STREAM rows never produce candidates, so only hash collisions
    are filtered here)."""
    from ..columnar.encoded import DictionaryColumn, bytes_equal_at
    build_row = gather_column_indices(build.perm, build_pos)
    ok = pair_valid
    for bk, sk in zip(build.key_cols, stream_keys):
        if isinstance(bk, DictionaryColumn) or \
                isinstance(sk, DictionaryColumn):
            # encoded key (ISSUE 18): byte-compare through spans into
            # the ORIGINAL buffers (the sides carry DIFFERENT
            # dictionaries, so code equality means nothing across them;
            # a materialized candidate gather would overflow the base
            # byte bucket under join fan-out)
            ok = ok & bytes_equal_at(bk, build_row, sk, stream_idx)
            continue
        b = gather_column(bk, build_row)
        s = gather_column(sk, stream_idx)
        if isinstance(bk, StringColumn):
            eq = string_equal(b, s)
            ok = ok & eq.data & eq.validity
        else:
            ok = ok & (b.data == s.data) & b.validity & s.validity
    return ok, build_row


def gather_column_indices(arr, idx):
    safe = jnp.clip(idx, 0, arr.shape[0] - 1)
    return jnp.where((idx >= 0) & (idx < arr.shape[0]), arr[safe], -1)


def inner_gather_maps(verified, stream_idx, build_row, total):
    """Compact verified pairs to the front: (stream_map, build_map, rows)."""
    cap = verified.shape[0]
    perm, n = compaction_order(verified, total)
    s = jnp.where(active_mask(n, cap), stream_idx[perm], -1)
    b = jnp.where(active_mask(n, cap), build_row[perm], -1)
    return s, b, n


def matched_flags(verified, idx, capacity: int):
    """Per-row matched flag via scatter-or (idx may repeat)."""
    flags = jnp.zeros((capacity,), jnp.int32)
    safe = jnp.clip(idx, 0, capacity - 1)
    contrib = (verified & (idx >= 0)).astype(jnp.int32)
    return flags.at[safe].max(contrib) > 0


def outer_extend_maps(s_map, b_map, n_pairs, unmatched_idx, n_unmatched,
                      null_on: str, out_capacity: int):
    """Append unmatched rows (other side -1 => null) after the matched pairs.

    null_on: which side of the appended rows is null ('build' for left outer,
    'stream' for right outer).
    """
    i = jnp.arange(out_capacity, dtype=jnp.int32)
    total = n_pairs + n_unmatched
    from_un = (i >= n_pairs) & (i < total)
    un_i = jnp.clip(i - n_pairs, 0, unmatched_idx.shape[0] - 1)
    pair_i = jnp.clip(i, 0, s_map.shape[0] - 1)
    if null_on == "build":
        s = jnp.where(from_un, unmatched_idx[un_i], jnp.where(i < n_pairs, s_map[pair_i], -1))
        b = jnp.where(from_un, -1, jnp.where(i < n_pairs, b_map[pair_i], -1))
    else:
        s = jnp.where(from_un, -1, jnp.where(i < n_pairs, s_map[pair_i], -1))
        b = jnp.where(from_un, unmatched_idx[un_i], jnp.where(i < n_pairs, b_map[pair_i], -1))
    return s, b, total


def unmatched_indices(matched, num_rows, capacity: int):
    """Indices of active rows whose matched flag is False, compacted."""
    act = active_mask(num_rows, capacity)
    keep = act & (~matched)
    perm, n = compaction_order(keep, num_rows)
    idx = jnp.where(active_mask(n, capacity), perm, -1)
    return idx, n


def cross_pairs(stream_rows, build_rows, chunk_start, out_capacity: int):
    """Nested-loop candidates: all (stream, build) pairs with flat pair index
    in [chunk_start, chunk_start+out_capacity). The exec layer loops chunks
    (reference GpuBroadcastNestedLoopJoinExecBase / GpuCartesianProductExec).

    Pair indices are int64: stream_rows*build_rows overflows int32 well
    inside practical cartesian-product sizes."""
    i = jnp.arange(out_capacity, dtype=jnp.int64) + jnp.int64(chunk_start)
    total = jnp.int64(stream_rows) * jnp.int64(build_rows)
    ok = i < total
    safe_build = jnp.maximum(jnp.int64(build_rows), 1)
    s = jnp.where(ok, i // safe_build, -1).astype(jnp.int32)
    b = jnp.where(ok, i % safe_build, -1).astype(jnp.int32)
    remaining = jnp.maximum(total - jnp.int64(chunk_start), 0)
    n = jnp.minimum(remaining, out_capacity).astype(jnp.int32)
    return s, b, n
