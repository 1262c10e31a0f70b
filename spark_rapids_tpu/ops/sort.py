"""Multi-column sort kernels — the device core behind GpuSortExec,
out-of-core merge sort, sort-based aggregation fallback and range
partitioning (reference GpuSortExec.scala:86, SortUtils.scala).

TPU-first design: instead of cuDF's comparator-based radix sort we lower
every ORDER BY to *order-key lanes* — unsigned integer arrays whose plain
ascending lexicographic order equals the requested Spark ordering (asc/desc,
nulls first/last, NaN-greatest, UTF-8 binary string order). The lanes feed
`jax.lax.sort(num_keys=k)`, which XLA compiles to its native tiled sort on
the MXU-adjacent vector units. One extra iota lane makes the sort stable and
doubles as the permutation used to gather the payload columns.

Inactive rows (index >= num_rows) always sort last via a leading
activity lane, so sorted batches keep the packed-prefix invariant.

The SORT's lanes (`packed_key_lanes`) are those fields at the width they
need, packed most significant first into as few u32 lanes as they fill: the
chip's compiler takes ~15 s a key lane up to four and does not finish twenty
(PERF.md). A string key's width is measured (`string_key_bytes`) and goes
down to one byte as well as up. `order_key_lanes` keeps the unpacked stack
(one lane a field, u64 string prefixes of at least DEFAULT_STRING_WORDS) for
the readers that compare lanes across batches or to their neighbours: segment
ids, the out-of-core merge's bound, window partitions.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.column import Column, StringColumn
from ..obs.dispatch import instrument
from ..types import BooleanType, DataType
from .basic import active_mask, gather_column


@dataclass(frozen=True)
class SortOrder:
    """One ORDER BY term: column ordinal + direction + null placement.

    Spark defaults: ascending => nulls first, descending => nulls last.
    """
    ordinal: int
    ascending: bool = True
    nulls_first: bool = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.nulls_first is None:
            object.__setattr__(self, "nulls_first", self.ascending)


#: default number of 8-byte words of string prefix used as sort lanes.
#: 32 bytes covers TPC-DS/TPC-H key domains; raise via SortSpec for longer.
DEFAULT_STRING_WORDS = 4


def _float_order_bits(data, bits_dtype, sign_bit):
    """IEEE-754 total order as unsigned ints, with Spark semantics:
    all NaNs collapse to one value greater than +inf; -0.0 == 0.0."""
    data = jnp.where(jnp.isnan(data), jnp.full((), jnp.nan, data.dtype), data)
    # -0.0 -> +0.0 via select: `x + 0.0` is NOT value-preserving for -0.0
    # and XLA's algebraic simplifier folds it away under jit
    data = jnp.where(data == jnp.zeros((), data.dtype),
                     jnp.zeros((), data.dtype), data)
    if jnp.dtype(bits_dtype).itemsize == 8:
        # direct f64 bitcasts don't compile on TPU (X64 pass limitation);
        # reconstruct the pattern arithmetically
        from .f64bits import f64_bits
        bits = f64_bits(data)
    else:
        bits = jax.lax.bitcast_convert_type(data, bits_dtype)
    neg = (bits >> (sign_bit)) & 1
    flipped = jnp.where(neg == 1, ~bits, bits | (jnp.ones((), bits_dtype) << sign_bit))
    return flipped


def _numeric_order_key(col: Column):
    """Map one fixed-width column to a single unsigned lane that sorts
    ascending in value order."""
    data = col.data
    dt = data.dtype
    if dt == jnp.bool_:
        return data.astype(jnp.uint32)
    if jnp.issubdtype(dt, jnp.floating):
        if dt == jnp.float64:
            return _float_order_bits(data, jnp.uint64, 63)
        return _float_order_bits(data.astype(jnp.float32), jnp.uint32, 31)
    if jnp.issubdtype(dt, jnp.signedinteger):
        bits = 8 * dt.itemsize
        udt = {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32, 64: jnp.uint64}[bits]
        unsigned = jax.lax.bitcast_convert_type(data, udt)
        return unsigned ^ (jnp.ones((), udt) << (bits - 1))
    return data  # already unsigned


def numeric_order_lanes(col: Column):
    """Order-consistent unsigned lane LIST for one fixed-width column:
    one lane for plain columns, two u64 limb lanes for decimal128
    (round 5: decimal keys)."""
    from ..columnar.column import Decimal128Column
    if isinstance(col, Decimal128Column):
        sign = jnp.uint64(1) << jnp.uint64(63)
        return [jax.lax.bitcast_convert_type(col.hi.data, jnp.uint64)
                ^ sign,
                jax.lax.bitcast_convert_type(col.lo.data, jnp.uint64)]
    return [_numeric_order_key(col)]


def string_prefix_lanes(col: StringColumn, num_words: int) -> List[jnp.ndarray]:
    """First `num_words`*8 bytes of each string as big-endian uint64 lanes;
    plain ascending uint64 order == UTF-8 binary order (zero-padded, so
    shorter strings sort before their extensions, matching Spark)."""
    cap = col.capacity
    starts = col.offsets[:cap]
    lengths = col.offsets[1:] - starts
    byte_cap = col.byte_capacity
    lanes = []
    for w in range(num_words):
        word = jnp.zeros((cap,), jnp.uint64)
        for b in range(8):
            j = w * 8 + b
            pos = starts + j
            in_str = j < lengths
            safe = jnp.clip(pos, 0, byte_cap - 1)
            byte = jnp.where(in_str, col.data[safe], 0).astype(jnp.uint64)
            word = (word << jnp.uint64(8)) | byte
        lanes.append(word)
    return lanes


def _max_string_length(offsets):
    return jnp.max(jnp.stack([jnp.max(o[1:] - o[:-1]) for o in offsets]))


# through the dispatch ledger like every other program: run eagerly the
# subtract and the two reductions were device time under no label
_max_string_length_jit = instrument(_max_string_length,
                                    label="sort.key_width")


def string_key_bytes(columns: Sequence[Column],
                     ordinals: Sequence[int]) -> int:
    """Byte width making string ordering EXACT for these batches: measures
    the longest string of the key columns on device (one program, one host
    sync, outside jit) and rounds UP to a power of two (1, 2, 4, 8, ...)
    so the static width buckets like capacities do. Goes DOWN to what the
    keys need: CHAR(1) keys sort on one byte each, not on 32. 1 where no
    key is a string (no sync)."""
    offsets = tuple(columns[i].offsets for i in ordinals
                    if isinstance(columns[i], StringColumn))
    if not offsets:
        return 1
    need = max(1, int(_max_string_length_jit(offsets)))
    return 1 << (need - 1).bit_length()


def string_words_for(columns: Sequence[Column], ordinals: Sequence[int],
                     num_rows=None) -> int:
    """`string_key_bytes` as a count of 8-byte words, never under
    DEFAULT_STRING_WORDS: the width of `order_key_lanes`' u64 prefix lanes
    (segment ids, the out-of-core merge's bound, window partitions, the
    hash group-by's string min/max)."""
    return max(DEFAULT_STRING_WORDS, string_key_bytes(columns, ordinals) // 8)


def order_key_lanes(columns: Sequence[Column], orders: Sequence[SortOrder],
                    num_rows, capacity: int,
                    string_words: int = DEFAULT_STRING_WORDS,
                    ) -> List[jnp.ndarray]:
    """Build the full lane stack: [activity, (nulls, value-lanes)*]."""
    act = active_mask(num_rows, capacity)
    lanes: List[jnp.ndarray] = [(~act).astype(jnp.uint32)]
    for o in orders:
        col = columns[o.ordinal]
        valid = col.validity & act
        # null lane: 0 sorts first. nulls_first => null rank 0, else rank 1
        # (then inverted for descending along with everything else).
        null_rank = jnp.where(valid, 1, 0) if o.nulls_first else \
            jnp.where(valid, 0, 1)
        lanes.append(null_rank.astype(jnp.uint32))
        if isinstance(col, StringColumn):
            vlanes = string_prefix_lanes(col, string_words)
        else:
            # one lane for plain columns, two limb lanes for
            # decimal128 (round 5: decimal keys; i64 bitcasts are fine
            # on TPU — only f64 sources are broken)
            vlanes = numeric_order_lanes(col)
        for v in vlanes:
            v = jnp.where(valid, v, jnp.zeros((), v.dtype))
            if not o.ascending:
                v = ~v
            lanes.append(v)
    return lanes


def _split_u64_lanes(lanes):
    """Split uint64 sort lanes into (hi, lo) uint32 pairs: emulated-u64
    compares make XLA's TPU sort ~5x slower than the u32 equivalent
    (measured v5e; order is identical lexicographically)."""
    out = []
    for lane in lanes:
        if lane.dtype == jnp.uint64:
            out.append((lane >> jnp.uint64(32)).astype(jnp.uint32))
            out.append(lane.astype(jnp.uint32))
        else:
            out.append(lane)
    return out


def _string_key_fields(col: StringColumn, key_bytes: int):
    """The first `key_bytes` bytes of each string, big-endian and
    zero-padded (a shorter string sorts before its extensions), as
    (u32 lane, bits) fields: one sub-word field under four bytes, else
    whole u32 words."""
    cap = col.capacity
    starts = col.offsets[:cap]
    lengths = col.offsets[1:] - starts
    byte_cap = col.byte_capacity
    step = min(key_bytes, 4)
    fields = []
    for w in range(0, key_bytes, step):
        word = jnp.zeros((cap,), jnp.uint32)
        for j in range(w, w + step):
            safe = jnp.clip(starts + j, 0, byte_cap - 1)
            byte = jnp.where(j < lengths, col.data[safe], 0)
            word = (word << jnp.uint32(8)) | byte.astype(jnp.uint32)
        fields.append((word, 8 * step))
    return fields


def _numeric_key_fields(col: Column):
    """One fixed-width column as (u32 lane, bits) fields in value order: a
    BOOLEAN is one bit, a narrow integer its own width, a 64-bit lane its
    high and low words."""
    lanes = numeric_order_lanes(col)
    if len(lanes) == 1 and col.data.dtype == jnp.bool_:
        return [(lanes[0], 1)]
    return [(lane.astype(jnp.uint32), 8 * lane.dtype.itemsize)
            for lane in _split_u64_lanes(lanes)]


def _pack_fields(fields) -> List[jnp.ndarray]:
    """Concatenate (u32 lane, bits) fields, most significant first, into
    as few u32 lanes as their bits fill. Plain ascending lexicographic
    order of the lanes is the lexicographic order of the fields: a field
    may straddle two lanes, and only the last lane has spare (low, zero)
    bits."""
    lanes: List[jnp.ndarray] = []
    cur, free = None, 32
    for v, bits in fields:
        if bits > free:
            # the high part fills the open lane, the low part opens the next
            low = bits - free
            lanes.append(cur | (v >> jnp.uint32(low)))
            v = v & jnp.uint32((1 << low) - 1)
            cur, free, bits = None, 32, low
        v = v << jnp.uint32(free - bits) if free > bits else v
        cur = v if cur is None else cur | v
        free -= bits
        if free == 0:
            lanes.append(cur)
            cur, free = None, 32
    if cur is not None:
        lanes.append(cur)
    return lanes


def _unpack_fields(lanes: Sequence[jnp.ndarray],
                   widths: Sequence[int]) -> List[jnp.ndarray]:
    """Inverse of `_pack_fields`: the u32 fields of those bit widths, in
    their order, out of the packed lanes."""
    def low_bits(v, bits: int):
        return v & jnp.uint32((1 << bits) - 1)

    out: List[jnp.ndarray] = []
    at, free = 0, 32
    for bits in widths:
        if bits > free:
            low = bits - free
            v = (low_bits(lanes[at], free) << jnp.uint32(low)) \
                | (lanes[at + 1] >> jnp.uint32(32 - low))
            at, free = at + 1, 32 - low
        else:
            free -= bits
            v = low_bits(lanes[at] >> jnp.uint32(free), bits)
        if free == 0:
            at, free = at + 1, 32
        out.append(v)
    return out


def packed_key_lanes(columns: Sequence[Column], orders: Sequence[SortOrder],
                     num_rows, capacity: int, key_bytes: int
                     ) -> List[jnp.ndarray]:
    """The sort's key lanes: `order_key_lanes`' fields (activity, then per
    key its null rank and its value) at the width they need, packed into as
    few u32 lanes as they fill. A string key takes `key_bytes` bytes
    (`string_key_bytes`: exact where it covers the longest string). Two
    CHAR(1) keys are 1 + 2 x (1 + 8) = 19 bits: ONE lane, where the
    unpacked stack was 19 (the chip's compiler takes ~15 s for one u32 key
    lane and 110 s for four)."""
    act = active_mask(num_rows, capacity)
    fields = [((~act).astype(jnp.uint32), 1)]
    for o in orders:
        col = columns[o.ordinal]
        valid = col.validity & act
        null_rank = valid if o.nulls_first else ~valid
        fields.append((null_rank.astype(jnp.uint32), 1))
        vfields = _string_key_fields(col, key_bytes) \
            if isinstance(col, StringColumn) else _numeric_key_fields(col)
        for v, bits in vfields:
            v = jnp.where(valid, v, jnp.uint32(0))
            if not o.ascending:
                v = v ^ jnp.uint32((1 << bits) - 1)
            fields.append((v, bits))
    return _pack_fields(fields)


def lexsort_permutation(lanes: Sequence[jnp.ndarray],
                        capacity: int) -> jnp.ndarray:
    """The stable permutation that orders rows by u32 key `lanes`, most
    significant first. Several lanes are sorted one at a time, least
    significant first, each pass a stable sort of (that lane read through
    the permutation so far, the permutation): ONE two-operand sort in a
    loop, whatever the number of lanes. The chip's compiler takes about
    half a minute for such a sort at a 32,768-row bucket and 456 s for
    five key lanes with their payload there (PERF.md section 6, PR 38):
    the cost is in the operands, not in the rows."""
    iota = jnp.arange(capacity, dtype=jnp.int32)
    if len(lanes) == 1:
        return jax.lax.sort((lanes[0], iota), num_keys=1, is_stable=True)[1]
    stacked = jnp.stack(lanes)

    def one_pass(j, perm):
        lane = stacked[len(lanes) - 1 - j]
        return jax.lax.sort((lane[perm], perm), num_keys=1,
                            is_stable=True)[1]

    return jax.lax.fori_loop(0, len(lanes), one_pass, iota)


def first_rows(lanes: Sequence[jnp.ndarray], capacity: int, limit: int,
               out_capacity: int) -> jnp.ndarray:
    """The rows a stable sort by `lanes` would put first, `limit` of them,
    as int32 (out_capacity,) row indices in that order (`capacity`, out of
    range, beyond them): found by selection, `limit` times the smallest key
    not yet taken (a masked minimum a lane, the lowest index among equals).
    No sort: a top-N under a small limit compiles in seconds and reads the
    batch `limit` x lanes times."""
    iota = jnp.arange(capacity, dtype=jnp.int32)
    top = jnp.uint32(0xFFFFFFFF)

    def take_next(i, carry):
        taken, out = carry
        cand = ~taken
        for lane in lanes:
            cand = cand & (lane == jnp.min(jnp.where(cand, lane, top)))
        row = jnp.min(jnp.where(cand, iota, capacity))
        return taken | (iota == row), out.at[i].set(row)

    _, out = jax.lax.fori_loop(
        0, limit, take_next,
        (jnp.zeros((capacity,), jnp.bool_),
         jnp.full((out_capacity,), capacity, jnp.int32)))
    return out


def sort_permutation(columns: Sequence[Column], orders: Sequence[SortOrder],
                     num_rows, capacity: int,
                     key_bytes: int = 8 * DEFAULT_STRING_WORDS):
    """Stable sort permutation: int32 (capacity,) such that gathering by it
    yields rows in the requested order, inactive rows last."""
    lanes = packed_key_lanes(columns, orders, num_rows, capacity, key_bytes)
    return lexsort_permutation(lanes, capacity)


def sort_batch_columns(columns: Sequence[Column], orders: Sequence[SortOrder],
                       num_rows, capacity: int,
                       key_bytes: int = 8 * DEFAULT_STRING_WORDS,
                       ) -> Tuple[List[Column], jnp.ndarray]:
    """Sort all columns of a batch; returns (sorted columns, permutation).

    Keys that pack into ONE lane: fixed-width payload columns ride INSIDE
    lax.sort as packed u32/f64 lanes (ops/rowpack) instead of being
    gathered by the permutation afterwards (round 4). The iota lane stays
    a KEY so the sort is stable and varlen columns still gather by it.
    Keys of several lanes: `lexsort_permutation`, then one packed row
    gather of the fixed-width columns (`ops/gather.py`): every operand of
    a sort costs the chip's compiler tens of seconds.
    """
    from .rowpack import pack_rows, split_packable, unpack_rows
    lanes = packed_key_lanes(columns, orders, num_rows, capacity, key_bytes)
    if len(lanes) > 1:
        from .gather import gather_batch_columns
        perm = lexsort_permutation(lanes, capacity)
        return gather_batch_columns(columns, perm), perm
    iota = jnp.arange(capacity, dtype=jnp.int32)
    p_idx, o_idx = split_packable(columns)
    out: List = [None] * len(columns)
    if len(p_idx) > 0:
        plan, imat, fmat = pack_rows([columns[i] for i in p_idx])
        ilanes = [imat[:, j] for j in range(imat.shape[1])]
        flanes = [fmat[:, j] for j in range(fmat.shape[1])] \
            if fmat is not None else []
        res = jax.lax.sort(
            tuple(lanes) + (iota,) + tuple(ilanes) + tuple(flanes),
            num_keys=len(lanes) + 1)
        perm = res[len(lanes)]
        s_il = res[len(lanes) + 1: len(lanes) + 1 + len(ilanes)]
        s_fl = res[len(lanes) + 1 + len(ilanes):]
        s_imat = jnp.stack(s_il, axis=1)
        s_fmat = jnp.stack(s_fl, axis=1) if flanes else None
        for j, c in zip(p_idx, unpack_rows(plan, s_imat, s_fmat)):
            out[j] = c
    else:
        res = jax.lax.sort(tuple(lanes) + (iota,), num_keys=len(lanes) + 1)
        perm = res[len(lanes)]
    for j in o_idx:
        # gather marks rows valid per source validity; the inactive tail
        # is handled by perm pointing at rows whose validity is False
        out[j] = gather_column(columns[j], perm, out_valid=None)
    return list(out), perm


def group_segment_ids(key_columns: Sequence[Column], num_rows, capacity: int,
                      string_words: int = DEFAULT_STRING_WORDS):
    """For KEY-SORTED columns: (segment_ids int32 (capacity,), num_groups).

    Rows with equal keys (nulls equal, Spark GROUP BY semantics) share an id;
    ids are dense 0..num_groups-1 in sorted order; inactive rows get id ==
    capacity (dropped by jax segment reductions with num_segments=capacity).
    """
    act = active_mask(num_rows, capacity)
    orders = [SortOrder(i) for i in range(len(key_columns))]
    lanes = order_key_lanes(key_columns, orders, num_rows, capacity,
                            string_words)[1:]  # drop activity lane
    boundary = jnp.zeros((capacity,), jnp.bool_)
    for lane in lanes:
        boundary = boundary | (lane != jnp.roll(lane, 1))
    boundary = boundary.at[0].set(True)
    boundary = boundary & act
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    num_groups = jnp.where(num_rows > 0, jnp.max(jnp.where(act, seg, -1)) + 1, 0)
    seg = jnp.where(act, seg, capacity)
    return seg, num_groups.astype(jnp.int32)
