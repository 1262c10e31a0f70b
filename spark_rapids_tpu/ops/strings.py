"""Varlen (string/binary) kernels over the (offsets, bytes) twin-array layout.

Replaces cuDF's strings column primitives (reference L6). XLA has no ragged
tensors, so every kernel is expressed as dense gathers over the padded byte
buffer. Most kernels find `row_of_byte` first: for each output byte position,
which row it belongs to, via searchsorted on the output offsets (O(B log N)
with B = byte capacity: ~18 B-wide gathers at Q14's shapes), then gather by
it; fully static shapes, MXU-free pure VPU work.

`gather_string`, the row gather every filter, sort, join emit and dictionary
decode of a string column goes through, needs no search (ISSUE 32): the
source of output byte p is p + (src_start - out_start) of p's row, a step
function of p that changes only at row starts, so it marks each row start
with the step's change (N-wide) and takes ONE prefix sum over the byte
bucket. `concat_string`, the primitive of the coalesce and of every other
concat of a string column, needs neither marks nor a gather (ISSUE 39): the
active rows of each input own one contiguous byte range, so the output is
two block moves at traced offsets, for the bytes as for the offsets and
validity. The other kernels keep the search until a benchmark cell runs them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..columnar.column import Column, StringColumn


def string_lengths(col: StringColumn):
    """int32 (capacity,): byte length per row (0 for null/inactive rows)."""
    return col.offsets[1:] - col.offsets[:-1]


def hex_digit_val(b):
    """Value of an ASCII hex digit byte; -1 for non-hex (shared by the
    json/codec/url kernels)."""
    v = jnp.full(b.shape, jnp.int32(-1))
    v = jnp.where((b >= ord("0")) & (b <= ord("9")),
                  b.astype(jnp.int32) - ord("0"), v)
    v = jnp.where((b >= ord("a")) & (b <= ord("f")),
                  b.astype(jnp.int32) - ord("a") + 10, v)
    v = jnp.where((b >= ord("A")) & (b <= ord("F")),
                  b.astype(jnp.int32) - ord("A") + 10, v)
    return v


def seg_incl_cumsum(x, row_start_pos):
    """Per-row inclusive cumsum of int32 x over a flat byte buffer:
    global cumsum minus the exclusive cumsum at each byte's row start."""
    c = jnp.cumsum(x, dtype=jnp.int32)
    return c - (c - x)[row_start_pos]


def _rebuild_offsets(lengths):
    """Exclusive-scan lengths into (capacity+1,) offsets."""
    return jnp.concatenate([
        jnp.zeros(1, jnp.int32),
        jnp.cumsum(lengths, dtype=jnp.int32),
    ])


def _bytes_by_row_marks(table, src_starts, new_offsets, byte_cap: int):
    """(byte_cap,) uint8: output row r's bytes are `table[src_starts[r]:]`
    laid from `new_offsets[r]` on; zeros past the last row.

    The source of output byte p is p + delta[row owning p], with delta =
    src_start - out_start constant over a row: mark each row's start with
    delta's change from the row before and prefix-sum the marks: one N-wide
    scatter and one B-wide scan, where finding the row by searchsorted is
    ~log N B-wide gathers (18x the time at Q14's shapes, 45x at 8M rows on
    v5e). The marks are ADDED: empty rows share a start with the next row
    and telescope to the last one's delta; starts at or past the bucket
    drop."""
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    delta = src_starts - new_offsets[:-1]
    marks = jnp.zeros(byte_cap, jnp.int32).at[new_offsets[:-1]].add(
        jnp.diff(delta, prepend=jnp.int32(0)), mode="drop",
        indices_are_sorted=True)
    src_pos = pos + jnp.cumsum(marks, dtype=jnp.int32)
    in_use = pos < new_offsets[-1]
    src_pos = jnp.where(in_use, jnp.clip(src_pos, 0, table.shape[0] - 1), 0)
    return jnp.where(in_use, table[src_pos], jnp.uint8(0))


def gather_string(col: StringColumn, indices, out_valid,
                  out_byte_capacity: int | None = None) -> StringColumn:
    """Gather rows of a string column by pre-clamped int32 `indices`.

    out_byte_capacity: static byte bucket of the result. Defaults to the
    input's byte bucket (sufficient for any permutation/filter; joins that
    duplicate long rows must pass a larger bucket).
    """
    byte_cap = out_byte_capacity or col.byte_capacity
    lengths = string_lengths(col)[indices]
    lengths = jnp.where(out_valid, lengths, 0)
    new_offsets = _rebuild_offsets(lengths)
    src_starts = col.offsets[indices]

    data = _bytes_by_row_marks(col.data, src_starts, new_offsets, byte_cap)
    return StringColumn(data, new_offsets, out_valid, col.dtype)


def _placed(x, start, size: int):
    """(size,) lane holding x from traced `start` on: out[p] = x[p - start]
    where that index is in x, else 0. A start below -len(x) or above size
    leaves x wholly outside and is clamped to such a start, so the lane is
    right for any start."""
    n = x.shape[0]
    buf = jnp.zeros((n + size + n,), x.dtype)
    buf = jax.lax.dynamic_update_slice(buf, x, (n + start,))
    return buf[n:n + size]


def concat_string(a: StringColumn, b: StringColumn, a_rows, b_rows,
                  out_capacity: int,
                  out_byte_capacity: int | None = None) -> StringColumn:
    """Concatenate active rows of two string columns.

    The active rows of a own the contiguous bytes
    a.data[a.offsets[0]:a.offsets[a_rows]], and likewise b's, so the output
    bytes are a's block at 0 followed by b's at a_bytes: two block moves at
    traced offsets (`_placed`), zeros past the total. The offsets are a's
    and b's shifted to those places, two blocks as for a fixed-width lane
    (`basic._concat_fixed`), the total at and past row a_rows + b_rows. No
    scatter, gather or scan: the row-start marks and per-byte gather this
    replaces cost 1.462 s of a TPC-H Q1 query's 3.8 s on v5e (PERF.md, PR
    36). An offsets[0] other than 0 is shifted out; padding rows' offsets
    and bytes are never read."""
    from .basic import _concat_fixed
    byte_cap = out_byte_capacity or (a.byte_capacity + b.byte_capacity)
    a0, b0 = a.offsets[0], b.offsets[0]
    a_bytes = a.offsets[a_rows] - a0
    total = a_bytes + b.offsets[b_rows] - b0
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    data = jnp.where(pos < a_bytes, _placed(a.data, -a0, byte_cap),
                     _placed(b.data, a_bytes - b0, byte_cap))
    data = jnp.where(pos < total, data, jnp.uint8(0))

    live = jnp.arange(out_capacity, dtype=jnp.int32) < a_rows + b_rows
    starts = _concat_fixed(a.offsets[:-1] - a0, b.offsets[:-1] - b0 + a_bytes,
                           a_rows, out_capacity)
    new_offsets = jnp.append(jnp.where(live, starts, total), total)
    validity = _concat_fixed(a.validity, b.validity, a_rows,
                             out_capacity) & live
    return StringColumn(data, new_offsets, validity, a.dtype)


# --- elementwise string functions ----------------------------------------

def str_length_bytes(col: StringColumn) -> Column:
    from ..types import INT
    return Column(string_lengths(col), col.validity, INT)


def str_length_chars(col: StringColumn) -> Column:
    """UTF-8 aware character count (Spark `length`): count non-continuation
    bytes ((b & 0xC0) != 0x80) per row via a segmented sum."""
    from ..types import INT
    cap = col.capacity
    is_start = ((col.data & 0xC0) != 0x80).astype(jnp.int32)
    csum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(is_start, dtype=jnp.int32)])
    counts = csum[col.offsets[1:]] - csum[col.offsets[:-1]]
    return Column(counts, col.validity, INT)


def str_upper_ascii(col: StringColumn) -> StringColumn:
    lower = (col.data >= ord("a")) & (col.data <= ord("z"))
    data = jnp.where(lower, col.data - 32, col.data)
    return StringColumn(data, col.offsets, col.validity, col.dtype)


def str_lower_ascii(col: StringColumn) -> StringColumn:
    upper = (col.data >= ord("A")) & (col.data <= ord("Z"))
    data = jnp.where(upper, col.data + 32, col.data)
    return StringColumn(data, col.offsets, col.validity, col.dtype)


def substring(col: StringColumn, start: int, length: int | None) -> StringColumn:
    """Spark substring semantics: 1-based start, negative = from end."""
    lens = string_lengths(col)
    if start > 0:
        begin = jnp.minimum(jnp.int32(start - 1), lens)
    elif start == 0:
        begin = jnp.zeros_like(lens)
    else:
        begin = jnp.maximum(lens + start, 0)
    if length is None:
        sub_len = lens - begin
    else:
        sub_len = jnp.clip(jnp.int32(length), 0, lens - begin)
    starts = col.offsets[:-1] + begin
    return _substring_gather(col, starts, sub_len)


def _substring_gather(col: StringColumn, src_starts, lengths) -> StringColumn:
    lengths = jnp.where(col.validity, lengths, 0)
    new_offsets = _rebuild_offsets(lengths)
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.searchsorted(new_offsets, pos, side="right").astype(jnp.int32) - 1
    row = jnp.clip(row, 0, col.capacity - 1)
    intra = pos - new_offsets[row]
    src_pos = src_starts[row] + intra
    in_use = pos < new_offsets[-1]
    src_pos = jnp.where(in_use, jnp.clip(src_pos, 0, byte_cap - 1), 0)
    data = jnp.where(in_use, col.data[src_pos], jnp.uint8(0))
    return StringColumn(data, new_offsets, col.validity, col.dtype)


def _match_at(col: StringColumn, needle: bytes, starts):
    """Bool per row: needle matches at byte position `starts` (absolute)."""
    ok = jnp.ones(col.capacity, dtype=jnp.bool_)
    byte_cap = col.byte_capacity
    for j, ch in enumerate(needle):
        p = jnp.clip(starts + j, 0, byte_cap - 1)
        ok = ok & (col.data[p] == jnp.uint8(ch))
    return ok


def str_starts_with(col: StringColumn, prefix: bytes) -> Column:
    from ..types import BOOLEAN
    lens = string_lengths(col)
    ok = (lens >= len(prefix)) & _match_at(col, prefix, col.offsets[:-1])
    return Column(ok, col.validity, BOOLEAN)


def str_ends_with(col: StringColumn, suffix: bytes) -> Column:
    from ..types import BOOLEAN
    lens = string_lengths(col)
    ok = (lens >= len(suffix)) & _match_at(col, suffix,
                                           col.offsets[1:] - len(suffix))
    return Column(ok, col.validity, BOOLEAN)


def str_contains(col: StringColumn, needle: bytes) -> Column:
    """Substring search: needle-length sliding window over the byte buffer,
    segmented to row boundaries. O(bytes * |needle|) VPU work."""
    from ..types import BOOLEAN
    if not needle:
        return Column(jnp.ones(col.capacity, jnp.bool_), col.validity, BOOLEAN)
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    hit = jnp.ones(byte_cap, dtype=jnp.bool_)
    for j, ch in enumerate(needle):
        p = jnp.clip(pos + j, 0, byte_cap - 1)
        hit = hit & (col.data[p] == jnp.uint8(ch))
    # a hit at byte p belongs to row r if p..p+len-1 inside row r's span
    row = jnp.searchsorted(col.offsets, pos, side="right").astype(jnp.int32) - 1
    row = jnp.clip(row, 0, col.capacity - 1)
    inside = (pos + len(needle)) <= col.offsets[row + 1]
    hit = hit & inside
    # segment-max hit per row
    csum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(hit.astype(jnp.int32))])
    per_row = (csum[jnp.minimum(col.offsets[1:], byte_cap)] -
               csum[jnp.minimum(col.offsets[:-1], byte_cap)]) > 0
    return Column(per_row, col.validity, BOOLEAN)


def _row_of_byte(col: StringColumn, pos):
    """Row owning each byte position of `col`'s buffer."""
    row = jnp.searchsorted(col.offsets, pos, side="right").astype(jnp.int32) - 1
    return jnp.clip(row, 0, col.capacity - 1)


def str_trim(col: StringColumn, side: str = "both",
             trim_chars: bytes = b" \t\n\r\x0b\x0c") -> StringColumn:
    """trim/ltrim/rtrim (reference GpuStringTrim, stringFunctions.scala).
    Default trim set matches Spark's whitespace trimming."""
    assert side in ("both", "left", "right")
    lens = string_lengths(col)
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = _row_of_byte(col, pos)
    intra = pos - col.offsets[row]
    in_use = pos < col.offsets[-1]
    is_trim = jnp.zeros(byte_cap, jnp.bool_)
    for ch in trim_chars:
        is_trim = is_trim | (col.data == jnp.uint8(ch))
    non_trim = in_use & ~is_trim
    big = jnp.int32(1 << 30)
    first_non = jax.ops.segment_min(jnp.where(non_trim, intra, big), row,
                                    num_segments=col.capacity)
    last_non = jax.ops.segment_max(jnp.where(non_trim, intra, -1), row,
                                   num_segments=col.capacity)
    lead = jnp.minimum(first_non, lens)
    # segment_max identity is INT_MIN for byte-less rows; clamp to "all
    # trimmed" (end 0) before arithmetic
    end = jnp.clip(last_non + 1, 0, lens)
    if side == "left":
        start, new_len = lead, lens - lead
    elif side == "right":
        start, new_len = jnp.zeros_like(lens), end
    else:
        start, new_len = lead, jnp.maximum(end - lead, 0)
    return _substring_gather(col, col.offsets[:-1] + start, new_len)


def str_pad(col: StringColumn, target: int, pad: bytes,
            side: str) -> StringColumn:
    """lpad/rpad, byte semantics (reference GpuStringLPad/RPad). Rows
    longer than `target` truncate to it; empty pad keeps short rows."""
    from ..columnar.column import bucket_capacity
    assert side in ("left", "right")
    target = max(target, 0)
    lens = string_lengths(col)
    if pad:
        out_lens = jnp.where(col.validity, jnp.int32(target), 0)
    else:
        out_lens = jnp.minimum(lens, target)
    out_lens = jnp.where(col.validity, out_lens, 0)
    new_offsets = _rebuild_offsets(out_lens)
    byte_cap = bucket_capacity(max(col.capacity * max(target, 1), 1))
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_offsets, pos, side="right")
                   .astype(jnp.int32) - 1, 0, col.capacity - 1)
    intra = pos - new_offsets[row]
    in_use = pos < new_offsets[-1]
    rl = lens[row]
    pad_arr = jnp.asarray(bytearray(pad or b"\0"), jnp.uint8)
    lp = max(len(pad), 1)
    if side == "left":
        pad_n = jnp.maximum(jnp.int32(target) - rl, 0) if pad \
            else jnp.zeros_like(rl)
        from_pad = intra < pad_n
        src_intra = intra - pad_n
        pad_idx = intra % lp
    else:
        from_pad = (intra >= rl) if pad else jnp.zeros_like(intra, jnp.bool_)
        src_intra = intra
        pad_idx = (intra - rl) % lp
    pad_byte = pad_arr[jnp.where(from_pad, pad_idx, 0)]
    src_pos = jnp.clip(col.offsets[row] + jnp.maximum(src_intra, 0), 0,
                       col.byte_capacity - 1)
    data = jnp.where(in_use, jnp.where(from_pad, pad_byte,
                                       col.data[src_pos]), jnp.uint8(0))
    return StringColumn(data, new_offsets, col.validity, col.dtype)


def str_repeat(col: StringColumn, n: int) -> StringColumn:
    """repeat(str, n) (reference GpuStringRepeat)."""
    from ..columnar.column import bucket_capacity
    n = max(int(n), 0)
    lens = string_lengths(col)
    out_lens = lens * n
    new_offsets = _rebuild_offsets(out_lens)
    byte_cap = bucket_capacity(max(col.byte_capacity * max(n, 1), 1))
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_offsets, pos, side="right")
                   .astype(jnp.int32) - 1, 0, col.capacity - 1)
    intra = pos - new_offsets[row]
    in_use = pos < new_offsets[-1]
    rl = jnp.maximum(lens[row], 1)
    src = jnp.clip(col.offsets[row] + intra % rl, 0, col.byte_capacity - 1)
    data = jnp.where(in_use, col.data[src], jnp.uint8(0))
    return StringColumn(data, new_offsets, col.validity, col.dtype)


def str_reverse(col: StringColumn) -> StringColumn:
    """reverse(str), byte order (exact for ASCII; multi-byte UTF-8 code
    points are byte-reversed — documented divergence, like the reference's
    early string kernels)."""
    lens = string_lengths(col)
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = _row_of_byte(col, pos)
    intra = pos - col.offsets[row]
    in_use = pos < col.offsets[-1]
    src = jnp.clip(col.offsets[row] + lens[row] - 1 - intra, 0, byte_cap - 1)
    data = jnp.where(in_use, col.data[src], jnp.uint8(0))
    return StringColumn(data, col.offsets, col.validity, col.dtype)


def str_initcap(col: StringColumn) -> StringColumn:
    """initcap: first letter of each whitespace-delimited word uppercase,
    rest lowercase (Spark semantics, ASCII letters)."""
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = _row_of_byte(col, pos)
    at_start = pos == col.offsets[row]
    prev = col.data[jnp.clip(pos - 1, 0, byte_cap - 1)]
    prev_is_space = (prev == ord(" ")) | (prev == ord("\t")) | \
        (prev == ord("\n")) | (prev == ord("\r"))
    word_start = at_start | prev_is_space
    b = col.data
    is_lower = (b >= ord("a")) & (b <= ord("z"))
    is_upper = (b >= ord("A")) & (b <= ord("Z"))
    up = jnp.where(is_lower, b - 32, b)
    low = jnp.where(is_upper, b + 32, b)
    data = jnp.where(word_start, up, low)
    return StringColumn(data, col.offsets, col.validity, col.dtype)


def str_locate(col: StringColumn, needle: bytes, start: int = 1) -> Column:
    """locate/instr/position: 1-based byte index of the first occurrence at
    or after `start` (1-based), 0 if absent (Java String.indexOf
    semantics, which Spark delegates to)."""
    from ..types import INT
    lens = string_lengths(col)
    start0 = max(int(start) - 1, 0)
    if not needle:
        # Java indexOf("", from) = min(max(from,0), len)
        res = jnp.minimum(jnp.int32(start0), lens) + 1
        return Column(res.astype(jnp.int32), col.validity, INT)
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    hit = jnp.ones(byte_cap, dtype=jnp.bool_)
    for j, ch in enumerate(needle):
        p = jnp.clip(pos + j, 0, byte_cap - 1)
        hit = hit & (col.data[p] == jnp.uint8(ch))
    row = _row_of_byte(col, pos)
    intra = pos - col.offsets[row]
    inside = (pos + len(needle)) <= col.offsets[row + 1]
    ok = hit & inside & (intra >= start0) & (pos < col.offsets[-1])
    big = jnp.int32(1 << 30)
    first = jax.ops.segment_min(jnp.where(ok, intra, big), row,
                                num_segments=col.capacity)
    res = jnp.where(first >= big, 0, first + 1)
    return Column(res.astype(jnp.int32), col.validity, INT)


def _needle_has_border(needle: bytes) -> bool:
    return any(needle[:k] == needle[len(needle) - k:]
               for k in range(1, len(needle)))


def select_literal_hits(col: StringColumn, search: bytes):
    """Byte mask of the greedy non-overlapping left-to-right occurrences
    of literal `search` (Java String.split/replace hit set).

    Fast path: a needle with no proper border cannot overlap itself, so
    every raw hit is automatically part of the greedy non-overlapping set.
    Bordered needles (e.g. "aa") run a device while_loop that advances
    per-row cursors hit by hit — exact Java semantics, vectorized across
    rows."""
    ls = len(search)
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = _row_of_byte(col, pos)
    intra = pos - col.offsets[row]
    in_use = pos < col.offsets[-1]
    hit = jnp.ones(byte_cap, dtype=jnp.bool_)
    for j, ch in enumerate(search):
        p = jnp.clip(pos + j, 0, byte_cap - 1)
        hit = hit & (col.data[p] == jnp.uint8(ch))
    hit = hit & in_use & ((pos + ls) <= col.offsets[row + 1])

    if _needle_has_border(search):
        # greedy selection: per-row cursor jumps to the next hit >= cursor
        big = jnp.int32(1 << 30)

        def next_hit(cursor):
            cand = jnp.where(hit & (intra >= cursor[row]), intra, big)
            return jax.ops.segment_min(cand, row,
                                       num_segments=col.capacity)

        def body(carry):
            cursor, sel = carry
            nxt = next_hit(cursor)
            found = nxt < big
            # rows with no further hit scatter out of bounds (dropped) —
            # clipping would collide them onto real byte positions
            sel_pos = jnp.where(found, col.offsets[:-1] + nxt,
                                jnp.int32(byte_cap))
            sel = sel.at[sel_pos].set(True, mode="drop")
            cursor = jnp.where(found, nxt + ls, big)
            return cursor, sel

        def cond(carry):
            cursor, _ = carry
            return jnp.any(cursor < big)

        cursor0 = jnp.zeros(col.capacity, jnp.int32)
        sel0 = jnp.zeros(byte_cap, jnp.bool_)
        _, selected = jax.lax.while_loop(cond, body, (cursor0, sel0))
        return selected & hit
    return hit


def str_replace(col: StringColumn, search: bytes,
                replacement: bytes) -> StringColumn:
    """replace(str, search, replace): non-overlapping left-to-right literal
    replacement (reference GpuStringReplace)."""
    from ..columnar.column import bucket_capacity
    if not search:
        return col
    ls, lr = len(search), len(replacement)
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = _row_of_byte(col, pos)
    in_use = pos < col.offsets[-1]
    selected = select_literal_hits(col, search)

    # emit lengths: 1 per plain byte, lr at a match start, 0 inside a match
    sel_csum = jnp.cumsum(selected.astype(jnp.int32))
    lo = jnp.clip(pos - ls, 0, byte_cap - 1)
    covered_cnt = jnp.where(pos >= 1, sel_csum[jnp.clip(pos - 1, 0, byte_cap - 1)], 0) \
        - jnp.where(pos >= ls, sel_csum[lo], 0)
    covered = (covered_cnt > 0) & ~selected
    emit = jnp.where(in_use, 1, 0)
    emit = jnp.where(selected, lr, emit)
    emit = jnp.where(covered, 0, emit)

    out_lens = jax.ops.segment_sum(emit, row, num_segments=col.capacity)
    out_lens = jnp.where(col.validity, out_lens, 0)
    new_offsets = _rebuild_offsets(out_lens)
    emit_start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(emit, dtype=jnp.int32)])
    out_byte_cap = byte_cap if lr <= ls else \
        bucket_capacity(max((byte_cap // ls + 1) * lr, byte_cap))
    opos = jnp.arange(out_byte_cap, dtype=jnp.int32)
    src = jnp.clip(jnp.searchsorted(emit_start, opos, side="right")
                   .astype(jnp.int32) - 1, 0, byte_cap - 1)
    k = opos - emit_start[src]
    out_in_use = opos < new_offsets[-1]
    repl_arr = jnp.asarray(bytearray(replacement or b"\0"), jnp.uint8)
    from_repl = selected[src]
    byte = jnp.where(from_repl, repl_arr[jnp.clip(k, 0, max(lr - 1, 0))],
                     col.data[src])
    data = jnp.where(out_in_use, byte, jnp.uint8(0))
    return StringColumn(data, new_offsets, col.validity, col.dtype)


def str_concat_pair(a: StringColumn, b: StringColumn) -> StringColumn:
    """concat(a, b): null-intolerant pairwise concatenation."""
    from ..columnar.column import bucket_capacity
    la, lb = string_lengths(a), string_lengths(b)
    valid = a.validity & b.validity
    out_lens = jnp.where(valid, la + lb, 0)
    new_offsets = _rebuild_offsets(out_lens)
    byte_cap = bucket_capacity(max(a.byte_capacity + b.byte_capacity, 1))
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_offsets, pos, side="right")
                   .astype(jnp.int32) - 1, 0, a.capacity - 1)
    intra = pos - new_offsets[row]
    in_use = pos < new_offsets[-1]
    from_a = intra < la[row]
    pa = jnp.clip(a.offsets[row] + intra, 0, a.byte_capacity - 1)
    pb = jnp.clip(b.offsets[row] + intra - la[row], 0, b.byte_capacity - 1)
    data = jnp.where(in_use, jnp.where(from_a, a.data[pa], b.data[pb]),
                     jnp.uint8(0))
    return StringColumn(data, new_offsets, valid, a.dtype)


def str_concat_ws(sep: bytes, cols) -> StringColumn:
    """concat_ws(sep, c1..ck): skips NULL children entirely; separator only
    between present children; never null (Spark semantics)."""
    from ..columnar.column import bucket_capacity
    k = len(cols)
    cap = cols[0].capacity
    lsep = len(sep)
    lens = [jnp.where(c.validity, string_lengths(c), 0) for c in cols]
    present = [c.validity for c in cols]
    # segment table per row: [c0, sep, c1, sep, c2, ...] (2k-1 segments)
    seg_lens = [lens[0] * present[0]]
    any_before = present[0]
    for i in range(1, k):
        seg_lens.append(jnp.where(any_before & present[i],
                                  jnp.int32(lsep), 0))
        seg_lens.append(jnp.where(present[i], lens[i], 0))
        any_before = any_before | present[i]
    seg = jnp.stack(seg_lens, axis=1)  # (cap, 2k-1)
    seg_ends = jnp.cumsum(seg, axis=1)
    out_lens = seg_ends[:, -1]
    new_offsets = _rebuild_offsets(out_lens)
    total_in = sum(c.byte_capacity for c in cols) + cap * lsep * (k - 1)
    byte_cap = bucket_capacity(max(total_in, 1))
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_offsets, pos, side="right")
                   .astype(jnp.int32) - 1, 0, cap - 1)
    intra = pos - new_offsets[row]
    in_use = pos < new_offsets[-1]
    # segment index: count of segment ends <= intra
    seg_idx = jnp.sum(intra[:, None] >= seg_ends[row], axis=1)
    seg_idx = jnp.clip(seg_idx, 0, 2 * k - 2)
    seg_start = seg_ends[row, seg_idx] - seg[row, seg_idx]
    local = intra - seg_start
    sep_arr = jnp.asarray(bytearray(sep or b"\0"), jnp.uint8)
    byte = sep_arr[jnp.clip(local, 0, max(lsep - 1, 0))]
    for i, c in enumerate(cols):
        pi = jnp.clip(c.offsets[row] + local, 0, c.byte_capacity - 1)
        byte = jnp.where(seg_idx == 2 * i, c.data[pi], byte)
    data = jnp.where(in_use, byte, jnp.uint8(0))
    valid = jnp.ones(cap, jnp.bool_)
    return StringColumn(data, new_offsets, valid, cols[0].dtype)


def str_translate(col: StringColumn, from_str: bytes,
                  to_str: bytes) -> StringColumn:
    """translate(str, from, to): per-byte mapping; positions of `from`
    beyond len(to) delete the byte (ASCII semantics; first occurrence in
    `from` wins, like Java)."""
    import numpy as np
    lut = np.arange(256, dtype=np.uint8)
    keep = np.ones(256, dtype=bool)
    seen = set()
    for i, ch in enumerate(from_str):
        if ch in seen:
            continue
        seen.add(ch)
        if i < len(to_str):
            lut[ch] = to_str[i]
        else:
            keep[ch] = False
    lut_d = jnp.asarray(lut)
    keep_d = jnp.asarray(keep)
    byte_cap = col.byte_capacity
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = _row_of_byte(col, pos)
    in_use = pos < col.offsets[-1]
    emit = jnp.where(in_use & keep_d[col.data], 1, 0)
    out_lens = jax.ops.segment_sum(emit, row, num_segments=col.capacity)
    out_lens = jnp.where(col.validity, out_lens, 0)
    new_offsets = _rebuild_offsets(out_lens)
    emit_start = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                  jnp.cumsum(emit, dtype=jnp.int32)])
    opos = jnp.arange(byte_cap, dtype=jnp.int32)
    src = jnp.clip(jnp.searchsorted(emit_start, opos, side="right")
                   .astype(jnp.int32) - 1, 0, byte_cap - 1)
    out_in_use = opos < new_offsets[-1]
    data = jnp.where(out_in_use, lut_d[col.data[src]], jnp.uint8(0))
    return StringColumn(data, new_offsets, col.validity, col.dtype)


def str_ascii(col: StringColumn) -> Column:
    """ascii(str): code of the first byte, 0 for empty (Spark: first
    character's codepoint; exact for ASCII)."""
    from ..types import INT
    lens = string_lengths(col)
    first = col.data[jnp.clip(col.offsets[:-1], 0, col.byte_capacity - 1)]
    res = jnp.where(lens > 0, first.astype(jnp.int32), 0)
    return Column(res, col.validity, INT)


def str_chr(codes: Column) -> StringColumn:
    """chr(n): 1-byte string from code n % 256; empty for n <= 0
    (Spark/Java Chr semantics for the ASCII range)."""
    from ..columnar.column import bucket_capacity
    from ..types import StringType
    cap = codes.capacity
    n = codes.data.astype(jnp.int64)
    code = (n % 256).astype(jnp.int32)
    out_lens = jnp.where(codes.validity & (n > 0) & (code > 0), 1, 0)
    out_lens = out_lens.astype(jnp.int32)
    new_offsets = _rebuild_offsets(out_lens)
    byte_cap = bucket_capacity(max(cap, 1))
    pos = jnp.arange(byte_cap, dtype=jnp.int32)
    row = jnp.clip(jnp.searchsorted(new_offsets, pos, side="right")
                   .astype(jnp.int32) - 1, 0, cap - 1)
    in_use = pos < new_offsets[-1]
    data = jnp.where(in_use, code[row].astype(jnp.uint8), jnp.uint8(0))
    return StringColumn(data, new_offsets, codes.validity, StringType())


def string_compare_cols(a: StringColumn, b: StringColumn):
    """Row-wise lexicographic byte compare -> int32 sign (-1/0/1).

    Sequential fold per row expressed as a device while_loop over byte
    positions, vectorized across rows; trip count is the max common prefix
    length in the batch (device scalar — no recompile).
    """
    la = string_lengths(a)
    lb = string_lengths(b)
    min_len = jnp.minimum(la, lb)
    max_t = jnp.max(min_len)
    sa, sb = a.offsets[:-1], b.offsets[:-1]

    def body(carry):
        t, res = carry
        pa = jnp.clip(sa + t, 0, a.byte_capacity - 1)
        pb = jnp.clip(sb + t, 0, b.byte_capacity - 1)
        ba = a.data[pa].astype(jnp.int32)
        bb = b.data[pb].astype(jnp.int32)
        active = (res == 0) & (t < min_len)
        diff = jnp.sign(ba - bb)
        return t + 1, jnp.where(active, diff, res)

    res0 = jnp.zeros(a.capacity, jnp.int32)
    _, res = jax.lax.while_loop(lambda c: c[0] < max_t, body,
                                (jnp.int32(0), res0))
    return jnp.where(res == 0, jnp.sign(la - lb), res)


def string_equal(a: StringColumn, b: StringColumn) -> Column:
    """Row-wise string equality via length check + prefix-sum byte compare."""
    from ..types import BOOLEAN
    la = string_lengths(a)
    lb = string_lengths(b)
    same_len = la == lb
    # compare bytes positionally: for each byte of a's row, compare with b's
    pos = jnp.arange(a.byte_capacity, dtype=jnp.int32)
    row = jnp.searchsorted(a.offsets, pos, side="right").astype(jnp.int32) - 1
    row = jnp.clip(row, 0, a.capacity - 1)
    intra = pos - a.offsets[row]
    b_pos = jnp.clip(b.offsets[row] + intra, 0, b.byte_capacity - 1)
    in_use = pos < a.offsets[-1]
    neq = in_use & (a.data != b.data[jnp.where(in_use, b_pos, 0)])
    csum = jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(neq.astype(jnp.int32))])
    any_neq = (csum[jnp.minimum(a.offsets[1:], a.byte_capacity)] -
               csum[jnp.minimum(a.offsets[:-1], a.byte_capacity)]) > 0
    eq = same_len & ~any_neq
    return Column(eq, a.validity & b.validity, BOOLEAN)


def string_to_padded(col: StringColumn, width: int):
    """(lengths (cap,), bytes (cap, width)): fixed-width row-major encoding
    for collective exchange (ICI all-to-all needs rectangular tensors; this
    is the TPU analog of JCudfSerialization's framed host buffers).
    Truncates rows longer than `width` — callers size width from host-known
    max length."""
    cap = col.capacity
    lengths = jnp.minimum(string_lengths(col), width)
    starts = col.offsets[:cap]
    j = jnp.arange(width, dtype=jnp.int32)
    pos = starts[:, None] + j[None, :]
    in_str = j[None, :] < lengths[:, None]
    safe = jnp.where(in_str, jnp.clip(pos, 0, col.byte_capacity - 1), 0)
    padded = jnp.where(in_str, col.data[safe], jnp.uint8(0))
    return lengths, padded


def string_from_padded(lengths, padded, validity,
                       dtype=None) -> StringColumn:
    """Inverse of string_to_padded: rebuild (offsets, bytes) columns.

    Byte capacity is the static worst case cap*width (callers keep width
    small); unused tail stays zero.
    """
    from ..columnar.column import bucket_capacity
    from ..types import StringType
    cap, width = padded.shape
    lengths = jnp.where(validity, lengths, 0)
    offsets = _rebuild_offsets(lengths)
    byte_cap = bucket_capacity(max(cap * width, 1))
    # row r's bytes start at r * width of the padded table laid flat: the
    # marks-and-prefix-sum form, no per-byte search for the row
    data = _bytes_by_row_marks(
        padded.reshape(-1), jnp.arange(cap, dtype=jnp.int32) * width,
        offsets, byte_cap)
    return StringColumn(data, offsets, validity, dtype or StringType())
