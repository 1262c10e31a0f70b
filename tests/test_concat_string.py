"""`concat_string` against a plain row-by-row reference.

The coalesce's primitive for a string column moves each input's active
bytes, offsets and validity as one contiguous block at a traced offset
(ISSUE 39; row-start marks and a per-byte gather before it, ISSUE 35); the
reference below walks the rows in Python. Each case is a way the blocks could
go wrong where a per-row walk could not: padding that holds garbage, an
offsets lane that does not start at 0, a total that fills the byte bucket.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.columnar import StringColumn
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.columnar.encoded import (DictionaryColumn,
                                               materialize_column)
from spark_rapids_tpu.ops.basic import concat_columns
from spark_rapids_tpu.ops.strings import concat_string

A = ["apple", "", None, "kiwi", "", "é中", None, "fig"]
B = ["", "banana", None, "", "x", "~~~~~~~~~~~~", ""]

#: name: (rows of a, rows of b, active rows of a, of b, output capacity or
#: None for the bucket of the total)
CASES = {
    "all_of_both": (A, B, len(A), len(B), None),
    "a_prefix_only": (A, B, 3, len(B), None),
    "b_prefix_only": (A, B, len(A), 2, None),
    "nothing_of_a": (A, B, 0, len(B), None),
    "nothing_of_b": (A, B, len(A), 0, None),
    "nothing_at_all": (A, B, 0, 0, None),
    "a_ends_in_empty_rows": (A, B, 5, 4, None),
    "b_starts_with_empty_rows": (["p", "q"], ["", "", "", "r"], 2, 4, None),
    "only_empty_and_null_rows": (["", None, ""], [None, "", ""], 3, 3, None),
    "wide_output": (A, B, len(A), len(B), 1024),
    "one_row_each": (["left"], ["right"], 1, 1, None),
}


def reference(a, b, a_rows, b_rows):
    return list(a[:a_rows]) + list(b[:b_rows])


def _run(a, b, a_rows, b_rows, cap, jit):
    ca, cb = StringColumn.from_pylist(a), StringColumn.from_pylist(b)
    cap = cap or bucket_capacity(max(a_rows + b_rows, 1))
    fn = concat_string
    if jit:
        fn = jax.jit(concat_string, static_argnums=(4,))
    return fn(ca, cb, jnp.int32(a_rows), jnp.int32(b_rows), cap), ca, cb


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_concat_string_equals_the_row_by_row_reference(case, jit):
    a, b, a_rows, b_rows, cap = CASES[case]
    out, ca, cb = _run(a, b, a_rows, b_rows, cap, jit)
    want = reference(a, b, a_rows, b_rows)
    n = a_rows + b_rows
    assert out.to_pylist(n) == want
    assert out.byte_capacity == ca.byte_capacity + cb.byte_capacity
    data = b"".join((s or "").encode() for s in want)
    got = np.asarray(out.data)
    assert got[:len(data)].tobytes() == data
    assert not got[len(data):].any()                   # zeros behind them
    offsets = np.asarray(out.offsets)
    assert offsets[0] == 0 and offsets[n] == len(data)
    assert (offsets[n:] == len(data)).all()            # empty past the rows
    assert not np.asarray(out.validity)[n:].any()


def _raw(data, offsets, validity):
    """A StringColumn exactly as given: padding rows and bytes as they are."""
    return StringColumn(jnp.asarray(np.frombuffer(data, np.uint8)),
                        jnp.asarray(offsets, jnp.int32),
                        jnp.asarray(validity, jnp.bool_))


def _raw_rows(data, offsets, validity, rows):
    return [data[offsets[i]:offsets[i + 1]].decode() if validity[i] else None
            for i in range(rows)]


G = 0xEE  # garbage byte

#: name: ((data, offsets, validity) of a, of b, active rows of a, of b,
#: output byte bucket or None for the sum of the inputs')
RAW_CASES = {
    # bytes past offsets[rows] are not zero, offsets past rows do not repeat
    # and padding rows claim to be valid
    "garbage_padding": (
        (b"abcde" + bytes([G] * 11), [0, 2, 2, 5, 9, 7, 12, 3, 16],
         [True, False, True, True, True, False, True, True]),
        (b"xyz" + bytes([G] * 5), [0, 1, 3, 8, 2], [True, True, True, True]),
        3, 2, None),
    # offsets[0] > 0: the bytes before it belong to no row
    "offsets_start_past_zero": (
        (bytes([G] * 4) + b"hello" + b"!" + bytes([G] * 6),
         [4, 9, 9, 10, 10], [True, False, True, False]),
        (bytes([G]) + b"wor" + b"ld" + bytes([G] * 2), [1, 4, 6, 6, 6],
         [True, True, False, False]),
        3, 2, None),
    "start_past_zero_and_garbage_both": (
        (bytes([G] * 3) + b"pq" + bytes([G] * 3), [3, 4, 5, 8, 1],
         [True, True, True, True]),
        (bytes([G] * 2) + b"rst" + bytes([G] * 3), [2, 2, 5, 7, 0],
         [False, True, True, True]),
        2, 2, None),
    # a_bytes + b_bytes is the whole default bucket: no byte of b is lost
    "total_fills_the_default_bucket": (
        (b"abcdefgh", [0, 5, 8], [True, True]),
        (b"ijklmnop", [0, 8, 8], [True, False]),
        2, 2, None),
    # the active rows fill a byte bucket narrower than the inputs' sum
    "total_fills_a_narrow_bucket": (
        (b"abcde" + bytes([G] * 3), [0, 3, 5, 8], [True, True, True]),
        (b"fgh" + bytes([G] * 5), [0, 1, 3, 6], [True, True, False]),
        2, 2, 8),
    "nothing_of_either_with_garbage": (
        (bytes([G] * 8), [2, 5, 8], [True, True]),
        (bytes([G] * 8), [1, 3, 8], [True, True]),
        0, 0, None),
}


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", sorted(RAW_CASES))
def test_concat_string_ignores_what_lies_outside_the_active_rows(case, jit):
    ra, rb, a_rows, b_rows, byte_cap = RAW_CASES[case]
    ca, cb = _raw(*ra), _raw(*rb)
    n = a_rows + b_rows
    cap = bucket_capacity(max(n, 1))
    fn = concat_string
    if jit:
        fn = jax.jit(concat_string, static_argnums=(4, 5))
    out = fn(ca, cb, jnp.int32(a_rows), jnp.int32(b_rows), cap, byte_cap)
    want = _raw_rows(*ra, a_rows) + _raw_rows(*rb, b_rows)
    assert out.to_pylist(n) == want
    assert out.byte_capacity == (byte_cap or
                                 ca.byte_capacity + cb.byte_capacity)
    data = (ra[0][ra[1][0]:ra[1][a_rows]] + rb[0][rb[1][0]:rb[1][b_rows]])
    got = np.asarray(out.data)
    assert got[:len(data)].tobytes() == data
    assert not got[len(data):].any()                   # zeros behind them
    offsets = np.asarray(out.offsets)
    assert offsets[0] == 0 and (offsets[n:] == len(data)).all()
    assert not np.asarray(out.validity)[n:].any()


@pytest.mark.parametrize("seed", range(6))
def test_concat_string_on_random_rows_and_through_concat_columns(seed):
    rng = np.random.default_rng(seed)

    def rows(n):
        return [None if rng.random() < 0.2 else
                "".join(rng.choice(list("abé中"), rng.integers(0, 9)))
                for _ in range(n)]

    a, b = rows(int(rng.integers(1, 300))), rows(int(rng.integers(1, 300)))
    a_rows = int(rng.integers(0, len(a) + 1))
    b_rows = int(rng.integers(0, len(b) + 1))
    ca, cb = StringColumn.from_pylist(a), StringColumn.from_pylist(b)
    for cap in (bucket_capacity(max(a_rows + b_rows, 1)),
                bucket_capacity(ca.capacity + cb.capacity)):
        out = concat_columns(ca, cb, jnp.int32(a_rows), jnp.int32(b_rows),
                             cap)
        assert out.to_pylist(a_rows + b_rows) == \
            reference(a, b, a_rows, b_rows)


def test_a_tree_of_concats_keeps_every_row():
    """The coalesce's shape: pairs, then pairs of pairs."""
    leaves = [[f"r{i}_{j}" * (j % 3) for j in range(5 + i)] for i in range(4)]
    cols = [(StringColumn.from_pylist(rows), len(rows)) for rows in leaves]
    while len(cols) > 1:
        nxt = []
        for (x, nx), (y, ny) in zip(cols[::2], cols[1::2]):
            cap = bucket_capacity(nx + ny)
            nxt.append((concat_string(x, y, jnp.int32(nx), jnp.int32(ny),
                                      cap), nx + ny))
        cols = nxt
    (out, n), = cols
    assert out.to_pylist(n) == [r for rows in leaves for r in rows]


def test_a_32_leaf_tree_of_decoded_char1_columns():
    """Q1's coalesce at a small scale: 32 batches of a decoded CHAR(1)
    dictionary column (codes of "A", "N", "R", some null, the last batch
    short), concatenated pairwise five levels deep as the exact lane does."""
    rng = np.random.default_rng(39)
    words = ["A", "N", "R"]
    dictionary = StringColumn.from_pylist(words)
    cap = 64
    cols, want = [], []
    for i in range(32):
        rows = cap if i < 31 else 23
        codes = rng.integers(0, len(words), cap).astype(np.int32)
        valid = (rng.random(cap) > 0.1) & (np.arange(cap) < rows)
        codes[~valid] = -1
        enc = DictionaryColumn(jnp.asarray(codes), dictionary.data,
                               dictionary.offsets, jnp.asarray(valid))
        cols.append((materialize_column(enc), rows))
        want += [words[c] if v else None
                 for c, v in zip(codes[:rows], valid[:rows])]
    while len(cols) > 1:
        cols = [(concat_columns(x, y, jnp.int32(nx), jnp.int32(ny),
                                bucket_capacity(nx + ny)), nx + ny)
                for (x, nx), (y, ny) in zip(cols[::2], cols[1::2])]
    (out, n), = cols
    assert n == 31 * cap + 23
    assert out.to_pylist(n) == want


def _lowered_for_the_chip(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("rows", [262144, 4194304],
                         ids=["first_level", "top_level"])
def test_concat_string_lowers_without_a_loop_at_q1s_shapes(rows):
    """A pair of the coalesce at TPC-H Q1's shapes, a CHAR(1) column: two
    262,144-row batches at the tree's first level, two 4,194,304-row halves
    at its top. A `while` in the lowered text is the per-byte binary search
    back (52% of a Q1 query's device time on the chip before ISSUE 35); a
    scatter or a gather is the row-start marks and the per-byte gather back
    (38% of it before ISSUE 39): the bytes and lanes move as blocks."""
    def concat(a_data, a_off, a_valid, b_data, b_off, b_valid, a_rows, b_rows):
        out = concat_string(StringColumn(a_data, a_off, a_valid),
                            StringColumn(b_data, b_off, b_valid),
                            a_rows, b_rows, 2 * rows)
        return out.data, out.offsets, out.validity

    S = jax.ShapeDtypeStruct
    side = (S((rows,), jnp.uint8), S((rows + 1,), jnp.int32),
            S((rows,), jnp.bool_))
    text = _lowered_for_the_chip(concat, *side, *side, S((), jnp.int32),
                                 S((), jnp.int32))
    assert "while" not in text
    assert "stablehlo.scatter" not in text
    assert "stablehlo.gather" not in text
