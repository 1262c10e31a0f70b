"""`gather_string` against a plain row-by-row reference.

The kernel finds the source of every output byte from row-boundary marks and
one prefix sum (ISSUE 32); the reference below walks the rows in Python. Each
case is a way the marks could go wrong where the per-byte search could not:
rows that share a start (empty ones), starts at or past the byte bucket, a
source that goes backwards (unsorted or repeated indices).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.columnar import StringColumn
from spark_rapids_tpu.columnar.encoded import (
    NULL_CODE, DictionaryColumn, materialize_column,
)
from spark_rapids_tpu.ops.basic import gather_column
from spark_rapids_tpu.ops.strings import gather_string


def reference(rows, indices, out_valid, byte_cap):
    """(data, offsets, total): the gathered rows laid end to end, cut at the
    byte bucket, zeros behind them."""
    out = b"".join(rows[i] if ok else b"" for i, ok in zip(indices, out_valid))
    lengths = [len(rows[i]) if ok else 0 for i, ok in zip(indices, out_valid)]
    offsets = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int32)
    data = np.zeros(byte_cap, np.uint8)
    kept = out[:byte_cap]
    data[:len(kept)] = np.frombuffer(kept, np.uint8)
    return data, offsets, len(out)


ROWS = [b"apple", b"", b"kiwi", b"", b"", b"fig", b"banana", b"x", b""]

#: name: (source rows, indices, out_valid or None for all True, byte bucket
#: or None for the source's)
CASES = {
    "empty_between_nonempty": (ROWS, [0, 1, 2, 3, 4, 5], None, None),
    "leading_and_trailing_empty": (ROWS, [1, 3, 4, 6, 0, 8, 1], None, None),
    "all_rows_empty": (ROWS, [1, 3, 4, 8, 1, 1], None, None),
    "invalid_rows": (ROWS, [0, 2, 5, 6, 7, 0],
                     [True, False, True, False, False, True], None),
    "all_rows_invalid": (ROWS, [0, 2, 5], [False, False, False], None),
    "repeated_indices": (ROWS, [6, 6, 6, 0, 0, 6, 2, 2], None, 256),
    "unsorted_indices": (ROWS, [7, 6, 5, 2, 0, 6, 2, 7, 0], None, 256),
    "one_row": (ROWS, [6], None, None),
    "one_empty_row": (ROWS, [1], None, None),
    "bucket_larger_than_need": (ROWS, [0, 2, 5], None, 1024),
    "bucket_smaller_than_need": (ROWS, [6, 0, 6, 2, 6, 0, 5, 6], None, 16),
    "bucket_ends_on_a_row_start": (ROWS, [2, 2, 2, 2, 0, 5], None, 16),
    "bucket_ends_before_empty_rows": (ROWS, [2, 2, 2, 2, 1, 3, 0], None, 16),
    "source_runs_backwards": ([b"ab", b"cde", b"f", b"ghij"], [3, 2, 1, 0],
                              None, None),
}


def _run(rows, indices, out_valid, byte_cap, jit):
    col = StringColumn.from_pylist(rows)
    n = len(indices)
    ok = [True] * n if out_valid is None else out_valid
    fn = gather_string
    if jit:
        fn = jax.jit(gather_string, static_argnums=(3,))
    got = fn(col, jnp.asarray(np.array(indices, np.int32)),
             jnp.asarray(np.array(ok, np.bool_)), byte_cap)
    cap = byte_cap or col.byte_capacity
    return got, reference(rows, indices, ok, cap), ok, cap


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_gather_string_equals_the_row_by_row_reference(case, jit):
    rows, indices, out_valid, byte_cap = CASES[case]
    got, (data, offsets, total), ok, cap = _run(rows, indices, out_valid,
                                                byte_cap, jit)
    assert got.data.shape == (cap,) and got.data.dtype == jnp.uint8
    kept = min(total, cap)
    assert np.array_equal(np.asarray(got.data)[:kept], data[:kept])
    assert not np.asarray(got.data)[kept:].any()        # the zero tail
    assert np.array_equal(np.asarray(got.offsets), offsets)
    assert np.array_equal(np.asarray(got.validity), np.array(ok))


@pytest.mark.parametrize("seed", range(6))
def test_gather_string_on_random_rows(seed):
    """Empty rows, invalid rows, repeats and a bucket on either side of the
    need, all at once."""
    rng = np.random.default_rng(seed)
    rows = [bytes(rng.integers(1, 255, int(n)).astype(np.uint8))
            for n in rng.integers(0, 9, 37) * (rng.random(37) > 0.3)]
    n = int(rng.integers(1, 90))
    indices = rng.integers(0, len(rows), n).tolist()
    ok = (rng.random(n) > 0.2).tolist()
    byte_cap = int(rng.choice([32, 128, 512]))
    got, (data, offsets, _), _, _ = _run(rows, indices, ok, byte_cap, True)
    assert np.array_equal(np.asarray(got.data), data)
    assert np.array_equal(np.asarray(got.offsets), offsets)


def test_gather_column_masks_out_of_range_indices_of_a_string_column():
    """-1 and past-the-end indices come out invalid and zero-length: the
    rows beside them keep their bytes."""
    col = StringColumn.from_pylist(ROWS)
    idx = jnp.asarray(np.array([6, -1, 0, col.capacity, 5], np.int32))
    got = gather_column(col, idx)
    assert got.to_pylist(5) == ["banana", None, "apple", None, "fig"]
    assert np.asarray(got.offsets).tolist() == [0, 6, 6, 11, 11, 14]


def test_a_dictionary_decode_with_null_codes_and_an_empty_entry():
    """`materialize_column` is a gather of the dictionary by the code lane:
    NULL_CODE rows decode to invalid zero-length rows, and code 1 is the
    empty string."""
    entries = [b"PROMO BRUSHED TIN", b"", b"STANDARD", b"ECONOMY PLATED"]
    shared = StringColumn.from_pylist(entries)
    codes = np.array([2, NULL_CODE, 1, 0, 0, 1, NULL_CODE, 3, 2, 1], np.int32)
    col = DictionaryColumn(jnp.asarray(codes), shared.data, shared.offsets,
                           jnp.asarray(codes != NULL_CODE))
    out = materialize_column(col)
    want = [None if c == NULL_CODE else entries[c].decode() for c in codes]
    assert isinstance(out, StringColumn)
    assert out.to_pylist(len(codes)) == want
    data, offsets, total = reference(
        entries, np.maximum(codes, 0), codes != NULL_CODE, out.byte_capacity)
    assert np.array_equal(np.asarray(out.data), data)
    assert np.array_equal(np.asarray(out.offsets), offsets)
    assert int(out.offsets[-1]) == total


# -- the structural guard -------------------------------------------------------

def _lowered_for_the_chip(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("rows", [262144, 131072],
                         ids=["dictionary_decode", "join_emit"])
def test_gather_string_lowers_without_a_loop_at_q14s_shapes(rows):
    """4,194,304 output bytes over 262,144 rows (the decode of `p_type`) and
    over 131,072 (the join's emit): a `while` in the lowered text is the
    per-byte binary search back, 1.11 s of a Q14 query's 1.73 s on the chip
    before ISSUE 32. One scatter places the row marks."""
    byte_cap = 4194304

    def gather(data, offsets, validity, indices, out_valid):
        out = gather_string(StringColumn(data, offsets, validity), indices,
                            out_valid, byte_cap)
        return out.data, out.offsets

    S = jax.ShapeDtypeStruct
    text = _lowered_for_the_chip(
        gather, S((byte_cap,), jnp.uint8), S((rows + 1,), jnp.int32),
        S((rows,), jnp.bool_), S((rows,), jnp.int32), S((rows,), jnp.bool_))
    assert "while" not in text
    assert text.count('"stablehlo.scatter"(') == 1
    # the guard can see what it guards against: the search lowers to a loop
    searched = _lowered_for_the_chip(
        lambda offsets: jnp.searchsorted(
            offsets, jnp.arange(byte_cap, dtype=jnp.int32), side="right"),
        S((rows + 1,), jnp.int32))
    assert "while" in searched
