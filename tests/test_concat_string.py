"""`concat_string` against a plain row-by-row reference.

The coalesce's primitive for a scanned string column finds the source of
every output byte from row-start marks and one prefix sum over the two
inputs' bytes laid end to end, and moves the per-row lanes as two blocks
(ISSUE 35); the reference below walks the rows in Python. Each case is a way
the marks or the blocks could go wrong where the per-byte search and the
per-row gathers could not.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.columnar import StringColumn
from spark_rapids_tpu.columnar.column import bucket_capacity
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
    `gather` of the rows' width is a per-row index walk back. One scatter
    places the row marks, one gather reads the bytes."""
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
    assert text.count('"stablehlo.scatter"(') == 1
    assert text.count('"stablehlo.gather"(') == 1
