"""Columnar substrate tests: round-trips, padding invariants, gather/compact/
concat kernels (the engine's copy_if/gather — reference cuDF L6 analog)."""

import numpy as np
import jax.numpy as jnp
import pytest

from spark_rapids_tpu.types import (
    BOOLEAN, DATE, DOUBLE, INT, LONG, STRING, DecimalType, Schema,
)
from spark_rapids_tpu.columnar import (
    Column, ColumnarBatch, StringColumn, bucket_capacity,
)
from spark_rapids_tpu.columnar.column import Decimal128Column
from spark_rapids_tpu.columnar.encoded import NULL_CODE, DictionaryColumn
from spark_rapids_tpu.ops.basic import (
    compact_columns, concat_columns, gather_column, slice_rows,
)


def make_batch():
    return ColumnarBatch.from_pydict(
        {
            "a": [1, 2, None, 4, 5],
            "b": [1.5, None, 3.5, -0.0, 2.25],
            "s": ["apple", None, "banana", "", "cherry"],
        },
        Schema.of(a=INT, b=DOUBLE, s=STRING),
    )


def test_roundtrip():
    b = make_batch()
    assert b.num_rows_host == 5
    assert b.capacity == 128
    d = b.to_pydict()
    assert d["a"] == [1, 2, None, 4, 5]
    assert d["b"] == [1.5, None, 3.5, -0.0, 2.25]
    assert d["s"] == ["apple", None, "banana", "", "cherry"]


def test_arrow_roundtrip():
    import pyarrow as pa
    t = pa.table({
        "x": pa.array([10, None, 30], pa.int64()),
        "y": pa.array(["a", "bb", None], pa.string()),
    })
    b = ColumnarBatch.from_arrow(t)
    t2 = b.to_arrow()
    assert t2.column("x").to_pylist() == [10, None, 30]
    assert t2.column("y").to_pylist() == ["a", "bb", None]


def test_gather_fixed():
    b = make_batch()
    idx = jnp.asarray(np.array([4, 0, 2] + [0] * 125, np.int32))
    valid = jnp.asarray(np.array([True] * 3 + [False] * 125))
    g = gather_column(b.column("a"), idx, valid)
    assert g.to_pylist(3) == [5, 1, None]


def test_gather_string():
    b = make_batch()
    idx = jnp.asarray(np.array([2, 0, 3, 1] + [0] * 124, np.int32))
    valid = jnp.asarray(np.array([True] * 4 + [False] * 124))
    g = gather_column(b.column("s"), idx, valid)
    assert g.to_pylist(4) == ["banana", "apple", "", None]


def test_compact():
    b = make_batch()
    keep = jnp.asarray(np.array([True, False, True, False, True] + [False] * 123))
    cols, n = compact_columns(b.columns, keep, b.num_rows)
    assert int(n) == 3
    assert cols[0].to_pylist(3) == [1, None, 5]
    assert cols[2].to_pylist(3) == ["apple", "banana", "cherry"]


def test_concat():
    a = Column.from_pylist([1, None, 3], INT)
    b = Column.from_pylist([7, 8], INT)
    out = concat_columns(a, b, jnp.int32(3), jnp.int32(2), 256)
    assert out.to_pylist(5) == [1, None, 3, 7, 8]


# -- concat_columns' fixed-width lanes are block moves at a traced offset:
# the cases below are the ways a block move goes wrong where a clipped
# gather could not (a clamped start, a longer input than the output, an
# input's padding landing in the result)

#: (a.capacity, b.capacity, a_rows, b_rows): out_capacity is the bucket of
#: the row total (concat_batches' exact lane) unless given
_CONCAT_SHAPES = [
    (256, 128, 0, 0, None),        # out 128 < a.capacity
    (256, 128, 0, 128, None),
    (256, 128, 1, 0, None),
    (256, 128, 1, 128, None),
    (256, 128, 256, 0, None),
    (256, 128, 256, 128, None),    # both full: out 512
    (128, 512, 0, 512, None),
    (128, 512, 1, 100, None),      # out 128 < b.capacity, < a_rows + b.cap
    (128, 512, 128, 0, None),      # a fills out; b's start would clamp
    (128, 512, 128, 512, None),
    (512, 512, 100, 90, None),     # sparse pair: out 256 < either capacity
    (512, 512, 1, 127, None),      # out 128, a_rows + b.capacity = 513
    (1024, 128, 130, 128, None),   # out 512 < a.capacity, b full
    (256, 128, 37, 53, 512),       # device lane: bucket of the capacities
]


def _poisoned_lane(rng, np_dtype, cap):
    if np_dtype == np.bool_:
        return rng.integers(0, 2, cap).astype(np.bool_)
    if np.issubdtype(np_dtype, np.floating):
        return rng.standard_normal(cap).astype(np_dtype) + 3.0
    lane = rng.integers(1, 1 << 30, cap).astype(np_dtype)
    return np.where(rng.integers(0, 2, cap) == 1, lane, -lane)


def _concat_side(kind, rng, cap, shared):
    """One input column with every lane random over the WHOLE capacity: the
    padding past its active rows is garbage (True validity, non-zero data)
    and nulls fall among the active rows."""
    valid = jnp.asarray(rng.integers(0, 4, cap) > 0)
    if kind == "decimal38":
        limbs = tuple(
            Column(jnp.asarray(_poisoned_lane(rng, np.int64, cap)),
                   jnp.asarray(rng.integers(0, 4, cap) > 0), LONG)
            for _ in range(2))
        return Decimal128Column(limbs, valid, DecimalType(38, 4))
    if kind == "dictionary":
        codes = rng.integers(0, 3, cap).astype(np.int32)
        return DictionaryColumn(jnp.asarray(codes), shared.data,
                                shared.offsets, valid)
    dtype, np_dtype = {"int": (INT, np.int32), "long": (LONG, np.int64),
                       "double": (DOUBLE, np.float64),
                       "boolean": (BOOLEAN, np.bool_),
                       "date": (DATE, np.int32)}[kind]
    return Column(jnp.asarray(_poisoned_lane(rng, np_dtype, cap)), valid,
                  dtype)


def _concat_lanes(col):
    """{lane name: host array} of every fixed-width lane of a column."""
    if isinstance(col, Decimal128Column):
        lanes = {"validity": col.validity, "hi": col.hi.data,
                 "lo": col.lo.data, "hi_validity": col.hi.validity,
                 "lo_validity": col.lo.validity}
    elif isinstance(col, DictionaryColumn):
        lanes = {"validity": col.validity, "codes": col.codes}
    else:
        lanes = {"validity": col.validity, "data": col.data}
    return {name: np.asarray(lane) for name, lane in lanes.items()}


@pytest.mark.parametrize("kind", ["int", "long", "double", "boolean", "date",
                                  "decimal38", "dictionary"])
@pytest.mark.parametrize("a_cap,b_cap,a_rows,b_rows,out_cap", _CONCAT_SHAPES)
def test_concat_fixed_width_block_moves(kind, a_cap, b_cap, a_rows, b_rows,
                                        out_cap):
    rng = np.random.default_rng(a_cap * 7 + b_cap * 3 + a_rows + b_rows)
    total = a_rows + b_rows
    out_cap = out_cap or bucket_capacity(total)
    shared = StringColumn.from_pylist(["ab", "c", "def"])
    a = _concat_side(kind, rng, a_cap, shared)
    b = _concat_side(kind, rng, b_cap, shared)
    out = concat_columns(a, b, jnp.int32(a_rows), jnp.int32(b_rows), out_cap)
    assert type(out) is type(a) and out.dtype == a.dtype
    assert out.capacity == out_cap
    a_lanes, b_lanes, got = (_concat_lanes(c) for c in (a, b, out))
    for name, ha in a_lanes.items():
        # the inactive tail: zero data, False validity, NULL_CODE codes
        want = np.full(out_cap, NULL_CODE if name == "codes" else 0,
                       ha.dtype)
        want[:a_rows] = ha[:a_rows]
        want[a_rows:total] = b_lanes[name][:b_rows]
        assert got[name].dtype == ha.dtype, name
        np.testing.assert_array_equal(got[name], want, err_msg=name)
    if kind == "dictionary":
        assert out.dict_data is shared.data
        assert out.dict_offsets is shared.offsets


def test_concat_string():
    a = StringColumn.from_pylist(["xx", None])
    b = StringColumn.from_pylist(["yyy", "z", ""])
    out = concat_columns(a, b, jnp.int32(2), jnp.int32(3), 256)
    assert out.to_pylist(5) == ["xx", None, "yyy", "z", ""]


def test_slice():
    c = Column.from_pylist([1, 2, 3, 4, 5, 6], LONG)
    s = slice_rows(c, jnp.int32(2), jnp.int32(3), 128)
    assert s.to_pylist(3) == [3, 4, 5]


def test_bucketing():
    from spark_rapids_tpu.columnar import bucket_capacity
    assert bucket_capacity(1) == 128
    assert bucket_capacity(128) == 128
    assert bucket_capacity(129) == 256
    assert bucket_capacity(1000) == 1024
