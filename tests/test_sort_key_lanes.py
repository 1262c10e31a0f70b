"""The sort's key lanes follow the keys' measured width (ISSUE 35).

`ops/sort.packed_key_lanes` packs the activity bit, each key's null rank and
its value, most significant first, into as few u32 lanes as they fill, and a
string key takes the bytes `string_key_bytes` measured (down to one, not
only up from 32). The order must not change for any input: UTF-8 binary
order, a shorter string before its extensions, nulls first or last and
descending as `SortOrder` says, stable. The oracle is Python's `sorted` on
the UTF-8 bytes.
"""

import functools
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec.basic import InMemoryScanExec
from spark_rapids_tpu.exec.sort import SortExec
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.obs import dispatch
from spark_rapids_tpu.ops import sort as ops_sort
from spark_rapids_tpu.ops.sort import (
    SortOrder, packed_key_lanes, sort_batch_columns, sort_permutation,
    string_key_bytes, string_words_for,
)
from spark_rapids_tpu.types import (
    BOOLEAN, DOUBLE, INT, LONG, STRING, Schema, StructField,
)

ROWS = 96
#: one, two and three UTF-8 bytes a character, so that code-point order and
#: byte order are told apart and prefixes are shared often
ALPHABET = ["a", "b", "~", "é", "中"]


def _strings(rng, max_bytes):
    out = []
    for _ in range(ROWS):
        s = ""
        want = rng.randint(0, max_bytes)
        while True:
            c = rng.choice(ALPHABET)
            if len((s + c).encode()) > want:
                break
            s += c
        out.append(None if rng.random() < 0.15 else s)
    return out


def _numbers(rng, dtype):
    if dtype is BOOLEAN:
        vals = [rng.random() < 0.5 for _ in range(ROWS)]
    elif dtype is DOUBLE:
        vals = [rng.choice([-2.5, -0.0, 0.0, 1.0, 1e300, -1e300, 3.25])
                for _ in range(ROWS)]
    else:
        top = 2**62 if dtype is LONG else 2**30
        vals = [rng.choice([-top, -3, 0, 2, 7, top]) for _ in range(ROWS)]
    return [None if rng.random() < 0.15 else v for v in vals]


def _batch(types, max_bytes, seed):
    rng = random.Random(seed)
    fields = [StructField(f"k{i}", t) for i, t in enumerate(types)]
    data = {f.name: _strings(rng, max_bytes) if f.data_type is STRING
            else _numbers(rng, f.data_type) for f in fields}
    fields.append(StructField("row", INT))          # rides the sort: stability
    data["row"] = list(range(ROWS))
    schema = Schema(tuple(fields))
    return ColumnarBatch.from_pydict(data, schema), data


def _oracle(data, orders):
    """Row numbers in the requested order, by Python's stable `sorted`."""
    names = list(data)

    def encoded(v):
        return v.encode() if isinstance(v, str) else v

    def cmp(a, b):
        for o in orders:
            x, y = data[names[o.ordinal]][a], data[names[o.ordinal]][b]
            if x is None or y is None:
                if x is None and y is None:
                    continue
                return -1 if (x is None) == o.nulls_first else 1
            x, y = encoded(x), encoded(y)
            if x != y:
                return (-1 if x < y else 1) * (1 if o.ascending else -1)
        return 0

    return sorted(range(ROWS), key=functools.cmp_to_key(cmp))


def _sorted_rows(batch, orders):
    key_bytes = string_key_bytes(batch.columns, [o.ordinal for o in orders])
    cols, perm = sort_batch_columns(batch.columns, orders, batch.num_rows,
                                    batch.capacity, key_bytes)
    rows = cols[-1].to_pylist(ROWS)
    assert np.asarray(perm)[:ROWS].tolist() == rows
    return rows, key_bytes


LAYOUTS = {
    "str": (STRING,),
    "str_int": (STRING, INT),
    "int_str_str": (INT, STRING, STRING),
    "double_str": (DOUBLE, STRING),
    "bool_str_long": (BOOLEAN, STRING, LONG),
}
DIRECTIONS = {"asc_nulls_first": (True, True), "asc_nulls_last": (True, False),
              "desc_nulls_first": (False, True),
              "desc_nulls_last": (False, False)}


@pytest.mark.parametrize("direction", sorted(DIRECTIONS))
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_rows_come_out_as_pythons_sorted_on_the_utf8_bytes(layout, direction):
    batch, data = _batch(LAYOUTS[layout], 40, seed=sum(map(ord, layout)))
    asc, nulls_first = DIRECTIONS[direction]
    orders = [SortOrder(i, asc, nulls_first)
              for i in range(len(LAYOUTS[layout]))]
    rows, key_bytes = _sorted_rows(batch, orders)
    assert rows == _oracle(data, orders)
    assert key_bytes == 64                       # 33..40 bytes: two buckets up


@pytest.mark.parametrize("max_bytes,bucket", [(0, 1), (1, 1), (2, 2), (3, 4),
                                              (4, 4), (7, 8), (12, 16),
                                              (31, 32)])
@pytest.mark.parametrize("layout", ["str", "int_str_str"])
def test_every_width_bucket_orders_exactly_and_stably(layout, max_bytes,
                                                      bucket):
    """Sub-word keys (1 and 2 bytes), one word, several: the width goes DOWN
    to what the longest key needs, and mixed directions still hold."""
    batch, data = _batch(LAYOUTS[layout], max_bytes, seed=max_bytes + 11)
    orders = [SortOrder(i, i % 2 == 0, i % 2 == 1)
              for i in range(len(LAYOUTS[layout]))]
    rows, key_bytes = _sorted_rows(batch, orders)
    assert rows == _oracle(data, orders)
    longest = max((len(s.encode()) for k, v in data.items() if k != "row"
                   for s in v if isinstance(s, str)), default=0)
    assert key_bytes == max(1, 1 << max(longest - 1, 0).bit_length())
    assert key_bytes <= bucket


def test_the_permutation_alone_agrees_with_the_batch_sort():
    batch, data = _batch((STRING, INT), 9, seed=5)
    orders = [SortOrder(0, False), SortOrder(1)]
    kb = string_key_bytes(batch.columns, [0, 1])
    perm = sort_permutation(batch.columns, orders, batch.num_rows,
                            batch.capacity, kb)
    assert np.asarray(perm)[:ROWS].tolist() == _oracle(data, orders)


def test_a_shorter_string_sorts_before_its_extensions_at_a_word_boundary():
    schema = Schema((StructField("k", STRING), StructField("row", INT)))
    keys = ["abcde", "abcd", "abc", "", "abcd~", None, "abcda"]
    batch = ColumnarBatch.from_pydict(
        {"k": keys, "row": list(range(len(keys)))}, schema)
    cols, _ = sort_batch_columns(batch.columns, [SortOrder(0)],
                                 batch.num_rows, batch.capacity,
                                 string_key_bytes(batch.columns, [0]))
    assert cols[0].to_pylist(len(keys)) == \
        [None, "", "abc", "abcd", "abcda", "abcde", "abcd~"]


# -- the packer ----------------------------------------------------------------

@pytest.mark.parametrize("widths", [
    (1, 1, 8, 1, 8),            # Q1's keys: 19 bits, one lane
    (1, 1, 32),                 # one INT key: 34 bits, a field straddles
    (1, 1, 32, 32, 1, 16, 1, 1),
    (32, 32), (31, 1, 1), (8,) * 9, (1,) * 33,
])
def test_packed_lanes_order_as_their_fields_do(widths):
    rng = np.random.default_rng(sum(widths))
    n = 512
    # few distinct values a field, in its high bits AND its low ones, so
    # that ties reach the later fields and a straddled field's two halves
    # both decide
    vals = [((rng.integers(0, 4, n, dtype=np.uint64) << max(w - 2, 0))
             | rng.integers(0, 2, n, dtype=np.uint64)) & (2**w - 1)
            for w in widths]
    vals = [v.astype(np.uint32) for v in vals]
    lanes = ops_sort._pack_fields([(jnp.asarray(v), w)
                                   for v, w in zip(vals, widths)])
    assert len(lanes) == -(-sum(widths) // 32)
    assert all(lane.dtype == jnp.uint32 for lane in lanes)
    by_fields = sorted(range(n), key=lambda i: tuple(int(v[i]) for v in vals))
    lanes = [np.asarray(lane) for lane in lanes]
    by_lanes = sorted(range(n), key=lambda i: tuple(int(x[i]) for x in lanes))
    assert by_lanes == by_fields
    same = {tuple(int(v[i]) for v in vals) for i in range(n)}
    assert len({tuple(int(x[i]) for x in lanes) for i in range(n)}) == len(same)


def test_string_words_for_keeps_its_floor_and_its_buckets():
    """The u64 prefix lanes of `order_key_lanes` (segment ids, the merge's
    bound, window partitions) keep today's widths: never under 4 words."""
    schema = Schema((StructField("k", STRING),))
    for longest, words, key_bytes in [(1, 4, 1), (32, 4, 32), (33, 8, 64),
                                      (64, 8, 64), (65, 16, 128)]:
        b = ColumnarBatch.from_pydict({"k": ["x" * longest, "y"]}, schema)
        assert string_words_for(b.columns, [0]) == words
        assert string_key_bytes(b.columns, [0]) == key_bytes
    ints = ColumnarBatch.from_pydict(
        {"v": [1, 2]}, Schema((StructField("v", INT),)))
    assert string_key_bytes(ints.columns, [0]) == 1     # no sync: no strings


# -- Q1's result sort ----------------------------------------------------------

Q1_SCHEMA = Schema(
    (StructField("l_returnflag", STRING), StructField("l_linestatus", STRING))
    + tuple(StructField(n, DOUBLE) for n in (
        "sum_qty", "sum_base_price", "sum_disc_price", "sum_charge",
        "avg_qty", "avg_price", "avg_disc"))
    + (StructField("count_order", LONG),))


def _q1_result():
    keys = [("N", "O"), ("A", "F"), ("R", "F"), ("N", "F")]
    data = {"l_returnflag": [k[0] for k in keys],
            "l_linestatus": [k[1] for k in keys]}
    for i, f in enumerate(Q1_SCHEMA.fields[2:9]):
        data[f.name] = [float(i + j) for j in range(4)]
    data["count_order"] = [10, 20, 30, 40]
    return ColumnarBatch.from_pydict(data, Q1_SCHEMA)


def _sorts_of(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _sorts_of(sub)


def test_q1s_result_sort_is_one_sort_on_at_most_three_keys():
    """Two CHAR(1) keys need 1 + 2 x (1 + 8) = 19 bits: one packed lane and
    the iota. The unpacked stack was 19 lanes and the iota (ISSUE 35), which
    the chip's compiler did not finish in 38 minutes."""
    batch = _q1_result()
    orders = [SortOrder(0), SortOrder(1)]
    kb = string_key_bytes(batch.columns, [0, 1])
    assert kb == 1
    lanes = packed_key_lanes(batch.columns, orders, batch.num_rows,
                             batch.capacity, kb)
    assert len(lanes) == 1 and lanes[0].dtype == jnp.uint32
    jaxpr = jax.make_jaxpr(lambda b: sort_batch_columns(
        b.columns, orders, b.num_rows, b.capacity, kb))(batch)
    sorts = list(_sorts_of(jaxpr.jaxpr))
    assert len(sorts) == 1
    assert sorts[0].params["num_keys"] == 2 <= 3
    plan = SortExec([col("l_returnflag"), col("l_linestatus")],
                    InMemoryScanExec([batch], Q1_SCHEMA))
    assert [r[:2] + r[-1:] for r in plan.collect()] == [
        ("A", "F", 20), ("N", "F", 40), ("N", "O", 10), ("R", "F", 30)]


@pytest.mark.parametrize("words", [1, 2, 4, 8])
def test_each_width_bucket_compiles_one_sort_program(words):
    """The static width is bucketed: keys of up to 4, 8, 16, 32 bytes (1, 2,
    4, 8 u32 words) compile one program each, and a second batch in the
    same bucket compiles none."""
    schema = Schema((StructField("k", STRING), StructField("v", INT)))

    def traces():
        return sum(p["traces"] for p in dispatch.programs()
                   if p["label"] == "SortExec.sort")

    dispatch.reset_dispatch_ledger()
    seen = []
    for longest in (4 * words, 4 * words - 1 if words > 1 else 3):
        keys = ["z" * longest, "a", None, "m" * (longest // 2 + 1)]
        batch = ColumnarBatch.from_pydict(
            {"k": keys, "v": [1, 2, 3, 4]}, schema)
        plan = SortExec([col("k")], InMemoryScanExec([batch], schema))
        assert [r[0] for r in plan.collect()] == \
            [None] + sorted(k for k in keys if k is not None)
        seen.append(traces())
    assert seen == [1, 1]
