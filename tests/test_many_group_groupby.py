"""The group-by of many groups (ISSUE 38): the exact tier's sort path taken
at once for a plan shape known to overflow the masked buckets, the memory of
a trip by plan fingerprint that makes the second `collect()` one pass of the
plan, and the top-N by selection. Every result against numpy / plain
Python on the same data."""

import numpy as np
import pytest

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.exec import aggregate, speculation, stage_compiler
from spark_rapids_tpu.exec.aggregate import AggregateExec
from spark_rapids_tpu.exec.basic import FilterExec, InMemoryScanExec
from spark_rapids_tpu.exec.sort import TopNExec
from spark_rapids_tpu.expr.aggexprs import Count, Sum
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.obs import dispatch
from spark_rapids_tpu.ops.sort import (SortOrder, first_rows,
                                       lexsort_permutation, packed_key_lanes)
from spark_rapids_tpu.types import (DATE, DOUBLE, INT, LONG, Schema,
                                    StructField)

SCHEMA = Schema((StructField("k", LONG), StructField("d", DATE),
                 StructField("p", INT), StructField("v", DOUBLE)))


def _rows(groups: int, n: int, seed: int):
    """`n` rows over `groups` distinct (k, d, p) keys, some with a NULL
    part (NULLs group together, apart from every value); the values are
    whole numbers, so a float64 sum is exact in any order."""
    rng = np.random.default_rng(seed)
    keys = []
    for g in range(groups):
        k = None if g % 11 == 3 else int(rng.integers(-2**62, 2**62))
        d = None if g % 13 == 5 else int(rng.integers(8000, 11000))
        keys.append((k, d, g % 3, g))
    pick = np.concatenate([np.arange(groups),
                           rng.integers(0, groups, max(n - groups, 0))])
    rng.shuffle(pick)
    keyed = [keys[i] for i in pick]
    vals = rng.integers(-1000, 1000, len(pick)).astype(float).tolist()
    return keyed, vals


def _batch(keyed, vals):
    return ColumnarBatch.from_pydict(
        {"k": [x[0] for x in keyed], "d": [x[1] for x in keyed],
         "p": [x[2] for x in keyed], "v": vals}, SCHEMA)


def _want(keyed, vals, keep=lambda v: True):
    """{(k, d, p): (sum, count)} over the kept rows: two groups whose parts
    are equal, NULLs among them, are one group."""
    out = {}
    for key, v in zip(keyed, vals):
        if keep(v):
            s, c = out.get(key[:3], (0.0, 0))
            out[key[:3]] = (s + v, c + 1)
    return out


def _agg(child):
    return AggregateExec([col("k"), col("d"), col("p")],
                         [(Sum(col("v")), "s"), (Count(), "c")], child)


def _as_dict(batch):
    rows = batch.to_pylist()
    got = {r[:3]: (r[3], r[4]) for r in rows}
    assert len(got) == len(rows), "a group came out twice"
    return got


@pytest.mark.parametrize("path", ["exact_tier", "exact_tier_row_mask",
                                  "sort_path"])
@pytest.mark.parametrize("groups", [1, 64, 65, 5000, 20000])
def test_the_exact_update_is_exact_at_any_number_of_groups(groups, path):
    """Every group once, its keys bit for bit (NULL parts too), sum and
    count exact: the exact tier's ONE program (masked buckets, and behind
    them under `lax.cond` the sort path, which a filter absorbed as a row
    mask makes compact first), and the sort path alone, which the few
    groups never reach through it."""
    masked = path == "exact_tier_row_mask"
    keyed, vals = _rows(groups, max(groups + 7, 3 * groups // 2), groups)
    scan = InMemoryScanExec([_batch(keyed, vals)], SCHEMA)
    agg = _agg(FilterExec(col("v") >= lit(0.0), scan) if masked else scan)
    out = agg._jit_update(agg._jit_pre(scan._batches[0])[0], 4) \
        if path == "sort_path" else agg._jit_step_exact(scan._batches[0])
    got = _as_dict(agg._evaluate(out))
    want = _want(keyed, vals, (lambda v: v >= 0.0) if masked
                 else (lambda v: True))
    assert got == want
    assert int(out.num_rows) == len(want)


def test_the_exact_update_of_an_empty_batch_has_no_groups():
    scan = InMemoryScanExec([_batch([], [])], SCHEMA)
    out = _agg(scan)._jit_step_exact(scan._batches[0])
    assert int(out.num_rows) == 0 and out.to_pylist() == []


def _labels():
    out = {}
    for p in dispatch.programs():
        out[p["label"]] = out.get(p["label"], 0) + p["dispatches"]
    return out


def _collect_counted(plan):
    before, c0 = _labels(), aggregate.counters()
    rows = plan.collect()
    after, c1 = _labels(), aggregate.counters()
    return (rows, {k: after[k] - before.get(k, 0) for k in after
                   if after[k] != before.get(k, 0)},
            {k: c1[k] - c0[k] for k in c1 if c1[k] != c0[k]})


@pytest.fixture
def fresh_memory():
    stage_compiler.reset_stage_counters()
    yield
    stage_compiler.reset_stage_counters()


def test_the_second_collect_of_a_tripping_shape_is_one_pass(fresh_memory):
    """The first query of a shape speculates, overflows the 64 slots, and
    runs its plan again (counted, one re-run); the trip is remembered by
    plan fingerprint, and the next query of the SAME shape, from a plan
    built anew, takes the many-group update in its first and only pass."""
    keyed, vals = _rows(300, 900, 5)
    want = _want(keyed, vals)

    def plan():
        return _agg(InMemoryScanExec([_batch(keyed, vals)], SCHEMA))

    first = plan()
    assert not speculation.known_to_trip(first.plan_fingerprint())
    rows, labels, moved = _collect_counted(first)
    assert {r[:3]: (r[3], r[4]) for r in rows} == want
    assert labels["AggregateExec.streaming_step"] == 1
    assert labels["AggregateExec.fused_update_exact"] == 1
    assert moved["spec_trips"] == moved["plan_reruns"] == 1
    assert speculation.known_to_trip(first.plan_fingerprint())

    for _ in range(2):
        rows, labels, moved = _collect_counted(plan())
        assert {r[:3]: (r[3], r[4]) for r in rows} == want
        assert "AggregateExec.streaming_step" not in labels
        assert labels["AggregateExec.fused_update_exact"] == 1
        assert moved == {"executions": 1, "many_group_updates": 1}


def test_a_shape_that_never_trips_is_untouched(fresh_memory):
    """Six groups fit the masked buckets: every query speculates, none runs
    again, nothing is remembered and no counter of the new ones moves."""
    keyed, vals = _rows(6, 400, 6)
    want = _want(keyed, vals)
    for _ in range(3):
        plan = _agg(InMemoryScanExec([_batch(keyed, vals)], SCHEMA))
        rows, labels, moved = _collect_counted(plan)
        assert {r[:3]: (r[3], r[4]) for r in rows} == want
        assert labels["AggregateExec.streaming_step"] == 1
        assert "AggregateExec.fused_update_exact" not in labels
        assert moved == {}
        assert not speculation.known_to_trip(plan.plan_fingerprint())


@pytest.mark.parametrize("rows,known,want_cap", [
    (3000, True, 4096),       # sparse, known to trip: a tight bucket
    (3000, False, 131072),    # not known to trip: no read, no move
    (40000, True, 131072),    # fills more than a quarter: left alone
])
def test_a_sparse_input_of_a_tripping_shape_is_moved_into_a_tight_bucket(
        fresh_memory, rows, known, want_cap):
    """A join hands the group-by its candidate bucket; the sort path's cost
    follows the capacity. Before the exact update of a shape KNOWN to trip
    a bucket of 65,536 slots or more whose rows fill under a quarter of it
    is moved (one host read of the row count) and groups the same."""
    keyed, vals = _rows(min(rows, 700), rows, rows)
    small = _batch(keyed, vals)
    from spark_rapids_tpu.exec.aggregate import _shrink_batch
    wide = _shrink_batch(small, 131072)           # the same rows, 131,072 slots
    wide = ColumnarBatch(wide.columns, rows, SCHEMA)
    agg = _agg(InMemoryScanExec([wide], SCHEMA))
    if known:
        speculation._note_tripped(agg.plan_fingerprint())
    moved = agg._tight_input(wide, agg.plan_fingerprint())
    assert moved.capacity == want_cap and int(moved.num_rows) == rows
    got = _as_dict(agg._evaluate(agg._jit_step_exact(moved)))
    assert got == _want(keyed, vals)


def test_a_stale_join_size_says_nothing_about_the_shape(fresh_memory):
    """A flag recorded without an owner (a join's cached sizes overflowed)
    trips the scope and is not remembered."""
    import jax.numpy as jnp
    with speculation.speculation_scope() as scope:
        scope.record(jnp.asarray(True))
        scope.record(jnp.asarray(False), owner="some-fingerprint")
        assert scope.tripped()
    assert not speculation.known_to_trip("some-fingerprint")
    assert not speculation.known_to_trip(None)
    with speculation.speculation_scope() as scope:
        scope.record(jnp.asarray(True), owner="some-fingerprint")
        assert scope.tripped()
    assert speculation.known_to_trip("some-fingerprint")
    assert not speculation.speculation_allowed("some-fingerprint")


# -- the sort of several key lanes, and the top-N ------------------------------

def _lanes(batch, orders):
    return packed_key_lanes(batch.columns, orders, batch.num_rows,
                            batch.capacity, 1)


@pytest.mark.parametrize("n", [0, 1, 200, 3000])
def test_lexsort_permutation_is_the_stable_order_of_all_lanes(n):
    """(k desc, v asc) over 128-bit keys makes five lanes: the loop of
    one-lane stable sorts gives the order numpy's lexsort gives."""
    keyed, vals = _rows(max(n // 3, 1), n, n)
    batch = _batch(keyed, vals)
    orders = [SortOrder(0, False), SortOrder(3, True), SortOrder(1, True)]
    lanes = _lanes(batch, orders)
    assert len(lanes) > 1
    perm = np.asarray(lexsort_permutation(lanes, batch.capacity))
    host = [np.asarray(x) for x in lanes]
    want = np.lexsort(tuple(reversed(host)))      # stable, last key first
    assert perm.tolist() == want.tolist()


@pytest.mark.parametrize("limit", [1, 10, 128])
def test_first_rows_are_the_sorts_first_rows_in_order(limit):
    keyed, vals = _rows(40, 500, limit)           # many equal keys: ties
    batch = _batch(keyed, vals)
    lanes = _lanes(batch, [SortOrder(2, False), SortOrder(0, True)])
    got = np.asarray(first_rows(lanes, batch.capacity, limit, 128))
    want = np.lexsort(tuple(reversed([np.asarray(x) for x in lanes])))
    assert got[:limit].tolist() == want[:limit].tolist()
    assert (got[limit:] == batch.capacity).all()


@pytest.mark.parametrize("limit,rows", [(10, 5000), (10, 4), (200, 5000)])
def test_top_n_returns_what_a_full_sort_and_a_slice_return(limit, rows):
    """`revenue DESC, date ASC` and a limit: by selection under the
    smallest bucket, off the permutation above it, the plain sorted batch
    where the batch is no larger than the limit's bucket."""
    rng = np.random.default_rng(rows + limit)
    data = {"k": rng.integers(0, 10**12, rows).tolist(),
            "d": rng.integers(9000, 9010, rows).tolist(),
            "p": [0] * rows,
            "v": np.round(rng.random(rows) * 50, 1).tolist()}   # ties
    data["v"][0] = None
    batch = ColumnarBatch.from_pydict(data, SCHEMA)
    top = TopNExec(limit, [SortOrder(3, False), SortOrder(1, True)],
                   InMemoryScanExec([batch], SCHEMA))
    got = top.collect()
    order = sorted(range(rows), key=lambda i: (
        data["v"][i] is None, -(data["v"][i] or 0.0), data["d"][i], i))
    want = [(data["k"][i], data["d"][i], 0, data["v"][i])
            for i in order[:limit]]
    assert got == want
