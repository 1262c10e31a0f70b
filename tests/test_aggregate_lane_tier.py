"""The lane tier at the head of the hash group-by's tiers (ISSUE 36): string
group keys no longer than 16 bytes ride as packed fixed-width lanes through
the masked-bucket kernel. The oracle is the sort path (`groupby_aggregate`),
reached by the same exec with its hash path closed; the counters of
`exec/aggregate.counters()` say which tier answered. A filter/project chain
under such a group-by rides into the lane tier as a row mask; those tests
hold it to the per-operator plan and to numpy. The last tests guard the
compiled text (no loop, no sort, no row-wide scatter) and run TPC-H Q1's own
plan at the benchmark's rehearsal scale."""

import math
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, StringColumn
from spark_rapids_tpu.exec import aggregate
from spark_rapids_tpu.exec.aggregate import AggregateExec
from spark_rapids_tpu.exec.basic import (FilterExec, InMemoryScanExec,
                                         ProjectExec)
from spark_rapids_tpu.expr.aggexprs import (
    Average, Count, First, Max, Min, Sum)
from spark_rapids_tpu.expr.core import col, lit
from spark_rapids_tpu.ops import maskedagg
from spark_rapids_tpu.ops.sort import _pack_fields, _unpack_fields
from spark_rapids_tpu.types import (
    DOUBLE, INT, LONG, STRING, Schema, StructField)

ROOT = Path(__file__).resolve().parents[1]

Q1_AGGS = [(Sum(col("d")), "sum_d"), (Sum(col("v")), "sum_v"),
           (Average(col("d")), "avg_d"), (Count(col("d")), "n_d"),
           (Count(), "n")]
ORDER_AGGS = [(Min(col("d")), "mn"), (Max(col("v")), "mx"),
              (First(col("v")), "f")]


def _schema(**fields):
    return Schema(tuple(StructField(k, t) for k, t in fields.items()))


def _plan(keys, aggs, batches, schema):
    return AggregateExec([col(k) for k in keys], aggs,
                         InMemoryScanExec(batches, schema))


def _rows(plan):
    def key(row):
        return tuple((v is None, "" if v is None else v) for v in row)
    return sorted(plan.collect(), key=lambda r: key(r[:plan._key_count]))


def _by_the_sort_path(monkeypatch, keys, aggs, batches, schema):
    """The same plan with the hash path (lane tier and rounds) closed."""
    with monkeypatch.context() as mp:
        mp.setattr(AggregateExec, "_hash_path_ok",
                   property(lambda self: False))
        return _rows(_plan(keys, aggs, batches, schema))


def _by_hand(key_lists, v, d, aggs):
    """Q1_AGGS or ORDER_AGGS over python lists, rows sorted as `_rows`."""
    groups = {}
    for i, key in enumerate(zip(*key_lists)):
        groups.setdefault(key, []).append(i)
    out = []
    for key, rows in groups.items():
        vs = [v[i] for i in rows if v[i] is not None]
        ds = [d[i] for i in rows if d[i] is not None]
        if aggs is Q1_AGGS:
            out.append(key + (math.fsum(ds) if ds else None,
                              sum(vs) if vs else None,
                              math.fsum(ds) / len(ds) if ds else None,
                              len(ds), len(rows)))
        else:
            out.append(key + (min(ds) if ds else None,
                              max(vs) if vs else None, v[rows[0]]))
    n = len(key_lists)
    return sorted(out, key=lambda r: tuple(
        (k is None, "" if k is None else k) for k in r[:n]))


def _same(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(b, float) and a is not None:
                assert a == pytest.approx(b, rel=1e-12, abs=1e-12)
            else:
                assert a == b and type(a) is type(b)


def _moved(before, **want):
    after = aggregate.counters()
    got = {k: after[k] - before[k] for k in before}
    want = {**{k: 0 for k in before}, **want}
    got.pop("executions"), want.pop("executions")
    assert got == want


def _values(n, seed=0):
    rng = np.random.default_rng(seed)
    v = [None if rng.random() < 0.1 else int(x)
         for x in rng.integers(-50, 50, n)]
    d = [None if rng.random() < 0.1 else float(x)
         for x in rng.normal(size=n) * 1000]
    return v, d


def _cycle(keys, n):
    return [keys[i % len(keys)] for i in range(n)]


KEYS = {
    # four keys, not three and not two: NULL apart from "", "a" from "a\0"
    "null_empty_a_a0": [None, "", "a", "a\0", "\0", "\0\0"],
    "exactly_key_bytes": ["abcd", "abce", "abc", "abcdefgh", "abcdefg",
                          "0123456789abcdef", "0123456789abcdeg"],
    "utf8": ["é", "e", "日本", "日", "本", "日本語", "ß", None],
    "char1": ["A", "N", "R"],
}


@pytest.mark.parametrize("aggs", [Q1_AGGS, ORDER_AGGS],
                         ids=["q1_aggregates", "min_max_first"])
@pytest.mark.parametrize("case", sorted(KEYS))
def test_one_string_key_answers_as_the_sort_path(case, aggs, monkeypatch):
    n = 300
    v, d = _values(n)
    schema = _schema(k=STRING, v=LONG, d=DOUBLE)
    batch = ColumnarBatch.from_pydict(
        {"k": _cycle(KEYS[case], n), "v": v, "d": d}, schema)
    keys = _cycle(KEYS[case], n)
    before = aggregate.counters()
    got = _rows(_plan(["k"], aggs, [batch], schema))
    _moved(before, hash_updates=1, lane_updates=1)
    _same(got, _by_hand([keys], v, d, aggs))
    assert len(got) == len(set(KEYS[case]))
    if case != "null_empty_a_a0":
        # the sort path's prefix lanes are zero-padded and carry no length:
        # it groups "a" with "a\0" and "" with "\0" (PERF.md section 7)
        _same(got, _by_the_sort_path(monkeypatch, ["k"], aggs, [batch],
                                     schema))


def test_two_string_keys(monkeypatch):
    n = 500
    v, d = _values(n, 1)
    rng = np.random.default_rng(1)
    flags = [None if rng.random() < 0.05 else "ANR"[i]
             for i in rng.integers(0, 3, n)]
    status = ["OF"[i] for i in rng.integers(0, 2, n)]
    schema = _schema(a=STRING, b=STRING, v=LONG, d=DOUBLE)
    batch = ColumnarBatch.from_pydict(
        {"a": flags, "b": status, "v": v, "d": d}, schema)
    want = _by_the_sort_path(monkeypatch, ["a", "b"], Q1_AGGS, [batch],
                             schema)
    before = aggregate.counters()
    got = _rows(_plan(["a", "b"], Q1_AGGS, [batch], schema))
    _moved(before, hash_updates=1, lane_updates=1)
    _same(got, want)
    assert len(got) == 8


def test_a_string_key_beside_an_int_and_a_double_key(monkeypatch):
    n = 400
    v, d = _values(n, 2)
    rng = np.random.default_rng(2)
    s = [[None, "yy", ""][i] for i in rng.integers(0, 3, n)]
    i = [None if rng.random() < 0.3 else 3 for _ in range(n)]
    f = [[0.0, -0.0, float("nan")][x] for x in rng.integers(0, 3, n)]
    schema = _schema(s=STRING, i=INT, f=DOUBLE, v=LONG, d=DOUBLE)
    batch = ColumnarBatch.from_pydict(
        {"s": s, "i": i, "f": f, "v": v, "d": d}, schema)
    aggs = [(Sum(col("v")), "sv"), (Count(), "n")]

    def rows(plan):
        # NaN keys: compare as text; a group of 0.0 and -0.0 may show either
        return sorted(repr(r).replace("-0.0", "0.0") for r in plan.collect())

    with monkeypatch.context() as mp:
        mp.setattr(AggregateExec, "_hash_path_ok",
                   property(lambda self: False))
        want = rows(_plan(["s", "i", "f"], aggs, [batch], schema))
    before = aggregate.counters()
    got = rows(_plan(["s", "i", "f"], aggs, [batch], schema))
    _moved(before, hash_updates=1, lane_updates=1)
    assert got == want and len(got) == 12          # -0.0 is 0.0


def test_rows_that_do_not_fill_their_bucket(monkeypatch):
    """130 rows in a 256-row bucket whose tail holds other keys' bytes."""
    n = 130
    v, d = _values(256, 3)
    schema = _schema(k=STRING, v=LONG, d=DOUBLE)
    full = ColumnarBatch.from_pydict(
        {"k": _cycle(["a", "b", None], n) + ["zz"] * (256 - n), "v": v,
         "d": d}, schema)
    batch = ColumnarBatch(full.columns, n, schema)
    assert batch.capacity == 256
    want = _by_the_sort_path(monkeypatch, ["k"], Q1_AGGS, [batch], schema)
    before = aggregate.counters()
    got = _rows(_plan(["k"], Q1_AGGS, [batch], schema))
    _moved(before, hash_updates=1, lane_updates=1)
    _same(got, want)
    assert [r[0] for r in got] == ["a", "b", None]


def test_a_merge_of_several_partials(monkeypatch):
    schema = _schema(k=STRING, v=LONG, d=DOUBLE)
    batches = []
    for b in range(3):
        v, d = _values(200, 10 + b)
        keys = _cycle(["A", "N", "R", None, ""][b:], 200)
        batches.append(ColumnarBatch.from_pydict(
            {"k": keys, "v": v, "d": d}, schema))
    aggs = Q1_AGGS + [(Min(col("d")), "mn"), (Max(col("v")), "mx")]
    want = _by_the_sort_path(monkeypatch, ["k"], aggs, batches, schema)
    before = aggregate.counters()
    got = _rows(_plan(["k"], aggs, batches, schema))
    # three updates and the merge of their partials
    _moved(before, hash_updates=4, lane_updates=4)
    _same(got, want)
    assert len(got) == 5


def test_more_keys_than_slots_go_on_to_the_hash_rounds(monkeypatch):
    n = 2000
    v, d = _values(n, 4)
    keys = [f"k{i % 100:02d}" for i in range(n)]      # 100 > 2 x 32 slots
    schema = _schema(k=STRING, v=LONG, d=DOUBLE)
    batch = ColumnarBatch.from_pydict({"k": keys, "v": v, "d": d}, schema)
    want = _by_the_sort_path(monkeypatch, ["k"], Q1_AGGS, [batch], schema)
    before = aggregate.counters()
    got = _rows(_plan(["k"], Q1_AGGS, [batch], schema))
    _moved(before, hash_updates=1, lane_leftovers=1)  # no round retried
    _same(got, want)
    assert len(got) == 100


def test_leftover_keys_skip_the_sweep_of_the_aggregates():
    n = 512
    keys = StringColumn.from_pylist([f"{i % 100:02d}" for i in range(n)])
    vals = Column.from_pylist(list(range(n)), LONG)
    _, results, _, leftover = maskedagg.masked_groupby_lanes(
        [keys], [("sum", vals), ("count_star", None)], jnp.int32(n), n, 2)
    assert bool(leftover)
    for _, (data, valid) in results:
        assert not np.asarray(data).any() and not np.asarray(valid).any()


@pytest.mark.parametrize("case", ["seventeen_bytes", "too_many_lanes"])
def test_keys_too_wide_decline_to_the_hash_rounds(case, monkeypatch):
    n = 200
    v, d = _values(n, 5)
    if case == "seventeen_bytes":
        names = ["k"]
        data = {"k": _cycle(["x" * 17, "y", None], n)}
    else:
        # four keys of 16 bytes are 20 lane columns: over the 16 of the
        # assignment's packed stats word
        names = ["k0", "k1", "k2", "k3"]
        data = {k: _cycle(["0123456789abcdef", "b"], n) for k in names}
    schema = _schema(**{k: STRING for k in names}, v=LONG, d=DOUBLE)
    batch = ColumnarBatch.from_pydict({**data, "v": v, "d": d}, schema)
    want = _by_the_sort_path(monkeypatch, names, Q1_AGGS, [batch], schema)
    before = aggregate.counters()
    plan = _plan(names, Q1_AGGS, [batch], schema)
    assert plan._lane_ok
    got = _rows(plan)
    _moved(before, hash_updates=1, lane_declines=1)
    _same(got, want)


@pytest.mark.parametrize("agg", [Min, First], ids=["min", "first"])
def test_a_string_buffer_never_builds_the_tier(agg):
    schema = _schema(k=STRING, s=STRING)
    batch = ColumnarBatch.from_pydict(
        {"k": ["a", "b", "a"], "s": ["x", "y", "w"]}, schema)
    plan = _plan(["k"], [(agg(col("s")), "m")], [batch], schema)
    assert not plan._lane_ok
    assert "lanes" not in plan._jit_update_hash
    assert "lanes" not in plan._jit_merge_hash
    before = aggregate.counters()
    got = dict(plan.collect())
    assert got == {"a": "w" if agg is Min else "x", "b": "y"}
    after = aggregate.counters()
    assert all(after[k] == before[k] for k in
               ("lane_updates", "lane_declines", "lane_leftovers"))


def test_fixed_width_keys_keep_the_one_program_masked_path():
    schema = _schema(k=INT, v=LONG)
    batch = ColumnarBatch.from_pydict({"k": [1, 2, 1], "v": [1, 2, 3]},
                                      schema)
    plan = _plan(["k"], [(Sum(col("v")), "s")], [batch], schema)
    assert plan._masked_ok and not plan._lane_ok
    assert "lanes" not in plan._jit_update_hash


# -- a filter/project chain absorbed as a row mask --------------------------------

CUT = 90
#: Sum, Sum and Count: one buffer each, so a partial's row is its answer
PARTIAL_AGGS = [(Sum(col("d")), "sum_d"), (Sum(col("v")), "sum_v"),
                (Count(), "n")]


def _stages(plan):
    """The kinds of the compiled stages in `plan`, the chain an aggregate
    drives through its `_source` included."""
    from spark_rapids_tpu.exec.base import TpuExec
    from spark_rapids_tpu.exec.stage_compiler import CompiledStageExec
    kinds, todo = [], [plan]
    while todo:
        node = todo.pop()
        if isinstance(node, CompiledStageExec):
            kinds.append(node._kind)
        todo.extend(node.children)
        if isinstance(getattr(node, "_source", None), TpuExec):
            todo.append(node._source)
    return kinds


def _chain(batches, schema, aggs, mode):
    """Filter(t <= CUT) -> Project(a, b, v, d * (1 - x) AS d) -> group-by on
    the two string keys: Q1's plan below its sort."""
    scan = InMemoryScanExec(batches, schema)
    filt = FilterExec(col("t") <= lit(CUT), scan)
    proj = ProjectExec([col("a"), col("b"), col("v"),
                        (col("d") * (lit(1.0) - col("x"))).alias("d")], filt)
    return AggregateExec([col("a"), col("b")], aggs, proj, mode=mode)


def _chain_data(case, n):
    rng = np.random.default_rng(len(case))
    v, d = _values(n, len(case))
    x = (rng.random(n) * 0.1).tolist()
    t = rng.integers(0, 100, n).tolist()               # about 91% kept
    a = [["A", "N", "R"][i] for i in rng.integers(0, 3, n)]
    b = [["F", "O"][i] for i in rng.integers(0, 2, n)]
    if case == "null_predicate":
        t = [None if i % 7 == 0 else ti for i, ti in enumerate(t)]
    elif case == "dropped_key_longer_than_kept":
        # 40 bytes: measured over every row, the lane tier would decline
        a = ["x" * 40 if ti > CUT else ai for ai, ti in zip(a, t)]
    elif case == "more_groups_than_lanes":
        # 100 groups: over the lane tier's 2 x 32 slots, 2 hash rounds do
        a = [f"k{i % 100:02d}" for i in range(n)]
        b = ["F"] * n
    elif case == "keeps_nothing":
        t = [CUT + 1 + ti for ti in t]
    return {"a": a, "b": b, "t": t, "v": v, "d": d, "x": x}


def _chain_by_numpy(data, aggs):
    """The filter and the product in numpy, the groups by hand."""
    t = np.array([np.nan if ti is None else ti for ti in data["t"]])
    kept = np.flatnonzero(t <= CUT)                    # NULL drops the row
    prod = np.array([np.nan if di is None else di for di in data["d"]]) \
        * (1.0 - np.array(data["x"]))
    d = [None if data["d"][i] is None else float(prod[i]) for i in kept]
    v = [data["v"][i] for i in kept]
    rows = _by_hand([[data["a"][i] for i in kept],
                     [data["b"][i] for i in kept]], v, d, Q1_AGGS)
    if aggs is PARTIAL_AGGS:                           # sum_d, sum_v, n
        rows = [r[:4] + r[6:] for r in rows]
    return rows


CHAIN_CASES = {
    # name: (batches, mode, aggregates, the counters that move)
    "q1_shape": (2, "complete", Q1_AGGS,
                 # two updates on the mask, and the merge of their partials
                 dict(hash_updates=3, lane_updates=3, lane_masked_updates=2)),
    "null_predicate": (1, "complete", Q1_AGGS,
                       dict(hash_updates=1, lane_updates=1,
                            lane_masked_updates=1)),
    "dropped_key_longer_than_kept": (1, "complete", Q1_AGGS,
                                     dict(hash_updates=1, lane_updates=1,
                                          lane_masked_updates=1)),
    "more_groups_than_lanes": (1, "complete", Q1_AGGS,
                               dict(hash_updates=1, lane_leftovers=1,
                                    mask_compactions=1)),
    "keeps_nothing": (1, "complete", Q1_AGGS,
                      dict(hash_updates=1, lane_updates=1,
                           lane_masked_updates=1)),
    "partial_mode": (1, "partial", PARTIAL_AGGS,
                     dict(hash_updates=1, lane_updates=1,
                          lane_masked_updates=1)),
}


@pytest.mark.parametrize("case", list(CHAIN_CASES))
def test_a_filtered_projection_rides_into_the_lane_tier_as_a_mask(case):
    from spark_rapids_tpu.config import (RapidsConf, active_conf,
                                         set_active_conf)
    from spark_rapids_tpu.exec.stage_compiler import compile_stages
    n_batches, mode, aggs, moved = CHAIN_CASES[case]
    n = 600
    schema = _schema(a=STRING, b=STRING, t=INT, v=LONG, d=DOUBLE, x=DOUBLE)
    data = _chain_data(case, n_batches * n)
    batches = [ColumnarBatch.from_pydict(
        {k: vals[i * n:(i + 1) * n] for k, vals in data.items()}, schema)
        for i in range(n_batches)]

    conf = active_conf()
    plan = compile_stages(_chain(batches, schema, aggs, mode), conf)
    assert isinstance(plan, AggregateExec) and plan._lane_ok
    assert [s[0] for s in plan._fused_steps] == ["filter", "project"]
    assert _stages(plan) == []                     # no map stage under it
    before = aggregate.counters()
    got = _rows(plan)
    _moved(before, **moved)

    set_active_conf(RapidsConf({"spark.rapids.tpu.fusion.enabled": "false",
                                "spark.rapids.tpu.stage.fusion.enabled":
                                    "false"}))
    try:
        per_op = _chain(batches, schema, aggs, mode)
        assert not per_op._fused_steps
        want = _rows(per_op)
    finally:
        set_active_conf(conf)
    _same(got, want)
    _same(got, _chain_by_numpy(data, aggs))
    assert (len(got) == 0) == (case == "keeps_nothing")


# -- the lanes themselves -------------------------------------------------------

@pytest.mark.parametrize("key_bytes", [1, 2, 4, 8, 16])
def test_a_string_is_spelt_back_from_its_lanes(key_bytes):
    rows = [None, "", "\0", "a", "a\0", "é", "日本語"[: key_bytes // 3],
            "z" * key_bytes, "\xff"[:key_bytes], "ab"[:key_bytes]]
    rows = [r for r in rows
            if r is None or len(r.encode()) <= key_bytes]
    c = StringColumn.from_pylist(rows)
    lanes = maskedagg.string_key_lanes(c, key_bytes)
    assert len(lanes) == maskedagg.key_lane_count(STRING, key_bytes) \
        == {1: 1, 2: 1, 4: 2, 8: 3, 16: 5}[key_bytes]
    assert all(ln.data.dtype == jnp.int32 and ln.dtype == INT
               for ln in lanes)
    back = maskedagg.string_from_key_lanes(lanes, key_bytes, STRING)
    assert back.to_pylist(len(rows)) == rows
    # equal strings, equal lanes; different strings differ in some lane
    words = np.stack([np.asarray(ln.data) for ln in lanes], axis=1)
    valid = np.asarray(c.validity)
    spelt = {tuple(w) if ok else None
             for w, ok in zip(words[:len(rows)], valid)}
    assert len(spelt) == len(set(rows))


@pytest.mark.parametrize("widths", [[1, 8], [5, 32, 32, 32, 32], [3, 32],
                                    [31, 2, 32, 7], [16, 16, 1]])
def test_unpack_fields_inverts_pack_fields(widths):
    rng = np.random.default_rng(sum(widths))
    fields = [jnp.asarray(rng.integers(0, 1 << b, 64, dtype=np.uint64)
                          .astype(np.uint32)) for b in widths]
    lanes = _pack_fields(list(zip(fields, widths)))
    assert len(lanes) == -(-sum(widths) // 32)
    for got, want in zip(_unpack_fields(lanes, widths), fields):
        assert np.array_equal(np.asarray(got), np.asarray(want))


# -- the compiled text ----------------------------------------------------------

CAP = 1 << 20


def _primitives(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _primitives(sub, found)
    return found


def test_the_lane_update_holds_no_loop_sort_or_row_wide_scatter():
    """Q1's update at a 1,048,576-row bucket, traced only (nothing runs):
    no `while` (a per-byte search for a row, `string_equal`'s or
    `searchsorted`'s), no `sort`, no scatter at all over the source rows,
    and of gathers over them only the `key_bytes` byte reads a string key
    needs (two CHAR(1) keys: two). The hash update this replaces held two
    24-step loops and seven scatter-adds, 20 s of a 24 s query on the chip
    (PERF.md, PR 35)."""
    schema = _schema(a=STRING, b=STRING, q=DOUBLE, p=DOUBLE, c=DOUBLE)
    small = ColumnarBatch.from_pydict(
        {"a": ["A"], "b": ["F"], "q": [1.0], "p": [2.0], "c": [0.5]}, schema)
    plan = _plan(["a", "b"],
                 [(Sum(col("q")), "sq"), (Sum(col("p")), "sp"),
                  (Average(col("q")), "aq"), (Average(col("c")), "ac"),
                  (Count(), "n")], [small], schema)
    pre, mask = plan._jit_pre(small)
    assert mask is None                            # no filter absorbed
    key_bytes = plan._lane_key_bytes(pre)
    assert key_bytes == 1

    def big(leaf):
        # rows, rows + 1 (offsets) and the byte bucket all scale
        n = leaf.shape[0] if leaf.shape else None
        if n is None:
            return jax.ShapeDtypeStruct((), leaf.dtype)
        grown = {small.capacity: CAP, small.capacity + 1: CAP + 1}[n]
        return jax.ShapeDtypeStruct((grown,), leaf.dtype)

    shapes = jax.tree_util.tree_map(big, pre)
    jaxpr = jax.make_jaxpr(lambda b: plan._lane_update(b, key_bytes))(shapes)
    eqns = _primitives(jaxpr.jaxpr, [])
    names = {e.primitive.name for e in eqns}
    assert not names & {"while", "sort", "scan"}

    def rows_of(eqn):
        return [v.aval.shape[0] for v in eqn.invars
                if getattr(v.aval, "shape", ())]

    wide = [e for e in eqns
            if e.primitive.name.startswith(("scatter", "gather"))
            and any(r >= CAP for r in rows_of(e))]
    assert [e.primitive.name for e in wide] == ["gather", "gather"]
    for e in wide:                                 # a byte read a key
        assert e.invars[0].aval.dtype == jnp.uint8
    # the aggregates sit behind the `leftover` flag
    assert "cond" in names


# -- TPC-H Q1's own plan --------------------------------------------------------

def test_q1s_plan_takes_the_lane_tier_once_a_query(tmp_path):
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from benchmarks.lib import datagen
    from benchmarks.lib.manifest import Manifest, apply_rehearsal
    from spark_rapids_tpu.api.session import TpuSession
    m = Manifest()
    cfg = apply_rehearsal(m.config(m.cell("q1_groupby_string_keys")["config"]))
    ref_mod = m.config_module(cfg, "reference")
    query_mod = m.config_module(cfg, "query")
    tables = ref_mod.generate(3500007930, cfg)
    paths = datagen.write_tables(str(tmp_path / "data"), tables,
                                 cfg["schema"], cfg["layout"])
    sess = TpuSession(dict(cfg.get("session_conf", {})))
    # the date filter and the two products ride into the group-by as its
    # row mask: no map stage compacts the batch before it
    assert not _stages(query_mod.build(sess, paths, cfg)._exec())
    before = aggregate.counters()
    rows = query_mod.build(sess, paths, cfg).collect()
    _moved(before, hash_updates=1, lane_updates=1, lane_masked_updates=1)
    rows2 = query_mod.build(sess, paths, cfg).collect()
    _moved(before, hash_updates=2, lane_updates=2, lane_masked_updates=2)
    assert rows == rows2

    answer = ref_mod.reference(tables, cfg)
    assert ref_mod.compare(rows, answer)["rows_wrong"] == 0
    assert [r[:2] for r in rows] == [a[:2] for a in answer]
    # avg_disc, the column whose scatter-add read 1.2e-10 on the chip:
    # against a float64 numpy sum over the group's rows
    line = tables["lineitem"]
    keep = line["l_shipdate"] <= ref_mod.cutoff(cfg)
    for row in rows:
        m_ = keep & (line["l_returnflag"] == row[0]) \
            & (line["l_linestatus"] == row[1])
        disc = line["l_discount"][m_].astype(np.float64)
        assert row[-1] == disc.size
        want = math.fsum(disc) / disc.size
        assert row[8] == pytest.approx(want, rel=1e-13)
