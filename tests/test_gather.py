"""Property tests for the gather engine (ISSUE 8, ops/gather.py): the
packed row gather must match a plain numpy oracle on randomized inputs
(null masks, mixed column widths, capacity-bucket padding, out-of-range
and empty index sets), and the gather-count drop is asserted
STRUCTURALLY (counts, not timing) via the numGathers metric and the
gather_stats event log.
"""

import glob
import json

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_tpu.columnar.column import Column, bucket_capacity
from spark_rapids_tpu.ops import gather as G
from spark_rapids_tpu.types import (
    BOOLEAN, BYTE, DOUBLE, FLOAT, INT, LONG, SHORT, Schema, StructField,
)


def _col(np_arr, dtype, null_every=0, capacity=None):
    c = Column.from_numpy(np_arr, dtype,
                          capacity=capacity or bucket_capacity(len(np_arr)))
    if null_every:
        v = np.asarray(c.validity).copy()
        v[::null_every] = False
        c = Column(c.data, jnp.asarray(v), dtype)
    return c


def _mixed_cols(rng, n, null_every=5):
    """One column of every packable width class (bool, i8, i16, i32,
    i64, f32, f64), nulls sprinkled at different cadences."""
    return [
        _col(rng.integers(0, 2, n).astype(bool), BOOLEAN, null_every),
        _col(rng.integers(-100, 100, n).astype(np.int8), BYTE, 0),
        _col(rng.integers(-1000, 1000, n).astype(np.int16), SHORT,
             max(0, null_every - 2)),
        _col(rng.integers(-(2**28), 2**28, n).astype(np.int32), INT, 3),
        _col(rng.integers(-(2**60), 2**60, n).astype(np.int64), LONG,
             null_every),
        _col(rng.random(n).astype(np.float32), FLOAT, 0),
        _col(rng.random(n) * 1e6, DOUBLE, 7),
    ]


def _assert_matches_numpy_take(cols, idx_np):
    """`gather_batch_columns` against `numpy.take`, with the engine's
    out-of-range rule (ops/rowpack.gather_rows, ops/basic.gather_column):
    an index < 0 or >= capacity yields an invalid row; a valid output
    row holds the source row's bits."""
    out = G.gather_batch_columns(cols, jnp.asarray(idx_np))
    for got, c in zip(out, cols):
        in_range = (idx_np >= 0) & (idx_np < c.capacity)
        safe = np.where(in_range, idx_np, 0)
        want_valid = np.take(np.asarray(c.validity), safe) & in_range
        assert np.array_equal(np.asarray(got.validity), want_valid)
        src = np.asarray(c.data)
        want = np.take(src, safe)[want_valid]
        have = np.asarray(got.data)[want_valid]
        assert have.dtype == src.dtype
        # bit-level: NaN payloads and -0.0 must survive the lane packing
        assert have.tobytes() == want.tobytes()


def _mixed_case(seed, n, n_out, oob, null_every=5):
    rng = np.random.default_rng(seed)
    cols = _mixed_cols(rng, n, null_every)
    cap = cols[0].capacity
    lo, hi = (-5, cap + 7) if oob else (0, n)
    idx = rng.integers(lo, hi, n_out).astype(np.int32)
    if oob:
        idx[:: max(1, n_out // 9)] = -1  # capacity-padding slots
    return cols, idx


def _int_only_case():
    """No f64 columns -> the packed f64 matrix is None end to end."""
    from spark_rapids_tpu.ops.rowpack import pack_rows
    rng = np.random.default_rng(3)
    cols = [_col(rng.integers(0, 99, 500).astype(np.int64), LONG, 4),
            _col(rng.integers(0, 9, 500).astype(np.int32), INT, 0)]
    assert pack_rows(cols)[2] is None
    return cols, rng.integers(-3, 600, 800).astype(np.int32)


def _all_invalid_case():
    """Every index out of range -> all-invalid rows."""
    cols = _mixed_cols(np.random.default_rng(4), 128, null_every=0)
    return cols, np.full((256,), -1, np.int32)


@pytest.mark.parametrize("case", [
    pytest.param(lambda: _mixed_case(0, 700, 1500, True),
                 id="mixed-dups-oob-padding"),
    pytest.param(lambda: _mixed_case(1, 64, 64, False),
                 id="mixed-in-range"),
    pytest.param(lambda: _mixed_case(2, 1, 300, True),
                 id="mixed-single-row-source"),
    pytest.param(_int_only_case, id="int-only-no-f64-matrix"),
    pytest.param(_all_invalid_case, id="all-invalid-index-set"),
])
def test_gather_batch_columns_matches_numpy_take(case):
    _assert_matches_numpy_take(*case())


def test_gather_batch_columns_matches_per_column():
    """The engine helper's packed path == per-column gather_column for
    every width class, including the masked tail."""
    from spark_rapids_tpu.ops.basic import active_mask, gather_column
    rng = np.random.default_rng(5)
    n = 400
    cols = _mixed_cols(rng, n)
    idx = jnp.asarray(rng.integers(0, n, 512).astype(np.int32))
    n_rows = jnp.int32(300)
    out = G.gather_batch_columns(cols, idx, num_rows=n_rows)
    midx = jnp.where(active_mask(n_rows, 512), idx, -1)
    for got, c in zip(out, cols):
        ref = gather_column(c, midx)
        assert np.array_equal(np.asarray(got.validity),
                              np.asarray(ref.validity))
        assert np.array_equal(
            np.asarray(got.data).view(np.uint8).tobytes(),
            np.asarray(ref.data).view(np.uint8).tobytes())


# --- engine level: the oracle's rows + structural gather counts ------------------


def _q3_join_tables():
    rng = np.random.default_rng(17)
    no, nl = 300, 1200
    orders = {"o_key": np.arange(no, dtype=np.int64).tolist(),
              "o_flag": rng.integers(0, 10, no).tolist()}
    lineitem = {"l_key": rng.integers(0, no, nl).tolist(),
                "l_price": (rng.random(nl) * 1000).round(6).tolist(),
                "l_qty": rng.integers(1, 50, nl).tolist()}
    return orders, lineitem


def _q3_join_session(extra_conf=None):
    """q3-shaped join + aggregate: orders (build) x lineitem (stream),
    fixed-width payload on both sides."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.expr.aggexprs import Sum
    from spark_rapids_tpu.expr.core import col, lit
    conf = {"spark.rapids.sql.batchSizeBytes": 16 << 10}
    conf.update(extra_conf or {})
    sess = TpuSession(conf)
    orders, lineitem = _q3_join_tables()
    o_schema = Schema((StructField("o_key", LONG),
                       StructField("o_flag", INT)))
    l_schema = Schema((StructField("l_key", LONG),
                       StructField("l_price", DOUBLE),
                       StructField("l_qty", LONG)))
    df_o = sess.from_pydict(orders, o_schema)
    df_l = sess.from_pydict(lineitem, l_schema)
    q = (df_l.join(df_o, left_on="l_key", right_on="o_key", how="inner")
             .filter(col("o_flag") < lit(8))
             .group_by("o_flag")
             .agg((Sum(col("l_price")), "rev"), (Sum(col("l_qty")), "q")))
    return sess, q


def test_gather_engine_q3_join_matches_oracle():
    """Every row gather of the join emit, the filter's compaction and
    the group-by: the q3-shaped query's rows equal a plain python
    join + filter + group-by (float sums to reduction-order tolerance)."""
    from spark_rapids_tpu.config import RapidsConf, set_active_conf
    orders, lineitem = _q3_join_tables()
    flag_of = dict(zip(orders["o_key"], orders["o_flag"]))
    want = {}
    for k, price, qty in zip(lineitem["l_key"], lineitem["l_price"],
                             lineitem["l_qty"]):
        flag = flag_of[k]
        if flag < 8:
            rev, q = want.get(flag, (0.0, 0))
            want[flag] = (rev + price, q + qty)
    try:
        _sess, q = _q3_join_session()
        got = {r[0]: (r[1], r[2]) for r in q.collect()}
    finally:
        set_active_conf(RapidsConf())
    assert set(got) == set(want)
    for flag, (rev, qty) in want.items():
        assert got[flag][1] == qty
        assert abs(got[flag][0] - rev) <= 1e-9 * abs(rev)


def test_gather_engine_filter_heavy_matches_oracle():
    """Filter-heavy plan (compaction path, ops/basic.compact_columns):
    the surviving rows are exactly the python filter's."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.config import RapidsConf, set_active_conf
    from spark_rapids_tpu.expr.core import col, lit
    rng = np.random.default_rng(23)
    n = 3000
    schema = Schema((StructField("a", LONG), StructField("b", INT),
                     StructField("c", DOUBLE),
                     StructField("d", BOOLEAN)))
    data = {"a": rng.integers(0, 1000, n).tolist(),
            "b": rng.integers(-50, 50, n).tolist(),
            "c": (rng.random(n) * 100).tolist(),
            "d": rng.integers(0, 2, n).astype(bool).tolist()}
    want = sorted(r for r in zip(data["a"], data["b"], data["c"],
                                 data["d"])
                  if r[0] % 3 == 0 and r[1] > -25 and r[3])
    try:
        df = TpuSession().from_pydict(data, schema)
        q = (df.filter(col("a") % lit(3) == lit(0))
               .filter(col("b") > lit(-25))
               .filter(col("d") == lit(True)))
        got = sorted(map(tuple, q.collect()))
    finally:
        set_active_conf(RapidsConf())
    assert got == want and len(got) > 0


def test_structural_gather_count_per_join_iteration(tmp_path):
    """The gather-elimination acceptance: the join probe materializes
    <= 5 row gathers PER STREAM ITERATION (the verify's key-pack gather
    on each side, one index materialization, one packed payload gather
    per side — down from the ~10 per-column payload gathers
    docs/perf.md r5 measured), and the numGathers totals reconcile with
    the gather_stats event and the op_close span batches. Counts only —
    CPU-runnable."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.config import RapidsConf, set_active_conf
    from spark_rapids_tpu.obs import events
    try:
        sess, q = _q3_join_session({
            # ISSUE 14: this test pins the PER-OP join exec's
            # structural gather discipline (the fused stage reuses the
            # same probe kernel; its gather accounting is covered by
            # test_stage_compiler)
            "spark.rapids.tpu.stage.fusion.enabled": "false",
            "spark.rapids.tpu.eventLog.enabled": True,
            "spark.rapids.tpu.eventLog.dir": str(tmp_path)})
        rows = q.collect()
        assert rows
        logged = []
        for f in glob.glob(str(tmp_path / "events-*.jsonl")):
            with open(f) as fh:
                logged += [json.loads(ln) for ln in fh if ln.strip()]
        gs = [e for e in logged if e.get("kind") == "gather_stats"
              and "HashJoin" in (e.get("op") or "")]
        assert gs, "join emitted no gather_stats event"
        closes = {e.get("op_id"): e for e in logged
                  if e.get("kind") == "op_close"}
        for e in gs:
            oc = closes.get(e.get("op_id"))
            assert oc is not None and oc["batches"] >= 1
            per_iter = e["count"] / oc["batches"]
            assert per_iter <= 5, (e, oc)
            assert e["packed"] >= 2 * oc["batches"]  # both sides packed
    finally:
        events.reset_event_bus()
        set_active_conf(RapidsConf())
        TpuSessionReset()


def TpuSessionReset():
    from spark_rapids_tpu.api.session import TpuSession
    TpuSession()


def test_filter_numgathers_metric_counts_one_packed_gather():
    """FilterExec's compaction = ONE packed row gather per batch for an
    all-fixed-width schema (the engine-wide helper at work)."""
    from spark_rapids_tpu.exec.basic import FilterExec, InMemoryScanExec
    from spark_rapids_tpu.expr.core import col, lit
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    rng = np.random.default_rng(31)
    n = 500
    schema = Schema((StructField("a", LONG), StructField("b", DOUBLE)))
    cols = [_col(rng.integers(0, 50, n).astype(np.int64), LONG),
            _col(rng.random(n) * 10, DOUBLE)]
    batches = [ColumnarBatch(cols, n, schema)] * 3
    f = FilterExec((col("a") > lit(10)), InMemoryScanExec(batches, schema))
    out = list(f.execute())
    assert len(out) == 3
    assert f.metrics["numGathers"].value == 3  # one packed gather each
    assert f.metrics["gatherTimeNs"].value > 0
