"""Planner-integrated exchange tests: with a session mesh, group-bys plan
as partial → ShuffleExchangeExec → final and equi-joins as
exchange-both-sides → per-partition ShuffledHashJoinExec, and results match
the single-partition plan exactly (reference analog:
GpuShuffleExchangeExecBase + GpuShuffledHashJoinExec integration tests).

Marked `slow`: each case drives the 8-virtual-device mesh end to end
(minutes on one core); the fast distributed-primitive coverage stays in
tier-1 via tests/test_parallel.py."""

import numpy as np
import pytest

pytestmark = pytest.mark.slow

import jax

from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col
from spark_rapids_tpu.api.session import TpuSession

needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 virtual devices")


@pytest.fixture(autouse=True)
def _drop_compiled_programs():
    """Each case compiles its programs for eight devices, and the file's
    compiled CPU programs together outgrow one process's memory maps
    (`vm.max_map_count`, 65,530: the whole file reached ~63,600 without
    this): drop them between cases."""
    yield
    from spark_rapids_tpu.obs import dispatch
    dispatch.reset_dispatch_ledger()
    jax.clear_caches()


def _sorted(rows):
    return sorted(rows, key=lambda r: tuple((x is None, x) for x in r))


def _data(rng, n=400, n_keys=7):
    return {
        "k": [int(x) for x in rng.integers(0, n_keys, n)],
        "s": [["alpha", "bravo", "charlie", None][int(x)]
              for x in rng.integers(0, 4, n)],
        "v": [int(x) for x in rng.integers(-50, 50, n)],
        "d": [float(x) for x in rng.normal(0, 10, n)],
    }


def _schema():
    from spark_rapids_tpu.types import (
        DOUBLE, LONG, STRING, Schema, StructField,
    )
    return Schema((StructField("k", LONG), StructField("s", STRING),
                   StructField("v", LONG), StructField("d", DOUBLE)))


def _both_sessions():
    # broadcast planning off: these tests cover the shuffled-exchange path
    # (tiny inputs would all fall under the broadcast threshold otherwise);
    # broadcast planning has its own suite in test_broadcast.py
    no_bcast = {"spark.rapids.sql.broadcastSizeThreshold": "-1"}
    return TpuSession(no_bcast), TpuSession(no_bcast, mesh_devices=8)


@needs_8
def test_plan_contains_exchange():
    single, dist = _both_sessions()
    rng = np.random.default_rng(0)
    data, sch = _data(rng), _schema()
    df = dist.from_pydict(data, sch, batch_rows=64)
    tree = df.group_by("k").agg((F.sum("v"), "sv"))._exec().tree_string()
    assert "ShuffleExchangeExec" in tree
    assert "AggregateExec[partial" in tree
    assert "AggregateExec[final" in tree
    # single-partition session: no exchange nodes
    df1 = single.from_pydict(data, sch, batch_rows=64)
    tree1 = df1.group_by("k").agg((F.sum("v"), "sv"))._exec().tree_string()
    assert "ShuffleExchangeExec" not in tree1


@needs_8
def test_distributed_groupby_matches_single():
    single, dist = _both_sessions()
    rng = np.random.default_rng(1)
    data, sch = _data(rng), _schema()

    def run(sess):
        df = sess.from_pydict(data, sch, batch_rows=64)
        return _sorted(df.group_by("k").agg(
            (F.sum("v"), "sv"), (F.count(), "c"), (F.min("d"), "mn"),
            (F.max("d"), "mx"), (F.avg("v"), "av")).collect())

    assert run(dist) == run(single)


@needs_8
def test_distributed_groupby_string_keys():
    single, dist = _both_sessions()
    rng = np.random.default_rng(2)
    data, sch = _data(rng), _schema()

    def run(sess):
        df = sess.from_pydict(data, sch, batch_rows=64)
        return _sorted(df.group_by("s").agg(
            (F.sum("v"), "sv"), (F.count(), "c")).collect())

    assert run(dist) == run(single)


@needs_8
def test_distributed_groupby_long_string_keys():
    """Keys > 64 bytes: the measured exchange width must prevent the
    fixed-width codec from truncating (review finding r1)."""
    single, dist = _both_sessions()
    from spark_rapids_tpu.types import LONG, STRING, Schema, StructField
    base = "x" * 100
    rng = np.random.default_rng(3)
    ks = [base + ["AA", "BB", "CC"][int(i)] for i in rng.integers(0, 3, 96)]
    vs = [int(x) for x in rng.integers(0, 9, 96)]
    sch = Schema((StructField("k", STRING), StructField("v", LONG)))
    data = {"k": ks, "v": vs}

    def run(sess):
        df = sess.from_pydict(data, sch, batch_rows=16)
        return _sorted(df.group_by("k").agg((F.sum("v"), "sv")).collect())

    assert run(dist) == run(single)


@needs_8
def test_distributed_groupby_skewed_keys():
    """All rows in one key → one partition takes everything; the measured
    slot capacity must still fit every row."""
    single, dist = _both_sessions()
    from spark_rapids_tpu.types import LONG, Schema, StructField
    sch = Schema((StructField("k", LONG), StructField("v", LONG)))
    data = {"k": [5] * 300, "v": list(range(300))}

    def run(sess):
        df = sess.from_pydict(data, sch, batch_rows=64)
        return df.group_by("k").agg((F.sum("v"), "sv"),
                                    (F.count(), "c")).collect()

    assert run(dist) == run(single) == [(5, sum(range(300)), 300)]


@needs_8
def test_distributed_distinct():
    single, dist = _both_sessions()
    rng = np.random.default_rng(4)
    data, sch = _data(rng), _schema()

    def run(sess):
        df = sess.from_pydict(data, sch, batch_rows=64)
        return _sorted(df.select("k", "s").distinct().collect())

    assert run(dist) == run(single)


@needs_8
@pytest.mark.parametrize("how", ["inner", "left_outer", "right_outer",
                                 "full_outer", "left_semi", "left_anti"])
def test_distributed_join_matches_single(how):
    single, dist = _both_sessions()
    from spark_rapids_tpu.types import LONG, STRING, Schema, StructField
    rng = np.random.default_rng(5)
    lsch = Schema((StructField("k", LONG), StructField("lv", LONG)))
    rsch = Schema((StructField("k", LONG), StructField("rv", STRING)))
    ldata = {"k": [int(x) for x in rng.integers(0, 30, 200)],
             "lv": [int(x) for x in rng.integers(0, 1000, 200)]}
    rdata = {"k": [int(x) for x in rng.integers(10, 40, 150)],
             "rv": [f"r{int(x)}" for x in rng.integers(0, 99, 150)]}

    def run(sess):
        l = sess.from_pydict(ldata, lsch, batch_rows=64)
        r = sess.from_pydict(rdata, rsch, batch_rows=64)
        return _sorted(l.join(r, on="k", how=how).collect())

    assert run(dist) == run(single)


@needs_8
def test_distributed_join_plan_shape():
    single, dist = _both_sessions()
    from spark_rapids_tpu.types import LONG, Schema, StructField
    lsch = Schema((StructField("k", LONG), StructField("lv", LONG)))
    rsch = Schema((StructField("k", LONG), StructField("rv", LONG)))
    l = dist.from_pydict({"k": [1, 2, 3], "lv": [10, 20, 30]}, lsch)
    r = dist.from_pydict({"k": [1, 2, 3], "rv": [10, 20, 30]}, rsch)
    tree = l.join(r, on="k")._exec().tree_string()
    assert "ShuffledHashJoinExec" in tree
    assert tree.count("ShuffleExchangeExec") == 2


@needs_8
def test_distributed_join_with_condition():
    single, dist = _both_sessions()
    from spark_rapids_tpu.types import LONG, Schema, StructField
    rng = np.random.default_rng(6)
    lsch = Schema((StructField("k", LONG), StructField("lv", LONG)))
    rsch = Schema((StructField("k", LONG), StructField("rv", LONG)))
    ldata = {"k": [int(x) for x in rng.integers(0, 10, 80)],
             "lv": [int(x) for x in rng.integers(0, 100, 80)]}
    rdata = {"k": [int(x) for x in rng.integers(0, 10, 80)],
             "rv": [int(x) for x in rng.integers(0, 100, 80)]}

    def run(sess):
        l = sess.from_pydict(ldata, lsch, batch_rows=32)
        r = sess.from_pydict(rdata, rsch, batch_rows=32)
        return _sorted(l.join(r, on="k",
                              condition=col("lv") > col("rv")).collect())

    assert run(dist) == run(single)


@needs_8
def test_groupby_after_join_distributed():
    """Exchange → join → exchange → aggregate, the canonical 2-stage plan."""
    single, dist = _both_sessions()
    from spark_rapids_tpu.types import LONG, Schema, StructField
    rng = np.random.default_rng(7)
    lsch = Schema((StructField("k", LONG), StructField("g", LONG)))
    rsch = Schema((StructField("k", LONG), StructField("w", LONG)))
    ldata = {"k": [int(x) for x in rng.integers(0, 25, 150)],
             "g": [int(x) for x in rng.integers(0, 5, 150)]}
    rdata = {"k": [int(x) for x in rng.integers(0, 25, 100)],
             "w": [int(x) for x in rng.integers(1, 10, 100)]}

    def run(sess):
        l = sess.from_pydict(ldata, lsch, batch_rows=64)
        r = sess.from_pydict(rdata, rsch, batch_rows=64)
        j = l.join(r, on="k")
        return _sorted(j.group_by("g").agg((F.sum("w"), "sw"),
                                           (F.count(), "c")).collect())

    assert run(dist) == run(single)


@needs_8
def test_shuffle_plan_exchange_disabled():
    sess = TpuSession({"spark.rapids.tpu.shuffle.planExchange": False},
                      mesh_devices=8)
    from spark_rapids_tpu.types import LONG, Schema, StructField
    sch = Schema((StructField("k", LONG), StructField("v", LONG)))
    df = sess.from_pydict({"k": [1, 1, 2], "v": [1, 2, 3]}, sch)
    tree = df.group_by("k").agg((F.sum("v"), "sv"))._exec().tree_string()
    assert "ShuffleExchangeExec" not in tree
    assert _sorted(df.group_by("k").agg((F.sum("v"), "sv")).collect()) \
        == [(1, 3), (2, 3)]


def _kv_schema():
    from spark_rapids_tpu.types import LONG, STRING, Schema, StructField
    return Schema((StructField("k", LONG), StructField("tag", STRING)))


@needs_8
def test_exchange_streams_in_bounded_rounds():
    # input many times the per-round budget: the exchange must run
    # MULTIPLE rounds with spillable staging, and results stay exact
    single, _ = _both_sessions()
    dist = TpuSession({"spark.rapids.sql.broadcastSizeThreshold": "-1",
                       "spark.rapids.sql.exchange.roundBytes": "16384",
                       # keep the upstream coalescer from folding the
                       # whole input into one batch before the exchange
                       "spark.rapids.sql.batchSizeBytes": "8192"},
                      mesh_devices=8)
    rng = np.random.default_rng(5)
    data, sch = _data(rng, n=1600), _schema()
    # joins exchange RAW rows (a partial aggregate would collapse to one
    # tiny state batch before the exchange)
    left = dist.from_pydict(data, sch, batch_rows=64)
    right = dist.from_pydict(
        {"k": list(range(7)), "tag": [f"t{i}" for i in range(7)]},
        _kv_schema(), batch_rows=64)
    q = left.join(right, on="k", how="inner")
    ex = q._exec()
    got = _sorted(ex.collect())
    sl = single.from_pydict(data, sch, batch_rows=64)
    sr = single.from_pydict(
        {"k": list(range(7)), "tag": [f"t{i}" for i in range(7)]},
        _kv_schema(), batch_rows=64)
    ref = _sorted(sl.join(sr, on="k", how="inner").collect())
    assert got == ref

    def find_exchanges(e, out):
        from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
        if isinstance(e, ShuffleExchangeExec):
            out.append(e)
        for c in e.children:
            find_exchanges(c, out)
        return out
    exchanges = find_exchanges(ex, [])
    assert exchanges, "no exchange planned"
    rounds = [getattr(x, "rounds", 0) for x in exchanges]
    assert max(rounds) > 1, rounds  # the big side ran multiple rounds
