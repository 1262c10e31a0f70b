"""Device-side shuffle partitioning (ISSUE 9): serialize_slice
byte-equality against the gather formulation across every column
family, the one-pass device split (counts + stable permutation + packed
D2H), zero host-side gathers on the device lanes (structural), engine
on/off equality under the PR 3 forced-spill recipe, seeded
`shuffle.decode` injection placement invariance across lanes, and the
vectorized range-key materialization."""

import decimal
import os
import sys
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.transfer import (fetch_batch_host,
                                                fetch_split_host)
from spark_rapids_tpu.shuffle import manager as shuffle_mgr
from spark_rapids_tpu.shuffle import serializer as ser
from spark_rapids_tpu.shuffle.manager import (HostShuffleReader,
                                              HostShuffleWriter,
                                              partition_batch_host,
                                              shuffle_manager)
from spark_rapids_tpu.types import (DOUBLE, INT, LONG, STRING, ArrayType,
                                    DecimalType, MapType, Schema,
                                    StructField, StructType)


@pytest.fixture(autouse=True)
def _isolation():
    prev = C.active_conf()
    faults.install(None)
    yield
    faults.install(None)
    C.set_active_conf(prev)


def _sorted(rows):
    return sorted(rows, key=repr)


def _rich_schema():
    return Schema((
        StructField("i", INT), StructField("l", LONG),
        StructField("d", DOUBLE), StructField("s", STRING),
        StructField("a", ArrayType(LONG)),
        StructField("m", MapType(LONG, STRING)),
        StructField("st", StructType((StructField("x", LONG),
                                      StructField("y", STRING)))),
        StructField("dec", DecimalType(30, 2)),
    ))


def _rich_host_batch(n=97):
    rng = np.random.default_rng(7)
    data = {
        "i": [None if x % 11 == 0 else int(x) for x in range(n)],
        "l": [int(x) for x in rng.integers(-10**12, 10**12, n)],
        "d": [None if x % 7 == 0 else float(rng.standard_normal())
              for x in range(n)],
        "s": [None if x % 5 == 0 else ("värde-%d" % x) * (x % 4)
              for x in range(n)],
        "a": [None if x % 9 == 0 else [int(v) for v in range(x % 5)]
              for x in range(n)],
        "m": [None if x % 8 == 0 else {int(k): f"v{k}"
                                       for k in range(x % 3)}
              for x in range(n)],
        "st": [None if x % 13 == 0 else {"x": int(x), "y": f"s{x}"}
               for x in range(n)],
        "dec": [None if x % 6 == 0
                else decimal.Decimal(x * 123456789).scaleb(-2)
                for x in range(n)],
    }
    batch = ColumnarBatch.from_pydict(data, _rich_schema())
    cols, nn = fetch_batch_host(batch)
    return ColumnarBatch(cols, nn, batch.schema), batch


# ---------------------------------------------------------------------------
# serializer: slice vs gather byte equality
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lo,hi", [(0, 0), (0, 97), (5, 5), (3, 41),
                                   (40, 97), (0, 1), (96, 97)])
def test_serialize_slice_matches_gather_bytes(lo, hi):
    """serialize_slice over any row range is byte-identical to
    serialize_batch over the gathered rows — across string/array/map/
    struct/decimal128 offsets, null masks and empty slices."""
    hb, _dev = _rich_host_batch()
    sliced = ser.serialize_slice(hb, lo, hi)
    gathered = ser.serialize_batch(
        ser.host_gather_batch(hb, np.arange(lo, hi)))
    assert sliced == gathered
    out = ser.deserialize_batch(sliced, hb.schema)
    assert out.to_pylist() == \
        ser.host_gather_batch(hb, np.arange(lo, hi)).to_pylist()


def test_host_slice_matches_gather_arrays():
    """host_slice_column reproduces host_gather_column's buckets and
    padding exactly (the byte-identity the frame equality rides on)."""
    import jax
    hb, _dev = _rich_host_batch()
    for lo, hi in [(0, 10), (17, 64), (0, 97), (96, 96)]:
        a = ser.host_slice_batch(hb, lo, hi)
        b = ser.host_gather_batch(hb, np.arange(lo, hi))
        la = jax.tree_util.tree_leaves(a.columns)
        lb = jax.tree_util.tree_leaves(b.columns)
        assert len(la) == len(lb)
        for x, y in zip(la, lb):
            assert x.shape == y.shape and x.dtype == y.dtype
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_partition_batch_host_stable_slices():
    """The rewritten host partitioner (ONE argsort + whole-batch gather
    + slice emission) keeps the per-partition stable-order contract."""
    hb, _dev = _rich_host_batch()
    n = hb.num_rows_host
    rng = np.random.default_rng(1)
    pid = rng.integers(0, 5, n)
    parts = partition_batch_host(hb, pid, 5)
    rows = hb.to_pylist()
    for p in range(5):
        expect = [rows[i] for i in range(n) if pid[i] == p]
        assert parts[p].to_pylist() == expect


# ---------------------------------------------------------------------------
# device split: counts + permutation + packed D2H + slice write
# ---------------------------------------------------------------------------

def _device_write(handle, mgr, batch, pid, map_id=0):
    """The device lane's write, driven at the writer API level: one
    traced split, one packed D2H, slice serialization."""
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.partition_split import (partition_table,
                                                      reorder_columns)
    n = batch.num_rows_host
    cap = batch.capacity
    full_pid = np.full(cap, handle.n_partitions, np.int64)
    full_pid[:n] = pid
    counts, order = partition_table(jnp.asarray(full_pid),
                                    batch.num_rows, cap,
                                    handle.n_partitions)
    cols = reorder_columns(batch.columns, order, batch.num_rows)
    host_counts, host_cols = fetch_split_host(counts, cols)
    bounds = np.concatenate([[0], np.cumsum(host_counts)])
    packed = ColumnarBatch(host_cols, n, batch.schema)
    w = HostShuffleWriter(handle, map_id, mgr)
    w.write_slices(packed, bounds)
    return w


def _host_write(handle, mgr, batch, pid, map_id=0):
    parts = partition_batch_host(batch, pid, handle.n_partitions)
    w = HostShuffleWriter(handle, map_id, mgr)
    w.write([[p] if p.num_rows_host else [] for p in parts])
    return w


def test_device_and_host_lanes_decode_identically():
    """Same batch, same pids through both lanes: identical frame
    counts, identical per-partition decoded rows."""
    _hb, dev = _rich_host_batch()
    n = dev.num_rows_host
    rng = np.random.default_rng(2)
    pid = rng.integers(0, 3, n)
    mgr = shuffle_manager()
    rows = dev.to_pylist()
    got = {}
    for lane, write in (("device", _device_write), ("host", _host_write)):
        handle = mgr.register(3, dev.schema)
        try:
            w = write(handle, mgr, dev, pid)
            got[lane] = (w.frames_written, [
                [r for b in HostShuffleReader(handle, mgr)
                 .read_partition(p) for r in b.to_pylist()]
                for p in range(3)])
        finally:
            mgr.unregister(handle)
    assert got["device"][0] == got["host"][0]
    assert got["device"][1] == got["host"][1]
    for p in range(3):
        expect = [rows[i] for i in range(n) if pid[i] == p]
        assert got["device"][1][p] == expect


def test_seeded_decode_injection_placement_unchanged_by_lane():
    """The chaos contract (PR 4/5): `shuffle.decode` verdicts key on
    (partition, global frame ordinal). The device lane preserves frame
    count and order, so a seeded corrupt plan must quarantine exactly
    the same frames as the host lane."""
    _hb, dev = _rich_host_batch()
    n = dev.num_rows_host
    rng = np.random.default_rng(3)
    pid = rng.integers(0, 4, n)
    mgr = shuffle_manager()
    spec = "shuffle.decode:prob=0.4,seed=11,kind=corrupt"
    outcomes = {}
    for lane, write in (("device", _device_write), ("host", _host_write)):
        handle = mgr.register(4, dev.schema)
        try:
            # two map tasks so global frame ordinals span map outputs
            write(handle, mgr, dev, pid, map_id=0)
            write(handle, mgr, dev, pid, map_id=1)
            faults.install(spec)
            r = HostShuffleReader(handle, mgr)
            corrupted = set()
            ok_rows = []
            for p in range(4):
                ordinal = 0
                for path in handle.map_outputs:
                    for fr in r._fetch_segment(path, p):
                        try:
                            b = r._decode(fr, f"p{p}:{ordinal}")
                            ok_rows.extend(b.to_pylist())
                        except faults.IntegrityError:
                            corrupted.add((p, ordinal))
                        ordinal += 1
            outcomes[lane] = (corrupted, _sorted(ok_rows))
        finally:
            faults.install(None)
            mgr.unregister(handle)
    assert outcomes["device"][0], "the seeded plan never fired"
    assert outcomes["device"][0] == outcomes["host"][0]
    assert outcomes["device"][1] == outcomes["host"][1]


# ---------------------------------------------------------------------------
# exchange integration: zero host gathers, on/off equality, events
# ---------------------------------------------------------------------------

def _join_query(sess, seed=4):
    from spark_rapids_tpu.api.session import TpuSession  # noqa: F401
    rng = np.random.default_rng(seed)
    ldata = {"k": [int(x) for x in rng.integers(0, 20, 300)],
             "v": [int(x) for x in rng.integers(0, 50, 300)]}
    rdata = {"k": [int(x) for x in rng.integers(0, 20, 200)],
             "w": [["a", "bb", None, "dddd"][int(x)]
                   for x in rng.integers(0, 4, 200)]}
    lsch = Schema((StructField("k", LONG), StructField("v", LONG)))
    rsch = Schema((StructField("k", LONG), StructField("w", STRING)))
    l = sess.from_pydict(ldata, lsch, batch_rows=64)
    r = sess.from_pydict(rdata, rsch, batch_rows=64)
    return l.join(r, on="k")


def test_hash_lane_pins_host_gathers_at_zero():
    """Acceptance (ISSUE 9): with devicePartition on (the default), the
    hash lane performs ZERO host-side row gathers per written batch —
    asserted structurally on the serializer's host-gather counter over
    a whole host-shuffled join."""
    from spark_rapids_tpu.api.session import TpuSession
    sess = TpuSession({"spark.rapids.sql.shuffle.partitions": "4",
                       "spark.rapids.sql.broadcastSizeThreshold": "-1"})
    q = _join_query(sess)
    before = ser.host_gather_calls()
    got = q.collect()
    assert got  # the query actually ran
    assert ser.host_gather_calls() == before, \
        "device-partition lane fell back to host gathers"


# moved to the slow tier by ISSUE 13 budget relief (18s: three full
# query runs; slice-vs-gather byte equality keeps the lane proven
# tier-1)
@pytest.mark.slow
def test_conf_off_restores_host_lane_and_results_match():
    from spark_rapids_tpu.api.session import TpuSession
    base = {"spark.rapids.sql.shuffle.partitions": "4",
            "spark.rapids.sql.broadcastSizeThreshold": "-1"}
    on = _join_query(TpuSession(base)).collect()
    off_sess = TpuSession(dict(
        base, **{"spark.rapids.tpu.shuffle.devicePartition.enabled":
                 "false"}))
    before = ser.host_gather_calls()
    off = _join_query(off_sess).collect()
    assert ser.host_gather_calls() > before  # host lane engaged
    plain = _join_query(__import__(
        "spark_rapids_tpu.api.session", fromlist=["TpuSession"]
    ).TpuSession()).collect()
    assert _sorted(on) == _sorted(off) == _sorted(plain)


def test_roundrobin_and_single_ride_device_lane():
    from spark_rapids_tpu.api.session import TpuSession
    rng = np.random.default_rng(0)
    sch = Schema((StructField("k", LONG), StructField("s", STRING)))
    data = {"k": [int(x) for x in rng.integers(-100, 100, 300)],
            "s": [None if x % 7 == 0 else f"v{x}"
                  for x in rng.integers(0, 60, 300)]}
    sess = TpuSession()
    df = sess.from_pydict(data, sch, batch_rows=64)
    before = ser.host_gather_calls()
    rr = df.repartition(4).collect()
    single = df.coalesce(1).collect()
    assert ser.host_gather_calls() == before
    assert _sorted(rr) == _sorted(single) == _sorted(df.collect())
    off = TpuSession({
        "spark.rapids.tpu.shuffle.devicePartition.enabled": "false"})
    df_off = off.from_pydict(data, sch, batch_rows=64)
    assert _sorted(df_off.repartition(4).collect()) == _sorted(rr)


def test_forced_spill_recipe_on_off_equality(tmp_path):
    """Engine-level equality under the PR 3 forced-spill recipe (tiny
    host spill limit + spill dir + small batches): the host-shuffled
    join and the range-partitioned global sort return identical rows
    with the device lane on and off."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.memory.budget import (reset_memory_budget)
    from spark_rapids_tpu.memory.catalog import reset_buffer_catalog
    base = {
        "spark.rapids.sql.shuffle.partitions": "3",
        "spark.rapids.sql.broadcastSizeThreshold": "-1",
        "spark.rapids.sql.batchSizeBytes": str(16 * 1024),
        "spark.rapids.memory.host.spillStorageSize": "1k",
        "spark.rapids.memory.spillDirectory": str(tmp_path),
    }
    off = dict(base, **{
        "spark.rapids.tpu.shuffle.devicePartition.enabled": "false"})
    try:
        reset_buffer_catalog()
        reset_memory_budget(256 * 1024)

        def drive(settings):
            sess = TpuSession(settings)
            join_rows = _join_query(sess, seed=9).collect()
            rng = np.random.default_rng(5)
            sch = Schema((StructField("k", LONG),
                          StructField("s", STRING)))
            data = {"k": [int(x) for x in rng.integers(-50, 50, 400)],
                    "s": [None if x % 7 == 0 else f"v{x}"
                          for x in rng.integers(0, 60, 400)]}
            df = sess.from_pydict(data, sch, batch_rows=64)
            sort_rows = df.sort("k").collect()
            return join_rows, sort_rows

        j_on, s_on = drive(base)
        j_off, s_off = drive(off)
        assert _sorted(j_on) == _sorted(j_off)
        assert [r[0] for r in s_on] == [r[0] for r in s_off] \
            == sorted(r[0] for r in s_on)
    finally:
        reset_buffer_catalog()
        reset_memory_budget()


def test_shuffle_write_event_and_metrics(monkeypatch, tmp_path):
    """One shuffle_write event per map task, lane=device, with the
    pack/serialize/io split, one gather_stats record per execution;
    shufflePackTimeNs and numGathers register on the exchange."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs import events
    rows = []
    real = events.emit

    def spy(kind, **fields):
        rows.append({"kind": kind, **fields})
        real(kind, **fields)

    monkeypatch.setattr(events, "emit", spy)
    # a live bus: GatherTracker.emit_event short-circuits without one
    events.enable(str(tmp_path), "MODERATE")
    try:
        sess = TpuSession({"spark.rapids.sql.shuffle.partitions": "3",
                           "spark.rapids.sql.broadcastSizeThreshold":
                               "-1"})
        q = _join_query(sess)
        plan = q._exec()
        out = [r for gen_b in plan.execute()
               for r in gen_b.to_pylist()]
        assert out
        writes = [r for r in rows if r["kind"] == "shuffle_write"]
        assert writes and all(w["lane"] == "device" for w in writes)
        assert all(w["frames"] >= 1 and w["bytes"] > 0 for w in writes)
        # the exchange follows the wired-exec convention: one
        # gather_stats record per execution covering the write phase
        gstats = [r for r in rows if r["kind"] == "gather_stats"
                  and r.get("op") == "HostShuffleExchangeExec"]
        assert gstats and all(g["count"] >= 1 for g in gstats)
        metrics = plan.all_metrics(level=2)
        packs = {k: v for k, v in metrics.items()
                 if k.endswith("shufflePackTimeNs")}
        assert packs and any(v > 0 for v in packs.values())
        gathers = {k: v for k, v in metrics.items()
                   if "HostShuffleExchangeExec" in k
                   and k.endswith("numGathers")}
        assert gathers and any(v > 0 for v in gathers.values())
    finally:
        events.reset_event_bus()


def test_empty_batch_stays_on_device_lane():
    """An empty batch with devicePartition on writes zero frames, does
    zero host gathers, and attributes to the DEVICE lane in both the
    shuffle counters and the shuffle_write event."""
    from spark_rapids_tpu.columnar.batch import empty_batch
    from spark_rapids_tpu.config import RapidsConf
    from spark_rapids_tpu.exec.basic import InMemoryScanExec
    from spark_rapids_tpu.exec.exchange import HostShuffleExchangeExec
    from spark_rapids_tpu.expr.core import col
    sch = Schema((StructField("k", LONG), StructField("v", LONG)))
    batches = [empty_batch(sch),
               ColumnarBatch.from_pydict({"k": [1, 2, 3],
                                          "v": [4, 5, 6]}, sch)]
    ex = HostShuffleExchangeExec([col("k")],
                                 InMemoryScanExec(batches, sch), 3,
                                 RapidsConf({}))
    g0 = ser.host_gather_calls()
    c0 = shuffle_mgr.counters()
    rows = [r for gen in ex.execute_partitions()
            for b in gen for r in b.to_pylist()]
    assert sorted(rows) == [(1, 4), (2, 5), (3, 6)]
    assert ser.host_gather_calls() == g0
    c1 = shuffle_mgr.counters()
    assert c1["batches"] - c0["batches"] == 2
    assert c1["device_batches"] - c0["device_batches"] == 2
    assert c1["host_batches"] == c0["host_batches"]


def test_profile_report_shuffle_rollup():
    import profile_report
    evs = [
        {"kind": "shuffle_write", "lane": "device", "bytes": 2048,
         "frames": 3, "pack_ns": 1000, "serialize_ns": 2000,
         "io_ns": 500},
        {"kind": "shuffle_write", "lane": "host", "bytes": 1024,
         "frames": 2, "pack_ns": 0, "serialize_ns": 700, "io_ns": 300},
    ]
    report = profile_report.build_report(evs)
    assert "shuffle writes: 2 maps" in report
    assert "5 frames" in report
    assert "1 device-partitioned" in report


def test_bench_shuffle_attribution_delta():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    try:
        import bench
    finally:
        sys.path.pop(0)
    first = bench.shuffle_attribution()
    for key in ("batches", "device_batches", "host_batches", "frames",
                "bytes", "pack_ns", "serialize_ns", "io_ns",
                "host_gathers"):
        assert key in first
    _hb, dev = _rich_host_batch(40)
    mgr = shuffle_manager()
    handle = mgr.register(2, dev.schema)
    try:
        _device_write(handle, mgr, dev, np.arange(40) % 2)
    finally:
        mgr.unregister(handle)
    delta = bench.shuffle_attribution()
    assert delta["batches"] == 1 and delta["device_batches"] == 1
    assert delta["frames"] == 2 and delta["bytes"] > 0
    assert delta["host_gathers"] == 0


# ---------------------------------------------------------------------------
# range-key vectorization
# ---------------------------------------------------------------------------

def test_host_key_array_matches_object_path():
    """The vectorized numeric/string range-key materialization returns
    exactly what the to_pylist object path returned (None for nulls,
    python floats for f32/f64 incl NaN, utf-8 strings), with and
    without a sampling stride."""
    from spark_rapids_tpu.columnar.column import (Column, StringColumn,
                                                  build_column)
    from spark_rapids_tpu.exec.exchange import _host_key_array
    from spark_rapids_tpu.types import FLOAT

    n = 60
    vals = [None if x % 7 == 0 else float(x) * 1.5 for x in range(n)]
    vals[3] = float("nan")
    fcol = build_column(vals, FLOAT)
    cols, _ = fetch_batch_host(ColumnarBatch(
        [fcol], n, Schema((StructField("f", FLOAT),))))
    got = _host_key_array(cols[0], n)
    expect = np.array(cols[0].to_pylist(n), dtype=object)
    assert len(got) == n
    for g, e in zip(got, expect):
        if e is None or e != e:  # null / NaN
            assert g is None or g != g
            assert (g is None) == (e is None)
        else:
            assert type(g) is type(e) and g == e

    svals = [None if x % 5 == 0 else f"s{x}-å" for x in range(n)]
    scol = build_column(svals, STRING)
    cols, _ = fetch_batch_host(ColumnarBatch(
        [scol], n, Schema((StructField("s", STRING),))))
    got = _host_key_array(cols[0], n)
    assert list(got) == scol.to_pylist(n)

    idx = np.arange(0, n, 7, dtype=np.int64)
    got = _host_key_array(cols[0], n, idx)
    assert list(got) == [scol.to_pylist(n)[i] for i in idx]

    # nested types decline the fast path (caller falls back)
    acol = build_column([[1], None, [2, 3]], ArrayType(LONG))
    assert _host_key_array(acol, 3) is None


def test_range_sort_unaffected_by_device_conf():
    """Range partitioning keeps the host lane (sampled bounds are host
    objects) and still sorts globally with the conf on or off."""
    from spark_rapids_tpu.api.session import TpuSession
    rng = np.random.default_rng(6)
    sch = Schema((StructField("k", DOUBLE), StructField("s", STRING)))
    data = {"k": [None if x % 11 == 0 else float(v) for x, v in
                  enumerate(rng.standard_normal(250))],
            "s": [f"v{x}" for x in range(250)]}
    for extra in ({}, {"spark.rapids.tpu.shuffle.devicePartition.enabled":
                       "false"}):
        sess = TpuSession(dict(
            {"spark.rapids.sql.shuffle.partitions": "3"}, **extra))
        df = sess.from_pydict(data, sch, batch_rows=64)
        got = [r[0] for r in df.sort("k").collect()]
        expect = sorted(data["k"], key=lambda v: (v is not None, v))
        assert got == expect
