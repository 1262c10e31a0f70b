"""Packed host->device upload engine (ISSUE 10): byte-roundtrip
property tests across every column family, host-pack vs D2H-pack byte
identity, the forced-dd f64 staging formulation, the staging pool
(grow-on-miss / LIFO reuse / LRU trim / leak baseline), structural
1-transfer-per-scan-batch pinning, engine-level on/off equality (incl.
the PR 3 forced-spill unspill lane and the host shuffle read seam),
seeded `device.dispatch` chaos keying with order-independent placement,
the fused split+pack single-dispatch program, the `upload`
event/metrics surface, and the bench / profile_report roll-ups."""

import decimal
import os
import sys
from pathlib import Path

import numpy as np
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar import transfer
from spark_rapids_tpu.columnar import upload
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import Column, host_build
from spark_rapids_tpu.types import (BOOLEAN, BYTE, DOUBLE, FLOAT, INT, LONG,
                                    SHORT, STRING, ArrayType, DecimalType,
                                    MapType, Schema, StructField, StructType)

OFF = {"spark.rapids.tpu.transfer.packedUpload.enabled": "false"}


@pytest.fixture(autouse=True)
def _isolation():
    prev = C.active_conf()
    faults.install(None)
    yield
    faults.install(None)
    C.set_active_conf(prev)


def _rich_schema():
    return Schema((
        StructField("b", BOOLEAN), StructField("t", BYTE),
        StructField("h", SHORT), StructField("i", INT),
        StructField("l", LONG), StructField("f", FLOAT),
        StructField("d", DOUBLE), StructField("s", STRING),
        StructField("a", ArrayType(LONG)),
        StructField("m", MapType(LONG, STRING)),
        StructField("st", StructType((StructField("x", LONG),
                                      StructField("y", STRING)))),
        StructField("dec", DecimalType(30, 2)),
    ))


def _rich_data(n, rng):
    def maybe(v, i):
        return None if i % 7 == 3 else v
    return {
        "b": [maybe(bool(x % 2), i)
              for i, x in enumerate(rng.integers(0, 2, n))],
        "t": [maybe(int(x), i)
              for i, x in enumerate(rng.integers(-128, 128, n))],
        "h": [maybe(int(x), i)
              for i, x in enumerate(rng.integers(-3000, 3000, n))],
        "i": [maybe(int(x), i)
              for i, x in enumerate(rng.integers(-10**6, 10**6, n))],
        "l": [maybe(int(x), i)
              for i, x in enumerate(rng.integers(-2**40, 2**40, n))],
        "f": [maybe(float(np.float32(x)), i)
              for i, x in enumerate(rng.random(n))],
        "d": [maybe(float(x), i) for i, x in enumerate(rng.random(n))],
        "s": [maybe(["", "a", "bb", "wörld", "longer-string"][int(x)], i)
              for i, x in enumerate(rng.integers(0, 5, n))],
        "a": [maybe([int(y) for y in rng.integers(0, 9, int(x))], i)
              for i, x in enumerate(rng.integers(0, 4, n))],
        "m": [maybe({int(y): "v" + str(y) for y in rng.integers(0, 5, x)},
                    i)
              for i, x in enumerate(rng.integers(0, 3, n))],
        "st": [maybe({"x": int(x), "y": maybe("s" + str(x), i + 1)}, i)
               for i, x in enumerate(rng.integers(0, 50, n))],
        "dec": [maybe(decimal.Decimal(int(x))
                      .scaleb(-2) * 10**int(abs(x) % 20), i)
                for i, x in enumerate(rng.integers(-10**6, 10**6, n))],
    }


def _leaf_equal(batch_a, batch_b):
    import jax
    la = jax.tree_util.tree_leaves(list(batch_a.columns))
    lb = jax.tree_util.tree_leaves(list(batch_b.columns))
    assert len(la) == len(lb)
    for a, b in zip(la, lb):
        na, nb = np.asarray(a), np.asarray(b)
        assert na.dtype == nb.dtype and na.shape == nb.shape, \
            (na.dtype, nb.dtype, na.shape, nb.shape)
        assert np.array_equal(na, nb, equal_nan=(na.dtype.kind == "f")), \
            na.dtype


# ---------------------------------------------------------------------------
# roundtrip properties
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [0, 5, 64, 200])
def test_packed_roundtrip_every_family(n, rng):
    """Packed upload of host columns is byte-identical (every leaf, incl.
    capacity padding) to the per-buffer device batch they came from."""
    sch = _rich_schema()
    dev = ColumnarBatch.from_pydict(_rich_data(n, rng), sch)
    host_cols, hn = transfer.fetch_batch_host(dev)
    assert hn == n
    up = upload.packed_upload_batch(host_cols, n, sch)
    _leaf_equal(dev, up)
    assert up.num_rows_host == n


def test_host_pack_matches_d2h_pack_bytes(rng):
    """pack_host_batch lays out EXACTLY the D2H wire format: the bytes
    equal np.asarray(_pack_jit(device_batch))."""
    sch = _rich_schema()
    dev = ColumnarBatch.from_pydict(_rich_data(37, rng), sch)
    host_cols, n = transfer.fetch_batch_host(dev)
    expect = np.asarray(transfer._pack_jit(dev))
    buf, total = upload.pack_host_batch(host_cols, n)
    try:
        assert total == expect.shape[0]
        assert (buf[:total] == expect).all()
    finally:
        upload.staging_pool().release(buf)


def test_capacity_padding_roundtrip(rng):
    """Columns grown past their natural bucket (capacity padding)
    roundtrip bit-exact, padding included."""
    with host_build():
        col = Column.from_numpy(
            np.arange(10, dtype=np.int64), LONG,
            validity=np.array([i % 3 != 1 for i in range(10)]),
            capacity=512)
    sch = Schema((StructField("x", LONG),))
    up = upload.packed_upload_batch([col], 10, sch)
    assert np.asarray(up.columns[0].data).shape == (512,)
    assert np.array_equal(np.asarray(up.columns[0].data), col.data)
    assert np.array_equal(np.asarray(up.columns[0].validity), col.validity)


def test_forced_dd_f64_staging(monkeypatch):
    """With the TPU dd-split forced on, f64 uploads stage as (hi, lo)
    float32 pairs and the device reconstructs hi + lo — the exact
    formulation jnp.asarray uses for f64 on a dd-emulating chip."""
    monkeypatch.setattr(transfer, "_dd_split", lambda: True)
    # values whose lo correction is a NORMAL float32 (or zero): XLA CPU
    # flushes subnormal f32 to zero, so a tiny-magnitude double's lo
    # term would legitimately differ from the numpy-computed oracle
    vals = np.array([1.25, 3.141592653589793, 1.0 / 3.0, 1e10 + 0.1,
                     0.0, -0.0, np.nan])
    with host_build():
        col = Column.from_numpy(vals, DOUBLE)
    sch = Schema((StructField("d", DOUBLE),))
    got = np.asarray(upload.packed_upload_batch(
        [col], len(vals), sch).columns[0].data)[: len(vals)]
    hi = vals.astype(np.float32)
    lo = (vals - hi.astype(np.float64)).astype(np.float32)
    expect = hi.astype(np.float64) + lo.astype(np.float64)
    assert np.array_equal(got, expect, equal_nan=True)


def test_upload_leaves_roundtrip(rng):
    """The unspill lane: arbitrary numpy leaf lists (dtypes, 2-D
    shapes) survive the packed leaf upload bit-exact."""
    leaves = [np.arange(10, dtype=np.int64), rng.random((3, 5)),
              np.array([True, False, True]),
              np.arange(4, dtype=np.int16),
              np.arange(6, dtype=np.uint8),
              np.array([], dtype=np.int32)]
    out = upload.upload_leaves(leaves, fault_key="unspill:test")
    assert len(out) == len(leaves)
    for a, b in zip(leaves, out):
        nb = np.asarray(b)
        assert nb.dtype == a.dtype and nb.shape == a.shape
        assert np.array_equal(nb, a)


def test_per_buffer_fallback_unrecognized_tree():
    """A column class the packer does not recognize keeps the
    per-buffer lane (conf on) — the documented nested-type escape
    hatch."""
    class OddColumn(Column):
        pass

    with host_build():
        col = OddColumn(np.arange(4, dtype=np.int64),
                        np.ones(4, np.bool_), LONG)
    before = upload.counters()
    batch = upload.to_device_batch([col], 4, Schema((StructField("x",
                                                                 LONG),)))
    after = upload.counters()
    assert after["per_buffer"] - before["per_buffer"] == 1
    assert after["packed"] - before["packed"] == 0
    assert np.array_equal(np.asarray(batch.columns[0].data),
                          np.arange(4))


# ---------------------------------------------------------------------------
# staging pool
# ---------------------------------------------------------------------------

def test_pool_grow_reuse_and_lru_trim():
    pool = upload.StagingPool()
    b1 = pool.acquire(1000)  # -> 1024 bucket, miss
    assert b1.shape == (1024,) and pool.misses == 1
    pool.release(b1)
    b2 = pool.acquire(900)  # same bucket: LIFO reuse hit
    assert b2 is b1 and pool.hits == 1
    pool.release(b2)
    assert pool.outstanding_bytes() == 0

    C.set_active_conf(C.RapidsConf(
        {"spark.rapids.tpu.transfer.packedUpload.poolBytes": "8k"}))
    pool = upload.StagingPool()
    bufs = [pool.acquire(4096) for _ in range(4)]
    assert pool.outstanding_bytes() == 4 * 4096  # in-flight never capped
    first_released = bufs[0]
    for b in bufs:
        pool.release(b)
    # cap 8k: the two OLDEST-returned buffers were trimmed
    assert pool.pooled_bytes() == 8192 and pool.trims == 2
    got = pool.acquire(4096)
    assert got is not first_released  # LRU victim really left the pool
    pool.release(got)
    assert pool.outstanding_bytes() == 0


def test_pool_presize_from_batch_size(tmp_path):
    """ISSUE 14 satellite (the PR 10 recorded TODO): configure()
    pre-sizes the bucket ladder from batchSizeBytes, so steady-state
    acquires at or under the target are ALL hits — the miss counter
    stays at zero."""
    pool = upload.StagingPool()
    added = pool.presize(64 * 1024, pool_cap=1 << 20)
    assert added == sum(256 << i for i in range(9))  # 256B..64KiB
    # every rung at or under the target acquires as a HIT
    for nbytes in (100, 600, 5000, 40_000, 65_536):
        buf = pool.acquire(nbytes)
        pool.release(buf)
    assert pool.misses == 0 and pool.hits == 5
    # past the target still grows on miss (the pre-ISSUE-14 behavior)
    big = pool.acquire(100_000)
    assert pool.misses == 1
    pool.release(big)
    # idempotent: a second presize with the rungs populated adds nothing
    assert pool.presize(64 * 1024, pool_cap=1 << 20) == 0
    # the cap bounds the ladder: a huge target stops at pool_cap
    capped = upload.StagingPool()
    capped.presize(1 << 30, pool_cap=4096)
    assert capped.pooled_bytes() <= 4096

    # the session-configure seam: a steady-state parquet scan hits the
    # pre-sized ladder with zero grow-on-miss allocations
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.api.session import TpuSession
    n = 4000
    pq.write_table(pa.table({
        "a": np.arange(n, dtype=np.int64),
        "b": np.arange(n, dtype=np.float64)}),
        tmp_path / "t.parquet")
    upload.reset_staging_pool()
    sess = TpuSession(
        {"spark.rapids.sql.batchSizeBytes": "1m",
         "spark.rapids.tpu.transfer.packedUpload.poolBytes": "16m"})
    proc = upload.staging_pool()
    assert proc.pooled_bytes() > 0 and proc.misses == 0  # pre-sized
    rows = sess.read_parquet(str(tmp_path / "t.parquet")).collect()
    assert len(rows) == n
    proc.settle()
    assert proc.misses == 0, proc.stats()  # zero grow-on-miss uploads
    upload.reset_staging_pool()


def test_concurrent_uploads_never_cross_contaminate():
    """Regression (found live via the PR 6 storm): PJRT CPU zero-copy
    is a PER-BUFFER decision — an aliased staging buffer returned to
    the pool and rewritten by another thread corrupted live device
    arrays. Eight lanes hammer the pool concurrently; every batch must
    read back its own values."""
    import threading
    sch = Schema((StructField("x", LONG), StructField("y", DOUBLE)))

    def mk(v):
        with host_build():
            return [Column.from_numpy(np.full(512, v, np.int64), LONG),
                    Column.from_numpy(np.full(512, float(v)), DOUBLE)]

    errs = []

    def lane(i):
        try:
            for k in range(15):
                v = i * 100 + k
                bt = upload.packed_upload_batch(mk(v), 512, sch)
                x = np.asarray(bt.columns[0].data)[:512]
                y = np.asarray(bt.columns[1].data)[:512]
                assert (x == v).all() and (y == float(v)).all(), \
                    (i, k, x[:3], y[:3])
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=lane, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errs, errs[:2]
    upload.staging_pool().settle()
    assert upload.staging_pool().outstanding_bytes() == 0


def test_pool_discard_on_upload_error(monkeypatch):
    """An injected failure during the device copy discards the staging
    buffer (never re-pooled) and leaves no outstanding bytes — the
    conftest tripwire baseline."""
    pool = upload.reset_staging_pool()
    faults.install("device.dispatch:prob=1,seed=1,kind=device,max=1")
    with host_build():
        col = Column.from_numpy(np.arange(8, dtype=np.int64), LONG)
    with pytest.raises(faults.InjectedDeviceError):
        upload.packed_upload_batch([col], 8, Schema(
            (StructField("x", LONG),)), fault_key="k0")
    faults.install(None)
    assert pool.outstanding_bytes() == 0
    assert pool.pooled_bytes() == 0  # discarded, not pooled
    upload.reset_staging_pool()


# ---------------------------------------------------------------------------
# structural transfer pinning + engine equality
# ---------------------------------------------------------------------------

def _write_parquet(tmp_path, rows=600):
    import pyarrow as pa
    import pyarrow.parquet as pq
    rng = np.random.default_rng(11)
    t = pa.table({
        "k": rng.integers(0, 20, rows),
        "v": rng.integers(0, 50, rows),
        "s": [None if i % 9 == 4 else f"s{i % 13}" for i in range(rows)],
    })
    path = os.path.join(str(tmp_path), "data.parquet")
    pq.write_table(t, path)
    return path


def test_scan_batch_pins_one_transfer(tmp_path):
    """Acceptance (ISSUE 10): with packedUpload on (the default) a scan
    batch crosses host->device as exactly ONE transfer; off, it pays
    one per buffer (2-3 per column)."""
    from spark_rapids_tpu.api.session import TpuSession
    path = _write_parquet(tmp_path)

    def drive(settings):
        sess = TpuSession(settings)
        before = upload.counters()
        rows = sess.read_parquet(path).collect()
        after = upload.counters()
        return rows, {k: after[k] - before[k] for k in after}

    rows_on, d_on = drive({})
    assert d_on["uploads"] >= 1 and d_on["packed"] == d_on["uploads"]
    assert d_on["transfers"] == d_on["uploads"]  # ONE per batch
    rows_off, d_off = drive(dict(OFF))
    assert d_off["per_buffer"] == d_off["uploads"] >= 1
    # 3 columns: fixed(2) + fixed(2) + dictionary-coded string(4:
    # codes + validity + dict offsets/bytes — parquet dictionary-encodes
    # strings by default, ISSUE 18) buffers + row count
    assert d_off["transfers"] == 9 * d_off["uploads"]
    assert sorted(rows_on, key=repr) == sorted(rows_off, key=repr)


def _join_agg_query(sess, seed=0):
    from spark_rapids_tpu.api import functions as F
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
    return l.join(r, on="k").group_by("k").agg(
        (F.count(), "n")).sort("k")


# moved to the slow tier by ISSUE 13 budget relief (47s: engine-level
# on/off equality; byte-roundtrip property tests + the forced-spill
# equality drive stay tier-1)
@pytest.mark.slow
def test_engine_scan_join_agg_on_off_equality(tmp_path):
    """Engine-level equality: parquet scan -> host-shuffled join ->
    agg -> sort returns identical rows with packedUpload on and off
    (the scan AND shuffle-read seams both ride the packed lane)."""
    from spark_rapids_tpu.api.session import TpuSession
    path = _write_parquet(tmp_path)
    base = {"spark.rapids.sql.shuffle.partitions": "4",
            "spark.rapids.sql.broadcastSizeThreshold": "-1"}

    def drive(settings):
        sess = TpuSession(settings)
        df = sess.read_parquet(path)
        from spark_rapids_tpu.api import functions as F
        joined = df.join(sess.read_parquet(path).select("k"), on="k")
        q = joined.group_by("k").agg((F.count(), "n")).sort("k")
        return q.collect()

    on_rows = drive(base)
    off_rows = drive(dict(base, **OFF))
    assert on_rows == off_rows


def _rows_equal_float_tolerant(xs, ys, float_cols=(1,)):
    """Exact on keys/counts, 1e-9-relative on float sums (the PR 3
    forced-spill tolerance: OOM-retry SPLIT points depend on thread
    interleaving, so float reduction order may differ)."""
    if len(xs) != len(ys):
        return False
    for x, y in zip(xs, ys):
        for i, (a, b) in enumerate(zip(x, y)):
            if i in float_cols:
                if abs(a - b) > 1e-9 * max(abs(a), abs(b), 1.0):
                    return False
            elif a != b:
                return False
    return True


@pytest.mark.slow
def test_forced_spill_unspill_packed_equality(tmp_path):
    """PR 3 forced-spill recipe (the proven scan->filter->join->agg->
    sort parquet shape under a 192 KiB budget): the catalog really
    spills, so unspill restores batches THROUGH the packed leaf lane —
    results identical with packedUpload on and off (float sums to
    reduction-order tolerance). `slow` (nightly): ~16s, and the packed
    unspill lane is unit-covered by test_upload_leaves_roundtrip plus
    every forced-spill suite running under the default-on conf."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col, lit
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.memory.budget import reset_memory_budget
    from spark_rapids_tpu.memory.catalog import (buffer_catalog,
                                                 reset_buffer_catalog)
    rng = np.random.default_rng(3)
    n_l, n_o = 4000, 500
    lp = os.path.join(str(tmp_path), "lines.parquet")
    op = os.path.join(str(tmp_path), "orders.parquet")
    pq.write_table(pa.table({
        "l_key": pa.array(rng.integers(0, n_o, n_l), pa.int64()),
        "l_val": pa.array(rng.random(n_l) * 100.0, pa.float64()),
        "l_flag": pa.array(rng.integers(0, 4, n_l), pa.int64()),
    }), lp, row_group_size=512)
    pq.write_table(pa.table({
        "o_key": pa.array(np.arange(n_o), pa.int64()),
        "o_flag": pa.array(rng.integers(0, 10, n_o), pa.int64()),
    }), op, row_group_size=128)

    results, spilled, upload_deltas = {}, {}, {}
    try:
        for mode, settings in (("on", {}), ("off", dict(OFF))):
            reset_buffer_catalog()
            reset_memory_budget(192 * 1024)  # fits one batch, not the query
            settings = dict(settings, **{
                "spark.rapids.memory.spillDirectory": str(tmp_path)})
            sess = TpuSession(settings)
            lines = sess.read_parquet(lp).filter(col("l_flag") != lit(0))
            orders = sess.read_parquet(op).filter(col("o_flag") < lit(5))
            j = lines.join(orders, left_on=["l_key"], right_on=["o_key"])
            agg = j.group_by("l_key").agg((F.sum("l_val"), "rev"),
                                          (F.count(), "cnt"))
            before = upload.counters()
            results[mode] = agg.sort(("rev", False)).collect()
            after = upload.counters()
            spilled[mode] = buffer_catalog().spilled_device_bytes
            upload_deltas[mode] = {k: after[k] - before[k] for k in after}
    finally:
        reset_buffer_catalog()
        reset_memory_budget()
    assert spilled["on"] > 0 and spilled["off"] > 0  # the budget DID bite
    # the packed lane really served the run (scan + unspill seams)
    assert upload_deltas["on"]["packed"] > 0
    assert upload_deltas["on"]["per_buffer"] == 0
    assert upload_deltas["off"]["packed"] == 0
    assert _rows_equal_float_tolerant(results["on"], results["off"])


def test_shuffle_read_decode_stays_host_until_seam(rng):
    """The deserializer returns host-backed batches for the reader
    (device=False) and promotes through the upload engine by default
    — the seam split ISSUE 10 wires."""
    import jax
    from spark_rapids_tpu.shuffle import serializer as ser
    sch = Schema((StructField("k", LONG), StructField("s", STRING)))
    b = ColumnarBatch.from_pydict(
        {"k": [1, None, 3], "s": ["a", None, "cc"]}, sch)
    frame = ser.serialize_batch(b)
    host = ser.deserialize_batch(frame, sch, device=False)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree_util.tree_leaves(list(host.columns)))
    before = upload.counters()
    dev = ser.deserialize_batch(frame, sch)
    after = upload.counters()
    assert after["transfers"] - before["transfers"] == 1
    assert dev.to_pydict() == b.to_pydict()


# ---------------------------------------------------------------------------
# fused split+pack (round-9 TODO satellite)
# ---------------------------------------------------------------------------

def test_fused_split_pack_frames_byte_identical(rng):
    """The fused split+pack program produces byte-identical shuffle
    frames to the conf-off host partitioner — and unpack_split_host on
    eval_shape templates equals fetch_split_host on real columns."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.partition_split import (partition_table,
                                                      reorder_columns)
    sch = Schema((StructField("k", LONG), StructField("s", STRING)))
    batch = ColumnarBatch.from_pydict(
        {"k": [int(x) for x in rng.integers(0, 100, 200)],
         "s": [None if x % 5 == 0 else f"v{x}"
               for x in rng.integers(0, 60, 200)]}, sch)
    n_parts = 4
    pid = jnp.asarray(np.asarray(
        rng.integers(0, n_parts, batch.capacity)), jnp.int32)

    def split(b):
        counts, order = partition_table(pid, b.num_rows, b.capacity,
                                        n_parts)
        return counts, reorder_columns(b.columns, order, b.num_rows)

    fused = jax.jit(lambda b: transfer.pack_split(*split(b)))
    tmpl_counts, tmpl_cols = jax.eval_shape(split, batch)
    buf = np.asarray(fused(batch))
    counts_a, cols_a = transfer.unpack_split_host(buf, tmpl_cols, n_parts)
    counts_b, cols_b = transfer.fetch_split_host(*split(batch))
    assert np.array_equal(counts_a, counts_b)
    for a, b in zip(cols_a, cols_b):
        la = jax.tree_util.tree_leaves(a)
        lb = jax.tree_util.tree_leaves(b)
        for x, y in zip(la, lb):
            assert np.array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# chaos: keyed device.dispatch coverage
# ---------------------------------------------------------------------------

def test_upload_chaos_key_placement_order_independent():
    """Seeded injection placement follows the batch's work-item KEY,
    not call order: uploading the same keyed batches in opposite orders
    fires on the same key set (the PR 6 placement-equality pattern)."""
    sch = Schema((StructField("x", LONG),))
    with host_build():
        cols = {f"key-{i:04d}": [Column.from_numpy(
            np.arange(16, dtype=np.int64) + i, LONG)] for i in range(12)}

    def drive(order):
        faults.install("device.dispatch:prob=0.4,seed=7,kind=device")
        hit = set()
        for key in order:
            try:
                upload.packed_upload_batch(cols[key][0:1] and cols[key],
                                           16, sch, fault_key=key)
            except faults.InjectedDeviceError:
                hit.add(key)
        faults.install(None)
        return hit

    keys = sorted(cols)
    a = drive(keys)
    b = drive(list(reversed(keys)))
    assert a == b and a  # same placement, and some draws actually fired


def test_unspill_fault_unwinds_budget_and_quota():
    """A device fault injected into the packed UNSPILL upload (after
    the budget reserve + quota charge, before the tier flip) must
    unwind both — the entry stays HOST, budget.used returns to its
    pre-acquire value, and a retried acquire after disarm succeeds
    (review r1 finding: the leak made every retry double-charge)."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch as CB
    from spark_rapids_tpu.memory.budget import (memory_budget,
                                                reset_memory_budget)
    from spark_rapids_tpu.memory.catalog import (StorageTier,
                                                 buffer_catalog,
                                                 reset_buffer_catalog)
    from spark_rapids_tpu.memory.spillable import SpillableBatch
    try:
        reset_buffer_catalog()
        reset_memory_budget(1 << 20)
        sch = Schema((StructField("a", LONG),))
        sb = SpillableBatch.from_batch(
            CB.from_pydict({"a": list(range(64))}, sch))
        cat = buffer_catalog()
        assert cat.synchronous_spill(None) > 0
        # async writeback releases the device budget only when the d2h
        # copy LANDS (PR 3) — settle it before snapshotting
        cat.drain_writeback()
        assert cat.tier_of(sb._handle) == StorageTier.HOST
        used_before = memory_budget().used
        faults.install("device.dispatch:prob=1,seed=5,kind=device,max=1")
        with pytest.raises(faults.InjectedDeviceError):
            sb.get_batch()
        faults.install(None)
        assert memory_budget().used == used_before  # reservation unwound
        assert cat.tier_of(sb._handle) == StorageTier.HOST
        got = sb.get_batch()  # clean retry works, charged exactly once
        assert got.to_pydict()["a"][:3] == [0, 1, 2]
        sb.release()
        sb.close()
        assert memory_budget().used == 0
    finally:
        reset_buffer_catalog()
        reset_memory_budget()


def test_upload_fault_recovers_via_task_retry(tmp_path):
    """An injected device fault on the scan upload lane recovers
    through the whole-plan task-retry lane (max=1: the re-execution's
    draws are exhausted) and the query result is correct."""
    from spark_rapids_tpu.api.session import TpuSession
    path = _write_parquet(tmp_path, rows=100)
    sess = TpuSession({
        "spark.rapids.tpu.test.faults":
            "device.dispatch:prob=1,seed=3,kind=device,max=1"})
    rows = sess.read_parquet(path).collect()
    assert len(rows) == 100
    stats = faults.active_plan().stats()
    assert stats.get("device.dispatch") == 1  # it really fired


# ---------------------------------------------------------------------------
# metrics / events / tooling surfaces
# ---------------------------------------------------------------------------

def test_upload_event_and_exec_metrics(monkeypatch, tmp_path):
    """One `upload` event per ingest with lane/seam/transfers;
    numUploads and uploadPackTimeNs register on SourceScanExec."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs import events
    rows_seen = []
    real = events.emit

    def spy(kind, **fields):
        rows_seen.append({"kind": kind, **fields})
        real(kind, **fields)

    monkeypatch.setattr(events, "emit", spy)
    events.enable(str(tmp_path), "MODERATE")
    try:
        path = _write_parquet(tmp_path)
        sess = TpuSession()
        df = sess.read_parquet(path)
        out = df.collect()
        assert out
        ups = [r for r in rows_seen if r["kind"] == "upload"]
        assert ups and all(u["lane"] == "packed" and u["transfers"] == 1
                           for u in ups)
        assert any(u["seam"] == "scan" for u in ups)
        m = sess.last_query_metrics() or {}
        scan_ups = [v for k, v in m.items() if "numUploads" in str(k)]
        assert scan_ups and sum(scan_ups) >= 1
    finally:
        events.reset_event_bus()


def test_profile_report_uploads_rollup():
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "tools"))
    from profile_report import build_report
    events = [
        {"kind": "upload", "lane": "packed", "seam": "scan",
         "bytes": 4096, "rows": 10, "cols": 3, "transfers": 1,
         "pack_ns": 1000},
        {"kind": "upload", "lane": "per_buffer", "seam": "unspill",
         "bytes": 2048, "rows": 0, "cols": 4, "transfers": 4,
         "pack_ns": 500},
    ]
    report = build_report(events)
    assert "uploads: 2 batches (1 packed, 1 per-buffer; 5 h2d" in report


def test_bench_upload_attribution_block():
    import importlib
    sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
    bench = importlib.import_module("bench")
    bench._attr_prev.pop("upload", None)
    first = bench.upload_attribution()
    assert set(first) >= {"uploads", "packed", "per_buffer", "transfers",
                          "bytes", "pack_ns"}
    sch = Schema((StructField("x", LONG),))
    with host_build():
        col = Column.from_numpy(np.arange(8, dtype=np.int64), LONG)
    upload.packed_upload_batch([col], 8, sch)
    delta = bench.upload_attribution()
    assert delta["uploads"] == 1 and delta["packed"] == 1 \
        and delta["transfers"] == 1
