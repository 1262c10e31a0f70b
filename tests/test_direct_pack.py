"""The scan seam's direct pack (ISSUE 34): a fixed-width Arrow column is
written from its Arrow buffers straight into its wire block of the staging
buffer. The oracle is the path it replaces, `pack_host_batch` over
`column_from_arrow`'s host columns: the staging buffers must agree byte for
byte over all `total` bytes, for every direct-packed type, null pattern,
slice, chunking and both f64 stagings; then the specs, the unpack program
and every answer are the built path's."""

import datetime
import decimal
import tracemalloc

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import faults
from spark_rapids_tpu.columnar import transfer, upload
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import (bucket_capacity,
                                              column_from_arrow, host_build)
from spark_rapids_tpu.obs import dispatch

OFF = {"spark.rapids.tpu.transfer.packedUpload.enabled": "false"}

#: every Arrow type the direct path takes, by a name for the test id
DIRECT_TYPES = {
    "int8": pa.int8(), "int16": pa.int16(), "int32": pa.int32(),
    "int64": pa.int64(), "float32": pa.float32(), "float64": pa.float64(),
    "date32": pa.date32(), "ts_us": pa.timestamp("us"),
    "ts_us_utc": pa.timestamp("us", tz="UTC"),
}


@pytest.fixture(autouse=True)
def _isolation():
    prev = C.active_conf()
    faults.install(None)
    yield
    faults.install(None)
    C.set_active_conf(prev)


@pytest.fixture
def dd(request, monkeypatch):
    """f64 staged as (hi, lo) float32 pairs, as on the chip, or not: a
    host-side comparison only (nothing here crosses to the device with the
    split forced on)."""
    monkeypatch.setattr(transfer, "_dd_split", lambda: request.param)
    return request.param


def _values(at, n, rng):
    if pa.types.is_floating(at):
        v = (rng.random(n) - 0.5) * 10.0 ** rng.integers(-30, 30, n)
        return v.astype(at.to_pandas_dtype())
    bits = at.bit_width
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    return rng.integers(lo, hi, n, dtype=np.int64, endpoint=True)


def _array(at, n, nulls, rng):
    vals = _values(at, n, rng)
    mask = {"none": None,
            "some": rng.random(n) < 0.3,
            "all": np.ones(n, np.bool_)}[nulls]
    if pa.types.is_floating(at):
        return pa.array(vals, type=at, mask=mask)
    width = {8: pa.int8(), 16: pa.int16(), 32: pa.int32(),
             64: pa.int64()}[at.bit_width]
    return pa.array(vals, type=pa.int64(), mask=mask).cast(width).cast(at)


def _table(at, n, nulls, layout, rng):
    """One column `x` of type `at` with `n` rows in the given layout."""
    if layout in ("plain", "sliced"):
        lead = 13 if layout == "sliced" else 0      # a bit offset of 13 % 8
        arr = _array(at, n + lead + 5, nulls, rng)
        return pa.table({"x": arr}).slice(lead, n)
    sizes = [n // 3, 0, n - n // 3 - n // 4, n // 4]    # an empty chunk too
    lead = 3 if layout == "chunked_sliced" else 0
    chunks = [_array(at, m + lead, nulls, rng).slice(lead) for m in sizes]
    return pa.table({"x": pa.chunked_array(chunks, type=at)})


def _built_cols(table, cap):
    with host_build():
        cols = [column_from_arrow(table.column(k)) for k in table.column_names]
        return [c.with_capacity(cap) if c.capacity < cap else c for c in cols]


def _staged(cols, n):
    """(bytes over `total`, specs) of one pack; the buffer goes back."""
    specs = tuple(upload._col_spec(c) for c in cols)
    buf, total = upload.pack_host_batch(cols, n, specs=specs)
    try:
        return buf[:total].copy(), specs
    finally:
        upload.staging_pool().release(buf)


def _assert_same_staging(table, cap=None):
    n = table.num_rows
    cap = cap or bucket_capacity(n)
    direct = [upload.ArrowFixed.of(table.column(k), cap)
              for k in table.column_names]
    assert all(d is not None for d in direct)
    got, got_specs = _staged(direct, n)
    want, want_specs = _staged(_built_cols(table, cap), n)
    assert got_specs == want_specs
    assert got.shape == want.shape
    assert np.array_equal(got, want), np.flatnonzero(got != want)[:8]


# ---------------------------------------------------------------------------
# byte identity with the built path
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dd", [False, True], indirect=True,
                         ids=["ieee", "dd"])
@pytest.mark.parametrize("layout", ["plain", "sliced", "chunked",
                                    "chunked_sliced"])
@pytest.mark.parametrize("nulls", ["none", "some", "all"])
@pytest.mark.parametrize("name", list(DIRECT_TYPES))
def test_staging_bytes_equal_the_built_paths(name, nulls, layout, dd, rng):
    _assert_same_staging(_table(DIRECT_TYPES[name], 203, nulls, layout, rng))


@pytest.mark.parametrize("dd", [False, True], indirect=True,
                         ids=["ieee", "dd"])
def test_special_doubles_keep_their_bits(dd):
    sub = np.float64(5e-324)
    vals = [np.nan, np.inf, -np.inf, -0.0, 0.0, sub, -sub, 2.0 ** -1040,
            1e300, -1e300, 3.5e38, float(np.finfo(np.float32).max) * 1.0000001,
            1.0 + 2.0 ** -40, np.pi, None,
            np.frombuffer(np.uint64(0x7FF8DEADBEEF0001).tobytes(),
                          np.float64)[0]]
    _assert_same_staging(pa.table({"x": pa.array(vals, type=pa.float64())}))


@pytest.mark.parametrize("dd", [False, True], indirect=True,
                         ids=["ieee", "dd"])
@pytest.mark.parametrize("n", [0, 1, 128, 256])
def test_empty_single_row_and_exactly_the_capacity(n, dd, rng):
    cols = {"d": pa.array(rng.random(n)),
            "l": pa.array(rng.integers(0, 99, n), mask=rng.random(n) < 0.5)}
    _assert_same_staging(pa.table(cols))
    assert bucket_capacity(n) == max(n, 128)       # no tail at 128 and 256


def test_a_chunked_array_of_no_chunks_and_a_record_batch(rng):
    empty = pa.table({"x": pa.chunked_array([], type=pa.int32())})
    _assert_same_staging(empty)
    rb = pa.record_batch({"x": pa.array(rng.random(40)),
                          "y": pa.array(np.arange(40, dtype=np.int16))})
    assert not hasattr(rb.column("x"), "chunks")
    _assert_same_staging(rb.slice(7, 21))


def test_a_column_padded_past_its_natural_bucket(rng):
    """`from_arrow` packs every column at the batch's capacity; a larger
    one (a caller that sized the bucket itself) zeroes a longer tail."""
    _assert_same_staging(pa.table({"x": pa.array(rng.random(100))}), cap=512)


@pytest.mark.parametrize("at,direct", [
    (pa.timestamp("ms"), False), (pa.timestamp("ns"), False),
    (pa.timestamp("s"), False), (pa.date64(), False), (pa.bool_(), False),
    (pa.string(), False), (pa.large_string(), False), (pa.binary(), False),
    (pa.decimal128(12, 2), False), (pa.decimal128(30, 2), False),
    (pa.null(), False), (pa.list_(pa.int64()), False),
    (pa.struct([("a", pa.int32())]), False),
    (pa.dictionary(pa.int32(), pa.string()), False),
    (pa.dictionary(pa.int32(), pa.int64()), False),
    (pa.uint32(), False), (pa.float16(), False), (pa.time32("s"), False),
    (pa.duration("us"), False),
] + [(t, True) for t in DIRECT_TYPES.values()])
def test_the_type_alone_chooses_the_path(at, direct):
    assert upload._arrow_direct(at) is direct
    if direct:
        src = upload.ArrowFixed.of(pa.array([], type=at), 128)
        assert upload._col_spec(src) == upload._col_spec(src.build())


# ---------------------------------------------------------------------------
# a mixed table: both ways into one buffer
# ---------------------------------------------------------------------------

def _mixed_table(n, rng):
    words = ["", "a", "bb", "wörld", "longer-string", None]
    return pa.table({
        "i": pa.array(rng.integers(-9, 9, n), mask=rng.random(n) < 0.2),
        "dict_s": pa.array([words[i % 6] for i in range(n)]
                           ).dictionary_encode(),
        "d": pa.array(rng.random(n)),
        "s": pa.array([words[(i * 5) % 6] for i in range(n)]),
        "dec": pa.array([None if i % 5 == 2 else decimal.Decimal(i) / 100
                         for i in range(n)], type=pa.decimal128(12, 2)),
        "b": pa.array(rng.random(n) < 0.5, mask=rng.random(n) < 0.1),
        "day": pa.array([datetime.date(1994, 1, 1)
                         + datetime.timedelta(days=i) for i in range(n)]),
        "ts_ms": pa.array(rng.integers(0, 10 ** 9, n)
                          ).cast(pa.timestamp("ms")),
    })


def _scan_cols(table):
    """The column list `ColumnarBatch.from_arrow` hands the packer."""
    cap = bucket_capacity(table.num_rows)
    with host_build():
        out = []
        for k in table.column_names:
            col = upload.ArrowFixed.of(table.column(k), cap)
            if col is None:
                col = column_from_arrow(table.column(k))
                if col.capacity < cap:
                    col = col.with_capacity(cap)
            out.append(col)
        return out


@pytest.mark.parametrize("dd", [False, True], indirect=True,
                         ids=["ieee", "dd"])
def test_a_mixed_table_packs_both_ways_into_one_buffer(dd, rng):
    t = _mixed_table(150, rng)
    cols = _scan_cols(t)
    assert [isinstance(c, upload.ArrowFixed) for c in cols] == \
        [True, False, True, False, False, False, True, False]
    got, got_specs = _staged(cols, 150)
    want, want_specs = _staged(_built_cols(t, bucket_capacity(150)), 150)
    assert got_specs == want_specs
    assert np.array_equal(got, want)


def _leaves(batch):
    import jax
    return [np.asarray(x) for x in
            jax.tree_util.tree_leaves((list(batch.columns), batch.num_rows))]


def _assert_same_leaves(a, b):
    la, lb = _leaves(a), _leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y, equal_nan=(x.dtype.kind == "f"))


def _unpack_traces():
    return sum(p["traces"] for p in dispatch.programs()
               if p["label"] == "upload.unpack_batch")


def test_from_arrow_counts_the_columns_and_shares_the_unpack_program(
        monkeypatch, rng):
    """`direct_cols` / `built_cols`, the `upload` event's `direct_cols`, the
    device batch's leaves and ONE trace of `upload.unpack_batch` for the
    direct and the built path of one table (the specs are `_col_spec`'s)."""
    from spark_rapids_tpu.obs import events
    seen = []
    real = events.emit
    monkeypatch.setattr(
        events, "emit",
        lambda kind, **f: (seen.append({"kind": kind, **f}),
                           real(kind, **f))[1])
    t = _mixed_table(177, rng)
    before, traces = upload.counters(), _unpack_traces()
    got = ColumnarBatch.from_arrow(t)
    mid, traces_mid = upload.counters(), _unpack_traces()
    assert mid["direct_cols"] - before["direct_cols"] == 3
    assert mid["built_cols"] - before["built_cols"] == 5
    assert traces_mid - traces <= 1

    monkeypatch.setattr(upload, "_arrow_direct", lambda at: False)
    want = ColumnarBatch.from_arrow(t)
    after = upload.counters()
    assert after["direct_cols"] == mid["direct_cols"]
    assert after["built_cols"] - mid["built_cols"] == 8
    assert _unpack_traces() == traces_mid           # the same program served
    assert got.schema == want.schema
    _assert_same_leaves(got, want)
    ups = [e for e in seen if e["kind"] == "upload"]
    assert [(e["lane"], e["cols"], e["direct_cols"]) for e in ups] == \
        [("packed", 8, 3), ("packed", 8, 0)]
    upload.staging_pool().settle()
    assert upload.staging_pool().outstanding_bytes() == 0


def test_the_conf_off_builds_host_columns_for_the_per_buffer_lane(rng):
    t = _mixed_table(90, rng)
    on = ColumnarBatch.from_arrow(t)
    C.set_active_conf(C.RapidsConf(dict(OFF)))
    before = upload.counters()
    off = ColumnarBatch.from_arrow(t)
    d = {k: v - before[k] for k, v in upload.counters().items()}
    assert d["per_buffer"] == 1 and d["packed"] == 0
    assert d["direct_cols"] == 0 and d["built_cols"] == 0
    _assert_same_leaves(on, off)


def test_a_stand_in_on_the_per_buffer_lane_is_built_first(rng):
    """`to_device_batch` with the conf off, handed stand-ins all the same
    (the conf changed between the build and the upload)."""
    t = pa.table({"x": pa.array(rng.random(50), mask=rng.random(50) < 0.3)})
    want = ColumnarBatch.from_arrow(t)
    src = upload.ArrowFixed.of(t.column("x"), 128)
    C.set_active_conf(C.RapidsConf(dict(OFF)))
    got = upload.to_device_batch([src], 50, want.schema, seam="scan")
    _assert_same_leaves(got, want)


# ---------------------------------------------------------------------------
# the served path: a Parquet scan
# ---------------------------------------------------------------------------

def _write_lineitem(tmp_path, rows=5000, groups=4):
    rng = np.random.default_rng(34)
    t = pa.table({
        "l_partkey": rng.integers(1, 2000, rows),
        "l_quantity": np.floor(rng.random(rows) * 50) + 1.0,
        "l_extendedprice": np.round(rng.random(rows) * 1e5, 2),
        "l_discount": pa.array(np.round(rng.random(rows) * 0.1, 2),
                               mask=rng.random(rows) < 0.05),
        "l_shipdate": pa.array(rng.integers(8000, 10500, rows)
                               .astype(np.int32)).cast(pa.date32()),
        "l_comment": [f"c{i % 37}" for i in range(rows)],
    })
    path = str(tmp_path / "lineitem.parquet")
    pq.write_table(t, path, row_group_size=rows // groups)
    return path


def _scan(path, settings=None):
    from spark_rapids_tpu.api.session import TpuSession
    sess = TpuSession(dict(settings or {}))
    before = upload.counters()
    rows = sess.read_parquet(path).collect()
    after = upload.counters()
    return rows, {k: after[k] - before[k] for k in after}


def test_a_parquet_scan_answers_as_the_built_path_does(tmp_path, monkeypatch):
    path = _write_lineitem(tmp_path)
    rows, d = _scan(path)
    assert d["uploads"] >= 1 and d["packed"] == d["uploads"]
    assert d["direct_cols"] == 5 * d["uploads"]
    assert d["built_cols"] == 1 * d["uploads"]      # l_comment
    monkeypatch.setattr(upload, "_arrow_direct", lambda at: False)
    rows_built, d_built = _scan(path)
    assert d_built["direct_cols"] == 0
    assert d_built["built_cols"] == 6 * d_built["uploads"]
    assert d_built["bytes"] == d["bytes"]
    assert len(rows) == 5000
    assert repr(rows) == repr(rows_built)           # in order, NaN-safe


def test_a_scans_device_batches_equal_the_built_paths(tmp_path, monkeypatch):
    from spark_rapids_tpu.io.parquet import ParquetSource
    path = _write_lineitem(tmp_path, rows=1200, groups=3)

    def batches():
        src = ParquetSource(path)
        return list(src.batches())

    got = batches()
    monkeypatch.setattr(upload, "_arrow_direct", lambda at: False)
    want = batches()
    assert len(got) == len(want) >= 1
    for a, b in zip(got, want):
        assert a.schema == b.schema
        _assert_same_leaves(a, b)


def test_a_second_scan_takes_its_buffers_from_the_pool(tmp_path):
    """Steady state: a second scan of the same file misses the pool no more
    than the first did, and not at all where the first one's buffers came
    back (a CPU backend that aliases a staging buffer keeps it: such a
    buffer is single-use by design)."""
    path = _write_lineitem(tmp_path)
    pool = upload.reset_staging_pool()
    try:
        rows1, d1 = _scan(path)
        pool.settle()
        came_back = pool.pooled_bytes() > 0
        rows2, d2 = _scan(path)
        pool.settle()
        assert repr(rows1) == repr(rows2)
        assert d2["pool_misses"] <= d1["pool_misses"]
        if came_back:
            assert d2["pool_misses"] == 0 and d2["pool_hits"] == d2["uploads"]
        assert pool.outstanding_bytes() == 0
    finally:
        upload.reset_staging_pool()


def test_the_direct_pack_makes_no_full_width_temporary(rng):
    """What the staging pool is for: with the buffer pooled, packing
    fixed-width columns without nulls allocates nothing that grows with
    the batch (the built path allocates several times its size)."""
    n = 1 << 17
    t = pa.table({"a": pa.array(rng.random(n)),
                  "k": pa.array(rng.integers(0, 1 << 40, n)),
                  "day": pa.array(rng.integers(0, 9999, n).astype(np.int32)
                                  ).cast(pa.date32())})
    cols = [upload.ArrowFixed.of(t.column(k), n) for k in t.column_names]
    pool = upload.staging_pool()
    pool.release(upload.pack_host_batch(cols, n)[0])       # warm the bucket

    def peak(pack):
        tracemalloc.start()
        try:
            buf, total = pack()
            return tracemalloc.get_traced_memory()[1], buf, total
        finally:
            tracemalloc.stop()

    direct_peak, buf, total = peak(lambda: upload.pack_host_batch(cols, n))
    pool.release(buf)
    built_peak, buf, _ = peak(
        lambda: upload.pack_host_batch(_built_cols(t, n), n))
    pool.release(buf)
    assert total > 20 * n
    assert direct_peak < 64 * 1024, direct_peak
    assert built_peak > total, built_peak


# ---------------------------------------------------------------------------
# failures leave nothing outstanding
# ---------------------------------------------------------------------------

def test_an_injected_dispatch_fault_leaves_nothing_outstanding(rng):
    pool = upload.reset_staging_pool()
    try:
        t = pa.table({"x": pa.array(rng.random(300)),
                      "s": pa.array(["v"] * 300)})
        faults.install("device.dispatch:prob=1,seed=1,kind=device,max=1")
        with pytest.raises(faults.InjectedDeviceError):
            ColumnarBatch.from_arrow(t, fault_key="k0")
        faults.install(None)
        assert pool.outstanding_bytes() == 0
        assert pool.pooled_bytes() == 0             # discarded, not pooled
        again = ColumnarBatch.from_arrow(t, fault_key="k0")
        assert again.num_rows_host == 300
        pool.settle()
        assert pool.outstanding_bytes() == 0
    finally:
        upload.reset_staging_pool()


def test_a_column_that_fails_to_pack_leaves_nothing_outstanding(
        monkeypatch, rng):
    pool = upload.reset_staging_pool()
    try:
        def bad(col, buf, pos, dd):
            raise ValueError("a bad column")
        monkeypatch.setattr(upload, "_pack_arrow_fixed", bad)
        t = pa.table({"x": pa.array(rng.random(10))})
        with pytest.raises(ValueError, match="a bad column"):
            ColumnarBatch.from_arrow(t)
        assert pool.outstanding_bytes() == 0 and pool.pooled_bytes() == 0
    finally:
        upload.reset_staging_pool()
