"""Dictionary-encoded execution (ISSUE 18): structural acceptance for
the encoded lane — byte-identical collects with the conf on vs off, the
>= 2x packed-upload byte shrink on a string-dictionary-heavy scan,
code-space predicate / dictionary-hash-table engagement, late
materialization ONLY at output-level seams, the PR 3 forced-spill
recipe flowing encoded batches through the spill lane, and seeded
`device.dispatch` chaos over the materialize seam."""

import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import config as C
from spark_rapids_tpu import faults
from spark_rapids_tpu.api import functions as F
from spark_rapids_tpu.api.functions import col, lit
from spark_rapids_tpu.api.session import TpuSession
from spark_rapids_tpu.columnar import encoded, upload
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.encoded import DictionaryColumn

OFF = {"spark.rapids.tpu.scan.encoded.enabled": "false"}

#: distinct values long enough that the decoded (offsets, bytes) layout
#: dominates the i32 code lane — the shrink the tentpole claims
CATS = ["alpha-category-00000000000000", "beta-category-111111111111111",
        "gamma-category-22222222222222", "delta-category-3333333333333"]


@pytest.fixture(autouse=True)
def _isolation():
    prev = C.active_conf()
    faults.install(None)
    yield
    faults.install(None)
    C.set_active_conf(prev)


def _delta(before, after):
    return {k: after[k] - before[k] for k in after}


def _write_strings(tmp_path, n=4000, name="strings.parquet", seed=18):
    rng = np.random.default_rng(seed)
    path = os.path.join(str(tmp_path), name)
    # parquet writes string columns dictionary-encoded BY DEFAULT —
    # no writer flags needed for the scan to see the encoded layout
    pq.write_table(pa.table({
        "s": pa.array([CATS[i] for i in rng.integers(0, len(CATS), n)]),
        "v": pa.array(rng.integers(0, 1000, n), pa.int64()),
    }), path)
    return path


# ---------------------------------------------------------------------------
# acceptance: byte-identical collects + the >= 2x upload byte shrink
# ---------------------------------------------------------------------------

def test_scan_collect_byte_identical_and_upload_shrink(tmp_path):
    """The headline structural claim: the same scan->collect returns
    IDENTICAL rows with the encoded lane on vs off, while the packed
    host->device upload ships <= half the bytes (codes + one dictionary
    instead of the decoded string buffers)."""
    path = _write_strings(tmp_path)
    results, up, enc = {}, {}, {}
    for mode, settings in (("on", {}), ("off", dict(OFF))):
        sess = TpuSession(dict(settings))
        df = sess.read_parquet(path)
        ub, eb = upload.counters(), encoded.counters()
        results[mode] = df.collect()
        up[mode] = _delta(ub, upload.counters())
        enc[mode] = _delta(eb, encoded.counters())
    assert results["on"] == results["off"]
    assert enc["on"]["cols_encoded"] >= 1
    assert enc["off"]["cols_encoded"] == 0
    assert enc["on"]["decoded_bytes_avoided"] > 0
    # the tentpole's transfer claim: >= 2x fewer H2D bytes encoded
    assert up["on"]["bytes"] * 2 <= up["off"]["bytes"], (up["on"],
                                                         up["off"])


def test_materializations_only_at_output_seam(tmp_path, monkeypatch):
    """scan -> filter(code-space equality) -> collect must decode each
    encoded column exactly once, at the OUTPUT seam — any `boundary`
    seam means an exec's consumes_encoded walk regressed."""
    path = _write_strings(tmp_path)
    seams = []
    real = encoded.materialize_column

    def rec(c, fault_key=None, seam="boundary"):
        seams.append(seam)
        return real(c, fault_key=fault_key, seam=seam)

    monkeypatch.setattr(encoded, "materialize_column", rec)
    sess = TpuSession()
    eb = encoded.counters()
    got = sess.read_parquet(path).filter(col("s") == lit(CATS[1])) \
        .collect()
    d = _delta(eb, encoded.counters())
    sess_off = TpuSession(dict(OFF))
    want = sess_off.read_parquet(path) \
        .filter(col("s") == lit(CATS[1])).collect()
    assert got == want and len(got) > 0
    assert d["code_space_predicates"] >= 1
    assert d["decoded_bytes_avoided"] > 0
    assert seams and set(seams) == {"output"}, seams


def test_dictionary_hash_precompute_matches_per_row_hash():
    """Ops-level pin of the join-hash precompute (the fast tier-1 face
    of the slow join drive below): murmur3 over an encoded key — one
    dictionary-table hash + a code-indexed take — equals the per-row
    string hash of the decoded column, nulls included."""
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.hashing import murmur3_batch
    C.set_active_conf(C.RapidsConf({}))
    vals = [CATS[i % len(CATS)] for i in range(37)] + [None, CATS[0]]
    enc = ColumnarBatch.from_arrow(
        pa.table({"s": pa.array(vals).dictionary_encode()}))
    assert isinstance(enc.columns[0], DictionaryColumn)
    plain = ColumnarBatch.from_arrow(pa.table({"s": pa.array(vals)}))
    eb = encoded.counters()
    h_enc = murmur3_batch(enc.columns)[:len(vals)]
    h_plain = murmur3_batch(plain.columns)[:len(vals)]
    d = _delta(eb, encoded.counters())
    assert d["dict_hash_tables"] >= 1
    assert jnp.array_equal(h_enc, h_plain)


@pytest.mark.slow  # ~11s: two fresh sessions compile the join+agg pipeline
def test_string_key_join_agg_identical_and_dict_hashed(tmp_path):
    """String-key hash join + aggregate: identical results on vs off,
    with the join's per-row hashes served by the once-per-dictionary
    murmur3 precompute (dict_hash_tables) instead of a per-row byte
    hash."""
    rng = np.random.default_rng(7)
    n = 3000
    lp = os.path.join(str(tmp_path), "facts.parquet")
    dp = os.path.join(str(tmp_path), "dim.parquet")
    pq.write_table(pa.table({
        "s": pa.array([CATS[i] for i in rng.integers(0, len(CATS), n)]),
        "v": pa.array(np.arange(n), pa.int64()),
    }), lp)
    pq.write_table(pa.table({
        "s2": pa.array(CATS[1:3]),
        "w": pa.array([10, 20], pa.int64()),
    }), dp)
    results, enc = {}, {}
    for mode, settings in (("on", {}), ("off", dict(OFF))):
        sess = TpuSession(dict(settings))
        facts = sess.read_parquet(lp)
        dim = sess.read_parquet(dp)
        q = facts.join(dim, left_on=["s"], right_on=["s2"]) \
            .group_by("s").agg((F.sum("v"), "sv"), (F.count(), "c"))
        eb = encoded.counters()
        results[mode] = sorted(q.collect())
        enc[mode] = _delta(eb, encoded.counters())
    assert results["on"] == results["off"] and len(results["on"]) == 2
    assert enc["on"]["dict_hash_tables"] >= 1
    assert enc["off"]["dict_hash_tables"] == 0


# ---------------------------------------------------------------------------
# the spill lane: encoded batches survive the PR 3 forced-spill recipe
# ---------------------------------------------------------------------------

def test_encoded_batch_spill_unspill_roundtrip(tmp_path):
    """Catalog-level pin of the spill lane (the fast tier-1 face of the
    slow forced-spill drive below): an encoded batch spills device ->
    host -> disk and unspills back with the DictionaryColumn pytree —
    not a decoded copy — and identical rows."""
    from spark_rapids_tpu.memory import (SpillableBatch, StorageTier,
                                         buffer_catalog,
                                         reset_buffer_catalog)
    C.set_active_conf(C.RapidsConf({
        "spark.rapids.memory.host.spillStorageSize": "1k",
        "spark.rapids.memory.spillDirectory": str(tmp_path),
    }))
    reset_buffer_catalog()
    try:
        vals = [CATS[i % len(CATS)] for i in range(200)] + [None]
        batch = ColumnarBatch.from_arrow(
            pa.table({"s": pa.array(vals).dictionary_encode()}))
        assert isinstance(batch.columns[0], DictionaryColumn)
        want = encoded.materialize_batch(batch).to_pydict()
        sb = SpillableBatch.from_batch(batch)
        cat = buffer_catalog()
        cat.synchronous_spill(None)  # device -> host -> (1k limit) -> disk
        assert cat.tier_of(sb._handle) == StorageTier.DISK
        got = sb.get_batch()
        assert isinstance(got.columns[0], DictionaryColumn)
        assert encoded.materialize_batch(got).to_pydict() == want
        sb.release()
        sb.close()
    finally:
        reset_buffer_catalog()


@pytest.mark.slow  # ~11s: two fresh sessions compile filter+join+agg
def test_forced_spill_through_encoded_lane(tmp_path):
    """The PR 3 forced-spill recipe (string-keyed scan->filter->join->
    agg under a 640 KiB budget): the catalog really spills batches that
    carry DictionaryColumns, and unspill restores the encoded pytree —
    results identical to the conf-off run."""
    from spark_rapids_tpu.memory.budget import reset_memory_budget
    from spark_rapids_tpu.memory.catalog import (buffer_catalog,
                                                 reset_buffer_catalog)
    rng = np.random.default_rng(3)
    n_l, n_o = 4000, 500
    lp = os.path.join(str(tmp_path), "lines.parquet")
    op = os.path.join(str(tmp_path), "orders.parquet")
    pq.write_table(pa.table({
        "l_key": pa.array(rng.integers(0, n_o, n_l), pa.int64()),
        "l_cat": pa.array([CATS[i]
                           for i in rng.integers(0, len(CATS), n_l)]),
        "l_val": pa.array(rng.random(n_l) * 100.0, pa.float64()),
    }), lp, row_group_size=512)
    pq.write_table(pa.table({
        "o_key": pa.array(np.arange(n_o), pa.int64()),
        "o_flag": pa.array(rng.integers(0, 10, n_o), pa.int64()),
    }), op, row_group_size=128)
    results, spilled, enc = {}, {}, {}
    try:
        for mode, settings in (("on", {}), ("off", dict(OFF))):
            reset_buffer_catalog()
            reset_memory_budget(640 * 1024)
            settings = dict(settings, **{
                "spark.rapids.memory.spillDirectory": str(tmp_path)})
            sess = TpuSession(settings)
            lines = sess.read_parquet(lp).filter(
                col("l_cat") != lit(CATS[0]))
            orders = sess.read_parquet(op).filter(
                col("o_flag") < lit(5))
            j = lines.join(orders, left_on=["l_key"],
                           right_on=["o_key"])
            agg = j.group_by("l_cat").agg((F.count(), "cnt"))
            eb = encoded.counters()
            results[mode] = sorted(agg.collect())
            enc[mode] = _delta(eb, encoded.counters())
            spilled[mode] = buffer_catalog().spilled_device_bytes
    finally:
        reset_buffer_catalog()
        reset_memory_budget()
    assert spilled["on"] > 0 and spilled["off"] > 0  # the budget bit
    assert enc["on"]["cols_encoded"] >= 1  # encoded batches in play
    assert results["on"] == results["off"] and len(results["on"]) == 3


# ---------------------------------------------------------------------------
# chaos: the materialize seam is a recoverable device-dispatch site
# ---------------------------------------------------------------------------

def test_chaos_inject_once_at_materialize_seam_recovers():
    """A seeded device fault at the materialize seam's device.dispatch
    check raises on the first decode and, with its max=1 budget spent,
    the retry decodes correctly — the inject-once -> recover contract
    every task-retry site obeys."""
    C.set_active_conf(C.RapidsConf({}))
    vals = ["a", "b", None, "a", "c"]
    batch = ColumnarBatch.from_arrow(
        pa.table({"s": pa.array(vals).dictionary_encode()}))
    assert isinstance(batch.columns[0], DictionaryColumn)
    faults.install("device.dispatch:prob=1,seed=0,kind=device,max=1")
    with pytest.raises(faults.InjectedDeviceError):
        encoded.materialize_batch(batch)
    out = encoded.materialize_batch(batch)  # budget spent -> clean
    injected = faults.stats().get("device.dispatch")
    faults.install(None)
    assert out.to_pydict() == {"s": vals}
    assert injected == 1


def test_chaos_e2e_encoded_query_recovers(tmp_path):
    """End to end: an encoded scan->filter->collect under a seeded
    inject-once device fault returns the fault-free result through the
    session's task-retry lane."""
    path = _write_strings(tmp_path, n=800)
    want = TpuSession().read_parquet(path) \
        .filter(col("s") == lit(CATS[2])).collect()
    sess = TpuSession({
        "spark.rapids.tpu.test.faults":
            "device.dispatch:prob=1,seed=0,kind=device,max=1",
        "spark.rapids.tpu.task.retryBackoffMs": "1",
    })
    got = sess.read_parquet(path).filter(col("s") == lit(CATS[2])) \
        .collect()
    assert got == want and len(got) > 0
    assert faults.stats().get("device.dispatch", 0) >= 1
