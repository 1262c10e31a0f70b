"""The mesh exchange (`ShuffleExchangeExec`) at the exec level, on four of the
conftest's virtual devices: partition i comes out as arrays of device i
alone, in the bucket of the largest partition, with its rows known on the
host; the fingerprint holds the partition count, the keys and the devices;
`exchange.counters()` counts what moved between chips; a consumer that
merges partitions moves them onto one chip, counted, and nothing else moves
them; and a program traced for one chip of a mesh is compiled for the
others ahead of their dispatches."""

import numpy as np
import pytest

import jax
from jax.sharding import Mesh

from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import bucket_capacity
from spark_rapids_tpu.exec import exchange
from spark_rapids_tpu.exec.basic import InMemoryScanExec
from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
from spark_rapids_tpu.expr.core import col
from spark_rapids_tpu.types import LONG, STRING, Schema, StructField

SCHEMA = Schema((StructField("k", LONG), StructField("v", LONG)))

needs_4 = pytest.mark.skipif(len(jax.devices()) < 4,
                             reason="needs 4 virtual devices")


def _mesh(devices):
    return Mesh(np.array(devices), ("data",))


def _batch(keys, device=None):
    b = ColumnarBatch.from_pydict(
        {"k": list(keys), "v": list(range(len(keys)))}, SCHEMA)
    if device is None:
        return b
    return ColumnarBatch(*jax.device_put((b.columns, b.num_rows), device),
                         SCHEMA, b._host_rows)


def _devices(batch):
    return {d for leaf in jax.tree_util.tree_leaves(batch)
            for d in leaf.devices()}


def _exchange(batches, keys=("k",), devices=None):
    devices = devices or jax.devices()[:4]
    return ShuffleExchangeExec([col(k) for k in keys],
                               InMemoryScanExec(batches, SCHEMA),
                               mesh=_mesh(devices))


def _drain(ex):
    return [list(part) for part in ex.execute_partitions()]


@needs_4
def test_fingerprints_differ_by_partition_count_keys_and_devices():
    devs = jax.devices()
    b = [_batch([1, 2, 3])]
    fp = _exchange(b).plan_fingerprint()
    assert fp is not None and fp == _exchange(b).plan_fingerprint()
    assert _exchange(b, keys=("v",)).plan_fingerprint() != fp
    assert _exchange(b, keys=("k", "v")).plan_fingerprint() != fp
    assert _exchange(b, devices=devs[:2]).plan_fingerprint() != fp
    assert _exchange(b, devices=devs[4:8]).plan_fingerprint() != fp
    assert _exchange(b, devices=devs[:4][::-1]).plan_fingerprint() != fp


@needs_4
def test_each_partition_is_on_its_own_device_with_host_rows():
    """One input batch on one device: the round is dealt out by rows over
    the four devices, the exchange moves each row to its partition's
    device; every partition's batch is arrays of that device alone, its
    rows known on the host, in the bucket of the largest partition."""
    rng = np.random.default_rng(3)
    keys = rng.integers(0, 1000, 700).tolist()
    exchange.reset_counters()
    ex = _exchange([_batch(keys)])
    parts = _drain(ex)
    assert exchange.counters()["spread_rounds"] == 1
    devs = jax.devices()[:4]
    assert len(parts) == 4
    got = []
    caps = set()
    for d, batches in zip(devs, parts):
        assert len(batches) == 1
        b = batches[0]
        assert _devices(b) == {d}
        assert b._host_rows is not None
        caps.add(b.capacity)
        got += [r[0] for r in b.to_pylist()]
    assert sorted(got) == sorted(keys)
    largest = max(b[0]._host_rows for b in parts)
    assert caps == {bucket_capacity(largest)}


@needs_4
def test_a_partitioned_producers_batches_stay_on_their_device():
    """Batches already on several devices (a partitioned producer's) are
    grouped by their device, so a row that is already in its partition's
    place moves nowhere: a second exchange on the same keys moves no row
    off its chip."""
    rng = np.random.default_rng(4)
    keys = rng.integers(0, 500, 600).tolist()
    first = _drain(_exchange([_batch(keys)]))
    exchange.reset_counters()
    again = _exchange([b for part in first for b in part])
    parts = _drain(again)
    c = exchange.counters()
    assert c["rounds"] == 1 and c["rows_in"] == 600
    assert c["rows_off_chip"] == 0 and c["bytes_off_chip"] == 0
    assert c["spread_rounds"] == 0 and c["rows_spread"] == 0
    assert [sorted(r[0] for b in p for r in b.to_pylist()) for p in parts] \
        == [sorted(r[0] for b in p for r in b.to_pylist()) for p in first]


@needs_4
def test_counters_over_a_seeded_skew():
    """80% of the rows share one key: its partition holds them all. The one
    input batch sits on chip 0 and is dealt out by rows, 250 a chip: the
    750 rows put on chips 1-3 count as spread, and every row whose
    partition is not the chip it entered the all-to-all from counts as off
    chip, both at the bytes its slot carries (two BIGINT lanes and their
    validity, and the slot's occupancy flag)."""
    rng = np.random.default_rng(5)
    keys = [7] * 800 + rng.integers(100, 10_000, 200).tolist()
    rng.shuffle(keys)
    exchange.reset_counters()
    parts = _drain(_exchange([_batch(keys, jax.devices()[0])]))
    rows = [sum(b._host_rows for b in p) for p in parts]
    part_of = {r[0]: p for p, bs in enumerate(parts)
               for b in bs for r in b.to_pylist()}
    stayed = sum(part_of[k] == i // 250 for i, k in enumerate(keys))
    c = exchange.counters()
    slot = 8 + 1 + 8 + 1 + 1
    assert sum(rows) == 1000 and max(rows) >= 800
    assert c == {"rounds": 1, "rows_in": 1000,
                 "rows_off_chip": 1000 - stayed,
                 "bytes_off_chip": (1000 - stayed) * slot,
                 "max_partition_rows": max(rows), "partitions": 4,
                 "batches_moved": 0, "rows_moved": 0, "bytes_moved": 0,
                 "spread_rounds": 1, "rows_spread": 750,
                 "bytes_spread": 750 * slot}


# -- a one-chip round dealt out by rows -----------------------------------------

STR_SCHEMA = Schema((StructField("k", LONG), StructField("s", STRING)))


def _ref_partition(key, n=4):
    """Spark's HashPartitioning of one BIGINT key, from the pure-python
    Murmur3_x86_32 of `test_hashing.py`: a NULL leaves the seed."""
    from test_hashing import py_murmur3_long, to_i32
    from spark_rapids_tpu.parallel.exchange import SHUFFLE_SEED
    h = SHUFFLE_SEED if key is None else py_murmur3_long(key, SHUFFLE_SEED)
    return to_i32(h) % n


def _live(piece):
    """A piece of the mesh programs' input (a leading axis of 1) as the
    batch it holds and its rows."""
    b = jax.tree_util.tree_map(lambda x: x[0], piece)
    return b.to_pylist()


@needs_4
@pytest.mark.parametrize("rows", [0, 1, 3, 1001, 1024])
def test_a_one_chip_round_is_dealt_out_by_rows(rows):
    """A round of R rows in a bucket of 1024 on one chip becomes four
    pieces of 256 slots, one program on that chip: each piece holds
    floor(R/4) or ceil(R/4) rows, the pieces in order are the batch, and
    the all-to-all step runs at 256 rows."""
    dev = jax.devices()[2]
    keys = list(range(rows))
    b = ColumnarBatch.from_pydict({"k": keys, "v": keys}, SCHEMA,
                                  capacity=1024)
    b = ColumnarBatch(*jax.device_put((b.columns, b.num_rows), dev),
                      SCHEMA, b._host_rows)
    ex = _exchange([b])
    pieces, cap, source = ex._round_inputs([b])
    assert (cap, source, len(pieces)) == (256, 2, 4)
    got = [_live(p) for p in pieces]
    assert {len(g) for g in got} <= {rows // 4, -(-rows // 4)}
    assert [r for g in got for r in g] == [(k, k) for k in keys]
    for p in pieces:
        assert {leaf.shape[:2] for leaf in jax.tree_util.tree_leaves(
            p.columns)} == {(1, 256)}
        assert _devices(p) == {dev}
    exchange.reset_counters()
    parts = _drain(ex)
    assert {key[0] for key in ex._steps} == {256}
    assert sorted(r for p in parts for bb in p for r in bb.to_pylist()) \
        == [(k, k) for k in keys]
    c = exchange.counters()
    assert (c["spread_rounds"], c["rows_spread"]) == \
        (1, rows - len(got[2]))


@needs_4
def test_a_round_under_the_floor_or_on_several_chips_is_not_spread():
    """A bucket of 256 rows makes pieces under the smallest bucket: the
    batch is device 0's piece. Batches already on two chips stay there."""
    devs = jax.devices()
    small = _batch(list(range(200)), devs[1])
    pieces, cap, source = _exchange([small])._round_inputs([small])
    assert (cap, source) == (256, None)
    assert _live(pieces[0]) == small.to_pylist()
    assert all(not _live(p) for p in pieces[1:])
    big = [ColumnarBatch.from_pydict({"k": [i] * 600, "v": [i] * 600},
                                     SCHEMA, capacity=1024) for i in (1, 2)]
    big = [ColumnarBatch(*jax.device_put((b.columns, b.num_rows), d),
                         SCHEMA, b._host_rows)
           for b, d in zip(big, devs[1:3])]
    exchange.reset_counters()
    parts = _drain(_exchange(big))
    c = exchange.counters()
    assert (c["spread_rounds"], c["rows_spread"], c["rows_in"]) == \
        (0, 0, 1200)
    assert sorted(r[0] for p in parts for b in p for r in b.to_pylist()) \
        == [1] * 600 + [2] * 600


@needs_4
@pytest.mark.parametrize("long_rows", [range(0, 40), range(900, 1001)],
                         ids=["first_piece", "last_piece"])
def test_each_partition_holds_what_a_reference_hash_partitioning_gives(
        long_rows):
    """A spread round's partitions hold, as multisets, the rows a plain
    hash partitioning of the whole batch gives: NULL keys and NULL strings
    among them, and long strings that all sit in one piece with every
    byte (each piece keeps the source's byte bucket)."""
    rng = np.random.default_rng(9)
    keys = [None if i % 97 == 5 else int(k)
            for i, k in enumerate(rng.integers(-10**12, 10**12, 1001))]
    vals = ["é" * 300 + str(i) if i in long_rows
            else (None if i % 13 == 0 else f"v{i}") for i in range(1001)]
    b = ColumnarBatch.from_pydict({"k": keys, "s": vals}, STR_SCHEMA)
    b = ColumnarBatch(*jax.device_put((b.columns, b.num_rows),
                                      jax.devices()[0]), STR_SCHEMA,
                      b._host_rows)
    exchange.reset_counters()
    ex = ShuffleExchangeExec([col("k")], InMemoryScanExec([b], STR_SCHEMA),
                             mesh=_mesh(jax.devices()[:4]))
    parts = _drain(ex)
    assert exchange.counters()["spread_rounds"] == 1
    want = [sorted((r for r in zip(keys, vals) if _ref_partition(r[0]) == p),
                   key=repr) for p in range(4)]
    got = [sorted((r for bb in part for r in bb.to_pylist()), key=repr)
           for part in parts]
    assert got == want
    assert sum(len(w) for w in want) == 1001


@needs_4
def test_a_merge_of_partitions_moves_them_onto_one_chip_and_counts():
    """Partitions of a mesh exchange sit on chips of their own. A consumer
    that merges them in one program (the top-N's last selection, a
    complete group-by) moves each onto the first one's chip, counted;
    anything else that hands a program arrays of two chips is refused by
    jit, with or without a mesh: nothing moves them silently."""
    from spark_rapids_tpu.exec.coalesce import concat_batches
    from spark_rapids_tpu.parallel.mesh import set_active_mesh
    devs = jax.devices()
    a, b, c = _batch([1, 2], devs[1]), _batch([3], devs[2]), _batch([4])
    assert list(exchange.onto_one_chip([a, b, c])) == [a, b, c]  # no mesh
    exchange.reset_counters()
    set_active_mesh(_mesh(devs[:4]))
    try:
        moved = list(exchange.onto_one_chip([a, b, c]))
        assert moved[0] is a and moved[2] is c      # uncommitted: left
        assert _devices(moved[1]) == {devs[1]} and moved[1]._host_rows == 1
        c_ = exchange.counters()
        assert (c_["batches_moved"], c_["rows_moved"]) == (1, b.capacity)
        assert c_["bytes_moved"] == sum(
            x.nbytes for x in jax.tree_util.tree_leaves(b))
        out = concat_batches(moved, SCHEMA)
        assert _devices(out.columns) == {devs[1]}
        assert [r[0] for r in out.to_pylist()] == [1, 2, 3, 4]
        with pytest.raises(ValueError, match="incompatible devices"):
            concat_batches([a, b], SCHEMA)
    finally:
        set_active_mesh(None)
    with pytest.raises(ValueError, match="incompatible devices"):
        concat_batches([a, b], SCHEMA)


@needs_4
def test_a_program_traced_for_one_chip_compiles_ahead_for_the_others():
    """A dispatch that traces a program for one chip of a mesh starts its
    compiles for the mesh's other chips; their dispatches then compile
    nothing and the program is traced once. Arrays of no one chip (what
    ingest uploads) start nothing, and neither does a session without a
    mesh."""
    import jax.numpy as jnp
    from jax._src import monitoring
    from spark_rapids_tpu.obs import dispatch
    from spark_rapids_tpu.parallel.mesh import set_active_mesh
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda ev, d, **kw: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    site = dispatch.instrument(lambda x, y, k: x * k + y,
                               label="test.ahead", static_argnums=(2,))
    devs = jax.devices()
    on = [jax.device_put(jnp.arange(8), d) for d in devs[:4]]
    one = jnp.ones(8, jnp.int32)             # uncommitted: follows the other
    before = dispatch.ahead_counters()["started"]
    site(on[0], one, 3)                              # no mesh
    site(jnp.arange(16), jnp.ones(16, jnp.int32), 3)  # not committed
    assert dispatch.ahead_counters()["started"] == before
    set_active_mesh(_mesh(devs[:4]))
    try:
        site(jnp.arange(32), jnp.ones(32, jnp.int32), 3)
        assert dispatch.ahead_counters()["started"] == before
        assert site(on[1], one, 5).tolist() == [i * 5 + 1 for i in range(8)]
        assert dispatch.ahead_counters()["started"] == before + 3
        for f in list(site._ahead.values()):
            f.result()
        n = len(compiles)
        for x in (on[0], on[2], on[3]):
            assert site(x, one, 5).tolist() == [i * 5 + 1 for i in range(8)]
        assert len(compiles) == n
        assert site._ahead is None
    finally:
        set_active_mesh(None)
    traced = {tuple(p["bucket"]): p["traces"] for p in dispatch.programs()
              if p["label"] == "test.ahead"}
    assert max(traced.values()) == 1


@needs_4
def test_a_program_traced_into_another_is_not_looked_at(monkeypatch):
    """Only a dispatch compiles ahead: an instrumented program called
    inside another's trace (the murmur3 kernel inside the exchange's
    measure program on the chip) gets tracers, whose device cannot be
    asked."""
    import jax.numpy as jnp
    from spark_rapids_tpu.obs import dispatch
    from spark_rapids_tpu.parallel.mesh import set_active_mesh
    inner = dispatch.instrument(lambda x: x * 2, label="test.inner")
    outer = dispatch.instrument(lambda x: inner(x) + 1, label="test.outer")
    devs = jax.devices()
    x = jax.device_put(jnp.arange(4), devs[2])
    set_active_mesh(_mesh(devs[:4]))
    try:
        assert outer(x).tolist() == [1, 3, 5, 7]
        monkeypatch.setattr(dispatch, "_ledger", None)
        assert outer(x).tolist() == [1, 3, 5, 7]
    finally:
        set_active_mesh(None)
