"""Per-kernel microbenchmark harness driving the fused-tier selection
(ISSUE 1: the tier choice is a measurement, not a guess).

For each kernel family and shape bucket, times the XLA formulation
against the fused Pallas kernel and records both. The records file
(tools/kern_bench.json by default) is what
`spark.rapids.tpu.pallas.fusedTier=auto` consults at trace time
(spark_rapids_tpu/ops/pallas_tier.py): a family only replaces its XLA
tier for a shape bucket where its recorded time wins.

Timing methodology: each lane chains every iteration's output into a
device checksum scalar (no iteration can be elided) and the clock stops
on the ONE device->host fetch of the final checksum. Median of --reps
timed runs.

Off-TPU the Pallas lanes run under the interpreter — they will lose by
orders of magnitude, which is precisely the point: `auto` then keeps the
XLA tier on CPU while a TPU round's records can flip it per shape.

Usage:
  python tools/kern_bench.py                          # default shapes
  python tools/kern_bench.py --families join_probe --shapes 4096x1024
  python tools/kern_bench.py --out tools/kern_bench.json --iters 20

Prints one JSON line per (family, shape) stage:
  {"family", "shape", "platform", "xla_ms", "pallas_ms", "winner"}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_SHAPES = {
    "join_probe": [(1 << 12, 1 << 10), (1 << 14, 1 << 12)],
    "scan_agg": [(1 << 14,), (1 << 16,)],
    "murmur3": [(1 << 16,), (1 << 20,)],
    # (gathered rows, source capacity) — the packed-row gather shapes
    # the join emit and filter compaction actually dispatch
    "gather": [(1 << 14, 1 << 12), (1 << 16, 1 << 14)],
    # (rows, n_partitions) — the device shuffle split pipeline: counts
    # + stable permutation + one partition-ordered packed row gather
    "partition_split": [(1 << 14, 8), (1 << 16, 32)],
    # (rows, n_columns) — packed one-copy host->device batch upload vs
    # the per-buffer jnp.asarray lane (ISSUE 10; lanes, not kernels)
    "h2d_upload": [(1 << 14, 8), (1 << 16, 16)],
    # (rows per map batch, n_partitions) — device-resident all_to_all
    # exchange vs the host serialize/LZ4 round trip it replaces
    # (ISSUE 16; lanes, not kernels)
    "ici_all_to_all": [(1 << 13, 8), (1 << 15, 8)],
    # (rows, dictionary entries) — the encoded lane's code-indexed take
    # of a per-dictionary table (precomputed hashes / literal hit
    # masks; ISSUE 18)
    "dict_gather": [(1 << 16, 1 << 10), (1 << 20, 1 << 12)],
}

#: smallest per-family shape for --quick CI smoke (compile + one
#: timed rep; proves the harness and the record layout, not the chip)
QUICK_SHAPES = {
    "join_probe": [(1 << 10, 1 << 8)],
    "scan_agg": [(1 << 12,)],
    "murmur3": [(1 << 14,)],
    "gather": [(1 << 11, 1 << 10)],
    "partition_split": [(1 << 11, 4)],
    "h2d_upload": [(1 << 11, 4)],
    "ici_all_to_all": [(1 << 10, 4)],
    "dict_gather": [(1 << 11, 1 << 8)],
}


def _timed(step, iters: int, reps: int) -> float:
    """Median wall-clock (ms) of `reps` runs of `iters` chained steps;
    step(chk) -> chk must consume and return the device checksum so no
    iteration can be elided or left queued when the clock stops."""
    import jax.numpy as jnp
    chk = step(jnp.float64(0.0))  # warm: compile + one round trip
    float(np.asarray(chk))
    times = []
    for _ in range(reps):
        chk = jnp.float64(0.0)
        t0 = time.perf_counter()
        for _ in range(iters):
            chk = step(chk)
        float(np.asarray(chk))  # forces completion of all iterations
        times.append((time.perf_counter() - t0) / iters * 1e3)
    return sorted(times)[len(times) // 2]


def bench_join_probe(shape, iters, reps, interpret):
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import Column, bucket_capacity
    from spark_rapids_tpu.ops.join import (
        BuildTable, expand_candidates, int_key_lanes, probe_counts,
        verify_pairs)
    from spark_rapids_tpu.ops.pallas_join import fused_probe_verify
    from spark_rapids_tpu.types import LONG

    ns, nb = shape
    rng = np.random.default_rng(0)
    bk = Column.from_numpy(rng.integers(0, nb, nb).astype(np.int64),
                           LONG, capacity=bucket_capacity(nb))
    sk = Column.from_numpy(rng.integers(0, nb, ns).astype(np.int64),
                           LONG, capacity=bucket_capacity(ns))
    build = BuildTable.build([bk], [bk], jnp.int32(nb), bk.capacity)
    lo, counts, _ = probe_counts(build, [sk], jnp.int32(ns), sk.capacity)
    cand_cap = bucket_capacity(max(int(jnp.sum(counts)), 1))
    bk_lanes, bvalid = build.key_lanes
    sk_lanes, svalid = int_key_lanes([sk])

    @jax.jit
    def xla_step(chk):
        s_idx, b_pos, _ = expand_candidates(lo, counts, cand_cap)
        pv = s_idx >= 0
        ver, b_row = verify_pairs(build, [sk],
                                  jnp.where(pv, s_idx, -1),
                                  jnp.where(pv, b_pos, -1), pv)
        return chk + jnp.sum(ver).astype(jnp.float64) \
            + jnp.sum(b_row).astype(jnp.float64)

    @jax.jit
    def pallas_step(chk):
        ver, s_idx, b_pos, b_row = fused_probe_verify(
            lo, counts, bk_lanes, bvalid, sk_lanes, svalid, build.perm,
            cand_cap, interpret=interpret)
        return chk + jnp.sum(ver).astype(jnp.float64) \
            + jnp.sum(b_row).astype(jnp.float64)

    return (_timed(xla_step, iters, reps),
            _timed(pallas_step, iters, reps))


def bench_scan_agg(shape, iters, reps, interpret, G=32, n_keys=24):
    """XLA lane = the engine's masked tier at its DEFAULT configuration
    (32 slots x 2 rounds, exec/aggregate.py), Pallas lane = the fused
    kernel exactly as AggregateExec._streaming_step calls it (G =
    min(32, slots), single round) — a recorded 'win' must reflect the
    real substitution, not a toy baseline. n_keys=24 keeps the bucket
    table realistically loaded (clean but not trivially sparse); note
    the auto tier keys records by SHAPE bucket only, so record with
    data whose cardinality resembles the production workload."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column, bucket_capacity
    from spark_rapids_tpu.expr.core import BoundReference
    from spark_rapids_tpu.ops.maskedagg import masked_groupby
    from spark_rapids_tpu.ops.pallas_fused import (
        compile_scan_agg_spec, fused_scan_agg_update)
    from spark_rapids_tpu.types import DOUBLE, LONG, Schema, StructField

    (n,) = shape
    rng = np.random.default_rng(1)
    key = Column.from_numpy(rng.integers(0, n_keys, n).astype(np.int64),
                            LONG, capacity=bucket_capacity(n))
    val = Column.from_numpy(rng.random(n) * 100, DOUBLE,
                            capacity=bucket_capacity(n))
    schema = Schema((StructField("k", LONG), StructField("v", DOUBLE)))
    batch = ColumnarBatch([key, val], n, schema)
    pre = [BoundReference(0, LONG, "k"), BoundReference(1, DOUBLE, "v")]
    agg_ops = [("sum", 1), ("count", 1), ("min", 1), ("max", 1)]
    spec = compile_scan_agg_spec([], pre, schema, 1, agg_ops, schema)
    assert spec is not None
    out_cap = bucket_capacity(G)

    def fold(chk, keys, results):
        for c in keys:
            chk = chk + jnp.sum(jnp.where(c.validity, c.data, 0)) \
                .astype(jnp.float64)
        for _, (d, v) in results:
            chk = chk + jnp.sum(jnp.where(v, d, jnp.zeros((), d.dtype))) \
                .astype(jnp.float64)
        return chk

    @jax.jit
    def xla_step(chk):
        # the engine's masked tier at its DEFAULT slots x rounds
        keys, results, ng, left = masked_groupby(
            [key], [(op, [key, val][s]) for op, s in agg_ops],
            batch.num_rows, batch.capacity, None, group_slots=32,
            rounds=2)
        return fold(chk, keys, results) + left

    @jax.jit
    def pallas_step(chk):
        keys, results, ng, left = fused_scan_agg_update(
            spec, batch, G, out_cap, interpret=interpret)
        return fold(chk, keys, results) + left

    return (_timed(xla_step, iters, reps),
            _timed(pallas_step, iters, reps))


def bench_murmur3(shape, iters, reps, interpret):
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.ops import hashing as H
    from spark_rapids_tpu.ops.pallas_kernels import murmur3_long_lanes

    (n,) = shape
    rng = np.random.default_rng(2)
    data = jnp.asarray(rng.integers(-(2**62), 2**62, n), jnp.int64)
    seeds = jnp.full((n,), jnp.uint32(42))

    @jax.jit
    def xla_step(chk):
        return chk + jnp.sum(H.murmur3_long(data, seeds)
                             .astype(jnp.float64))

    @jax.jit
    def pallas_step(chk):
        return chk + jnp.sum(
            murmur3_long_lanes(data, seeds, interpret=interpret)
            .astype(jnp.float64))

    return (_timed(xla_step, iters, reps),
            _timed(pallas_step, iters, reps))


def bench_gather(shape, iters, reps, interpret):
    """Packed row gather (ISSUE 8): XLA's one-row-gather-over-the-pack
    formulation (ops/rowpack.gather_rows — the engine's floor) vs the
    DMA kernel (ops/pallas_gather.py), over a representative payload
    mix (1 LONG + 4 INT + 1 DOUBLE + 1 BOOLEAN = 9 u32 lanes incl the
    validity lane)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import Column, bucket_capacity
    from spark_rapids_tpu.ops.pallas_gather import pallas_gather_rows
    from spark_rapids_tpu.ops.rowpack import gather_rows, pack_rows
    from spark_rapids_tpu.types import BOOLEAN, DOUBLE, INT, LONG

    nout, cap = shape
    rng = np.random.default_rng(3)
    ccap = bucket_capacity(cap)
    cols = [Column.from_numpy(
        rng.integers(-(2**40), 2**40, cap).astype(np.int64), LONG,
        capacity=ccap)]
    for i in range(4):
        cols.append(Column.from_numpy(
            rng.integers(-1000, 1000, cap).astype(np.int32), INT,
            capacity=ccap))
    cols.append(Column.from_numpy(rng.random(cap), DOUBLE, capacity=ccap))
    cols.append(Column.from_numpy(rng.integers(0, 2, cap).astype(bool),
                                  BOOLEAN, capacity=ccap))
    plan, imat, fmat = pack_rows(cols)
    idx = jnp.asarray(rng.integers(0, cap, nout), jnp.int32)

    def fold(chk, gi, gf):
        chk = chk + jnp.sum(gi, dtype=jnp.float64)
        if gf is not None:
            chk = chk + jnp.sum(gf).astype(jnp.float64)
        return chk

    @jax.jit
    def xla_step(chk):
        gi, gf = gather_rows(plan, imat, fmat, idx)
        return fold(chk, gi, gf)

    @jax.jit
    def pallas_step(chk):
        gi, gf = pallas_gather_rows(plan, imat, fmat, idx,
                                    interpret=interpret)
        return fold(chk, gi, gf)

    return (_timed(xla_step, iters, reps),
            _timed(pallas_step, iters, reps))


def bench_partition_split(shape, iters, reps, interpret):
    """Device shuffle partition split (ISSUE 9): segment-sum counts +
    stable sort-by-pid permutation + ONE partition-ordered packed row
    gather over the 9-lane payload mix — the exact pipeline
    `HostShuffleExchangeExec`'s device lane dispatches per written
    batch. XLA lane serves the gather from ops/rowpack (the floor),
    Pallas lane from the DMA kernel; the counts/permutation prefix is
    shared, so the delta isolates the tiered step."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import Column, bucket_capacity
    from spark_rapids_tpu.ops.pallas_gather import pallas_gather_rows
    from spark_rapids_tpu.ops.partition_split import partition_table
    from spark_rapids_tpu.ops.rowpack import gather_rows, pack_rows
    from spark_rapids_tpu.types import BOOLEAN, DOUBLE, INT, LONG

    rows, n_parts = shape
    rng = np.random.default_rng(4)
    cap = bucket_capacity(rows)
    cols = [Column.from_numpy(
        rng.integers(-(2**40), 2**40, rows).astype(np.int64), LONG,
        capacity=cap)]
    for _ in range(4):
        cols.append(Column.from_numpy(
            rng.integers(-1000, 1000, rows).astype(np.int32), INT,
            capacity=cap))
    cols.append(Column.from_numpy(rng.random(rows), DOUBLE, capacity=cap))
    cols.append(Column.from_numpy(rng.integers(0, 2, rows).astype(bool),
                                  BOOLEAN, capacity=cap))
    plan, imat, fmat = pack_rows(cols)
    pid = jnp.asarray(rng.integers(0, n_parts, cap), jnp.int32)
    num_rows = jnp.int32(rows)

    def split(gather_fn):
        counts, order = partition_table(pid, num_rows, cap, n_parts)
        gi, gf = gather_fn(plan, imat, fmat, order)
        chk = jnp.sum(counts).astype(jnp.float64) \
            + jnp.sum(gi, dtype=jnp.float64)
        if gf is not None:
            chk = chk + jnp.sum(gf).astype(jnp.float64)
        return chk

    @jax.jit
    def xla_step(chk):
        return chk + split(gather_rows)

    @jax.jit
    def pallas_step(chk):
        return chk + split(
            lambda p, i, f, idx: pallas_gather_rows(
                p, i, f, idx, interpret=interpret))

    return (_timed(xla_step, iters, reps),
            _timed(pallas_step, iters, reps))


def bench_h2d_upload(shape, iters, reps, interpret):
    """Packed one-copy host->device upload (columnar/upload.py: pool
    staging pack + ONE device_put + jitted device unpack) vs the
    per-buffer lane (one jnp.asarray per data/validity buffer). The
    record's two slots map lanes, not kernels: xla_ms = per-buffer,
    pallas_ms = packed. `interpret` is unused — neither lane is a
    Pallas kernel; the runtime gate is
    spark.rapids.tpu.transfer.packedUpload.enabled, and a TPU round
    reads this family to quantify the one-copy win per rows x cols
    bucket."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import (Column, bucket_capacity,
                                                  host_build)
    from spark_rapids_tpu.columnar.upload import packed_upload_batch
    from spark_rapids_tpu.types import (BOOLEAN, DOUBLE, INT, LONG, Schema,
                                        StructField)

    rows, n_cols = shape
    rng = np.random.default_rng(7)
    cap = bucket_capacity(rows)
    dtypes = [LONG, INT, DOUBLE, BOOLEAN]
    fields, cols = [], []
    with host_build():
        for c in range(n_cols):
            dt = dtypes[c % len(dtypes)]
            if dt is LONG:
                vals = rng.integers(-(2**40), 2**40, rows).astype(np.int64)
            elif dt is INT:
                vals = rng.integers(-1000, 1000, rows).astype(np.int32)
            elif dt is DOUBLE:
                vals = rng.random(rows)
            else:
                vals = rng.integers(0, 2, rows).astype(bool)
            valid = rng.random(rows) > 0.1
            cols.append(Column.from_numpy(vals, dt, valid, capacity=cap))
            fields.append(StructField(f"c{c}", dt))
    schema = Schema(tuple(fields))
    host_leaves = jax.tree_util.tree_flatten(cols)[0]

    @jax.jit
    def _chk(leaves, chk):
        for x in leaves:
            chk = chk + jnp.sum(x.astype(jnp.float64))
        return chk

    def per_buffer_step(chk):
        dev = [jnp.asarray(a) for a in host_leaves]
        return _chk(dev, chk)

    def packed_step(chk):
        batch = packed_upload_batch(cols, rows, schema)
        return _chk(jax.tree_util.tree_leaves(list(batch.columns)), chk)

    return (_timed(per_buffer_step, iters, reps),
            _timed(packed_step, iters, reps))


def bench_ici_all_to_all(shape, iters, reps, interpret):
    """ICI-native device-resident shuffle exchange (ISSUE 16). The
    record's two slots map lanes, not kernels: xla_ms = the host
    fallback lane's per-map-batch serialize/LZ4 -> deserialize/upload
    round trip (shuffle/serializer.py), pallas_ms = the packed device
    all_to_all exchange step (parallel/exchange.exchange_columns under
    shard_map). `interpret` is unused — neither lane is a Pallas
    kernel; the runtime gate is spark.rapids.tpu.shuffle.ici.enabled.
    Shape is (rows per map batch, n_partitions); the mesh spans
    min(n_partitions, visible devices) so the family records on a
    single-device host too (there the collective degenerates to a local
    permutation — a TPU pod round is what makes the record
    meaningful)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import Column, bucket_capacity
    from spark_rapids_tpu.parallel.distributed import stack_batches
    from spark_rapids_tpu.parallel.exchange import (exchange_columns,
                                                    negotiate_slot_cap)
    from spark_rapids_tpu.parallel.mesh import (DATA_AXIS, device_mesh,
                                                shard_map_compat)
    from spark_rapids_tpu.shuffle.serializer import (deserialize_batch,
                                                     serialize_batch)
    from spark_rapids_tpu.types import LONG, Schema, StructField

    rows, n_parts = shape
    n = min(n_parts, len(jax.devices()))
    mesh = device_mesh(n)
    rng = np.random.default_rng(16)
    cap = bucket_capacity(rows)
    schema = Schema((StructField("k", LONG), StructField("v", LONG)))
    batches = []
    for _ in range(n):
        k = Column.from_numpy(
            rng.integers(0, 1 << 20, rows).astype(np.int64), LONG,
            capacity=cap)
        v = Column.from_numpy(
            rng.integers(-(2**40), 2**40, rows).astype(np.int64), LONG,
            capacity=cap)
        batches.append(ColumnarBatch([k, v], rows, schema))
    stacked = stack_batches(batches)
    # worst-case-safe slot cap (one device could hash every row to one
    # partition); production rounds negotiate a measured cap instead
    slot_cap = negotiate_slot_cap(rows, cap)

    def spmd(st):
        local = jax.tree_util.tree_map(lambda x: x[0], st)
        cols, n_recv = exchange_columns(
            list(local.columns), (0,), local.num_rows, local.capacity,
            DATA_AXIS, n, slot_cap=slot_cap)
        return jax.tree_util.tree_map(
            lambda x: x[None], ColumnarBatch(cols, n_recv, schema))

    step = jax.jit(shard_map_compat(
        spmd, mesh=mesh, in_specs=P(DATA_AXIS), out_specs=P(DATA_AXIS)))

    def fold(chk, batch):
        for c in batch.columns:
            chk = chk + jnp.sum(jnp.where(c.validity, c.data, 0)) \
                .astype(jnp.float64)
        return chk

    def host_step(chk):
        for b in batches:
            chk = fold(chk, deserialize_batch(serialize_batch(b), schema))
        return chk

    def ici_step(chk):
        return fold(chk, step(stacked))

    return (_timed(host_step, iters, reps),
            _timed(ici_step, iters, reps))


def bench_dict_gather(shape, iters, reps, interpret):
    """Code-indexed take over a per-dictionary lookup table (ISSUE 18):
    the encoded lane's dict_take (columnar/encoded.py) — precomputed
    join hashes, literal hit masks and late materialization all index a
    small table by the i32 code lane. xla_ms = the `table[clip(codes)]`
    take; pallas_ms = the DMA row gather (ops/pallas_gather.py) over
    the table as a one-lane matrix, exactly the tier dict_take selects
    between. Shape is (rows, dictionary entries)."""
    import jax
    import jax.numpy as jnp

    from spark_rapids_tpu.ops.pallas_gather import dma_row_gather

    rows, n = shape
    rng = np.random.default_rng(18)
    table = jnp.asarray(
        rng.integers(0, 1 << 32, n, dtype=np.uint64).astype(np.uint32))
    codes = jnp.asarray(rng.integers(0, n, rows), jnp.int32)
    mat = table.reshape(n, 1)

    @jax.jit
    def xla_step(chk):
        out = table[jnp.clip(codes, 0, n - 1)]
        return chk + jnp.sum(out, dtype=jnp.float64)

    def pallas_step(chk):
        out = dma_row_gather(mat, codes, interpret=interpret)[:, 0]
        return chk + jnp.sum(out, dtype=jnp.float64)

    return (_timed(xla_step, iters, reps),
            _timed(jax.jit(pallas_step), iters, reps))


BENCHES = {
    "join_probe": bench_join_probe,
    "scan_agg": bench_scan_agg,
    "murmur3": bench_murmur3,
    "gather": bench_gather,
    "partition_split": bench_partition_split,
    "h2d_upload": bench_h2d_upload,
    "ici_all_to_all": bench_ici_all_to_all,
    "dict_gather": bench_dict_gather,
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--families", nargs="*", default=list(BENCHES),
                    choices=list(BENCHES))
    ap.add_argument("--shapes", nargs="*", default=None,
                    help="override shapes, e.g. 4096x1024 (join) or "
                         "65536 (1-D families)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke: one tiny shape per family, 2 iters "
                         "x 1 rep — proves the harness + record layout "
                         "end to end, not the chip")
    ap.add_argument("--out", default=None,
                    help="records file (default tools/kern_bench.json)")
    ap.add_argument("--dry-run", action="store_true",
                    help="measure and print, do not write the record "
                         "file")
    args = ap.parse_args(argv)
    if args.quick:
        args.iters = min(args.iters, 2)
        args.reps = 1
        if args.out is None and not args.dry_run:
            # a 1-rep tiny-shape smoke record is NOISE, not a
            # measurement — never let it land in the production file
            # the auto tier trusts
            ap.error("--quick writes throwaway records; pass an "
                     "explicit --out (not the production "
                     "kern_bench.json) or --dry-run")
    if args.out is None:
        args.out = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            "kern_bench.json")

    import jax

    from spark_rapids_tpu.ops.pallas_kernels import on_tpu
    from spark_rapids_tpu.ops.pallas_tier import (
        KERN_BENCH_SCHEMA, shape_bucket)

    platform = jax.default_backend()
    interpret = not on_tpu()

    # merge with existing records so shape coverage accumulates — but
    # only records of the CURRENT layout; a stale-schema file is
    # discarded loudly (the tier selector already refuses to read it)
    doc = {"schema": KERN_BENCH_SCHEMA, "records": []}
    if os.path.exists(args.out) and not args.dry_run:
        try:
            with open(args.out) as f:
                old = json.load(f)
            if old.get("schema") == KERN_BENCH_SCHEMA:
                doc = old
            else:
                print(json.dumps({
                    "discarded_stale_records": args.out,
                    "old_schema": old.get("schema"),
                    "schema": KERN_BENCH_SCHEMA}))
        except (OSError, ValueError):
            pass
    index = {(r["family"], r["platform"], tuple(r["shape_bucket"])): r
             for r in doc.get("records", ())}

    if args.shapes and len(args.families) != 1:
        ap.error("--shapes overrides one family's shape list; pass "
                 "exactly one --families with it (families differ in "
                 "shape arity)")

    for family in args.families:
        shapes = (QUICK_SHAPES if args.quick else DEFAULT_SHAPES)[family]
        if args.shapes:
            shapes = [tuple(int(x) for x in s.split("x"))
                      for s in args.shapes]
            arity = len(DEFAULT_SHAPES[family][0])
            bad = [s for s in shapes if len(s) != arity]
            if bad:
                ap.error(f"{family} shapes need {arity} dims "
                         f"(got {bad})")
        for shape in shapes:
            xla_ms, pallas_ms = BENCHES[family](
                shape, args.iters, args.reps, interpret)
            rec = {
                "schema": KERN_BENCH_SCHEMA,
                "family": family,
                "platform": platform,
                "shape": list(shape),
                "shape_bucket": list(shape_bucket(shape)),
                "xla_ms": round(xla_ms, 4),
                "pallas_ms": round(pallas_ms, 4),
                "winner": "pallas" if pallas_ms < xla_ms else "xla",
                "iters": args.iters,
                "interpret": interpret,
                "recorded_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            }
            index[(family, platform, tuple(rec["shape_bucket"]))] = rec
            print(json.dumps({k: rec[k] for k in (
                "family", "shape", "platform", "xla_ms", "pallas_ms",
                "winner")}))

    if not args.dry_run:
        doc["schema"] = KERN_BENCH_SCHEMA
        doc["records"] = list(index.values())
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=1)
        print(json.dumps({"written": args.out, "schema": KERN_BENCH_SCHEMA,
                          "records": len(doc["records"])}))


if __name__ == "__main__":
    main()
