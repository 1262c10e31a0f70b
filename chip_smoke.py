#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the engine still starts on the chip.

One process, normal entry points only: seeded data is written as Parquet,
read back with `TpuSession.read_parquet`, planned, and `collect()`ed; every
result is compared with a numpy oracle computed here.

    python chip_smoke.py              # one chip: the q1 and q3 shapes, cold + warm
    python chip_smoke.py --chips 4    # four chips: the q3 shape over the mesh
                                      # exchanges vs a one-chip session, nothing else

It refuses to run unless jax's first device is a TPU, exits non-zero at the
first phase that fails, and prints as its LAST line
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
Earlier lines are one JSON object each. It states no timing as a result:
wall-clock and compile seconds are printed so a later PR can see what a
cold process costs, not compared with anything.
"""

import argparse
import glob
import json
import os
import shutil
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

#: lineitem-shaped table for the q1 shape: 16M rows, ~0.45 GB on the device
#: (`bench.py` ROWS; about TPC-H SF3's lineitem row count)
Q1_ROWS = 1 << 24
#: the q3 pair (`bench.py` N_ORDERS x N_LINES)
Q3_ORDERS = 1 << 19
Q3_LINES = 1 << 21
#: q3 groups the joined rows by the order's ship priority (TPC-H q3 groups by
#: l_orderkey, o_orderdate, o_shippriority). Grouping by l_orderkey as
#: `bench.py` does sends 512K groups to the exact sort tier, whose program and
#: the 2M-row top-N sort behind it take the chip's compiler ~8 and >7 minutes
#: (PERF.md, PR 23): past this script's 1200 s. The join is the full pair.
Q3_PRIORITIES = 8
TOP_N = 5
#: DOUBLE sums: f64 is EMULATED on the chip (double-double f32 pairs, ~48
#: mantissa bits) and the reduction order differs from numpy's, so sums are
#: held to a relative tolerance. Integer columns and group keys are exact.
SUM_RTOL = 1e-9


def emit(**rec):
    print(json.dumps(rec), flush=True)


# -- seeded data ------------------------------------------------------------

def gen_lineitem(seed: int, rows: int):
    rng = np.random.default_rng([seed, 1])
    return {
        "returnflag": rng.integers(0, 4, rows, dtype=np.int32),
        "quantity": rng.integers(1, 51, rows, dtype=np.int64),
        "extendedprice": rng.random(rows) * 1000.0,
        "discount": rng.random(rows) * 0.1,
    }


def gen_q3(seed: int, n_orders: int, n_lines: int):
    rng = np.random.default_rng([seed, 3])
    orders = {
        "o_orderkey": np.arange(n_orders, dtype=np.int64),
        "o_flag": rng.integers(0, 10, n_orders, dtype=np.int32),
        "o_shippriority": rng.integers(0, Q3_PRIORITIES, n_orders,
                                       dtype=np.int32),
    }
    lines = {
        "l_orderkey": rng.integers(0, n_orders, n_lines, dtype=np.int64),
        "l_price": rng.random(n_lines) * 1000.0,
        "l_disc": rng.random(n_lines) * 0.1,
        "l_flag": rng.integers(0, 4, n_lines, dtype=np.int32),
    }
    return orders, lines


def write_parquet(dirpath: str, cols, n_files: int, groups_per_file: int):
    """Several files, several row groups each; returns the glob to read."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    os.makedirs(dirpath, exist_ok=True)
    rows = len(next(iter(cols.values())))
    per_file = -(-rows // n_files)
    for i in range(n_files):
        sl = slice(i * per_file, min(rows, (i + 1) * per_file))
        pq.write_table(pa.table({k: v[sl] for k, v in cols.items()}),
                       os.path.join(dirpath, f"part-{i:03d}.parquet"),
                       row_group_size=max(1, -(-per_file // groups_per_file)))
    return os.path.join(dirpath, "*.parquet")


# -- the plain reference ----------------------------------------------------

def q1_oracle(d):
    keep = d["quantity"] <= 45
    flag = d["returnflag"][keep]
    qty = d["quantity"][keep]
    dp = (d["extendedprice"] * (1.0 - d["discount"]))[keep]
    return {int(k): (int(qty[flag == k].sum()), float(dp[flag == k].sum()),
                     int((flag == k).sum()))
            for k in np.unique(flag)}


def q3_oracle(orders, lines, top_n: int = TOP_N):
    """[(shippriority, revenue, joined rows)] of the top_n revenues,
    revenue descending. o_orderkey is dense (arange), so the join is a
    lookup by key."""
    assert (orders["o_orderkey"] == np.arange(len(orders["o_flag"]))).all()
    lkey = lines["l_orderkey"]
    keep = (lines["l_flag"] != 0) & (orders["o_flag"][lkey] < 5)
    prio = orders["o_shippriority"][lkey[keep]]
    rev = (lines["l_price"] * (1.0 - lines["l_disc"]))[keep]
    sums = np.bincount(prio, weights=rev, minlength=Q3_PRIORITIES)
    counts = np.bincount(prio, minlength=Q3_PRIORITIES)
    top = [p for p in np.argsort(-sums, kind="stable") if counts[p]][:top_n]
    return [(int(p), float(sums[p]), int(counts[p])) for p in top]


# -- the queries, through the session API -----------------------------------

def q1_query(sess, path):
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.expr.core import lit
    return (sess.read_parquet(path)
            .filter(col("quantity") <= lit(45))
            .select(col("returnflag"), col("quantity"),
                    (col("extendedprice") * (lit(1.0) - col("discount")))
                    .alias("disc_price"))
            .group_by("returnflag")
            .agg((F.sum(col("quantity")), "sum_qty"),
                 (F.sum(col("disc_price")), "sum_disc_price"),
                 (F.count(), "n")))


def q3_query(sess, orders_path, lines_path, top_n: int = TOP_N):
    from spark_rapids_tpu.api import functions as F
    from spark_rapids_tpu.api.functions import col
    from spark_rapids_tpu.expr.core import lit
    orders = sess.read_parquet(orders_path).filter(col("o_flag") < lit(5))
    lines = sess.read_parquet(lines_path).filter(col("l_flag") != lit(0))
    return (lines.join(orders, left_on=col("l_orderkey"),
                       right_on=col("o_orderkey"))
            .select(col("o_shippriority"),
                    (col("l_price") * (lit(1.0) - col("l_disc")))
                    .alias("rev"))
            .group_by("o_shippriority")
            .agg((F.sum(col("rev")), "revenue"), (F.count(), "n"))
            .sort(("revenue", False))
            .limit(top_n))


def check_q1(rows, oracle) -> float:
    """Keys, integer sums and counts exact; DOUBLE sums within SUM_RTOL.
    Returns the largest relative error seen."""
    got = {int(k): (int(sq), float(sdp), int(n)) for k, sq, sdp, n in rows}
    assert len(got) == len(rows), f"duplicate group keys: {rows}"
    assert sorted(got) == sorted(oracle), (sorted(got), sorted(oracle))
    worst = 0.0
    for k, (sq, sdp, n) in oracle.items():
        assert got[k][0] == sq and got[k][2] == n, (k, got[k], oracle[k])
        worst = max(worst, abs(got[k][1] - sdp) / abs(sdp))
    assert worst <= SUM_RTOL, f"q1 DOUBLE sum off by {worst} > {SUM_RTOL}"
    return worst


def check_q3(rows, oracle) -> float:
    """Same keys in the same order and the same joined-row counts (exact);
    revenues within SUM_RTOL."""
    assert [(int(k), int(n)) for k, _, n in rows] == \
        [(k, n) for k, _, n in oracle], (rows, oracle)
    worst = max(abs(float(g) - e) / abs(e)
                for (_, g, _), (_, e, _) in zip(rows, oracle))
    assert worst <= SUM_RTOL, f"q3 DOUBLE sum off by {worst} > {SUM_RTOL}"
    return worst


# -- the engine's own records -----------------------------------------------

def timed_collect(df):
    """collect() plus the dispatch ledger's deltas over it. collect()
    returns host rows, so every queued program has completed."""
    from spark_rapids_tpu.obs import dispatch
    c0 = dispatch.counters()
    t0 = time.perf_counter()
    rows = df.collect()
    wall = time.perf_counter() - t0
    c1 = dispatch.counters()
    return rows, {
        "wall_s": wall,
        "compiles": c1["traces"] - c0["traces"],
        "compile_s": (c1["compile_ns"] - c0["compile_ns"]) / 1e9,
        "dispatches": c1["dispatches"] - c0["dispatches"],
    }


def cold_then_warm(name, make_df, check, rows_in):
    """Run the query twice from a freshly built DataFrame, checking both."""
    for run in ("cold", "warm"):
        rows, rec = timed_collect(make_df())
        emit(query=name, run=run, rows_in=rows_in, rows_out=len(rows),
             max_rel_err=check(rows), sum_rtol=SUM_RTOL, **rec)


def traced_programs():
    from spark_rapids_tpu.obs import dispatch
    return {(p["label"], tuple(map(str, p["bucket"]))): p
            for p in dispatch.programs()}


def assert_chip_did_the_work(lifecycle0) -> None:
    """`lifecycle0`: `lifecycle.counters()` from before the queries."""
    from spark_rapids_tpu.exec import lifecycle
    from spark_rapids_tpu.obs import dispatch
    progs = dispatch.programs()
    platforms = sorted({p["platform"] for p in progs})
    counters = dispatch.counters()
    assert platforms == ["tpu"], f"ledger platforms {platforms}"
    assert counters["dispatches"] > 0, counters
    lc = lifecycle.counters()
    assert lifecycle.open_breakers() == [], lifecycle.open_breakers()
    assert lc["breaker_open"] == lifecycle0["breaker_open"], lc
    assert lc["whole_plan_retries"] == lifecycle0["whole_plan_retries"], lc
    emit(proof="ledger", platforms=platforms,
         dispatches=counters["dispatches"], programs=counters["programs"],
         open_breakers=[], breaker_trips=0, task_retries=0)
    progs.sort(key=lambda p: -p["compile_ns"])
    emit(phase="compile_top", programs=[
        {"label": p["label"], "compile_s": p["compile_ns"] / 1e9,
         "traces": p["traces"]} for p in progs[:8]])


def assert_join_hash_is_pallas(new_programs) -> None:
    """The q3 join's bucket hash ran the murmur3 PALLAS kernel: a program
    of the join traced `pallas.murmur3_long` inline (the ledger records
    what each trace inlined), and that program is a Mosaic kernel on this
    device (`tpu_custom_call` in its compiled text), not the XLA
    formulation `ops/hashing.py` keeps for the CPU."""
    import jax
    import jax.numpy as jnp
    from spark_rapids_tpu.ops.pallas_kernels import murmur3_long_lanes
    hashed = sorted({p["label"] for p in new_programs
                     if "pallas.murmur3_long" in p["inlined"]})
    assert hashed, ("no q3 program inlined pallas.murmur3_long: "
                    f"{[(p['label'], p['inlined']) for p in new_programs]}")
    text = murmur3_long_lanes._jit.lower(
        jax.ShapeDtypeStruct((Q3_LINES,), jnp.int64),
        jax.ShapeDtypeStruct((Q3_LINES,), jnp.uint32)).compile().as_text()
    assert "tpu_custom_call" in text, "murmur3_long is not a Mosaic kernel"
    emit(proof="join_hash", kernel="pallas.murmur3_long",
         inlined_by=hashed, tpu_custom_call=True)


def native_codec_state() -> str:
    """'built' or 'loaded': the codec is compiled from committed source
    into an ignored .so on first use (native/__init__.py)."""
    from spark_rapids_tpu import native
    fresh = (os.path.exists(native._SO) and
             os.path.getmtime(native._SO) >= os.path.getmtime(native._SRC))
    assert native.native_lib() is not None, "native codec did not build"
    return "loaded" if fresh else "built"


# -- one chip ---------------------------------------------------------------

def run_one_chip(seed, work, q1_rows=Q1_ROWS, q3_orders=Q3_ORDERS,
                 q3_lines=Q3_LINES) -> None:
    import jax
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec import lifecycle

    t0 = time.perf_counter()
    li = gen_lineitem(seed, q1_rows)
    orders, lines = gen_q3(seed, q3_orders, q3_lines)
    li_path = write_parquet(os.path.join(work, "lineitem"), li, 8, 4)
    o_path = write_parquet(os.path.join(work, "orders"), orders, 2, 2)
    l_path = write_parquet(os.path.join(work, "q3_lineitem"), lines, 4, 2)
    q1_want = q1_oracle(li)
    q3_want = q3_oracle(orders, lines)
    emit(phase="data", seed=seed, q1_rows=q1_rows,
         q3_orders=q3_orders, q3_lines=q3_lines,
         parquet_files=len(glob.glob(os.path.join(work, "*", "*.parquet"))),
         setup_s=time.perf_counter() - t0)
    del li

    sess = TpuSession()
    lifecycle0 = lifecycle.counters()

    emit(plan="q1", tree=q1_query(sess, li_path)._exec().tree_string())
    cold_then_warm("q1", lambda: q1_query(sess, li_path),
                   lambda rows: check_q1(rows, q1_want), q1_rows)

    before = traced_programs()
    emit(plan="q3", tree=q3_query(sess, o_path, l_path)._exec().tree_string())
    cold_then_warm("q3", lambda: q3_query(sess, o_path, l_path),
                   lambda rows: check_q3(rows, q3_want),
                   q3_orders + q3_lines)
    new = [p for k, p in traced_programs().items()
           if k not in before or p["traces"] > before[k]["traces"]]

    assert_chip_did_the_work(lifecycle0)
    assert_join_hash_is_pallas(new)
    stats = jax.devices()[0].memory_stats()
    emit(phase="device_memory", peak_bytes_in_use=stats["peak_bytes_in_use"],
         bytes_limit=stats["bytes_limit"])


# -- four chips -------------------------------------------------------------

def assert_four_devices(n_exchanges: int, shards) -> None:
    """The exchanged arrays' shards sit on four distinct devices, each
    holding a different slice (uploads alone would leave all on device 0)."""
    assert n_exchanges > 0, "no collective exchange step ran"
    assert len(shards) == 4 and len(set(shards.values())) == 4, \
        f"exchange output shards (device -> slice start): {shards}"


def run_four_chips(seed, work, q3_orders=Q3_ORDERS,
                   q3_lines=Q3_LINES) -> None:
    """Only what exists across chips: the q3 shape over the mesh exchange
    (`ShuffleExchangeExec`, all_to_all) and over the ICI lane of
    `HostShuffleExchangeExec`, each against a one-chip session here."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.exec import exchange
    from spark_rapids_tpu.exec import lifecycle
    from spark_rapids_tpu.shuffle import manager as shuffle_mgr

    orders, lines = gen_q3(seed, q3_orders, q3_lines)
    o_path = write_parquet(os.path.join(work, "orders"), orders, 2, 2)
    l_path = write_parquet(os.path.join(work, "q3_lineitem"), lines, 4, 2)
    want = q3_oracle(orders, lines)
    rows_in = q3_orders + q3_lines
    lifecycle0 = lifecycle.counters()

    def run(name, sess, must_plan):
        df = q3_query(sess, o_path, l_path)
        tree = df._exec().tree_string()
        for node in must_plan:
            assert node in tree, f"{name}: no {node} in\n{tree}"
        n0 = exchange.exchange_placement(reset=True)["exchanges"]
        rows, rec = timed_collect(df)
        placed = exchange.exchange_placement()
        emit(query="q3", lane=name, rows_in=rows_in, rows_out=len(rows),
             max_rel_err=check_q3(rows, want), sum_rtol=SUM_RTOL,
             exchanges=placed["exchanges"] - n0,
             shard_device_to_slice=placed["shards"], **rec)
        return rows, placed["exchanges"] - n0, placed["shards"]

    no_bcast = {"spark.rapids.sql.broadcastSizeThreshold": "-1"}
    one, _, _ = run("one_chip", TpuSession(no_bcast), ())

    mesh, n_ex, devs = run(
        "mesh_all_to_all", TpuSession(no_bcast, mesh_devices=4),
        ("ShuffleExchangeExec", "ShuffledHashJoinExec"))
    assert [(k, n) for k, _, n in mesh] == [(k, n) for k, _, n in one], \
        (mesh, one)
    assert_four_devices(n_ex, devs)

    c0, i0 = shuffle_mgr.counters(), shuffle_mgr.ici_counters()
    ici, n_ex, devs = run("ici_host_exchange", TpuSession({
        **no_bcast,
        "spark.rapids.sql.shuffle.partitions": "4",
        "spark.rapids.tpu.shuffle.planExchange": "false",
        "spark.rapids.tpu.shuffle.ici.enabled": "true",
        # an armed skew splitter (adaptive, default on) keeps a join's
        # stream-side exchange on the host lane; off, all three ride ICI
        "spark.rapids.tpu.adaptive.skewedPartitionFactor": "0",
    }, mesh_devices=4), ("HostShuffleExchangeExec",))
    c1, i1 = shuffle_mgr.counters(), shuffle_mgr.ici_counters()
    assert [(k, n) for k, _, n in ici] == [(k, n) for k, _, n in one], \
        (ici, one)
    assert c1["frames"] == c0["frames"], "ICI lane wrote host frames"
    rounds = i1["rounds"] - i0["rounds"]
    assert rounds > 0 and i1["fallbacks"] == i0["fallbacks"], (i0, i1)
    assert_four_devices(n_ex, devs)
    emit(proof="ici", rounds=rounds, host_frames=0, fallbacks=0)

    assert_chip_did_the_work(lifecycle0)


# -- entry ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--work-dir", default=os.path.join(HERE, ".chip_smoke_work"))
    args = ap.parse_args(argv)

    # Refuse first: no CPU run of this script ends in `ok`.
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py needs a TPU; jax selected {dev.platform!r} "
              f"({dev.device_kind}). Run it through the chip tool.",
              file=sys.stderr)
        return 2
    if len(jax.devices()) != args.chips:
        print(f"chip_smoke.py --chips {args.chips} found "
              f"{len(jax.devices())} device(s)", file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}

    import spark_rapids_tpu  # noqa: F401 — x64 + compile-cache placement
    emit(phase="start", device=device, jax=jax.__version__,
         compile_cache_dir=jax.config.jax_compilation_cache_dir,
         compile_cache_from_env=bool(
             os.environ.get("JAX_COMPILATION_CACHE_DIR")),
         native_codec=native_codec_state(),
         cuts=["q3 groups by o_shippriority (8 groups), not l_orderkey "
               "(512K groups): the exact sort tier costs the chip's "
               "compiler ~8 min, past this script's 1200 s; sizes uncut"])

    shutil.rmtree(args.work_dir, ignore_errors=True)
    os.makedirs(args.work_dir)
    try:
        if args.chips == 4:
            run_four_chips(args.seed, args.work_dir)
        else:
            run_one_chip(args.seed, args.work_dir)
    finally:
        shutil.rmtree(args.work_dir, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
