"""Ask the chip's compiler, without the chip: the main path's programs are
compiled for a DESCRIBED v5e at the shapes `chip_smoke.py` runs, so a kernel
the TPU compiler refuses fails here and costs no chip time. Four sections:
the murmur3 Pallas kernels, every planner-built program of chip_smoke's two
queries, the coalesce's concat, and the mesh exchange step for the 2x2 mesh.

The topology is described inside a module-scoped fixture (never at import:
only one process may hold libtpu, and every xdist worker imports this file),
the compiles run in this process, and the persistent compile cache is off
around them (an entry compiled for a described chip cannot be read back).
A compile that passes is not a chip run.

The planner-built programs are captured at 1/512 of chip_smoke's sizes — every
capacity on that path is a power of two, so scaling each one by 512 gives
exactly the shapes the full-size run dispatches — and re-lowered with
`on_tpu()` / `jax.default_backend()` steered from here, which is what routes
the hash through the murmur3 Pallas kernels and f64 through double-double
transfer, as on the chip.
"""

import os
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import chip_smoke  # noqa: E402

SCALE = 512
ROWS_2M = 1 << 21


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _steer(mp, *, on_tpu: bool):
    """Make engine code that asks which backend it is on take its TPU
    branch. The upload pool's cached CPU-family answer is pinned first:
    it guards staging-buffer aliasing on the backend that really runs."""
    from spark_rapids_tpu.columnar import upload
    from spark_rapids_tpu.obs import dispatch
    from spark_rapids_tpu.ops import pallas_kernels
    mp.setattr(upload, "_CPU_FAMILY", True)
    mp.setattr(dispatch, "_platform_cache", "cpu")
    mp.setattr(jax, "default_backend", lambda: "tpu")
    if on_tpu:
        mp.setattr(pallas_kernels, "on_tpu", lambda: True)


@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """{label: [(site, args, kwargs)]} of every top-level dispatch of the
    q1 and q3 shapes, run through the planner at 1/SCALE size with f64 in
    double-double transfer (the hash stays on XLA: the kernels cannot RUN
    here outside interpret mode, only compile)."""
    from spark_rapids_tpu.api.session import TpuSession
    from spark_rapids_tpu.obs import dispatch
    work = tmp_path_factory.mktemp("chip_compile")
    li = chip_smoke.gen_lineitem(0, chip_smoke.Q1_ROWS // SCALE)
    orders, lines = chip_smoke.gen_q3(0, chip_smoke.Q3_ORDERS // SCALE,
                                      chip_smoke.Q3_LINES // SCALE)
    li_path = chip_smoke.write_parquet(str(work / "li"), li, 8, 4)
    o_path = chip_smoke.write_parquet(str(work / "o"), orders, 2, 2)
    l_path = chip_smoke.write_parquet(str(work / "l"), lines, 4, 2)

    calls = {}
    orig = dispatch.InstrumentedJit.__call__

    def recording(self, *a, **k):
        if getattr(dispatch._tls, "pending", None) is None \
                and dispatch._no_trace_in_progress():
            calls.setdefault(self.label, []).append((self, a, k))
        return orig(self, *a, **k)

    with pytest.MonkeyPatch.context() as mp:
        _steer(mp, on_tpu=False)
        mp.setattr(dispatch.InstrumentedJit, "__call__", recording)
        dispatch.reset_dispatch_ledger()
        sess = TpuSession()
        q1 = chip_smoke.q1_query(sess, li_path).collect()
        q3 = chip_smoke.q3_query(sess, o_path, l_path).collect()
    dispatch.reset_dispatch_ledger()
    # the double-double transfer lane is exact on the CPU: same oracle
    chip_smoke.check_q1(q1, chip_smoke.q1_oracle(li))
    chip_smoke.check_q3(q3, chip_smoke.q3_oracle(orders, lines))
    return calls


def _full_size(x, sharding):
    """Abstract stand-in for one captured argument at chip_smoke's size."""
    if isinstance(x, int) and not isinstance(x, bool):
        return x * SCALE if x > 128 else x
    if not hasattr(x, "shape"):
        return x  # static layout words, data types, flags
    shape = tuple(x.shape)
    if shape and shape[0] > 128:
        if x.dtype == np.uint8:  # packed upload: 4-byte header + rows
            shape = ((shape[0] - 4) * SCALE + 4,) + shape[1:]
        else:
            shape = (shape[0] * SCALE,) + shape[1:]
    return jax.ShapeDtypeStruct(shape, x.dtype, sharding=sharding)


def _compile_for_chip(site, args, kwargs, sharding):
    a, k = jax.tree_util.tree_map(lambda x: _full_size(x, sharding),
                                  (args, kwargs))
    return site._jit.lower(*a, **k).compile()


@pytest.fixture(scope="module")
def largest_text(captured, one_chip):
    """label -> text compiled at chip_smoke's size for the label's dispatch
    that saw the most bytes (q1's fully coalesced 16M-row batch for the
    shared upload/concat programs); each label compiles once. The caller
    steers the backend questions first."""
    texts = {}

    def text_of(label):
        if label not in texts:
            assert label in captured, sorted(captured)
            site, args, kwargs = max(captured[label], key=lambda c: sum(
                leaf.nbytes for leaf in jax.tree_util.tree_leaves(c[1:])
                if hasattr(leaf, "nbytes")))
            texts[label] = _compile_for_chip(site, args, kwargs,
                                             one_chip).as_text()
        return texts[label]
    return text_of


# -- the murmur3 kernels: they gate the default main path on a TPU ----------

@pytest.mark.parametrize("kernel,dtype", [("murmur3_long_lanes", jnp.int64),
                                          ("murmur3_int_lanes", jnp.int32)])
def test_murmur3_kernels_compile_at_2m_rows(one_chip, kernel, dtype):
    from spark_rapids_tpu.ops import pallas_kernels
    compiled = getattr(pallas_kernels, kernel)._jit.lower(
        jax.ShapeDtypeStruct((ROWS_2M,), dtype, sharding=one_chip),
        jax.ShapeDtypeStruct((ROWS_2M,), jnp.uint32, sharding=one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


# -- the planner-built programs of the two queries ---------------------------

#: label -> must its compiled text hold a Mosaic kernel (the join's hash)?
PLANNED = {
    "upload.unpack_batch": False,            # packed H2D, f64 as dd pairs
    "coalesce.concat_pair": False,
    "CompiledStageExec.step": False,         # q1: filter+project+agg fold
    "FilterExec.filter": False,              # q3 build side
    "CompiledStageExec.sizing": True,        # q3 join build + candidate count
    "CompiledStageExec.probe_step": True,    # q3 probe + masked-bucket agg
    "SortExec.sort": False,                  # top-N over the 128-row result
    "transfer.pack_batch": False,            # packed D2H of the result
}


@pytest.mark.parametrize("label", sorted(PLANNED))
def test_planned_program_compiles_at_chip_smoke_shapes(
        largest_text, monkeypatch, label):
    _steer(monkeypatch, on_tpu=True)
    text = largest_text(label)
    assert ("tpu_custom_call" in text) == PLANNED[label], label


def test_concat_pair_compiles_without_a_gather(largest_text, monkeypatch):
    """Coalesce copies two contiguous blocks a lane: a `gather(` in the
    compiled program is the per-row index walk back (two a lane, ~30 ns a
    row on the chip, 98.8% of a Q6 query's device time before PR 28)."""
    _steer(monkeypatch, on_tpu=True)
    text = largest_text("coalesce.concat_pair")
    assert "gather(" not in text
    assert "dynamic-update-slice(" in text


def test_captured_shapes_are_chip_smokes(captured):
    """Scaled by 512 the q1 stage sees the whole 16M-row table in one
    batch and the join sees the 512K x 2M pair; and chip_smoke's q3 stays
    off the exact sort tier, whose program takes the chip's compiler ~8
    minutes (so it is not compiled here either)."""
    def caps(arg):
        return {leaf.shape[0] * SCALE
                for leaf in jax.tree_util.tree_leaves(arg) if leaf.shape}
    _, args, _ = captured["CompiledStageExec.step"][-1]
    assert caps(args[0]) == {chip_smoke.Q1_ROWS}
    _, args, _ = captured["CompiledStageExec.sizing"][-1]
    assert caps(args[0]) == {chip_smoke.Q3_ORDERS}
    assert caps(args[1]) == {chip_smoke.Q3_LINES}
    assert not [k for k in captured if k.endswith("_exact")]


# -- four chips: the distributed step, for the described 2x2 mesh -------------

def test_mesh_exchange_step_compiles_for_the_2x2_mesh(topo, monkeypatch):
    """`ShuffleExchangeExec`'s SPMD step (hash-partition -> all_to_all ->
    compact) over the four described chips, at the per-chip share of
    chip_smoke's 2M-row q3 stream side: the collective is in the compiled
    text, and so is the murmur3 Mosaic kernel — inside shard_map it needs
    no partitioning. (A mesh SESSION hashes with XLA today, because its
    other programs are partitioned automatically: `hashing._use_pallas`;
    this exec is handed its mesh directly, so the kernel stays.)"""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.exec.basic import InMemoryScanExec
    from spark_rapids_tpu.exec.exchange import ShuffleExchangeExec
    from spark_rapids_tpu.expr.core import col
    from spark_rapids_tpu.parallel.distributed import stack_batches
    from spark_rapids_tpu.types import (DOUBLE, INT, LONG, Schema,
                                        StructField)
    _steer(monkeypatch, on_tpu=True)
    mesh = Mesh(np.array(topo.devices), ("data",))
    schema = Schema((StructField("l_orderkey", LONG),
                     StructField("l_price", DOUBLE),
                     StructField("l_disc", DOUBLE),
                     StructField("l_flag", INT)))
    tiny = ColumnarBatch.from_pydict(
        {"l_orderkey": [1, 2], "l_price": [1.0, 2.0],
         "l_disc": [0.1, 0.2], "l_flag": [1, 2]}, schema)
    exchange = ShuffleExchangeExec(
        [col("l_orderkey")], InMemoryScanExec([tiny], schema), mesh=mesh)
    cap = chip_smoke.Q3_LINES // 4
    over_mesh = NamedSharding(mesh, P("data"))
    stacked = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((4, cap) if x.ndim == 2 else (4,),
                                       x.dtype, sharding=over_mesh),
        stack_batches([tiny] * 4))
    compiled = exchange._get_step(cap, cap // 2, 8)._jit.lower(
        stacked).compile()
    text = compiled.as_text()
    assert "all-to-all" in text and "tpu_custom_call" in text
    # bytes on EACH chip, from the compiler: a few tens of MB of 16 GB
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes \
        + mem.temp_size_in_bytes < 1 << 30
