"""CPU tests of `chip_smoke.py` and of what it relies on: it refuses to end in
`ok` off the chip, its generator and oracle agree with the engine at a tiny
size, the compile cache is placed from outside, and the engine no longer
answers for a device it could not see (ISSUE 23). None of this is a chip run.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run(args, env_extra, cwd=ROOT):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_extra)
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


# -- refusal ----------------------------------------------------------------

@pytest.mark.parametrize("extra", [[], ["--chips", "4"]])
def test_refuses_to_end_in_ok_on_the_cpu(extra):
    r = _run([str(ROOT / "chip_smoke.py"), *extra], {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "needs a TPU" in r.stderr


# -- generator + oracle vs the engine, tiny -----------------------------------

@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    work = tmp_path_factory.mktemp("chip_smoke_tiny")
    li = chip_smoke.gen_lineitem(7, 20_000)
    orders, lines = chip_smoke.gen_q3(7, 3_000, 12_000)
    return {
        "li": li, "orders": orders, "lines": lines,
        "li_path": chip_smoke.write_parquet(str(work / "li"), li, 3, 2),
        "o_path": chip_smoke.write_parquet(str(work / "o"), orders, 2, 2),
        "l_path": chip_smoke.write_parquet(str(work / "l"), lines, 2, 3),
    }


def test_q1_shape_matches_oracle_through_read_parquet(tiny):
    from spark_rapids_tpu.api.session import TpuSession
    df = chip_smoke.q1_query(TpuSession(), tiny["li_path"])
    assert "SourceScanExec[ParquetSource]" in df._exec().tree_string()
    want = chip_smoke.q1_oracle(tiny["li"])
    assert len(want) == 4
    assert chip_smoke.check_q1(df.collect(), want) <= chip_smoke.SUM_RTOL


def test_q3_shape_matches_oracle_through_read_parquet(tiny):
    from spark_rapids_tpu.api.session import TpuSession
    df = chip_smoke.q3_query(TpuSession(), tiny["o_path"], tiny["l_path"])
    tree = df._exec().tree_string()
    assert "HashJoinExec" in tree and "SortExec" in tree, tree
    want = chip_smoke.q3_oracle(tiny["orders"], tiny["lines"])
    assert len(want) == chip_smoke.TOP_N
    assert chip_smoke.check_q3(df.collect(), want) <= chip_smoke.SUM_RTOL


def test_checks_reject_a_wrong_answer(tiny):
    want = chip_smoke.q1_oracle(tiny["li"])
    rows = [(k, sq, sdp, n) for k, (sq, sdp, n) in want.items()]
    chip_smoke.check_q1(rows, want)
    off_by_one = [(k, sq + (k == 2), sdp, n) for k, sq, sdp, n in rows]
    with pytest.raises(AssertionError):
        chip_smoke.check_q1(off_by_one, want)
    drifted = [(k, sq, sdp * (1 + 1e-6), n) for k, sq, sdp, n in rows]
    with pytest.raises(AssertionError, match="DOUBLE sum off"):
        chip_smoke.check_q1(drifted, want)
    want3 = chip_smoke.q3_oracle(tiny["orders"], tiny["lines"])
    with pytest.raises(AssertionError):
        chip_smoke.check_q3(list(reversed(want3)), want3)


def test_same_seed_same_data_other_seed_other_data():
    a, b = chip_smoke.gen_lineitem(3, 1000), chip_smoke.gen_lineitem(3, 1000)
    c = chip_smoke.gen_lineitem(4, 1000)
    assert all((a[k] == b[k]).all() for k in a)
    assert not (a["extendedprice"] == c["extendedprice"]).all()


def test_four_chip_lanes_on_four_virtual_devices(tmp_path, monkeypatch):
    """The `--chips 4` path end to end on virtual CPU devices: the mesh
    all_to_all exchange and the ICI lane of the host exchange both match
    the one-chip session and leave shards on four distinct devices. Only
    the ledger's `tpu` assertion is stood down."""
    monkeypatch.setattr(chip_smoke, "assert_chip_did_the_work",
                        lambda *a: None)
    chip_smoke.run_four_chips(1, str(tmp_path), q3_orders=2048,
                              q3_lines=8192)


# -- compile cache: placed from outside ---------------------------------------

_PRINT_CACHE_DIR = ("import spark_rapids_tpu, jax; "
                    "print(jax.config.jax_compilation_cache_dir)")


def test_compile_cache_env_set_means_code_sets_nothing(tmp_path):
    outside = str(tmp_path / "placed_from_outside")
    r = _run(["-c", _PRINT_CACHE_DIR],
             {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": outside})
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip() == outside


def test_compile_cache_env_unset_is_one_fixed_dir_in_the_checkout(tmp_path):
    here = _run(["-c", _PRINT_CACHE_DIR], {"JAX_PLATFORMS": "cpu"})
    elsewhere = _run(["-c", _PRINT_CACHE_DIR],
                     {"JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT),
                      "TMPDIR": str(tmp_path)}, cwd=tmp_path)
    assert here.returncode == 0 and elsewhere.returncode == 0, here.stderr
    assert here.stdout.strip() == str(ROOT / ".jax_cache")
    assert elsewhere.stdout.strip() == here.stdout.strip()
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()


# -- no fallback that hides the device ----------------------------------------

class _FakeTpu:
    platform = "tpu"
    device_kind = "fake"

    def __init__(self, stats):
        self._stats = stats

    def memory_stats(self):
        return self._stats


def test_detect_hbm_constant_is_for_the_cpu_only(monkeypatch):
    import jax
    from spark_rapids_tpu.memory import budget
    assert budget._detect_hbm() == budget._DEFAULT_HBM  # CPU backend
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_FakeTpu({"bytes_limit": 123})])
    assert budget._detect_hbm() == 123
    for stats in (None, {}, {"bytes_in_use": 1}):
        monkeypatch.setattr(jax, "devices", lambda *a, s=stats: [_FakeTpu(s)])
        with pytest.raises(RuntimeError, match="bytes_limit"):
            budget._detect_hbm()


def test_device_manager_rejects_an_ordinal_it_cannot_see():
    import jax
    from spark_rapids_tpu.memory.device_manager import DeviceManager
    with pytest.raises(ValueError, match="out of range"):
        DeviceManager().initialize(device_ordinal=len(jax.devices()))
    assert DeviceManager().initialize(device_ordinal=0).device \
        is jax.devices()[0]


def test_backend_failure_reaches_the_hash_tier_choice(monkeypatch):
    """`on_tpu()` and `_use_pallas()` let a backend that cannot start
    raise instead of quietly answering 'not a TPU' / 'use XLA'."""
    import jax
    from spark_rapids_tpu.ops import hashing, pallas_kernels

    def dead(*a):
        raise RuntimeError("backend cannot start")

    assert pallas_kernels.on_tpu() is False and not hashing._use_pallas()
    monkeypatch.setattr(jax, "devices", dead)
    with pytest.raises(RuntimeError, match="cannot start"):
        pallas_kernels.on_tpu()
    with pytest.raises(RuntimeError, match="cannot start"):
        hashing._use_pallas()


def test_mesh_session_hashes_with_xla_not_mosaic(monkeypatch):
    """Found by a four-chip run: programs that take shards of
    mesh-sharded arrays are partitioned by XLA, and Mosaic kernels cannot
    be partitioned automatically. The mesh exchange hands each partition
    on as arrays of its own chip, so a mesh session keeps the Pallas
    kernels; the host exchange's ICI lane still hands on mesh shards, so a
    mesh session with that lane on hashes with XLA."""
    from spark_rapids_tpu.config import RapidsConf, set_active_conf
    from spark_rapids_tpu.ops import hashing, pallas_kernels
    from spark_rapids_tpu.parallel.mesh import device_mesh, set_active_mesh
    monkeypatch.setattr(pallas_kernels, "on_tpu", lambda: True)
    try:
        set_active_conf(RapidsConf({}))
        set_active_mesh(None)
        assert hashing._use_pallas()
        set_active_mesh(device_mesh(1))
        assert hashing._use_pallas()
        set_active_mesh(device_mesh(4))
        assert hashing._use_pallas()
        set_active_conf(RapidsConf(
            {"spark.rapids.tpu.shuffle.ici.enabled": "true"}))
        assert not hashing._use_pallas()
        set_active_mesh(device_mesh(1))
        assert hashing._use_pallas()
    finally:
        set_active_mesh(None)
        set_active_conf(RapidsConf({}))


def test_bench_probe_requires_a_tpu():
    import bench
    with pytest.raises(RuntimeError, match="measures the chip"):
        bench.init_backend()
