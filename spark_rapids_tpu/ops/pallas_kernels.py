"""Pallas TPU kernels for the hash hotspot (SURVEY §2.9 #40: the
blueprint's Pallas tier over the XLA substrate; reference analog: the
hand-tuned CUDA hash kernels in spark-rapids-jni `Hash`).

Murmur3 is the engine's hottest scalar kernel — every shuffle partition
id, hash-join bucket and group-by probe hashes its keys with Spark-exact
murmur3_x86_32 (ops/hashing.py). The XLA path is ~20 elementwise HLOs per
key column; this kernel runs the whole mixing pipeline on the VPU inside
one VMEM tile, one HBM read + one write per block.

TPU constraints shape the ABI:
- the VPU has no 64-bit lanes → a LONG key is bitcast OUTSIDE the kernel
  to two int32 planes (low, high), which is exactly how murmur3 consumes
  an 8-byte value anyway (two 32-bit mix rounds);
- tiles are (sublane, 128): rows pad to TILE_ROWS×128 and view 2-D.
  Padding rows hash to garbage and are masked by callers (validity
  discipline is the engine-wide contract for padded capacity buckets);
- the running hash (seed) is a PER-ROW vector, because Spark chains
  columns by feeding column i's hash in as column i+1's seed.

Off-TPU the same kernel runs under the Pallas interpreter, so the CPU
test suite validates bit-exactness against the XLA path and the host
oracle. Enable on device via spark.rapids.tpu.pallas.enabled.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.dispatch import instrument as _instrument

TILE_ROWS = 256  # (256, 128) int32 tile = 128 KiB VMEM per operand


def _rotl(x, r):
    return jnp.bitwise_or(
        jax.lax.shift_left(x, np.uint32(r)),
        jax.lax.shift_right_logical(x, np.uint32(32 - r)))


C1 = np.uint32(0xCC9E2D51)
C2 = np.uint32(0x1B873593)
M5 = np.uint32(0xE6546B64)
F1 = np.uint32(0x85EBCA6B)
F2 = np.uint32(0xC2B2AE35)


def _mix_k1(k1):
    return _rotl(k1 * C1, 15) * C2


def _mix_h1(h1, k1):
    h1 = jnp.bitwise_xor(h1, k1)
    return _rotl(h1, 13) * np.uint32(5) + M5


def _fmix(h1, length):
    h1 = jnp.bitwise_xor(h1, np.uint32(length))
    h1 = jnp.bitwise_xor(h1, jax.lax.shift_right_logical(h1, np.uint32(16)))
    h1 = h1 * F1
    h1 = jnp.bitwise_xor(h1, jax.lax.shift_right_logical(h1, np.uint32(13)))
    h1 = h1 * F2
    return jnp.bitwise_xor(h1, jax.lax.shift_right_logical(h1, np.uint32(16)))


def _two_word_kernel(lo_ref, hi_ref, seed_ref, out_ref):
    """Spark murmur3 of an 8-byte value from two uint32 planes, per-row
    running-hash seeds (LONG/TIMESTAMP/DOUBLE lanes)."""
    h1 = _mix_h1(seed_ref[:], _mix_k1(lo_ref[:]))
    h1 = _mix_h1(h1, _mix_k1(hi_ref[:]))
    out_ref[:] = _fmix(h1, 8)


def _one_word_kernel(w_ref, seed_ref, out_ref):
    """4-byte value lanes (INT/FLOAT/DATE/BOOLEAN)."""
    out_ref[:] = _fmix(_mix_h1(seed_ref[:], _mix_k1(w_ref[:])), 4)


def pad_to_tiles(x: jnp.ndarray, tile_rows: int = TILE_ROWS):
    """Pad a 1-D lane to a whole number of (tile_rows, 128) VMEM tiles and
    view it 2-D. Returns (tiled view, original length). Shared by every
    Pallas family (murmur3, fused join probe, fused scan-aggregate) so
    padding discipline — garbage rows masked by callers — is uniform."""
    n = x.shape[0]
    per_tile = tile_rows * 128
    tiles = max(1, -(-n // per_tile))
    padded = tiles * per_tile
    if padded != n:
        x = jnp.pad(x, (0, padded - n))
    return x.reshape(tiles * tile_rows, 128), n


def tile_spec(tile_rows: int = TILE_ROWS):
    """BlockSpec for one (tile_rows, 128) VMEM tile of a grid-tiled lane."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec((tile_rows, 128), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)


def whole_spec():
    """BlockSpec for an operand resident in full across the whole grid
    (bucket tables, key lanes, permutations): every grid step sees the
    same block. Sized by the caller; the fused-tier selector gates shapes
    so these fit the VMEM budget on hardware."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    return pl.BlockSpec(memory_space=pltpu.VMEM)


# back-compat private aliases (murmur3 kernels below predate the shared
# helpers going public)
_pad_to_tiles = pad_to_tiles
_tile_spec = tile_spec


@functools.partial(_instrument, label="pallas.murmur3_long",
                   static_argnames=("interpret",))
def murmur3_long_lanes(data_i64, seeds_u32, interpret: bool = False):
    """Per-row murmur3 update over int64 lanes; seeds/result uint32."""
    from jax import enable_x64
    from jax.experimental import pallas as pl

    pair = jax.lax.bitcast_convert_type(
        data_i64.astype(jnp.int64), jnp.uint32)  # (n, 2): [low, high]
    lo, n = _pad_to_tiles(pair[:, 0])
    hi, _ = _pad_to_tiles(pair[:, 1])
    seeds, _ = _pad_to_tiles(seeds_u32.astype(jnp.uint32))
    rows = lo.shape[0]
    # mosaic wants i32 grid/index arithmetic; the engine's global x64
    # mode would trace the index maps as i64 and fail legalization
    with enable_x64(False):
        # contract: ok dispatch-ledger — traced inline into the
        # instrumented murmur3_long_lanes program above
        out = pl.pallas_call(
            _two_word_kernel,
            out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            grid=(rows // TILE_ROWS,),
            in_specs=[_tile_spec(), _tile_spec(), _tile_spec()],
            out_specs=_tile_spec(),
            interpret=interpret,
        )(lo, hi, seeds)
    return out.reshape(-1)[:n]


@functools.partial(_instrument, label="pallas.murmur3_int",
                   static_argnames=("interpret",))
def murmur3_int_lanes(data_i32, seeds_u32, interpret: bool = False):
    from jax import enable_x64
    from jax.experimental import pallas as pl

    w, n = _pad_to_tiles(jax.lax.bitcast_convert_type(
        data_i32.astype(jnp.int32), jnp.uint32))
    seeds, _ = _pad_to_tiles(seeds_u32.astype(jnp.uint32))
    rows = w.shape[0]
    with enable_x64(False):
        # contract: ok dispatch-ledger — traced inline into the
        # instrumented murmur3_int_lanes program above
        out = pl.pallas_call(
            _one_word_kernel,
            out_shape=jax.ShapeDtypeStruct((rows, 128), jnp.uint32),
            grid=(rows // TILE_ROWS,),
            in_specs=[_tile_spec(), _tile_spec()],
            out_specs=_tile_spec(),
            interpret=interpret,
        )(w, seeds)
    return out.reshape(-1)[:n]


def on_tpu() -> bool:
    """Is the default device a TPU? A backend that cannot start raises
    here — answering False would send every Pallas call site to
    interpret mode and hide the failure."""
    return jax.devices()[0].platform == "tpu"
