"""Spark-compatible hash kernels: Murmur3_x86_32 (seed 42) and XxHash64.

Replaces the reference's JNI Hash kernels (spark-rapids-jni `Hash`, used by
HashFunctions.scala and GpuHashPartitioningBase.scala). Bit-for-bit parity
with Spark's Murmur3Hash / XxHash64 expressions is required because hash
partitioning decides shuffle placement: a CPU-partial / TPU-final aggregate
must agree on row placement.

All lanes vectorized on the VPU; uint32/uint64 wrap-around arithmetic is
native in XLA. Variable-length (string) hashing uses a device-side
while_loop over 4-byte words with per-row masking — trip count is the max
byte length in the batch, known only on device, which XLA handles fine in a
while loop (no recompile).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..columnar.column import Column, StringColumn, StructColumn
from ..columnar.encoded import DictionaryColumn, row_byte_lanes
from ..types import (
    BooleanType, ByteType, DateType, DecimalType, DoubleType, FloatType,
    IntegerType, LongType, ShortType, StringType, TimestampType,
)

# --- Murmur3_x86_32 -------------------------------------------------------

# numpy (not jnp) scalars: a module-level jnp call builds a jax array at
# IMPORT time, and a first import inside a jit trace captures a tracer —
# the PR 2 order-dependent leak class (contract rule trace-module-jnp).
# Every use site has a jax operand, so dtype semantics are unchanged.
_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)


def _rotl32(x, r):
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1):
    return _rotl32(k1 * _C1, 15) * _C2


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(h1, 13)
    return h1 * jnp.uint32(5) + jnp.uint32(0xE6546B64)


def _fmix(h1, length):
    h1 = h1 ^ jnp.uint32(length)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * jnp.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * jnp.uint32(0xC2B2AE35)
    h1 = h1 ^ (h1 >> 16)
    return h1


def _use_pallas() -> bool:
    """Static (trace-time) tier choice: the Pallas kernel on real TPU
    when spark.rapids.tpu.pallas.enabled, else the fused-XLA path. An
    open `pallas_hash` circuit breaker (exec/lifecycle.FAMILY_DOMAINS
    entry for the `murmur3` family, ISSUE 8) demotes NEW traces to the
    XLA formulation."""
    from ..config import PALLAS_ENABLED, active_conf
    from ..parallel.mesh import active_mesh
    from .pallas_kernels import on_tpu
    if not (on_tpu() and active_conf().get(PALLAS_ENABLED)):
        return False
    # A mesh session's per-partition programs take shards of mesh-sharded
    # arrays, so XLA partitions them — and "Mosaic kernels cannot be
    # automatically partitioned" (the four-chip run of PR 23 died in the
    # shuffled join's build). Until shards are single-device arrays, a
    # mesh of more than one device hashes with the XLA formulation.
    mesh = active_mesh()
    if mesh is not None and mesh.size > 1:
        return False
    from ..exec import lifecycle
    if not lifecycle.breaker_allows(lifecycle.FAMILY_DOMAINS["murmur3"]):
        return False
    # a classified-transient failure of this attempt then counts
    # against the domain, and a half-open probe can close on success
    lifecycle.note_engagement("murmur3")
    return True


def murmur3_int(v, seed):
    """v: int32 lanes; seed: uint32 lanes. Spark Murmur3_x86_32.hashInt."""
    if _use_pallas():
        from .pallas_kernels import murmur3_int_lanes
        return murmur3_int_lanes(v, seed)
    k1 = _mix_k1(v.astype(jnp.uint32))
    return _fmix(_mix_h1(seed, k1), 4)


def murmur3_long(v, seed):
    if _use_pallas():
        from .pallas_kernels import murmur3_long_lanes
        return murmur3_long_lanes(v, seed)
    v = v.astype(jnp.uint64)
    low = (v & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    high = (v >> 32).astype(jnp.uint32)
    h1 = _mix_h1(seed, _mix_k1(low))
    h1 = _mix_h1(h1, _mix_k1(high))
    return _fmix(h1, 8)


def _normalize_float(data, dtype):
    """Spark normalizes -0.0 to 0.0 before hashing."""
    zero = jnp.zeros((), data.dtype)
    return jnp.where(data == zero, zero, data)


def murmur3_bytes(lengths, starts, data, byte_cap, seed):
    """Spark Murmur3_x86_32.hashUnsafeBytes over per-row (start, length)
    byte spans of a flat buffer: little-endian 4-byte words, then
    trailing bytes one at a time (sign-extended). The span form (ISSUE
    18) lets dictionary columns hash through code-indirected starts
    without materializing."""
    def word_at(t):
        # little-endian 4-byte word at starts + 4t per row
        base = starts + 4 * t
        b = [data[jnp.clip(base + j, 0, byte_cap - 1)].astype(jnp.uint32)
             for j in range(4)]
        return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)

    max_words = jnp.max(lengths) // 4

    def body(carry):
        t, h1 = carry
        active = (4 * (t + 1)) <= lengths
        h1_new = _mix_h1(h1, _mix_k1(word_at(t)))
        return t + 1, jnp.where(active, h1_new, h1)

    def cond(carry):
        t, _ = carry
        return t < max_words

    h0 = jnp.broadcast_to(seed, lengths.shape).astype(jnp.uint32)
    _, h1 = jax.lax.while_loop(cond, body, (jnp.int32(0), h0))

    # trailing 0..3 bytes, one at a time, sign-extended to int32
    aligned = (lengths // 4) * 4
    for j in range(3):
        p = jnp.clip(starts + aligned + j, 0, byte_cap - 1)
        byte = data[p].astype(jnp.int8).astype(jnp.int32)  # sign extension
        active = (aligned + j) < lengths
        h1 = jnp.where(active, _mix_h1(h1, _mix_k1(byte.astype(jnp.uint32))), h1)
    return _fmix(h1, lengths.astype(jnp.uint32))


def murmur3_string(col: StringColumn, seed):
    """Spark Murmur3_x86_32.hashUnsafeBytes over a string column."""
    lengths = (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)
    return murmur3_bytes(lengths, col.offsets[:-1], col.data,
                         col.byte_capacity, seed)


def murmur3_column(col: Column, seed) -> jnp.ndarray:
    """Per-row murmur3 update: null rows leave the running hash unchanged
    (Spark semantics). seed is uint32 lanes (running hash)."""
    dt = col.dtype
    if isinstance(col, DictionaryColumn):
        # non-uniform running hash: hash each row's dictionary bytes
        # through code-indirected (start, length) spans — no decode.
        # (murmur3_batch owns the uniform-seed precompute fast path.)
        lengths, starts, data, byte_cap = row_byte_lanes(col)
        h = murmur3_bytes(lengths.astype(jnp.int32), starts, data,
                          byte_cap, seed)
    elif isinstance(col, StringColumn):
        h = murmur3_string(col, seed)
    elif isinstance(col, StructColumn):
        h = seed
        for kid in col.children:
            h = murmur3_column(kid, h)
        return jnp.where(col.validity, h, seed)
    elif isinstance(dt, BooleanType):
        h = murmur3_int(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (ByteType, ShortType, IntegerType, DateType)):
        h = murmur3_int(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (LongType, TimestampType)):
        h = murmur3_long(col.data, seed)
    elif isinstance(dt, FloatType):
        bits = jax.lax.bitcast_convert_type(
            _normalize_float(col.data, dt), jnp.int32)
        h = murmur3_int(bits, seed)
    elif isinstance(dt, DoubleType):
        from .f64bits import f64_bits_signed
        bits = f64_bits_signed(_normalize_float(col.data, dt))
        h = murmur3_long(bits, seed)
    elif isinstance(dt, DecimalType) and not dt.is_decimal128:
        h = murmur3_long(col.data, seed)
    else:
        raise TypeError(f"murmur3 unsupported for {dt}")
    return jnp.where(col.validity, h, seed)


def murmur3_batch(columns, seed: int = 42) -> jnp.ndarray:
    """Spark Murmur3Hash(cols..., 42) -> int32 lanes."""
    cap = columns[0].capacity
    h = jnp.full((cap,), jnp.uint32(seed))
    for i, col in enumerate(columns):
        if i == 0 and isinstance(col, DictionaryColumn):
            # ISSUE 18: the running hash is still the uniform scalar
            # seed, so hash the dictionary ONCE and serve per-row
            # hashes as a code-indexed gather of the precomputed table
            # (not a re-hash per row). Later fold positions carry
            # per-row hashes and take murmur3_column's span path.
            from ..columnar.encoded import dict_take, dictionary_hashes
            table = dictionary_hashes(col, seed)
            h = jnp.where(col.validity, dict_take(table, col.codes), h)
        else:
            h = murmur3_column(col, h)
    return h.astype(jnp.int32)


# --- XxHash64 -------------------------------------------------------------

# numpy scalars for the same reason as _C1/_C2 above (every use site
# folds into a jax uint64 expression: seeds are always jax lanes)
_P1 = np.uint64(0x9E3779B185EBCA87)
_P2 = np.uint64(0xC2B2AE3D27D4EB4F)
_P3 = np.uint64(0x165667B19E3779F9)
_P4 = np.uint64(0x85EBCA77C2B2AE63)
_P5 = np.uint64(0x27D4EB2F165667C5)


def _rotl64(x, r):
    return (x << r) | (x >> (64 - r))


def _xx_fmix(h):
    h = h ^ (h >> 33)
    h = h * _P2
    h = h ^ (h >> 29)
    h = h * _P3
    h = h ^ (h >> 32)
    return h


def xxhash64_int(v, seed):
    """Spark XXH64.hashInt: the int's 4 bytes, zero-extended."""
    h = seed + _P5 + jnp.uint64(4)
    k = (v.astype(jnp.uint32).astype(jnp.uint64)) * _P1
    h = _rotl64(h ^ k, 23) * _P2 + _P3
    return _xx_fmix(h)


def xxhash64_long(v, seed):
    h = seed + _P5 + jnp.uint64(8)
    k = _rotl64(v.astype(jnp.uint64) * _P2, 31) * _P1
    h = h ^ k
    h = _rotl64(h, 27) * _P1 + _P4
    return _xx_fmix(h)


def xxhash64_string(col: StringColumn, seed):
    """XXH64 over utf-8 bytes per row (Spark XXH64.hashUnsafeBytesBlock)."""
    lengths = (col.offsets[1:] - col.offsets[:-1]).astype(jnp.int32)
    starts = col.offsets[:-1]
    byte_cap = col.byte_capacity
    data = col.data

    def word64_at(base):
        b = [data[jnp.clip(base + j, 0, byte_cap - 1)].astype(jnp.uint64)
             for j in range(8)]
        out = b[0]
        for j in range(1, 8):
            out = out | (b[j] << (8 * j))
        return out

    def word32_at(base):
        b = [data[jnp.clip(base + j, 0, byte_cap - 1)].astype(jnp.uint32)
             for j in range(4)]
        return b[0] | (b[1] << 8) | (b[2] << 16) | (b[3] << 24)

    n = lengths.shape[0]
    seed_l = jnp.broadcast_to(seed, (n,)).astype(jnp.uint64)
    long_input = lengths >= 32

    # 32-byte stripe accumulators (only for rows with >= 32 bytes)
    v1 = seed_l + _P1 + _P2
    v2 = seed_l + _P2
    v3 = seed_l
    v4 = seed_l - _P1
    stripes = lengths // 32
    max_stripes = jnp.max(stripes)

    def stripe_body(carry):
        s, v1, v2, v3, v4 = carry
        base = starts + 32 * s
        act = s < stripes

        def upd(v, off):
            nv = _rotl64(v + word64_at(base + off) * _P2, 31) * _P1
            return jnp.where(act, nv, v)

        return s + 1, upd(v1, 0), upd(v2, 8), upd(v3, 16), upd(v4, 24)

    _, v1, v2, v3, v4 = jax.lax.while_loop(
        lambda c: c[0] < max_stripes, stripe_body,
        (jnp.int32(0), v1, v2, v3, v4))

    hash_big = (_rotl64(v1, 1) + _rotl64(v2, 7) + _rotl64(v3, 12) +
                _rotl64(v4, 18))

    def merge(h, v):
        h = h ^ (_rotl64(v * _P2, 31) * _P1)
        return h * _P1 + _P4

    hash_big = merge(merge(merge(merge(hash_big, v1), v2), v3), v4)
    h = jnp.where(long_input, hash_big, seed_l + _P5)
    h = h + lengths.astype(jnp.uint64)

    # remaining 8-byte words
    consumed = stripes * 32
    rem8 = (lengths - consumed) // 8
    max8 = jnp.max(rem8)

    def rem8_body(carry):
        t, h, consumed_t = carry
        act = t < rem8
        k = _rotl64(word64_at(starts + consumed_t) * _P2, 31) * _P1
        nh = _rotl64(h ^ k, 27) * _P1 + _P4
        return (t + 1, jnp.where(act, nh, h),
                jnp.where(act, consumed_t + 8, consumed_t))

    _, h, consumed = jax.lax.while_loop(
        lambda c: c[0] < max8, rem8_body, (jnp.int32(0), h, consumed))

    # one 4-byte word
    has4 = (lengths - consumed) >= 4
    k4 = word32_at(starts + consumed).astype(jnp.uint64) * _P1
    nh = _rotl64(h ^ k4, 23) * _P2 + _P3
    h = jnp.where(has4, nh, h)
    consumed = jnp.where(has4, consumed + 4, consumed)

    # trailing bytes
    for j in range(3):
        p = jnp.clip(starts + consumed + j, 0, byte_cap - 1)
        act = (consumed + j) < lengths
        k1 = data[p].astype(jnp.uint64) * _P5
        nh = _rotl64(h ^ k1, 11) * _P1
        h = jnp.where(act, nh, h)
    return _xx_fmix(h)


def xxhash64_column(col: Column, seed) -> jnp.ndarray:
    dt = col.dtype
    if isinstance(col, StringColumn):
        h = xxhash64_string(col, seed)
    elif isinstance(dt, BooleanType):
        h = xxhash64_int(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (ByteType, ShortType, IntegerType, DateType)):
        h = xxhash64_int(col.data.astype(jnp.int32), seed)
    elif isinstance(dt, (LongType, TimestampType)):
        h = xxhash64_long(col.data, seed)
    elif isinstance(dt, FloatType):
        bits = jax.lax.bitcast_convert_type(
            _normalize_float(col.data, dt), jnp.int32)
        h = xxhash64_int(bits, seed)
    elif isinstance(dt, DoubleType):
        from .f64bits import f64_bits_signed
        bits = f64_bits_signed(_normalize_float(col.data, dt))
        h = xxhash64_long(bits, seed)
    elif isinstance(dt, DecimalType) and not dt.is_decimal128:
        h = xxhash64_long(col.data, seed)
    elif isinstance(col, StructColumn):
        # decimal128/struct: fold the children (limbs) — engine-internal
        # consistency (bucketing/grouping); cross-system partition parity
        # for >18-digit decimals is not claimed
        h = seed
        for kid in col.children:
            h = xxhash64_column(kid, h)
        return jnp.where(col.validity, h, seed)
    else:
        raise TypeError(f"xxhash64 unsupported for {dt}")
    return jnp.where(col.validity, h, seed)


def xxhash64_batch(columns, seed: int = 42) -> jnp.ndarray:
    """Spark XxHash64(cols..., 42) -> int64 lanes; null columns pass seed on."""
    cap = columns[0].capacity
    h = jnp.full((cap,), jnp.uint64(seed))
    for col in columns:
        h = xxhash64_column(col, h)
    return h.astype(jnp.int64)


def pmod(h, n: int):
    """Spark's positive-mod used by hash partitioning."""
    r = h % n
    return jnp.where(r < 0, r + n, r)
