"""v2 sort-cost microbench: all inputs generated ON DEVICE (no 200 MB
of host-side constant uploads as in v1), forced-checksum timing,
progress printed per step."""

import sys
import time

sys.path.insert(0, "/root/repo")

import numpy as np
import jax
import jax.numpy as jnp

N = 1 << 21
print("building device inputs", flush=True)
iota = jnp.arange(N, dtype=jnp.int32)
# cheap on-device pseudo-random u32s (LCG mix of iota)
keys = (iota.astype(jnp.uint32) * jnp.uint32(2654435761)
        + jnp.uint32(12345)) ^ (iota.astype(jnp.uint32) >> 7)
mat8 = (keys[:, None] * (jnp.arange(8, dtype=jnp.uint32) + 1)[None, :])
perm = jax.lax.sort((keys, iota), num_keys=1)[1]
jax.block_until_ready(mat8)
fmat = (keys.astype(jnp.float64) * 1e-3)[:, None] * jnp.ones(
    (1, 2), jnp.float64)
print("inputs ready", flush=True)


def timed(name, fn, iters=6):
    out = fn(jnp.uint32(0))
    float(np.asarray(out))  # force compile + first run
    t0 = time.perf_counter()
    chk = jnp.uint32(0)
    for _ in range(iters):
        chk = fn(chk)
    float(np.asarray(chk))
    dt = (time.perf_counter() - t0) / iters * 1e3
    print(f"{name:42s} {dt:8.1f} ms", flush=True)


def sort_l(lanes):
    @jax.jit
    def f(salt):
        ops = [keys ^ salt] + [keys] * (lanes - 1) + [iota]
        out = jax.lax.sort(tuple(ops), num_keys=lanes)
        return out[-1][0].astype(jnp.uint32)
    return f


for L in (1, 2, 4, 6):
    timed(f"sort {L} u32 keys + iota key", sort_l(L))


@jax.jit
def sort_payload8(salt):
    ops = [keys ^ salt, iota] + [mat8[:, j] for j in range(8)]
    out = jax.lax.sort(tuple(ops), num_keys=2)
    return out[2][0].astype(jnp.uint32)


timed("sort 1 key + iota + 8 u32 payload", sort_payload8)


@jax.jit
def sort_payload8_f2(salt):
    ops = [keys ^ salt, iota] + [mat8[:, j] for j in range(8)] \
        + [fmat[:, 0], fmat[:, 1]]
    out = jax.lax.sort(tuple(ops), num_keys=2)
    return out[2][0].astype(jnp.uint32)


timed("sort 1key+iota+8u32+2f64 payload", sort_payload8_f2)


@jax.jit
def gather8(salt):
    g = mat8[perm]
    return g[0, 0] + salt


timed("row gather (N,8) u32 matrix", gather8)


@jax.jit
def fused_flag_sort(salt):
    flag = (keys ^ salt) >> jnp.uint32(31)
    word = (flag << jnp.uint32(31)) | iota.astype(jnp.uint32)
    out = jax.lax.sort((word,), num_keys=1)
    return out[0][0]


timed("compaction fused flag|iota 1 lane", fused_flag_sort)


@jax.jit
def two_lane_compaction(salt):
    flag = (keys ^ salt) >> jnp.uint32(31)
    out = jax.lax.sort((flag, iota), num_keys=2)
    return out[1][0].astype(jnp.uint32)


timed("compaction flag + iota 2 lanes", two_lane_compaction)


@jax.jit
def segscan_f64(salt):
    seg_start = (keys ^ salt) < jnp.uint32(1 << 24)

    def comb(a, b):
        av, af = a
        bv, bf = b
        return jnp.where(bf, bv, av + bv), af | bf
    v = fmat[:, 0]
    out, _ = jax.lax.associative_scan(comb, (v, seg_start))
    return out[0].astype(jnp.uint32) + salt


timed("segmented f64 cumsum (assoc scan)", segscan_f64)


@jax.jit
def plain_cumsum(salt):
    return jnp.cumsum(fmat[:, 0])[0].astype(jnp.uint32) + salt


timed("plain f64 cumsum", plain_cumsum)


@jax.jit
def segsum_scatter(salt):
    seg = (keys ^ salt) >> jnp.uint32(13)  # ~256K segments
    out = jax.ops.segment_sum(fmat[:, 0], seg.astype(jnp.int32),
                              num_segments=1 << 19)
    return out[0].astype(jnp.uint32) + salt


timed("segment_sum scatter f64 -> 512K", segsum_scatter)
