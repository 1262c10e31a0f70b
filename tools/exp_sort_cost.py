"""Measure v5e lax.sort cost vs lane count, and the packed-gather /
scatter alternatives, with the forced-checksum timing pattern
(see exp_q3_stages)."""

import sys
import time

sys.path.insert(0, "/root/repo")

import numpy as np
import jax
import jax.numpy as jnp

N = 1 << 21
rng = np.random.default_rng(0)
keys = jnp.asarray(rng.integers(0, 1 << 31, N, dtype=np.uint32))
iota = jnp.arange(N, dtype=jnp.int32)
mat8 = jnp.asarray(rng.integers(0, 1 << 31, (N, 8), dtype=np.uint32))
mat16 = jnp.asarray(rng.integers(0, 1 << 31, (N, 16), dtype=np.uint32))
perm = jnp.asarray(rng.permutation(N).astype(np.int32))


def timed(name, fn, iters=6):
    out = fn(jnp.uint32(0))
    float(np.asarray(out))  # force
    t0 = time.perf_counter()
    chk = jnp.uint32(0)
    for _ in range(iters):
        chk = fn(chk)
    float(np.asarray(chk))
    dt = (time.perf_counter() - t0) / iters * 1e3
    print(f"{name:34s} {dt:8.1f} ms", flush=True)


def sort_l(lanes):
    @jax.jit
    def f(salt):
        ops = [keys ^ salt] + [keys] * (lanes - 1) + [iota]
        out = jax.lax.sort(tuple(ops), num_keys=lanes, is_stable=True)
        return out[-1][0].astype(jnp.uint32)
    return f


for L in (1, 2, 3, 4, 6, 8):
    timed(f"lax.sort {L} u32 key lanes + iota", sort_l(L))


@jax.jit
def sort_nokey_payload8(salt):
    # 1 key lane, 8 payload lanes carried through the sort
    ops = [keys ^ salt] + [mat8[:, j] for j in range(8)] + [iota]
    out = jax.lax.sort(tuple(ops), num_keys=1, is_stable=True)
    return out[-1][0].astype(jnp.uint32)


timed("sort 1 key + 8 payload lanes", sort_nokey_payload8)


@jax.jit
def gather_mat8(salt):
    g = mat8[perm ^ (salt & 0)]
    return g[0, 0] + salt


@jax.jit
def gather_mat16(salt):
    g = mat16[perm ^ (salt & 0)]
    return g[0, 0] + salt


timed("row gather (N,8) u32", gather_mat8)
timed("row gather (N,16) u32", gather_mat16)


@jax.jit
def scatter_mat8(salt):
    out = jnp.zeros((N, 8), jnp.uint32).at[perm].set(mat8)
    return out[0, 0] + salt


timed("row scatter .at[].set (N,8)", scatter_mat8)


@jax.jit
def packed_flag_sort(salt):
    # compaction-order candidate: single fused lane (flag<<31 | iota)
    flag = (keys ^ salt) >> jnp.uint32(31)
    word = (flag << jnp.uint32(31)) | iota.astype(jnp.uint32)
    out = jax.lax.sort((word,), num_keys=1, is_stable=False)
    return out[0][0]


timed("compaction: fused flag|iota 1-lane", packed_flag_sort)


@jax.jit
def two_lane_compaction(salt):
    flag = (keys ^ salt) >> jnp.uint32(31)
    out = jax.lax.sort((flag, iota), num_keys=1, is_stable=True)
    return out[1][0].astype(jnp.uint32)


timed("compaction: flag + iota 2-lane", two_lane_compaction)


@jax.jit
def cumsum_scatter_compact(salt):
    keep = ((keys ^ salt) >> jnp.uint32(31)) == 0
    dest = jnp.cumsum(keep.astype(jnp.int32)) - 1
    dest = jnp.where(keep, dest, N)
    out = jnp.zeros((N, 8), jnp.uint32).at[dest].set(mat8, mode="drop")
    return out[0, 0] + salt


timed("compaction: cumsum + row scatter", cumsum_scatter_compact)
