"""Measure gather formulations on the real chip (round-4 join unlock).

Each variant is timed steady-state: an int32 device carry chains iterations
(no elision), ONE d2h fetch at the end. Fast variants use more iters so
per-program launch cost (not measured on this installation) amortizes.
"""

import time
import sys

import numpy as np

import jax
import jax.numpy as jnp

M = 1 << 19   # table rows (q3 build side)
N = 1 << 21   # queries (q3 stream side)


def timeit(name, fn, iters=8):
    c = jnp.int32(0)
    c = fn(c)  # warm/compile
    c.block_until_ready()
    t0 = time.perf_counter()
    c = jnp.int32(0)
    for _ in range(iters):
        c = fn(c)
    int(c)  # one fetch
    dt = (time.perf_counter() - t0) / iters * 1e3
    print(f"{name:34s} {dt:9.2f} ms")
    return dt


def main():
    rng = np.random.default_rng(0)
    idx = jnp.asarray(rng.integers(0, M, N, dtype=np.int32))
    t_i32 = jnp.asarray(rng.integers(0, 1 << 30, M, dtype=np.int32))
    t_i64 = t_i32.astype(jnp.int64)
    t_r8 = jnp.asarray(rng.integers(0, 1 << 30, (M, 8), dtype=np.int32))
    t_r128 = jnp.asarray(
        rng.integers(0, 1 << 30, (M, 128), dtype=np.int32))
    sidx = jnp.sort(idx)
    perm = jnp.asarray(rng.permutation(N).astype(np.int32))
    vals = jnp.asarray(rng.integers(0, 1 << 30, N, dtype=np.int32))
    key32 = jnp.asarray(rng.integers(0, 1 << 31, N, dtype=np.uint32))

    which = sys.argv[1:] if len(sys.argv) > 1 else None

    def want(n):
        return which is None or any(w in n for w in which)

    if want("g_1col_i32"):
        @jax.jit
        def f(c):
            y = t_i32[(idx + (c & 1))]
            return c + y[0]
        timeit("g_1col_i32 (2M from 512K)", f, 4)

    if want("g_1col_i64"):
        @jax.jit
        def f(c):
            y = t_i64[(idx + (c & 1))]
            return c + y[0].astype(jnp.int32)
        timeit("g_1col_i64", f, 4)

    if want("g_row8"):
        @jax.jit
        def f(c):
            y = t_r8[(idx + (c & 1))]
            return c + y[0, 0]
        timeit("g_row8 (2M rows of 8xi32)", f, 4)

    if want("g_row128"):
        @jax.jit
        def g(c, t):
            y = t[(idx + (c & 1))]
            return c + y[0, 0]

        def f(c):
            return g(c, t_r128)
        timeit("g_row128 (2M rows of 128xi32)", f, 2)

    if want("g_row32"):
        t_r32 = t_r128[:, :32]

        @jax.jit
        def g(c, t):
            y = t[(idx + (c & 1))]
            return c + y[0, 0]

        def f(c):
            return g(c, t_r32)
        timeit("g_row32 (2M rows of 32xi32)", f, 4)

    if want("g_two_in_one"):
        @jax.jit
        def f(c):
            y = t_i32[(idx + (c & 1))]
            z = t_i32[(idx ^ 1)]
            return c + y[0] + z[0]
        timeit("two 1col gathers in one program", f, 4)

    if want("g_sorted"):
        @jax.jit
        def f(c):
            y = jnp.take(t_i32, sidx + (c & 1), indices_are_sorted=True)
            return c + y[0]
        timeit("g_sorted_flag", f, 4)

    if want("taa"):
        # per-lane gather: table (4096,128), idx rows in [0,4096)
        tl = t_r128[:4096]
        il = (idx.reshape(-1, 128) % 4096)

        @jax.jit
        def f(c):
            y = jnp.take_along_axis(tl, (il + (c & 1)) % 4096, axis=0)
            return c + y[0, 0]
        timeit("taa_perlane XLA (16K,128)<-4096", f, 4)

    if want("scatter_set"):
        @jax.jit
        def f(c):
            z = jnp.zeros((N,), jnp.int32)
            z = z.at[perm].set(vals + (c & 1), mode="drop",
                               unique_indices=True)
            return c + z[0]
        timeit("scatter_set 2M unique", f, 4)

    if want("scatter_add"):
        @jax.jit
        def f(c):
            z = jnp.zeros((M,), jnp.int32)
            z = z.at[idx].add(vals + (c & 1), mode="drop")
            return c + z[0]
        timeit("scatter_add 2M->512K", f, 4)

    if want("sort2"):
        @jax.jit
        def f(c):
            k, v = jax.lax.sort((key32 + (c & 1).astype(jnp.uint32), vals),
                                num_keys=1)
            return c + v[0]
        timeit("sort 2M (u32 key + i32 payload)", f, 4)

    if want("sort3"):
        @jax.jit
        def f(c):
            k, v, w = jax.lax.sort(
                (key32 + (c & 1).astype(jnp.uint32), vals, perm), num_keys=1)
            return c + v[0]
        timeit("sort 2M (u32 + 2 payloads)", f, 4)

    if want("pallas_dg"):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        R = 4096  # table rows per lane-block; table (R,128) = 2MB VMEM

        def kern(t_ref, i_ref, o_ref):
            o_ref[:] = jnp.take_along_axis(t_ref[:], i_ref[:], axis=0)

        tl = t_r128[:R]
        il = (idx.reshape(-1, 128) % R)
        S = il.shape[0]  # 16384
        BLK = R  # out block rows must equal table rows for the rule

        def dg(tbl, ii):
            with jax.enable_x64(False):
                return pl.pallas_call(
                    kern,
                    out_shape=jax.ShapeDtypeStruct((S, 128), jnp.int32),
                    grid=(S // BLK,),
                    in_specs=[
                        pl.BlockSpec((R, 128), lambda i: (0, 0),
                                     memory_space=pltpu.VMEM),
                        pl.BlockSpec((BLK, 128), lambda i: (i, 0),
                                     memory_space=pltpu.VMEM),
                    ],
                    out_specs=pl.BlockSpec((BLK, 128), lambda i: (i, 0),
                                           memory_space=pltpu.VMEM),
                )(tbl, ii)

        @jax.jit
        def f(c):
            y = dg(tl, (il + (c & 1)) % R)
            return c + y[0, 0]
        timeit("pallas dynamic_gather perlane 2M", f, 8)


if __name__ == "__main__":
    main()
