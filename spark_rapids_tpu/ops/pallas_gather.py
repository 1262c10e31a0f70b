"""DMA-driven packed row gather — the Pallas `gather` kernel family
(ISSUE 8 tentpole; reference analog: cuDF's gather as a first-class
table primitive behind JoinGatherer, not N per-column ops).

XLA's random gather on v5e is loop-bound, not bandwidth-bound
(docs/perf.md "The q3 lane"; costs not measured on this installation).
The engine already
amortizes column count by packing fixed-width columns into one u32
(+ one f64) matrix (ops/rowpack.py); this kernel replaces the XLA row
gather OVER that packed layout with explicit per-row DMA: index tiles
stream through SMEM, the source matrix stays in HBM, and a window of
in-flight async copies moves whole packed rows straight into the VMEM
output tile — one HBM touch per gathered row, no gather loop.

ABI (shared engine contracts):
- the source matrix is ALL u32 lanes: the wrapper bitcasts the f64
  matrix to two u32 lanes per column (TPU kernels avoid 64-bit lanes,
  same discipline as the murmur3/join kernels) and splits it back after
  the gather, so null masks and payload ride ONE pass;
- out-of-range indices (idx < 0 or >= capacity) read row 0 and the
  wrapper zeroes the validity lanes — bit-identical to
  ops/rowpack.gather_rows, which the interpret-mode property tests
  assert elementwise (tests/test_pallas_gather.py);
- index arrays are capacity-bucket padded by callers; padded slots are
  -1 and come back all-invalid (the engine-wide padding contract of
  ops/pallas_kernels.py).

Like the other families the kernel traces under enable_x64(False) on
hardware (mosaic wants i32 grid arithmetic) and under the engine's
global x64 mode in interpret mode. Selection is a measurement: the
`gather` family in tools/kern_bench.py + ops/pallas_tier.py decides
per shape bucket; no record -> the XLA row gather stays.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..obs.dispatch import instrument as _instrument

#: rows of packed output per grid step (each row is one DMA)
GATHER_TILE_ROWS = 256
#: in-flight row copies per grid step (W distinct DMA semaphores;
#: iteration r starts row r+W-1 before waiting row r, so up to W-1
#: copies overlap — the guide's double-buffer pattern generalized)
DMA_WINDOW = 8

#: host-side count of pallas_call dispatches (trace-time): lets tests
#: and bench attribution assert the measured tier actually routed a
#: gather through the kernel rather than silently falling back
_kernel_traces = 0


def kernel_trace_count() -> int:
    return _kernel_traces


def _gather_kernel_body(window: int, tile_rows: int):
    def kernel(idx_ref, src_ref, out_ref, sems):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        def dma(r):
            # interpret mode traces under the engine's global x64, so
            # loop counters arrive as i64 — normalize for the i32 slot
            # arithmetic either way
            r = jnp.asarray(r, jnp.int32)
            i = idx_ref[r, 0]
            return pltpu.make_async_copy(
                src_ref.at[pl.ds(i, 1), :],
                out_ref.at[pl.ds(r, 1), :],
                sems.at[jax.lax.rem(r, jnp.int32(window))])

        def warm(r, c):
            dma(r).start()
            return c

        jax.lax.fori_loop(0, min(window - 1, tile_rows), warm, 0)

        def body(r, c):
            nxt = r + jnp.int32(window - 1)

            @pl.when(nxt < jnp.int32(tile_rows))
            def _():
                dma(nxt).start()

            dma(r).wait()
            return c

        jax.lax.fori_loop(0, tile_rows, body, 0)

    return kernel


@functools.partial(_instrument, label="pallas.gather",
                   static_argnames=("interpret",))
def dma_row_gather(mat: jnp.ndarray, idx: jnp.ndarray,
                   interpret: bool = False) -> jnp.ndarray:
    """out[i] = mat[idx[i]] by per-row DMA; the caller pre-sanitizes idx
    to [0, capacity) (out-of-range handling is the wrapper's job)."""
    import contextlib

    from jax import enable_x64
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    global _kernel_traces
    _kernel_traces += 1

    n = idx.shape[0]
    # the chip's compiler refuses a DMA slice narrower than the 128-lane
    # tiling ("Slice shape along dimension 1 must be aligned to tiling
    # (128), but is 8"), so rows travel padded to a multiple of 128 u32
    # lanes — up to 16x the bytes of a typical 8-lane packed row, which
    # is the measurement kern_bench has to take before `auto` picks this.
    # (Past 128 lanes the matrix is (8,128)-tiled and a one-row slice is
    # refused again, along dimension 0: such rows stay on the XLA tier.)
    true_lanes = mat.shape[1]
    lanes = -(-true_lanes // 128) * 128
    mat = jnp.pad(mat, ((0, 0), (0, lanes - true_lanes)))
    tr = GATHER_TILE_ROWS
    rows = max(1, -(-n // tr)) * tr
    idx2d = jnp.pad(idx.astype(jnp.int32), (0, rows - n)).reshape(rows, 1)
    grid = rows // tr

    # see ops/pallas_join.py: hardware traces x64-off for i32 grid
    # arithmetic; the interpreter re-canonicalizes under the global mode
    ctx = contextlib.nullcontext() if interpret else enable_x64(False)
    with ctx:
        # contract: ok dispatch-ledger — traced inline into the
        # instrumented dma_row_gather program above
        out = pl.pallas_call(
            _gather_kernel_body(DMA_WINDOW, tr),
            out_shape=jax.ShapeDtypeStruct((rows, lanes), jnp.uint32),
            grid=(grid,),
            in_specs=[
                pl.BlockSpec((tr, 1), lambda i: (i, 0),
                             memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((tr, lanes), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
            scratch_shapes=[pltpu.SemaphoreType.DMA((DMA_WINDOW,))],
            interpret=interpret,
        )(idx2d, mat)
    return out[:n, :true_lanes]


def pallas_gather_rows(plan, imat, fmat, idx, interpret: bool = False
                       ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray]]:
    """Drop-in for ops/rowpack.gather_rows served by the DMA kernel.

    Packs the f64 matrix into u32 lanes beside the int matrix so ONE
    kernel pass moves the whole row (validity bits + data), then splits
    and re-masks exactly like the XLA formulation.
    """
    cap = imat.shape[0]
    ni = imat.shape[1]
    parts = [imat]
    nf = 0
    if fmat is not None:
        nf = fmat.shape[1]
        f_u32 = jax.lax.bitcast_convert_type(fmat, jnp.uint32)
        parts.append(f_u32.reshape(cap, 2 * nf))
    mat = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    in_range = (idx >= 0) & (idx < cap)
    safe = jnp.where(in_range, idx, 0).astype(jnp.int32)
    g = dma_row_gather(mat, safe, interpret=interpret)

    nv = plan.n_valid_lanes
    gi = g[:, :ni]
    if nv:
        vmask = jnp.where(in_range, jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
        gi = jnp.concatenate([gi[:, :nv] & vmask[:, None], gi[:, nv:]],
                             axis=1)
    gf = None
    if fmat is not None:
        gf = jax.lax.bitcast_convert_type(
            g[:, ni:].reshape(idx.shape[0], nf, 2), fmat.dtype)
    return gi, gf
