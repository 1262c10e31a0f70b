"""Fused probe-verify-emit Pallas kernel for the hash join (ISSUE 1
tentpole; reference analog: the cuDF mixed-join probe kernels that
spark-rapids treats as the entire point of the accelerator).

The XLA probe path runs bucket-range lookup, candidate-pair expansion,
key verification and packed-row gathers as SEPARATE programs with
full-width candidate-level intermediates round-tripping HBM between them
(ops/join.py, exec/joins.py:_probe_kernel). This kernel streams candidate
tiles through VMEM once: it forward-fills the owner-row index (the
cummax formulation of `expand_candidates`, carried across sequential
grid steps in SMEM), derives (stream_idx, build_pos) in-register, walks
the sorted-bucket `BuildTable` key lanes to verify exact key equality,
and emits (verified, stream_idx, build_pos, build_row) in one pass — no
expanded-index or gathered-key intermediate ever materializes in HBM.

Layout contract: candidates are walked in exactly the flat order of
`expand_candidates` (position start_i + k for stream row i's k-th
candidate), built from the SAME `candidate_fill_inputs` arrays, so the
two tiers are bit-identical — the interpret-mode property tests in
tier-1 assert elementwise equality (tests/test_pallas_fused.py).

Eligibility (gated by the caller / exec tier selector):
- every join key integer-like on both sides (ops/join.int_key_lanes):
  float keys keep IEEE `==` semantics the bit-equality lanes cannot
  express, strings/decimals are varlen/two-limb;
- candidate capacity < 2^31 (the i32 fast path's own bound);
- key-lane + permutation tables VMEM-resident on hardware — the
  measured tier (tools/kern_bench.py) only turns the kernel on where it
  actually wins, so oversize shapes simply keep the XLA tier.

All lanes are 32-bit, so like the murmur3 kernels the pallas_call traces
under jax.enable_x64(False) (mosaic wants i32 grid arithmetic).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.dispatch import instrument as _instrument

from .pallas_kernels import TILE_ROWS, pad_to_tiles, tile_spec, whole_spec

# candidate tiles are smaller than the murmur3 tiles: the kernel keeps
# several whole side tables VMEM-resident next to the streamed tile
PROBE_TILE_ROWS = 64


def _probe_kernel_body(n_lanes: int):
    """Kernel factory: the number of u32 key lanes is static per shape."""

    def kernel(total_ref, seg_ref, lo_ref, start_ref, *refs):
        from jax.experimental import pallas as pl
        bk_refs = refs[:n_lanes]
        sk_refs = refs[n_lanes:2 * n_lanes]
        (bvalid_ref, svalid_ref, perm_ref, ver_ref, sidx_ref, bpos_ref,
         brow_ref, carry_ref) = refs[2 * n_lanes:]
        t = pl.program_id(0)

        # --- owner-row forward fill: flat cummax over the (TR, 128) tile
        # with the running maximum carried across sequential grid steps
        # (full-slice scratch stores only: indexed/conditional stores
        # discharge through dtype-fragile selects in interpret mode) ---
        seg = seg_ref[:]                                  # (TR, 128) i32
        row_incl = jax.lax.cummax(seg, axis=1)
        last = row_incl[:, 127:128]                       # (TR, 1)
        incl = jax.lax.cummax(last, axis=0)               # (TR, 1)
        carry = jnp.where(t == jnp.int32(0), jnp.int32(0),
                          carry_ref[:][0])
        prev = jnp.concatenate(
            [jnp.zeros((1, 1), jnp.int32), incl[:-1]], axis=0)
        prev = jnp.maximum(prev, carry)
        row_f = jnp.maximum(row_incl, prev)               # (TR, 128)
        carry_ref[:] = jnp.maximum(carry, incl[-1, 0]).reshape(1)

        # --- expand in-register: (stream_idx, build_pos) per candidate ---
        tr = seg.shape[0]
        i_flat = (jnp.int32(t) * jnp.int32(tr * 128)
                  + jax.lax.broadcasted_iota(jnp.int32, (tr, 128), 0)
                  * jnp.int32(128)
                  + jax.lax.broadcasted_iota(jnp.int32, (tr, 128), 1))
        total = total_ref[0, 0]
        in_range = i_flat < total
        lo_arr = lo_ref[:]
        start_arr = start_ref[:]
        neg1 = jnp.int32(-1)
        b_pos = lo_arr[row_f] + (i_flat - start_arr[row_f])
        s_idx = jnp.where(in_range, row_f, neg1)

        # --- verify: exact key equality over the u32 lanes ---
        build_cap = perm_ref.shape[0]
        safe_b = jnp.clip(b_pos, jnp.int32(0), jnp.int32(build_cap - 1))
        ok = in_range
        for bk_ref, sk_ref in zip(bk_refs, sk_refs):
            ok = ok & (bk_ref[:][safe_b] == sk_ref[:][row_f])
        ok = ok & (bvalid_ref[:][safe_b] != jnp.int32(0)) \
            & (svalid_ref[:][row_f] != jnp.int32(0))

        # --- emit ---
        b_pos_m = jnp.where(in_range, b_pos, neg1)
        pos_ok = (b_pos_m >= jnp.int32(0)) & (b_pos_m < jnp.int32(build_cap))
        b_row = jnp.where(pos_ok, perm_ref[:][safe_b], neg1)
        ver_ref[:] = ok.astype(jnp.int32)
        sidx_ref[:] = s_idx
        bpos_ref[:] = b_pos
        brow_ref[:] = b_row

    return kernel


@functools.partial(_instrument, label="pallas.join_probe",
                   static_argnames=("out_capacity", "interpret"))
def fused_probe_verify(lo, counts, bk_lanes, bvalid, sk_lanes, svalid,
                       perm, out_capacity: int, interpret: bool = False):
    """One-pass probe of a bucketed build side.

    lo/counts: per-stream-row candidate range (ops/join.probe_counts);
    bk_lanes/sk_lanes: u32 equality lanes (build side in SORTED order —
    BuildTable.key_lanes); bvalid/svalid: i32 combined key-validity
    lanes; perm: sorted position -> original build row.

    Returns (verified bool, stream_idx i32, build_pos i32, build_row i32)
    over the flat candidate layout of `expand_candidates` — bit-identical
    to the XLA expand+verify pipeline for integer keys.
    """
    from jax import enable_x64
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu
    from .join import candidate_fill_inputs

    assert len(bk_lanes) == len(sk_lanes)
    n_lanes = len(bk_lanes)
    seg, ls = candidate_fill_inputs(lo, counts, out_capacity)
    total = jnp.sum(counts.astype(jnp.int64)) if counts.shape[0] \
        else jnp.int64(0)
    total32 = jnp.minimum(total, out_capacity).astype(jnp.int32)

    seg2d, _ = pad_to_tiles(seg, PROBE_TILE_ROWS)
    rows = seg2d.shape[0]
    grid = rows // PROBE_TILE_ROWS
    tspec = tile_spec(PROBE_TILE_ROWS)
    out_struct = jax.ShapeDtypeStruct((rows, 128), jnp.int32)

    import contextlib

    # mosaic wants i32 grid/index arithmetic, so the hardware path traces
    # under x64-off like the murmur3 kernels; the interpreter must trace
    # under the engine's global x64 mode instead — its state-discharge
    # replay re-canonicalizes jaxpr consts, and a jaxpr traced x64-off
    # then replayed x64-on trips dtype checks (every kernel value is
    # explicitly 32-bit typed either way)
    ctx = contextlib.nullcontext() if interpret else enable_x64(False)
    with ctx:
        smem_spec = pl.BlockSpec(
            (1, 1), lambda i: (0, 0), memory_space=pltpu.SMEM)
        whole = whole_spec()
        # contract: ok dispatch-ledger — this pallas_call is traced
        # inline into the instrumented fused_probe_verify program above
        ver, s_idx, b_pos, b_row = pl.pallas_call(
            _probe_kernel_body(n_lanes),
            out_shape=(out_struct,) * 4,
            grid=(grid,),
            in_specs=[smem_spec, tspec]
            + [whole] * (2 + 2 * n_lanes + 3),
            out_specs=(tspec,) * 4,
            scratch_shapes=[pltpu.SMEM((1,), jnp.int32)],
            interpret=interpret,
        )(total32.reshape(1, 1), seg2d, ls[:, 0], ls[:, 1],
          *[ln.astype(jnp.uint32) for ln in bk_lanes],
          *[ln.astype(jnp.uint32) for ln in sk_lanes],
          bvalid.astype(jnp.int32), svalid.astype(jnp.int32),
          perm.astype(jnp.int32))
    flat = slice(None, out_capacity)
    return (ver.reshape(-1)[flat] != 0, s_idx.reshape(-1)[flat],
            b_pos.reshape(-1)[flat], b_row.reshape(-1)[flat])
