"""Core row-layout kernels: active masks, compaction, gather, concat, slice.

These replace cuDF's gather/copy_if/concatenate primitives (reference L6,
SURVEY §2.9) with static-shape XLA programs. The universal trick: row counts
live in a device scalar (`num_rows`) while array shapes stay at the capacity
bucket, so filters/joins don't recompile.

Conventions:
  * every kernel is shape-polymorphic only in the capacity bucket;
  * rows with index >= num_rows are "inactive": validity False, data zero;
  * kernels return (columns..., new_num_rows) and always re-normalize the
    inactive region so downstream kernels can rely on it.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from ..columnar.column import ArrayColumn, Column, StringColumn, StructColumn
from ..columnar.encoded import NULL_CODE, DictionaryColumn
from .strings import gather_string


def active_mask(num_rows, capacity: int):
    """Bool (capacity,): True for logical rows."""
    return jnp.arange(capacity, dtype=jnp.int32) < num_rows


def sanitize(col: Column, num_rows) -> Column:
    """Force the inactive tail to (zero, invalid) so padded slots never leak."""
    act = active_mask(num_rows, col.capacity)
    validity = col.validity & act
    if isinstance(col, DictionaryColumn):
        codes = jnp.where(act, col.codes, jnp.int32(NULL_CODE))
        return DictionaryColumn(codes, col.dict_data, col.dict_offsets,
                                validity, col.dtype)
    if isinstance(col, StringColumn):
        return StringColumn(col.data, col.offsets, validity, col.dtype)
    if isinstance(col, StructColumn):
        kids = tuple(sanitize(k, num_rows) for k in col.children)
        return type(col)(kids, validity, col.dtype)  # incl. Decimal128
    if isinstance(col, ArrayColumn):
        return ArrayColumn(col.child, col.offsets, validity, col.dtype)
    from ..columnar.column import MapColumn
    if isinstance(col, MapColumn):
        return MapColumn(col.keys, col.values, col.offsets, validity,
                         col.dtype)
    data = jnp.where(act, col.data, jnp.zeros((), col.data.dtype))
    return Column(data, validity, col.dtype)


def gather_column(col: Column, indices, out_valid=None,
                  out_byte_capacity: int | None = None) -> Column:
    """Gather rows by int32 indices (the JoinGatherer primitive,
    reference JoinGatherer.scala). indices shape defines output capacity.
    `out_valid` masks output rows (False -> null+inactive slot).
    Out-of-range indices produce invalid rows.
    """
    from .gather import record as _record_gather
    cap = col.capacity
    # structural accounting (ISSUE 8): one materializing per-column
    # gather; no-op unless a wired exec's GatherTracker is observing
    _record_gather(1, nbytes=int(indices.shape[0])
                   * (col.data.dtype.itemsize
                      if type(col) is Column else 4))
    in_range = (indices >= 0) & (indices < cap)
    safe = jnp.where(in_range, indices, 0)
    valid = col.validity[safe] & in_range
    if out_valid is not None:
        valid = valid & out_valid
    if isinstance(col, DictionaryColumn):
        # codes gather fixed-width-style; the dictionary payload rides
        # along untouched (the whole point of staying encoded)
        codes = jnp.where(valid, col.codes[safe], jnp.int32(NULL_CODE))
        return DictionaryColumn(codes, col.dict_data, col.dict_offsets,
                                valid, col.dtype)
    if isinstance(col, StringColumn):
        return gather_string(col, safe, valid, out_byte_capacity)
    if isinstance(col, StructColumn):
        kids = tuple(gather_column(k, indices, out_valid, out_byte_capacity)
                     for k in col.children)
        return type(col)(kids, valid, col.dtype)  # incl. Decimal128
    if isinstance(col, ArrayColumn):
        from .collection import gather_array
        return gather_array(col, safe, valid,
                            out_child_capacity=out_byte_capacity)
    from ..columnar.column import MapColumn
    if isinstance(col, MapColumn):
        from .collection import gather_array
        from .maps import map_keys, map_values
        # duplicating gathers pass (entries, key_bytes, value_bytes)
        if isinstance(out_byte_capacity, tuple):
            elems, kb, vb = out_byte_capacity
            kcap = (elems, kb) if kb is not None else elems
            vcap = (elems, vb) if vb is not None else elems
        else:
            kcap = vcap = out_byte_capacity
        gk = gather_array(map_keys(col), safe, valid,
                          out_child_capacity=kcap)
        gv = gather_array(map_values(col), safe, valid,
                          out_child_capacity=vcap)
        return MapColumn(gk.child, gv.child, gk.offsets, valid, col.dtype)
    data = jnp.where(valid, col.data[safe], jnp.zeros((), col.data.dtype))
    return Column(data, valid, col.dtype)


def compaction_order(keep, num_rows):
    """Stable permutation moving kept active rows to the front.

    Returns (perm, new_num_rows). This is the engine's copy_if.

    HAZARD: slots at positions >= new_num_rows hold the DROPPED rows'
    indices (it is a full permutation) — an unmasked gather silently
    resurrects dropped rows as plausible-looking data. Every caller MUST
    mask the tail (gather with an active_mask(new_num_rows) out_valid, or
    wrap tail indices to -1). Use masked_compaction_order for the
    fail-safe variant that pre-wraps tail slots to -1.
    """
    cap = keep.shape[0]
    act = active_mask(num_rows, cap)
    k = keep & act
    iota = jnp.arange(cap, dtype=jnp.int32)
    # stable sort on the drop flag: kept rows first in original order.
    # Measured ~2x the scatter formulation on v5e (round 4): lax.sort is
    # the chip's cheapest reordering primitive.
    _, perm = jax.lax.sort(((~k).astype(jnp.uint32), iota), num_keys=1,
                           is_stable=True)
    new_rows = jnp.sum(k, dtype=jnp.int32)
    return perm, new_rows


def masked_compaction_order(keep, num_rows):
    """Fail-safe compaction_order: tail slots (>= new_num_rows) are -1, so
    an unmasked gather yields invalid rows instead of resurrecting dropped
    ones."""
    perm, new_rows = compaction_order(keep, num_rows)
    out_valid = active_mask(new_rows, keep.shape[0])
    return jnp.where(out_valid, perm, -1), new_rows


def compact_columns(columns: Sequence[Column], keep, num_rows
                    ) -> Tuple[Tuple[Column, ...], jnp.ndarray]:
    """Filter: keep rows where `keep` is True (null predicate rows dropped
    by the caller having already AND-ed validity into keep).

    Fixed-width columns compact through ONE packed row gather (XLA's
    gather cost on v5e is per-row loop overhead, not bytes — see
    ops/rowpack), routed through the gather engine (ops/gather) so the
    structural numGathers accounting covers every compaction in the
    engine; varlen/nested columns keep the per-column path."""
    from .gather import gather_batch_columns
    perm, new_rows = compaction_order(keep, num_rows)
    cap = keep.shape[0]
    out_valid = active_mask(new_rows, cap)
    out = gather_batch_columns(columns, perm, out_valid=out_valid)
    return tuple(out), new_rows


def concat_columns(a: Column, b: Column, a_rows, b_rows, out_capacity: int
                   ) -> Column:
    """Concatenate two columns' active rows (the coalesce primitive).

    Fixed-width lanes (data, validity, dictionary codes, struct / decimal128
    children) move as two contiguous blocks at a traced offset — no per-row
    gather; so do a string column's bytes, offsets and validity
    (`strings.concat_string`). Arrays keep a body of their own
    (`collection.concat_arrays` gathers the kept elements). The contract is
    a_rows <= a.capacity, b_rows <= b.capacity and
    a_rows + b_rows <= out_capacity; out_capacity may be smaller than either
    input's capacity (concat_batches' exact lane buckets the known row
    total). Rows at and past a_rows + b_rows come out zero / invalid /
    NULL_CODE whatever the inputs' padding holds.
    """
    out_valid = active_mask(a_rows + b_rows, out_capacity)
    if isinstance(a, DictionaryColumn):
        # coalesce inputs are materialized at the operator boundary
        # (exec/base.py), so this only fires for two views of the SAME
        # dictionary (e.g. slices of one scan batch) — concat the code
        # lanes fixed-width-style. Distinct dictionaries cannot be
        # merged shape-stably here; crash loudly rather than misread.
        assert isinstance(b, DictionaryColumn) \
            and a.dict_data is b.dict_data \
            and a.dict_offsets is b.dict_offsets, \
            "concat of distinct dictionaries — materialize first"
        codes = _concat_fixed(a.codes, b.codes, a_rows, out_capacity)
        codes = jnp.where(out_valid, codes, jnp.int32(NULL_CODE))
        valid = _concat_fixed(a.validity, b.validity, a_rows,
                              out_capacity) & out_valid
        return DictionaryColumn(codes, a.dict_data, a.dict_offsets,
                                valid, a.dtype)
    if isinstance(a, StringColumn):
        from .strings import concat_string
        return concat_string(a, b, a_rows, b_rows, out_capacity)
    if isinstance(a, StructColumn):
        kids = tuple(concat_columns(ka, kb, a_rows, b_rows, out_capacity)
                     for ka, kb in zip(a.children, b.children))
        valid = _concat_fixed(a.validity, b.validity, a_rows,
                              out_capacity) & out_valid
        return type(a)(kids, valid, a.dtype)  # incl. Decimal128
    if isinstance(a, ArrayColumn):
        # gather both sides' rows into the output slot order; gather_array
        # rebuilds offsets and compacts the child elements
        from .collection import concat_arrays
        return concat_arrays(a, b, a_rows, b_rows, out_capacity)
    data = _concat_fixed(a.data, b.data, a_rows, out_capacity)
    valid = _concat_fixed(a.validity, b.validity, a_rows,
                          out_capacity) & out_valid
    data = jnp.where(out_valid, data, jnp.zeros((), data.dtype))
    return Column(data, valid, a.dtype)


def _concat_fixed(a, b, a_rows, out_capacity: int):
    """(out_capacity,) lane: a[:a_rows], then b from row a_rows on. Rows past
    the two active blocks hold padding of a or b: the caller masks them.

    dynamic_update_slice clamps its start so the update fits, so b lands in
    a buffer of out_capacity + b.capacity rows (a_rows <= out_capacity:
    never clamped), of which the first out_capacity are kept."""
    buf = jnp.zeros((out_capacity + b.shape[0],), a.dtype)
    buf = jax.lax.dynamic_update_slice(buf, a[:out_capacity], (0,))
    buf = jax.lax.dynamic_update_slice(buf, b, (a_rows,))
    return buf[:out_capacity]


def slice_rows(col: Column, start, length, out_capacity: int) -> Column:
    """Rows [start, start+length) moved to the front of a fresh column."""
    idx = jnp.arange(out_capacity, dtype=jnp.int32) + start
    out_valid = jnp.arange(out_capacity, dtype=jnp.int32) < length
    return gather_column(col, idx, out_valid)
