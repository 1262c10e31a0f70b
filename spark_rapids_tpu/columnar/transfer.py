"""Packed device->host batch transfer.

Host materialization of a result batch used to fetch every column (and the
row count) as its own d2h transfer. Each transfer pays a full round-trip
latency — on remote-attached TPUs that latency dwarfs the kernels, and even
locally it serializes the pipeline once per column. The analog in the
reference is JCudfSerialization packing a table into ONE host buffer
(SURVEY §2.5); here a tiny jitted packer bit-casts every buffer of the
batch into one contiguous uint8 vector so materialization is exactly one
transfer, then numpy views slice it back apart on the host.

Layout (all little-endian, matching XLA bitcasts on every supported host):
  [int32 num_rows][per column: blocks in schema order]
    fixed-width col : data bytes (cap*itemsize)  + validity (cap bytes)
    string/binary   : offsets ((cap+1)*4) + data (byte_cap) + validity
    struct          : validity + child blocks
    array           : offsets ((cap+1)*4) + validity + child blocks
"""

from __future__ import annotations

import threading
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .column import (ArrayColumn, Column, MapColumn, StringColumn,
                     StructColumn)

# process-cumulative packed-D2H counters (ISSUE 11: the device->host
# half of the telemetry plane's link-byte attribution — the H2D mirror
# lives in columnar/upload.py)
_COUNTER_LOCK = threading.Lock()
_COUNTERS = {"d2h_copies": 0, "d2h_bytes": 0}


def note_d2h(nbytes: int) -> None:
    """One packed device->host copy landed (`exec/exchange.py` calls
    this for the fused split+pack buffer it fetches itself)."""
    with _COUNTER_LOCK:
        _COUNTERS["d2h_copies"] += 1
        _COUNTERS["d2h_bytes"] += int(nbytes)


def counters() -> Dict[str, int]:
    with _COUNTER_LOCK:
        return dict(_COUNTERS)


def _dd_split() -> bool:
    """True when f64 must travel as (hi, lo) float32 pairs: TPU emulates
    f64 as double-double, its compiler has no f64 bitcast, and the dd pair
    IS the exact device value (reconstruction is lossless by construction).
    CPU/GPU keep the direct IEEE-754 bitcast."""
    return jax.default_backend() not in ("cpu", "gpu", "cuda", "rocm")


def _bytes_of(arr) -> jnp.ndarray:
    """Flatten any device array into a uint8 vector via bitcast.

    64-bit integer lanes are staged through uint32: TPU's X64 rewriting
    pass stores 64-bit values as u32 pairs and implements 64->32 bitcasts,
    but not a direct 64->8 bitcast. The u32 pair order matches the
    little-endian byte order numpy `.view()` expects on the host
    (asserted by tests).
    """
    if arr.dtype == jnp.bool_:
        return arr.astype(jnp.uint8).ravel()
    if arr.dtype == jnp.uint8:
        return arr.ravel()
    if arr.dtype == jnp.float64 and _dd_split():
        hi = arr.astype(jnp.float32)
        lo = (arr - hi.astype(jnp.float64)).astype(jnp.float32)
        arr = jnp.stack([hi, lo], axis=-1).ravel()
    elif np.dtype(arr.dtype).itemsize == 8:
        # ravel between the two bitcasts: XLA's simplifier mis-folds a
        # chained 64->32->8 bitcast into one op with the wrong shape
        arr = jax.lax.bitcast_convert_type(arr, jnp.uint32).ravel()
    return jax.lax.bitcast_convert_type(arr, jnp.uint8).ravel()


def _pack_column(col: Column, out: List[jnp.ndarray]) -> None:
    if isinstance(col, StringColumn):
        out.append(_bytes_of(col.offsets))
        out.append(_bytes_of(col.data))
        out.append(_bytes_of(col.validity))
        return
    if isinstance(col, StructColumn):
        out.append(_bytes_of(col.validity))
        for k in col.children:
            _pack_column(k, out)
        return
    if isinstance(col, ArrayColumn):
        out.append(_bytes_of(col.offsets))
        out.append(_bytes_of(col.validity))
        _pack_column(col.child, out)
        return
    if isinstance(col, MapColumn):
        out.append(_bytes_of(col.offsets))
        out.append(_bytes_of(col.validity))
        _pack_column(col.keys, out)
        _pack_column(col.values, out)
        return
    out.append(_bytes_of(col.data))
    out.append(_bytes_of(col.validity))


def _pack_impl(batch) -> jnp.ndarray:
    pieces: List[jnp.ndarray] = [
        _bytes_of(jnp.asarray(batch.num_rows, jnp.int32).reshape(1))]
    for col in batch.columns:
        _pack_column(col, pieces)
    return jnp.concatenate(pieces)


from ..obs import op_span
from ..obs.dispatch import instrument as _instrument

_pack_jit = _instrument(_pack_impl, label="transfer.pack_batch")


def _take(buf: np.ndarray, pos: int, n: int) -> Tuple[np.ndarray, int]:
    return buf[pos: pos + n], pos + n


def _unpack_column(col: Column, buf: np.ndarray, pos: int
                   ) -> Tuple[Column, int]:
    cap = col.capacity
    if isinstance(col, StringColumn):
        raw, pos = _take(buf, pos, (cap + 1) * 4)
        offsets = raw.view(np.int32)
        data, pos = _take(buf, pos, col.byte_capacity)
        v, pos = _take(buf, pos, cap)
        return StringColumn(data, offsets, v.astype(np.bool_), col.dtype), pos
    if isinstance(col, StructColumn):
        v, pos = _take(buf, pos, cap)
        kids = []
        for k in col.children:
            kid, pos = _unpack_column(k, buf, pos)
            kids.append(kid)
        # type(col) keeps Decimal128Column limbs as decimal, not struct
        return type(col)(tuple(kids), v.astype(np.bool_), col.dtype), pos
    if isinstance(col, ArrayColumn):
        raw, pos = _take(buf, pos, (cap + 1) * 4)
        offsets = raw.view(np.int32)
        v, pos = _take(buf, pos, cap)
        kid, pos = _unpack_column(col.child, buf, pos)
        return ArrayColumn(kid, offsets, v.astype(np.bool_), col.dtype), pos
    if isinstance(col, MapColumn):
        raw, pos = _take(buf, pos, (cap + 1) * 4)
        offsets = raw.view(np.int32)
        v, pos = _take(buf, pos, cap)
        keys, pos = _unpack_column(col.keys, buf, pos)
        vals, pos = _unpack_column(col.values, buf, pos)
        return MapColumn(keys, vals, offsets, v.astype(np.bool_),
                         col.dtype), pos
    np_dtype = np.dtype(col.data.dtype)
    if np_dtype == np.bool_:
        raw, pos = _take(buf, pos, cap)
        data = raw.astype(np.bool_)
    elif np_dtype == np.float64 and _dd_split():
        raw, pos = _take(buf, pos, cap * 8)
        pair = raw.view(np.float32).reshape(cap, 2)
        data = pair[:, 0].astype(np.float64) + pair[:, 1].astype(np.float64)
    else:
        raw, pos = _take(buf, pos, cap * np_dtype.itemsize)
        data = raw.view(np_dtype)
    v, pos = _take(buf, pos, cap)
    return Column(data, v.astype(np.bool_), col.dtype), pos


def _pack_split_impl(counts, columns) -> jnp.ndarray:
    pieces: List[jnp.ndarray] = [_bytes_of(counts.astype(jnp.int32))]
    for col in columns:
        _pack_column(col, pieces)
    return jnp.concatenate(pieces)


_pack_split_jit = _instrument(_pack_split_impl,
                              label="transfer.pack_split")


def pack_split(counts, columns) -> jnp.ndarray:
    """Traceable split packer: (count table, partition-ordered columns)
    -> one uint8 buffer. Exposed so the exchange can fuse it INTO the
    partition-split traced program (ISSUE 10 satellite — shuffle write
    is ONE dispatch, split + reorder + pack)."""
    return _pack_split_impl(counts, list(columns))


def unpack_split_host(buf: np.ndarray, template_columns,
                      n_parts: int) -> Tuple[np.ndarray, List[Column]]:
    """Host-side unpack of a pack_split buffer. `template_columns` only
    provides the layout (class / capacity / dtype per column) — column
    objects or `jax.eval_shape` results both work, so the fused
    split+pack program never has to materialize per-column device
    arrays. Returns (counts int64 numpy, numpy-backed columns)."""
    host_counts = buf[: 4 * n_parts].view(np.int32).astype(np.int64)
    pos = 4 * n_parts
    out: List[Column] = []
    for col in template_columns:
        host_col, pos = _unpack_column(col, buf, pos)
        out.append(host_col)
    assert pos == buf.shape[0], (pos, buf.shape)
    return host_counts, out


def fetch_split_host(counts, columns) -> Tuple[np.ndarray, List[Column]]:
    """Packed D2H lane for the device shuffle partition split (ISSUE 9):
    land the per-partition count table AND the partition-ordered columns
    in ONE host copy. The count table is the only host-synced control
    value of the split; the column payload rides the same buffer instead
    of per-column pulls.

    Returns (counts int64 numpy, numpy-backed columns).
    """
    n_parts = int(counts.shape[0])
    buf = np.asarray(_pack_split_jit(counts, list(columns)))  # ONE d2h
    note_d2h(buf.nbytes)
    return unpack_split_host(buf, columns, n_parts)


def fetch_batch_host(batch) -> Tuple[List[Column], int]:
    """Materialize a device batch with ONE d2h transfer.

    Returns (numpy-backed columns, host row count). Already-host batches
    (numpy leaves) pass through untouched.
    """
    # late-materialization output seam (ISSUE 18): a batch fetched for
    # host consumption genuinely needs full values — decode encoded
    # columns through the gather engine before the packed d2h
    from .encoded import materialize_batch
    batch = materialize_batch(batch, seam="output")
    leaves = jax.tree_util.tree_leaves(batch.columns)
    if batch._host_rows is not None and all(
            isinstance(x, np.ndarray) for x in leaves):
        return list(batch.columns), batch._host_rows
    # the one place the driving thread waits for the device: every
    # program the query enqueued has to finish before these bytes exist
    with op_span("result.fetch", phase="device-wait"):
        packed = _pack_jit(batch)
        buf = np.asarray(packed)  # the single transfer
    note_d2h(buf.nbytes)
    n = int(buf[:4].view(np.int32)[0])
    pos = 4
    cols: List[Column] = []
    for col in batch.columns:
        host_col, pos = _unpack_column(col, buf, pos)
        cols.append(host_col)
    assert pos == buf.shape[0], (pos, buf.shape)
    return cols, n
