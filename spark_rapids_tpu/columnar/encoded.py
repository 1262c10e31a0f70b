"""Dictionary-encoded string columns (ISSUE 18): keep Parquet
dictionary columns compressed from scan to output, materialize late.

"GPU Acceleration of SQL Analytics on Compressed Data" (PAPERS.md)
shows predicates and join keys can be evaluated directly on dictionary
codes; Theseus makes data movement the first-class design axis. The
engine analog: a `DictionaryColumn` carries a device-resident i32 code
lane plus the per-batch dictionary payload (Arrow (offsets, bytes)
layout, bucket-padded like every other buffer), so

  * the packed H2D upload ships codes + dictionary instead of the
    decoded width (typically a >=2x byte shrink on string-heavy scans),
  * HBM and the spill catalog hold the encoded bytes for the whole
    query (the column is a registered pytree; the catalog spills any
    pytree),
  * equality / IN / null predicates compare i32 codes on device after
    translating the literal through the dictionary ONCE per program
    (expr/predicates.py), and hash joins hash the dictionary once then
    gather precomputed hashes by code (ops/hashing.py),
  * decode happens at ONE chokepoint — `materialize_column` — routed
    through the gather engine (ops/gather.py: a dictionary decode IS a
    row gather of the dictionary by the code lane), only at seams that
    genuinely need full values (operator boundaries whose consumer
    cannot take encoded input, and output collection).

Null/inactive rows use the sentinel code `NULL_CODE` (-1), matching
the engine's -1 invalid-index gather idiom: an unmasked gather of the
dictionary by raw codes yields invalid rows for nulls, never garbage.

The column deliberately carries `data=None` (the StructColumn
precedent): any kernel that was not taught the encoded layout crashes
loudly on `.data` instead of silently misreading codes as values —
the materialize-at-boundary walk in exec/base.py exists so that crash
is unreachable in planned queries.
"""

from __future__ import annotations

import threading
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .column import (Column, StringColumn, _dev, _pad_np, bucket_capacity)
from ..types import BinaryType, DataType, StringType

__all__ = [
    "NULL_CODE", "DictionaryColumn", "dictionary_from_arrow", "dict_take",
    "dictionary_hashes", "row_byte_lanes", "bytes_equal_rows",
    "encoded_equal_literal", "materialize_column", "materialize_batch",
    "batch_has_encoded", "encoded_sig", "note_scan_batch", "counters",
]

#: sentinel code for null/inactive rows — out of range for every
#: dictionary, so unmasked gathers yield invalid rows (the -1 idiom)
NULL_CODE = -1


# ---------------------------------------------------------------------------
# process counters (bench.py embeds per-record deltas via _delta_since;
# the encoded_scan event and the advisor rule read the same totals)
# ---------------------------------------------------------------------------

_COUNTER_LOCK = threading.Lock()
_COUNTERS = {
    "cols_encoded": 0,          # DictionaryColumns built at scan seams
    "codes_bytes": 0,           # code-lane bytes (codes + validity)
    "dict_bytes": 0,            # dictionary payload bytes (offsets + data)
    "decoded_bytes_avoided": 0,  # eager-decode bytes the lane did NOT build
    "materializations": 0,      # late decodes through the gather engine
    "materialized_bytes": 0,    # decoded bytes actually produced late
    "code_space_predicates": 0,  # predicates evaluated on i32 codes
    "dict_hash_tables": 0,      # per-dictionary murmur3 precomputes
    "scan_string_bytes": 0,     # plain (decoded) string bytes built at scan
}


def _note(**deltas) -> None:
    with _COUNTER_LOCK:
        for k, v in deltas.items():
            _COUNTERS[k] += v


def counters() -> Dict[str, int]:
    with _COUNTER_LOCK:
        return dict(_COUNTERS)


# ---------------------------------------------------------------------------
# the column
# ---------------------------------------------------------------------------


class DictionaryColumn(Column):
    """Encoded varlen column: int32 codes into a per-batch dictionary.

    codes    — int32 (capacity,); NULL_CODE for null/inactive rows
    validity — bool (capacity,)
    dict_offsets / dict_data — the dictionary's Arrow (offsets, bytes)
        twin arrays, bucket-padded like a StringColumn's; padded
        dictionary slots are zero-length entries no valid code refers to
    """

    __slots__ = ("codes", "dict_data", "dict_offsets")

    def __init__(self, codes, dict_data, dict_offsets, validity,
                 dtype: DataType = StringType()):
        super().__init__(None, validity, dtype)
        self.codes = codes
        self.dict_data = dict_data
        self.dict_offsets = dict_offsets

    # -- shape -------------------------------------------------------------
    @property
    def capacity(self) -> int:
        return int(self.validity.shape[0])

    @property
    def dict_capacity(self) -> int:
        return int(self.dict_offsets.shape[0]) - 1

    @property
    def dict_byte_capacity(self) -> int:
        return int(self.dict_data.shape[0])

    def dict_view(self) -> StringColumn:
        """The dictionary itself as a StringColumn (every entry valid —
        padded slots are zero-length and unreferenced)."""
        return StringColumn(self.dict_data, self.dict_offsets,
                            jnp.ones((self.dict_capacity,), jnp.bool_),
                            self.dtype)

    def with_capacity(self, capacity: int) -> "DictionaryColumn":
        cap = self.capacity
        if capacity == cap:
            return self
        assert capacity > cap, (capacity, cap)
        extra = capacity - cap
        if isinstance(self.codes, np.ndarray):
            codes = np.concatenate(
                [self.codes, np.full(extra, NULL_CODE, self.codes.dtype)])
            validity = np.concatenate(
                [self.validity, np.zeros(extra, self.validity.dtype)])
        else:
            codes = jnp.concatenate(
                [self.codes, jnp.full((extra,), NULL_CODE, self.codes.dtype)])
            validity = jnp.concatenate(
                [self.validity, jnp.zeros((extra,), self.validity.dtype)])
        return DictionaryColumn(codes, self.dict_data, self.dict_offsets,
                                validity, self.dtype)

    # -- host materialization (test/debug surface) -------------------------
    def to_pylist(self, num_rows: int) -> List:
        from .column import _host
        codes, valid, data, off = _host(
            self.codes[:num_rows], self.validity[:num_rows], self.dict_data,
            self.dict_offsets)
        binary = isinstance(self.dtype, BinaryType)
        out: List = []
        for i in range(num_rows):
            c = int(codes[i])
            if not valid[i] or c < 0 or c >= self.dict_capacity:
                out.append(None)
                continue
            b = data[off[c]: off[c + 1]].tobytes()
            out.append(b if binary else b.decode("utf-8"))
        return out

    def __repr__(self):
        return (f"DictionaryColumn(cap={self.capacity}, "
                f"dict={self.dict_capacity}x{self.dict_byte_capacity}B)")


def _dict_flatten(c: DictionaryColumn):
    return (c.codes, c.dict_data, c.dict_offsets, c.validity), c.dtype


def _dict_unflatten(dtype, children):
    codes, dict_data, dict_offsets, validity = children
    return DictionaryColumn(codes, dict_data, dict_offsets, validity, dtype)


jax.tree_util.register_pytree_node(DictionaryColumn, _dict_flatten,
                                   _dict_unflatten)


# ---------------------------------------------------------------------------
# scan construction (io/parquet.py requests Arrow dictionary arrays;
# columnar/column.column_from_arrow routes them here)
# ---------------------------------------------------------------------------


def dictionary_from_arrow(arr, dt: DataType) -> Optional[DictionaryColumn]:
    """pyarrow DictionaryArray -> encoded column, or None when the
    array is not an encodable shape (non-string values, nulls inside
    the dictionary itself) — the caller then decodes eagerly."""
    import pyarrow as pa

    dic = arr.dictionary
    if not (pa.types.is_string(dic.type) or pa.types.is_large_string(dic.type)
            or pa.types.is_binary(dic.type)
            or pa.types.is_large_binary(dic.type)):
        return None
    if dic.null_count:
        return None
    n = len(arr)
    validity = np.asarray(arr.is_valid())
    idx = arr.indices
    if idx.null_count:
        idx = idx.fill_null(0)
    codes = np.asarray(idx).astype(np.int32, copy=True)
    np.putmask(codes, ~validity, NULL_CODE)
    cap = bucket_capacity(n)
    from .column import _string_from_arrow_buffers
    view = _string_from_arrow_buffers(dic, dt, len(dic))
    col = DictionaryColumn(
        _dev(_pad_np(codes, cap, fill=NULL_CODE)),
        view.data, view.offsets,
        _dev(_pad_np(validity.astype(np.bool_), cap, fill=False)), dt)
    return col


# ---------------------------------------------------------------------------
# code-indexed gather of a per-dictionary precomputed table
# ---------------------------------------------------------------------------


def dict_take(table, codes):
    """out[i] = table[clip(codes[i])] for a per-dictionary table
    (precomputed hashes, a literal's hit mask), accounted on the gather
    engine (a code-indexed take IS a row gather)."""
    from ..ops import gather as gather_engine
    gather_engine.record(1, nbytes=int(codes.shape[0])
                         * int(np.dtype(table.dtype).itemsize))
    return table[jnp.clip(codes, 0, int(table.shape[0]) - 1)]


def dictionary_hashes(col: DictionaryColumn, seed: int):
    """murmur3 over the dictionary entries ONCE (uint32 (dict_cap,)) —
    the join-hash precompute: per-row hashes are then one dict_take of
    this table by the code lane instead of a re-hash per row."""
    from ..ops.hashing import murmur3_string
    _note(dict_hash_tables=1)
    view = col.dict_view()
    h0 = jnp.full((col.dict_capacity,), jnp.uint32(seed))
    return murmur3_string(view, h0)


# ---------------------------------------------------------------------------
# encoded comparisons
# ---------------------------------------------------------------------------


def row_byte_lanes(col):
    """(lengths, starts, data, byte_capacity) per-row byte views for a
    StringColumn or a DictionaryColumn — the shared shape every
    byte-wise kernel (hashing, join verify) consumes, so encoded
    columns compare/hash without materializing."""
    if isinstance(col, DictionaryColumn):
        dlens = col.dict_offsets[1:] - col.dict_offsets[:-1]
        safe = jnp.clip(col.codes, 0, col.dict_capacity - 1)
        lengths = jnp.where(col.validity, dlens[safe], 0)
        starts = col.dict_offsets[:-1][safe]
        return lengths, starts, col.dict_data, col.dict_byte_capacity
    from ..ops.strings import string_lengths
    return string_lengths(col), col.offsets[:-1], col.data, col.byte_capacity


def _bytes_equal_spans(la, sa, da, lb, sb, db):
    """Byte equality of (start, length) spans a vs b over their flat
    buffers: bool per row. O(max common length) vectorized byte steps,
    the string_compare_cols loop shape."""
    len_eq = la == lb
    max_len = jnp.max(jnp.where(len_eq, la, 0))
    da_cap = int(da.shape[0])
    db_cap = int(db.shape[0])

    def cond(carry):
        j, ok = carry
        return j < max_len

    def body(carry):
        j, ok = carry
        ba = da[jnp.clip(sa + j, 0, da_cap - 1)]
        bb = db[jnp.clip(sb + j, 0, db_cap - 1)]
        ok = ok & ((j >= la) | (ba == bb))
        return j + jnp.int32(1), ok

    _, ok = jax.lax.while_loop(cond, body, (jnp.int32(0), len_eq))
    return ok


def bytes_equal_rows(a, b):
    """Row-wise byte equality between two varlen columns (string or
    dictionary, any mix): bool (capacity,), ignoring validity — callers
    AND validity in."""
    la, sa, da, _bca = row_byte_lanes(a)
    lb, sb, db, _bcb = row_byte_lanes(b)
    return _bytes_equal_spans(la, sa, da, lb, sb, db)


def _span_lanes_at(col, idx):
    """(lengths, starts, validity) of col[idx] as spans into col's
    ORIGINAL byte buffer — no gathered byte materialization. Negative /
    out-of-range idx rows come back invalid with length 0."""
    lengths, starts, data, _bc = row_byte_lanes(col)
    cap = int(lengths.shape[0])
    in_range = (idx >= 0) & (idx < cap)
    safe = jnp.where(in_range, idx, 0)
    valid = col.validity[safe] & in_range
    return jnp.where(valid, lengths[safe], 0), starts[safe], data, valid


def bytes_equal_at(a, a_idx, b, b_idx):
    """Candidate-level varlen key verify (join): byte equality of
    a[a_idx] vs b[b_idx] ANDed with both rows' validity, comparing
    through spans into the ORIGINAL buffers. A materialized candidate
    gather cannot do this soundly: its byte bucket is sized for the
    base batch, and a join fan-out overflows it (rows past the bucket
    silently truncate)."""
    la, sa, da, va = _span_lanes_at(a, a_idx)
    lb, sb, db, vb = _span_lanes_at(b, b_idx)
    return _bytes_equal_spans(la, sa, da, lb, sb, db) & va & vb


def encoded_equal_literal(col: DictionaryColumn, value) -> Column:
    """EqualTo(dictionary column, string literal) in code space: compare
    the literal against the dictionary ONCE (per traced program — jit
    caching makes that once per (batch shape, dict shape)), then the
    per-row answer is a dict_take of the hit lane by the code lane.
    Returns a BOOLEAN Column with Spark's 3VL (null rows stay null)."""
    from ..types import BOOLEAN
    cap = col.capacity
    _note(code_space_predicates=1)
    if value is None:
        zeros = jnp.zeros((cap,), jnp.bool_)
        return Column(zeros, zeros, BOOLEAN)
    raw = value.encode("utf-8") if isinstance(value, str) else bytes(value)
    m = len(raw)
    dlens = col.dict_offsets[1:] - col.dict_offsets[:-1]
    if m == 0:
        hit = dlens == 0
    else:
        lit = jnp.asarray(np.frombuffer(raw, np.uint8))
        starts = col.dict_offsets[:-1]
        pos = starts[:, None] + jnp.arange(m, dtype=jnp.int32)[None, :]
        entry = col.dict_data[jnp.clip(pos, 0, col.dict_byte_capacity - 1)]
        hit = (dlens == m) & jnp.all(entry == lit[None, :], axis=1)
    row_hit = dict_take(hit, col.codes)
    return Column(jnp.where(col.validity, row_hit, False),
                  col.validity, BOOLEAN)


# ---------------------------------------------------------------------------
# late materialization — the ONE decode chokepoint
# ---------------------------------------------------------------------------


def _decoded_bytes(col: DictionaryColumn):
    dlens = col.dict_offsets[1:] - col.dict_offsets[:-1]
    safe = jnp.clip(col.codes, 0, col.dict_capacity - 1)
    return jnp.sum(jnp.where(col.validity, dlens[safe], 0))


def _decode(col: DictionaryColumn, byte_cap: int) -> StringColumn:
    from ..ops.basic import gather_column
    return gather_column(col.dict_view(), col.codes,
                         out_valid=col.validity,
                         out_byte_capacity=byte_cap)


# through the dispatch ledger like every other program: run eagerly they
# were device time under no label (a third of the chip's busy time in
# TPC-H Q14, ISSUE 31)
from ..obs.dispatch import instrument as _instrument

_decoded_bytes_jit = _instrument(_decoded_bytes,
                                 label="encoded.decoded_bytes")
_decode_jit = _instrument(_decode, label="encoded.decode",
                          static_argnums=(1,))


def decoded_byte_bucket(col: DictionaryColumn) -> int:
    """Byte bucket a full decode of `col` needs (host sync — the
    materialize seams are host-level by design, so the decoded buffer
    is sized tight instead of to a static worst case)."""
    from ..obs.dispatch import host_read
    need = int(host_read("encoded.decoded_bytes", _decoded_bytes_jit(col)))
    return bucket_capacity(max(need, 1))


def materialize_column(col, fault_key: Optional[str] = None,
                       seam: str = "boundary"):
    """Decode a DictionaryColumn to a full-width StringColumn through
    the gather engine (a dictionary decode IS a row gather of the
    dictionary by the code lane: NULL_CODE rows come out invalid via
    the standard -1 gather masking). Non-encoded columns pass through.
    Host-level only — this is the late-materialization seam, routed
    through the `device.dispatch` chaos fault point like every other
    host->device dispatch boundary."""
    if not isinstance(col, DictionaryColumn):
        return col
    from .. import faults
    faults.check("device.dispatch", key=fault_key)
    byte_cap = decoded_byte_bucket(col)
    out = _decode_jit(col, byte_cap)
    _note(materializations=1, materialized_bytes=byte_cap)
    return out


def batch_has_encoded(batch) -> bool:
    return any(isinstance(c, DictionaryColumn) for c in batch.columns)


def encoded_sig(columns: Sequence) -> tuple:
    """Per-lane encoded-ness marker folded into stage-compiler program
    keys so cached programs never cross representations."""
    return tuple(isinstance(c, DictionaryColumn) for c in columns)


def materialize_batch(batch, fault_key: Optional[str] = None,
                      seam: str = "boundary"):
    """Materialize every encoded column of a batch (identity when none
    are encoded) — the operator-boundary / output-collection seam."""
    if not batch_has_encoded(batch):
        return batch
    cols = [materialize_column(c, fault_key=fault_key, seam=seam)
            for c in batch.columns]
    out = batch.with_columns(cols, batch.schema)
    from ..obs import events as obs_events
    if obs_events.active_bus() is not None:
        obs_events.emit("encoded_materialize", seam=seam,
                        cols=sum(1 for c in batch.columns
                                 if isinstance(c, DictionaryColumn)))
    return out


# ---------------------------------------------------------------------------
# scan-seam accounting (the `encoded_scan` event + advisor evidence)
# ---------------------------------------------------------------------------


def _decoded_nbytes_estimate(col: DictionaryColumn) -> int:
    """Bytes the eager-decode lane would have built for this column
    (string data bucket + offsets + validity) — all numpy at the scan
    seam (pre-upload), so this is a pure host computation."""
    codes = np.asarray(col.codes)
    off = np.asarray(col.dict_offsets)
    valid = np.asarray(col.validity)
    dlens = off[1:] - off[:-1]
    safe = np.clip(codes, 0, col.dict_capacity - 1)
    total = int(np.where(valid, dlens[safe], 0).sum())
    cap = col.capacity
    return bucket_capacity(max(total, 1)) + (cap + 1) * 4 + cap


def note_scan_batch(columns: Sequence) -> None:
    """Account a scan-built batch: encoded lanes bump the counters the
    encoded_scan event / bench attribution / advisor rule read; plain
    string lanes bump scan_string_bytes (the advisor's evidence that a
    conf-off scan is shipping decoded width)."""
    enc = [c for c in columns if isinstance(c, DictionaryColumn)]
    plain = sum(c.data.nbytes + c.offsets.nbytes for c in columns
                if isinstance(c, StringColumn))
    if plain:
        _note(scan_string_bytes=int(plain))
    if not enc:
        return
    codes_bytes = sum(c.codes.nbytes + c.validity.nbytes for c in enc)
    dict_bytes = sum(c.dict_data.nbytes + c.dict_offsets.nbytes for c in enc)
    avoided = 0
    for c in enc:
        est = _decoded_nbytes_estimate(c)
        have = c.codes.nbytes + c.validity.nbytes \
            + c.dict_data.nbytes + c.dict_offsets.nbytes
        avoided += max(est - have, 0)
    _note(cols_encoded=len(enc), codes_bytes=int(codes_bytes),
          dict_bytes=int(dict_bytes), decoded_bytes_avoided=int(avoided))
    from ..obs import events as obs_events
    if obs_events.active_bus() is None:
        return
    with _COUNTER_LOCK:
        mats = _COUNTERS["materializations"]
    obs_events.emit("encoded_scan", cols_encoded=len(enc),
                    codes_bytes=int(codes_bytes), dict_bytes=int(dict_bytes),
                    decoded_bytes_avoided=int(avoided),
                    materializations=mats)
