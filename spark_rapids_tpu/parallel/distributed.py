"""Distributed query steps: SPMD pipelines compiled once over the whole
mesh (reference analog: the UCX shuffle + partial/final aggregate pattern,
GpuShuffleExchangeExecBase.scala:277 + GpuAggregateExec partial/final).

The biggest architectural departure from the reference (SURVEY §7 risk
register): instead of independent tasks pulling batches through a
transport, a distributed step is ONE resident XLA program over the mesh —
local partial aggregate, ICI all-to-all exchange by key hash, local final
merge — with XLA scheduling compute/communication overlap. Spark tasks
enqueue batches into this program instead of talking to a shuffle service.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..columnar.batch import ColumnarBatch
from ..columnar.column import Column, StringColumn
from ..ops.aggregate import groupby_aggregate
from ..types import DataType, Schema
from .exchange import exchange_columns
from .mesh import DATA_AXIS


def stack_batches(batches: Sequence[ColumnarBatch]) -> ColumnarBatch:
    """Stack n same-capacity batches along a new leading device axis; the
    result's leaves have shape (n, ...) ready for shard_map over 'data'.
    Row and string-byte buckets are aligned across batches first."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                  *align_batches(batches))


def align_batches(batches: Sequence[ColumnarBatch]) -> List[ColumnarBatch]:
    """The batches in one row bucket and, column by column, one string-byte
    bucket: pieces of one stacked array must have equal shapes."""
    cap = max(b.capacity for b in batches)
    batches = [b.sized_to(cap) for b in batches]
    aligned = []
    byte_caps = {}
    for b in batches:
        for i, c in enumerate(b.columns):
            if isinstance(c, StringColumn):
                byte_caps[i] = max(byte_caps.get(i, 0), c.byte_capacity)
    for b in batches:
        cols = [c.with_byte_capacity(byte_caps[i])
                if isinstance(c, StringColumn) else c
                for i, c in enumerate(b.columns)]
        aligned.append(b.with_columns(cols, b.schema))
    return aligned


def place_on_mesh(pieces: Sequence, mesh: Mesh, axis_name: str = DATA_AXIS):
    """One tree of global arrays sharded over `axis_name` from per-device
    trees whose leaves have a leading axis of 1: piece i becomes mesh
    device i's shard, moved there where it sits elsewhere and left in
    place (no copy) where it is there already."""
    devs = list(mesh.devices.flat)
    sharding = NamedSharding(mesh, P(axis_name))
    placed = [jax.device_put(p, d) for p, d in zip(pieces, devs)]

    def glue(*xs):
        shape = (sum(x.shape[0] for x in xs),) + xs[0].shape[1:]
        return jax.make_array_from_single_device_arrays(shape, sharding,
                                                        list(xs))
    return jax.tree_util.tree_map(glue, *placed)


def device_shards(tree, mesh: Mesh) -> List:
    """For each device of the mesh, in mesh order, the tree of that
    device's own shards of `tree`'s sharded leaves: single-device arrays on
    that device, the shard's buffer itself (no copy, nothing through the
    host)."""
    def own(x, d):
        return next(s.data for s in x.addressable_shards if s.device == d)
    return [jax.tree_util.tree_map(lambda x, d=d: own(x, d), tree)
            for d in mesh.devices.flat]


def _squeeze0(tree):
    return jax.tree_util.tree_map(lambda x: x[0], tree)


def _expand0(tree):
    return jax.tree_util.tree_map(lambda x: x[None], tree)


def required_string_width(batches: Sequence[ColumnarBatch]) -> int:
    """Exact fixed-width byte size for exchanging these batches' string
    columns (host-side, pre-jit): the max string length rounded to 8.
    Pass to make_distributed_groupby/exchange_columns — the fixed-width
    codec TRUNCATES beyond this width."""
    width = 8
    for b in batches:
        for c in b.columns:
            if isinstance(c, StringColumn):
                lengths = c.offsets[1:] - c.offsets[:-1]
                max_len = int(jnp.max(lengths)) if c.capacity else 0
                width = max(width, (max_len + 7) // 8 * 8)
    return width


def make_distributed_groupby(mesh: Mesh, key_count: int,
                             update_inputs: Sequence[Tuple[str, int]],
                             merge_ops: Sequence[str],
                             buffer_types: Sequence[DataType],
                             out_schema: Schema,
                             string_words: int = 4,
                             string_width: int = 64,
                             axis_name: str = DATA_AXIS):
    """Build the jitted SPMD group-by step.

    update_inputs: [(op, input ordinal into the local batch)] per buffer
    (ordinal -1 => count_star). merge_ops/buffer_types: one per buffer.
    string_width: fixed-width byte size for exchanged string columns —
    size it with required_string_width(batches) or longer keys TRUNCATE.
    Input: stacked batch with leaves (n, ...); output: stacked aggregated
    batch, one shard per device holding that device's hash partitions.
    """
    n_parts = mesh.shape[axis_name]
    # sort-lane width must cover the exchanged width for exact key grouping
    string_words = max(string_words, string_width // 8)

    def spmd(stacked: ColumnarBatch) -> ColumnarBatch:
        local = _squeeze0(stacked)
        cap = local.capacity
        keys = list(local.columns[:key_count])
        agg_inputs = [(op, local.columns[ordinal] if ordinal >= 0 else None)
                      for op, ordinal in update_inputs]
        # phase 1: local partial aggregate
        pkeys, presults, pgroups = groupby_aggregate(
            keys, agg_inputs, local.num_rows, cap, string_words)
        partial_cols = list(pkeys)
        for r, bt in zip(presults, buffer_types):
            if r[0] == "col":
                partial_cols.append(r[1])
            else:
                data, valid = r[1]
                partial_cols.append(Column(data.astype(bt.jnp_dtype),
                                           valid, bt))
        # phase 2: all-to-all exchange so equal keys colocate
        recv_cols, n_recv = exchange_columns(
            partial_cols, list(range(key_count)), pgroups, cap,
            axis_name, n_parts, string_width=string_width)
        # phase 3: final merge aggregate on the received partition
        rkeys = recv_cols[:key_count]
        rbufs = recv_cols[key_count:]
        m_inputs = [(op, c) for op, c in zip(merge_ops, rbufs)]
        fkeys, fresults, fgroups = groupby_aggregate(
            rkeys, m_inputs, n_recv, recv_cols[0].capacity, string_words)
        out_cols = list(fkeys)
        for r, bt in zip(fresults, buffer_types):
            if r[0] == "col":
                out_cols.append(r[1])
            else:
                data, valid = r[1]
                out_cols.append(Column(data.astype(bt.jnp_dtype), valid, bt))
        out = ColumnarBatch(out_cols, fgroups, out_schema)
        return _expand0(out)

    from .mesh import shard_map_compat
    mapped = shard_map_compat(spmd, mesh=mesh,
                              in_specs=P(axis_name),
                              out_specs=P(axis_name))
    from ..obs.dispatch import instrument
    jitted = instrument(mapped, label="distributed.agg_exchange_step")

    def checked(stacked: ColumnarBatch) -> ColumnarBatch:
        # the fixed-width exchange codec TRUNCATES beyond string_width;
        # enforce the contract here instead of relying on callers to
        # remember required_string_width (review finding r1). One host
        # sync per step call, outside the compiled program.
        for c in stacked.columns:
            if isinstance(c, StringColumn) and c.offsets.shape[-1] > 1:
                lengths = c.offsets[:, 1:] - c.offsets[:, :-1]
                max_len = int(jnp.max(lengths))
                if max_len > string_width:
                    raise ValueError(
                        f"string key of {max_len} bytes exceeds the "
                        f"exchange width {string_width}; size it with "
                        "required_string_width(batches)")
        return jitted(stacked)

    return checked


def unstack_batches(stacked: ColumnarBatch, n: int) -> List[ColumnarBatch]:
    return [jax.tree_util.tree_map(lambda x: x[i], stacked)
            for i in range(n)]
