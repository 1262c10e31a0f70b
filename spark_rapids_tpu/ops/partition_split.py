"""Device-side shuffle partition split (ISSUE 9): given device partition
ids, produce the per-partition count table and a pid-stable permutation
so the whole batch can be emitted as ONE partition-ordered reorder —
the engine analog of the reference's GpuHashPartitioning pid kernel +
`contiguous_split` (one device pass, packed per-partition buffers).

The host shuffle writer used to split every batch with
O(n_partitions x n_columns) serial numpy gathers (`partition_batch_host`
-> `host_gather_column` per partition per column), squarely inside
`shuffleWriteTime`. This module moves the split onto the device:

  1. `partition_table` — per-partition counts (segment_sum) and a
     stable sort-by-pid permutation in one traced program; the offset
     table is the only value the host ever syncs on.
  2. `reorder_columns` — the partition-major reorder, routed through
     the gather engine (`ops/gather.gather_batch_columns`), so the
     fixed-width lanes ride ONE packed row gather and the structural
     numGathers/gatherTimeNs accounting covers the shuffle write path.

The reordered batch then lands on the host as a single packed D2H copy
(`columnar/transfer.fetch_split_host`) and each partition serializes
straight from a row-range slice (`shuffle/serializer.serialize_slice`)
— zero host-side row gathers per written batch.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

__all__ = ["partition_table", "reorder_columns"]


def partition_table(pid, num_rows, capacity: int, n_partitions: int
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Per-partition counts + pid-stable permutation, one traced pass.

    `pid` is the per-row partition id (any int dtype; values >=
    n_partitions and rows >= num_rows count as inactive). Returns
    (counts (n_partitions,) int32, order (capacity,) int32) where
    `order` lists source rows in partition-major order, original row
    order preserved within a partition (stable), inactive rows last.
    """
    from .basic import active_mask
    act = active_mask(num_rows, capacity)
    key = jnp.where(act, pid.astype(jnp.int32), jnp.int32(n_partitions))
    key = jnp.clip(key, 0, n_partitions)
    ones = jnp.where(key < n_partitions, jnp.int32(1), jnp.int32(0))
    counts = jax.ops.segment_sum(ones, key,
                                 num_segments=n_partitions + 1)
    iota = jnp.arange(capacity, dtype=jnp.int32)
    # stable sort by pid: partitions become contiguous, row order within
    # a partition is the input order (lax.sort is the chip's cheapest
    # reordering primitive — same formulation as compaction_order)
    _, order = jax.lax.sort((key.astype(jnp.uint32), iota), num_keys=1,
                            is_stable=True)
    return counts[:n_partitions], order


def reorder_columns(columns: Sequence, order, num_rows) -> List:
    """Partition-major reorder of a batch's columns by the
    `partition_table` permutation, through the gather engine (ONE
    packed row gather for the fixed-width lanes, tier-aware; varlen
    keeps the per-column device path). Output slots >= num_rows are
    masked invalid."""
    from .gather import gather_batch_columns
    return gather_batch_columns(columns, order, num_rows=num_rows)
