"""Contract-analyzer fixture: the host-sync-chokepoint rule FIRES here —
bare device-to-host reads the sync ledger cannot count (ISSUE 40)."""

import jax


def bare_fetch(total):
    return int(jax.device_get(total))  # host-sync-chokepoint


def bare_wait(leaves):
    jax.block_until_ready(leaves)  # host-sync-chokepoint


def bare_method_wait(arr):
    return arr.block_until_ready()  # host-sync-chokepoint
