"""Contract-analyzer fixture twin: host-sync-chokepoint stays SILENT —
reads through the door are clean, an accepted bare read carries a
justified suppression."""

import jax

from spark_rapids_tpu.obs.dispatch import host_read


def routed(total):
    # the door itself: not flagged
    return int(host_read("fixture.total", total))


def routed_wait(leaves):
    host_read("fixture.wait", leaves, fetch=False)


def accepted_bare(total):
    # contract: ok host-sync-chokepoint — fixture: measured elsewhere
    return jax.device_get(total)
