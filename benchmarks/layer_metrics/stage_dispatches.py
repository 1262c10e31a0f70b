"""fused stages: device dispatches per query of every program that is not
ingest (`upload.*`, `coalesce.*`): stage step/sizing/probe, filters, sorts."""

from benchmarks.lib.observe import is_ingest


def read(obs):
    if not obs.queries:
        return None
    return obs.dispatches(lambda label: not is_ingest(label)) / obs.queries
