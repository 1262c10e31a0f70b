"""scan + upload + coalesce: device dispatches per query of the programs
labelled `upload.*` and `coalesce.*` in the dispatch ledger."""

from benchmarks.lib.observe import is_ingest


def read(obs):
    if not obs.queries:
        return None
    return obs.dispatches(is_ingest) / obs.queries
