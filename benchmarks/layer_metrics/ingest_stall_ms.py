"""scan + upload + coalesce: milliseconds per query the consumer spent
blocked on the scan/upload producers (phase ledger `pipeline-stall`)."""


def read(obs):
    if not obs.queries or "pipeline-stall" not in obs.window["phases"]:
        return None
    return obs.window["phases"]["pipeline-stall"] / 1e6 / obs.queries
