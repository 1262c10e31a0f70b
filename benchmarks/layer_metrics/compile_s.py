"""stage compiler: seconds the dispatch ledger booked to tracing and
compiling over set-up (a hit in the persistent cache still books its trace
and its load)."""


def read(obs):
    return obs.setup["counters"]["compile_ns"] / 1e9
