"""fused stages: dispatches per query of the join stage's cold-path sizing
program (ledger label `CompiledStageExec.sizing`): each is followed by a
blocking `device_get` of the candidate total and the byte needs, a host
sync the query pays (span `join.sizing`). 0 on a warm process, whose size
cache serves every query; the first thing to read when a window holds a
slow query. None where no query completed."""

LABEL = "CompiledStageExec.sizing"


def read(obs):
    if not obs.queries:
        return None
    return obs.dispatches(lambda label: label == LABEL) / obs.queries
