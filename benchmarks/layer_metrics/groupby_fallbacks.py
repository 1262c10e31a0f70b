"""fused stages: times a query's hash group-by left its cheap tier: batches
that went on from 2 rounds to 6 (`hash_round_retries`: a second program and
a second host sync) and batches or merges that fell to the sort path
(`exact_fallbacks`), over the group-by's drives (`executions`): the
WINDOW's delta of `exec/aggregate.counters()` (`lib/observe.families`), so
the set-up's queries are not in it. 0 is a reading: every batch resolved in
2 rounds. None where the program has no such counters, or no group-by drove
in the window."""

from benchmarks.lib.observe import family


def read(obs):
    c = family(obs, "aggregate")
    if not {"executions", "hash_round_retries", "exact_fallbacks"} <= set(c) \
            or not c["executions"]:
        return None
    return (c["hash_round_retries"] + c["exact_fallbacks"]) / c["executions"]
