"""fused stages: times a query's hash group-by left its cheap tier: batches
that went on from 2 rounds to 6 (`hash_round_retries`: a second program and
a second host sync) and batches or merges that fell to the sort path
(`exact_fallbacks`), over the group-by's drives (`executions`), from
`exec/aggregate.counters()`. The counters are the process's: the set-up's
queries are the window's query over the same rows, so what one of them
leaves its tier for, every one does. 0 is a reading: every batch resolved
in 2 rounds. None where the program has no such counters, or no group-by
drove."""


def read(obs):
    from spark_rapids_tpu.exec import aggregate
    c = getattr(aggregate, "counters", dict)()
    if not {"executions", "hash_round_retries", "exact_fallbacks"} <= set(c) \
            or not c["executions"]:
        return None
    return (c["hash_round_retries"] + c["exact_fallbacks"]) / c["executions"]
