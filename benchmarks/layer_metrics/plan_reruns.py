"""session / planner: times a query ran its whole plan a second time
(`TpuExec.collect`: the speculation flag tripped and every operator ran
again on its exact tier), over the window's queries: the WINDOW's delta of
`plan_reruns` in `exec/aggregate.counters()` (`lib/observe.families`). 0 is
a reading: every query was one pass of its plan. None where the program has
no such counter, or no query completed."""

from benchmarks.lib.observe import family


def read(obs):
    c = family(obs, "aggregate")
    if "plan_reruns" not in c or not obs.queries:
        return None
    return c["plan_reruns"] / obs.queries
