"""session / planner: milliseconds of `df._exec()` (logical plan to exec
tree) on a fresh DataFrame, the mean over one planning per traced query.
Source: the benchmark's own clock (host_clock)."""


def read(obs):
    if not obs.plan_s:
        return None
    return 1e3 * sum(obs.plan_s) / len(obs.plan_s)
