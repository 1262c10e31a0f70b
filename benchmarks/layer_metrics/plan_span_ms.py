"""session / planner: milliseconds per query of planning where the query pays
for it: the span `session.plan` around `self._exec()` inside `collect()`
(phase ledger `plan`, driving thread). The planner's one metric since PR 30
retired `plan_ms`, which timed a second planning from outside, after the
window."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "plan")
