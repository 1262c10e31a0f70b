"""fused stages: milliseconds per query the driving thread spent on the
join's build side: the span `join.build` in `CompiledStageExec.
_execute_join_agg` around the build child's drain (its scan's stalls are
booked to `pipeline-stall`, its dictionary decode's enqueues and size
sync are in here) and the concatenation of its batches (phase ledger
`join-build`). The table itself is built inside the first probe step, on
the device: `join_busy_share` holds that time."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "join-build")
