"""fused stages: milliseconds per query the driving thread spent in a
group-by the stage compiler did not fuse: the spans `agg.update` around each
source batch's update (the pre-projection's and the hash update's enqueues
and the host reads of `leftover`, where the host waits for the chip to
finish the batch) and `agg.merge` around the merge of the partials (phase
ledger `group-agg`). Silent on a program without the phase."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "group-agg")
