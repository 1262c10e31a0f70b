"""fused stages: milliseconds per query the driving thread spent in the
result sort: the span `sort.result` in `SortExec._sort_one` around the
key-width sync (the longest string key, measured on the device: the host
waits for everything before it) and the sort program's enqueue (phase
ledger `sort`). Silent on a program without the phase."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "sort")
