"""kernels: the join's share of its roofline (memory-bound: the bytes the
QUERY needs the join and its two sums to touch, the configuration's
`join_probe` work model, over the HBM peak) against the device time of the
join's programs in the trace: the ones `join_busy_share` counts
(`lib/join_programs`). `lib/roofline.roofline_share` does the arithmetic
and is silent where the cell has no such kernel, no trace was taken or none
of the programs ran."""

from benchmarks.lib.join_programs import join_modules
from benchmarks.lib.roofline import roofline_share


def read(obs):
    modules = join_modules(obs.trace) if obs.trace is not None else None
    return roofline_share(obs, "join_probe", modules) if modules else None
