"""kernels: the group-by update's share of its roofline (memory-bound: the
bytes the QUERY needs the update to touch, the configuration's
`groupby_update` work model, over the HBM peak) against the device time of
the programs that see every source row (`lib/groupby_programs.
UPDATE_LABELS`, found by label). `lib/roofline.roofline_share` does the
arithmetic and is silent where the cell has no such kernel, no trace was
taken or none of the programs ran."""

from benchmarks.lib.groupby_programs import UPDATE_LABELS, modules_of
from benchmarks.lib.roofline import roofline_share


def read(obs):
    modules = modules_of(obs.trace, UPDATE_LABELS) \
        if obs.trace is not None else None
    return roofline_share(obs, "groupby_update", modules) if modules else None
