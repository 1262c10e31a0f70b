"""kernels: the join and group-by's share of their roofline (memory-bound:
the bytes the QUERY needs touched, the configuration's `join_groupby` work
model, over the HBM peak) against the device time of the programs that
carry the stage's join and group-by (`lib/q3_programs.JOIN_GROUPBY_LABELS`,
found by label). `lib/roofline.roofline_share` does the arithmetic and is
silent where the cell has no such kernel, no trace was taken or none of the
programs ran."""

from benchmarks.lib.groupby_programs import modules_of
from benchmarks.lib.q3_programs import JOIN_GROUPBY_LABELS
from benchmarks.lib.roofline import roofline_share


def read(obs):
    modules = modules_of(obs.trace, JOIN_GROUPBY_LABELS) \
        if obs.trace is not None else None
    return roofline_share(obs, "join_groupby", modules) if modules else None
