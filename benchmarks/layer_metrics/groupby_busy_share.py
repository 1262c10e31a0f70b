"""fused stages: share of the chip's busy time taken by the group-by's own
programs (`lib/groupby_programs.GROUPBY_LABELS`: the pre-projection, the
hash update, the merges, the evaluation and the shrink of a group-by the
stage compiler did not fuse), found through `obs.dispatch.module_labels()`.
Silent without a trace, with a program that has no such map, where no
group-by program ran in the window, and when one module serves a group-by
label and another one."""

from benchmarks.lib.groupby_programs import GROUPBY_LABELS, modules_of


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    groupby_s = sum((modules_of(obs.trace, GROUPBY_LABELS) or {}).values())
    if groupby_s <= 0:
        return None
    return 100.0 * groupby_s / obs.trace.busy_s
