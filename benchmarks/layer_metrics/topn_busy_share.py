"""fused stages: share of the chip's busy time taken by the result sort
under the limit (`lib/q3_programs.TOPN_LABELS`: `TopNExec`'s sort program
and its key-width read), found through `obs.dispatch.module_labels()`.
Silent without a trace, with a program that has no such map, where no such
program ran in the window, and when one module serves such a label and
another one."""

from benchmarks.lib.groupby_programs import modules_of
from benchmarks.lib.q3_programs import TOPN_LABELS


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    topn_s = sum((modules_of(obs.trace, TOPN_LABELS) or {}).values())
    if topn_s <= 0:
        return None
    return 100.0 * topn_s / obs.trace.busy_s
