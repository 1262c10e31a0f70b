"""fused stages: share of the chip's busy time taken by the join's own
programs (`lib/join_programs.JOIN_LABELS`: the fused join stage's sizing
and probe steps, which build the table too, and the per-operator join's
programs), found through `obs.dispatch.module_labels()`. Silent without a
trace, with a program that has no such map, where no join program ran in
the window, and when one module serves a join label and another one."""

from benchmarks.lib.join_programs import join_modules


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    join_s = sum((join_modules(obs.trace) or {}).values())
    if join_s <= 0:
        return None
    return 100.0 * join_s / obs.trace.busy_s
