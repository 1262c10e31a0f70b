"""A kernel's share of its roofline: the least time the chip could take for
the work the QUERY needs of it (the configuration's work model), over the
device time its programs took in the trace. No clamp: a share over 100 means
the work is counted too high or the time leaves out part of it."""

from .peaks import roofline_seconds


def roofline_share(obs, kernel: str, modules: dict):
    """`modules`: {XLA module name in the trace: the ledger label of the
    program}. None where the cell has no such kernel, no trace was taken, or
    none of the programs ran in the window."""
    work = obs.work.get(kernel)
    if work is None or obs.trace is None or not obs.queries:
        return None
    device_s = sum(obs.trace.module_s.get(m, 0.0) for m in modules)
    if device_s <= 0:
        return None
    return 100.0 * roofline_seconds(work, obs.peaks) * obs.queries / device_s
