"""device: share of the chip's busy time spent in programs the dispatch
ledger has a label for (`obs.dispatch.module_labels()` joins a trace's XLA
module names to the engine's names). What is missing from 100% ran on the
device past `obs.dispatch.instrument`: eager ops, un-instrumented jits.
Silent without a trace and with a program that has no such map."""


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    from spark_rapids_tpu.obs import dispatch
    labels = getattr(dispatch, "module_labels", dict)()
    labelled_s = sum(seconds for module, seconds in obs.trace.module_s.items()
                     if module in labels)
    if labelled_s <= 0:
        return None
    return 100.0 * labelled_s / obs.trace.busy_s
