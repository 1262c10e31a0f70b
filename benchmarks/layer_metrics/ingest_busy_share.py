"""scan + upload + coalesce: share of the chip's busy time taken by the
programs of the ingest path, found by the program's own map from XLA module
to dispatch-ledger label (`obs.dispatch.module_labels()`) and not by a name
kept here: a module counts when every label it serves is an ingest label
(`lib.observe.is_ingest`). Silent without a trace, with a program that has
no such map, and when one module's labels fall on both sides (its time
cannot be split)."""

from benchmarks.lib.observe import is_ingest


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    from spark_rapids_tpu.obs import dispatch
    labels = getattr(dispatch, "module_labels", dict)()
    ingest_s = 0.0
    for module, seconds in obs.trace.module_s.items():
        sides = {is_ingest(label) for label in labels.get(module, ())}
        if len(sides) == 2:
            return None
        if sides == {True}:
            ingest_s += seconds
    if ingest_s <= 0:
        return None
    return 100.0 * ingest_s / obs.trace.busy_s
