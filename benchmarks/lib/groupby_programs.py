"""Which programs of a trace are the group-by's: what `groupby_busy_share`
and `groupby_update_roofline` share. Found by the program's own map from XLA
module to dispatch-ledger label (`obs.dispatch.module_labels()`), not by a
module name kept here (the hash update's module is `jit__traced`: its
function is a `functools.partial`)."""

#: the programs that see every source row: the pre-projection of keys and
#: aggregate inputs, the hash update (2 or 6 rounds), the sort-path update,
#: and the two one-program forms of a group-by on fixed-width keys
UPDATE_LABELS = frozenset({
    "AggregateExec.pre_project", "AggregateExec.update_hash",
    "AggregateExec.update", "AggregateExec.fused_update_exact",
    "AggregateExec.streaming_step"})

#: those, and what works on partials and the result: the merges, the
#: evaluation of the buffers (an average's division), and the sizing and the
#: shrink of a partial into a tight bucket
GROUPBY_LABELS = UPDATE_LABELS | frozenset({
    "AggregateExec.merge", "AggregateExec.merge_hash",
    "AggregateExec.merge_auto", "AggregateExec.concat_merge",
    "AggregateExec.evaluate", "aggregate.partial_size",
    "aggregate.shrink_batch"})


def modules_of(trace, wanted: frozenset) -> dict:
    """{XLA module in the trace: its seconds} for the modules whose every
    label is in `wanted`. Empty where the program has no such map or none
    of them ran; None where one module serves a wanted label and another
    one (its time cannot be split)."""
    from spark_rapids_tpu.obs import dispatch
    labels = getattr(dispatch, "module_labels", dict)()
    out = {}
    for module, seconds in trace.module_s.items():
        sides = {label in wanted for label in labels.get(module, ())}
        if len(sides) == 2:
            return None
        if sides == {True}:
            out[module] = seconds
    return out
