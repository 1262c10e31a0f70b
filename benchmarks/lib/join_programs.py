"""Which programs of a trace are the join's: what `join_busy_share` and
`join_probe_roofline` share. Found by the program's own map from XLA module
to dispatch-ledger label (`obs.dispatch.module_labels()`), not by a module
name kept here."""

#: the fused join stage's sizing, probe step and exact probe step (its build
#: table is computed inside the probe step), and the per-operator join's
#: build, counts and probe
JOIN_LABELS = frozenset({
    "CompiledStageExec.sizing", "CompiledStageExec.probe_step",
    "CompiledStageExec.probe_step_exact",
    "HashJoinExec.build", "HashJoinExec.counts", "HashJoinExec.probe"})


def join_modules(trace) -> dict:
    """{XLA module in the trace: its seconds} for the modules whose every
    label is a join label. Empty where the program has no such map or no
    join program ran; None where one module serves a join label and another
    one (its time cannot be split)."""
    from spark_rapids_tpu.obs import dispatch
    labels = getattr(dispatch, "module_labels", dict)()
    out = {}
    for module, seconds in trace.module_s.items():
        sides = {label in JOIN_LABELS for label in labels.get(module, ())}
        if len(sides) == 2:
            return None
        if sides == {True}:
            out[module] = seconds
    return out
