"""Which programs of a trace are the mesh exchange's: what
`exchange_busy_share` and `exchange_roofline` share. Found by the program's
own map from XLA module to dispatch-ledger label
(`obs.dispatch.module_labels()`): a module every label of which is one of
`ShuffleExchangeExec`'s (its measure and all-to-all step over the mesh, and
the per-chip programs that hand a chip's batch in and its partition out)."""

EXCHANGE_PREFIX = "ShuffleExchangeExec."


def exchange_modules(trace) -> dict:
    """{XLA module in the trace: its seconds} for the modules whose every
    label is an exchange label. Empty where the program has no such map or
    no exchange program ran; None where one module serves an exchange label
    and another one (its time cannot be split)."""
    from spark_rapids_tpu.obs import dispatch
    labels = getattr(dispatch, "module_labels", dict)()
    out = {}
    for module, seconds in trace.module_s.items():
        sides = {label.startswith(EXCHANGE_PREFIX)
                 for label in labels.get(module, ())}
        if len(sides) == 2:
            return None
        if sides == {True}:
            out[module] = seconds
    return out
