"""kernels: share of the chip's busy time taken by the dictionary decode of
a string column at a stage's boundary (`columnar/encoded.materialize_column`:
a row gather of the dictionary by the code lane, `ops/strings.gather_string`,
and the program that sizes its byte bucket), found through the program's own
map from XLA module to dispatch-ledger label (`obs.dispatch.module_labels()`):
a module counts when every label it serves is one of `DECODE_LABELS`. 43.9%
of Q14's busy time before ISSUE 32, which took the per-byte binary search
out of the gather. Silent without a trace, with a program that has no such
map or no such label (before PR 31 the decode ran eagerly), where no decode
ran in the window, and when one module serves a decode label and another
one (its time cannot be split)."""

#: the decode itself and the program that sizes its byte bucket
DECODE_LABELS = frozenset({"encoded.decode", "encoded.decoded_bytes"})


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    from spark_rapids_tpu.obs import dispatch
    labels = getattr(dispatch, "module_labels", dict)()
    decode_s = 0.0
    for module, seconds in obs.trace.module_s.items():
        sides = {label in DECODE_LABELS for label in labels.get(module, ())}
        if len(sides) == 2:
            return None
        if sides == {True}:
            decode_s += seconds
    if decode_s <= 0:
        return None
    return 100.0 * decode_s / obs.trace.busy_s
