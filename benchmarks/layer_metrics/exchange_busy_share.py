"""exchange: share of the chips' busy time taken by the mesh exchange's own
programs (`lib/exchange_programs`: every label `ShuffleExchangeExec.*`),
both as means over the chips of the trace. Silent without a trace, with a
program that has no module map, where no exchange program ran in the window,
and when one module serves an exchange label and another one."""

from benchmarks.lib.exchange_programs import exchange_modules


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0:
        return None
    exchange_s = sum((exchange_modules(obs.trace) or {}).values())
    if exchange_s <= 0:
        return None
    return 100.0 * exchange_s / obs.trace.busy_s
