"""exchange: share of the mesh exchange's rounds whose input sat on one chip
and was dealt out by rows over the chips before the all-to-all: `100 *
dispatches(ShuffleExchangeExec.spread) / dispatches(ShuffleExchangeExec.measure)`
over the window (one measure a round, one spread a spread round). 0 is a
reading: rounds ran and none was spread (their input already sat on several
chips, or the program deals a one-chip round out by batch). None where no
measure ran in the window."""

SPREAD = "ShuffleExchangeExec.spread"
MEASURE = "ShuffleExchangeExec.measure"


def read(obs):
    rounds = obs.dispatches(lambda label: label == MEASURE)
    if not rounds:
        return None
    return 100.0 * obs.dispatches(lambda label: label == SPREAD) / rounds
