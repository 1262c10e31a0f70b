"""exchange: milliseconds per query the driving thread spent in the mesh
exchange's rounds: the span `exchange.mesh_round` around each
`ShuffleExchangeExec` round (its chips' pieces put on the mesh, the measure
program, the host read `exchange.round` that sizes the receive slots, the
all-to-all step's enqueue and the partitions taken off), phase ledger
`ici-collective`. Silent on a program without the phase or where no query
completed."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "ici-collective")
