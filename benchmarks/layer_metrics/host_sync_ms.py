"""device: milliseconds per query the driving thread spent in a
device-to-host read outside every other span (phase ledger `host-sync`:
the spans `sync.<site>` of `obs.dispatch.host_read` where no span encloses
them; a read inside `agg.update`, `sort.result`, `result.fetch` or
`join.build` stays with that span's phase). Silent on a program without the
phase."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "host-sync")
