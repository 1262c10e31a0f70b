"""scan + upload + coalesce: milliseconds per query of file decode, the spans
`scan.decode` around each decode task of `io/multifile.threaded_chunks`
(phase ledger `scan-decode`). Thread-time: the tasks run on the shared
`multifile-read` pool and overlap, so this can exceed the wall they took."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "scan-decode")
