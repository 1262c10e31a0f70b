"""device: milliseconds per query the driving thread spent blocked on a
device-to-host read, the spans `result.fetch` in `columnar/transfer.
fetch_batch_host` and around the speculation flag in `TpuExec.collect`
(phase ledger `device-wait`): the host waiting for the chip to finish."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "device-wait")
