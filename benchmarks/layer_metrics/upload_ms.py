"""scan + upload + coalesce: milliseconds per query of host-to-device upload on
the scan producer: the spans `upload.pack` (host column build, numpy pack
into staging) and `upload.put` (`device_put` and the unpack program's
enqueue) of `columnar/upload.py` (phase ledger `upload`)."""

from benchmarks.lib.phase_ms import phase_ms


def read(obs):
    return phase_ms(obs, "upload")
