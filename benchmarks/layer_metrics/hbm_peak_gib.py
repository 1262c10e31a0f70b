"""device: GiB of the chip's memory in use at the peak, on the fullest chip
(`memory_stats()["peak_bytes_in_use"]`, the runtime allocator's counter),
read when the window has closed and before the reference runs. None where
the backend reports no peak (the CPU)."""


def read(obs):
    return obs.memory_peak_bytes / 2**30 if obs.memory_peak_bytes else None
