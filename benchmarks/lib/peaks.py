"""Published peaks of one chip, keyed by `jax.devices()[0].device_kind`.
A device that is not in the table is an error, never a default."""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "source": 'Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, '
                  "16 GB HBM2e at 819 GB/s per chip",
    },
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"the table has {sorted(PEAKS)}")
    return PEAKS[device_kind]


def roofline_seconds(work: dict, peaks: dict) -> float:
    """The least time the chip could take for `work` ({"bytes", "flops"}):
    the larger of bytes over peak bytes/s and flops over peak flop/s."""
    return max(work.get("bytes", 0) / peaks["hbm_bytes_per_s"],
               work.get("flops", 0) / peaks["bf16_flops_per_s"])
