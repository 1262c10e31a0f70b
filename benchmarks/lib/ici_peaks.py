"""Published chip-to-chip interconnect peaks, keyed like `peaks.PEAKS` by
`jax.devices()[0].device_kind`. A device that is not in the table reads as
no peak: the reader that needs one stays silent."""

from .peaks import PEAKS

ICI_PEAKS = {
    "TPU v5 lite": {
        "ici_bytes_per_s": 1600e9 / 8,
        "source": 'Google Cloud documentation, "TPU v5e": 1,600 Gbps of '
                  "inter-chip interconnect bandwidth per chip",
    },
}


def ici_peak_for(peaks: dict):
    """The ICI peak of one chip, in bytes/s, of the device whose published
    peaks (`peaks.PEAKS[kind]`) a run was given; None for another device or
    none (a CPU rehearsal)."""
    for kind, entry in PEAKS.items():
        if entry == peaks and kind in ICI_PEAKS:
            return ICI_PEAKS[kind]["ici_bytes_per_s"]
    return None
