"""kernels: the mesh exchange's share of its roofline, bound by the
interconnect: the least bytes any plan of the query on these chips must move
between them (the configuration's work model, `ici_bytes` of its
`join_groupby` entry), over the ICI peak of one chip times the chips of the
trace (`lib/ici_peaks`), against the device time of the exchange's programs
(`lib/exchange_programs`, a mean over the chips as the peak's sum is). No
clamp. Silent without a trace, a peak, the work model's entry or an
exchange program in the window: never 0."""

from benchmarks.lib.exchange_programs import exchange_modules
from benchmarks.lib.ici_peaks import ici_peak_for


def read(obs):
    work = obs.work.get("join_groupby", {}).get("ici_bytes")
    peak = ici_peak_for(obs.peaks)
    if not work or peak is None or obs.trace is None or not obs.queries:
        return None
    device_s = sum((exchange_modules(obs.trace) or {}).values())
    if device_s <= 0:
        return None
    least_s = work / (peak * obs.trace.chips)
    return 100.0 * least_s * obs.queries / device_s
