"""What the benchmark reads from the program: the dispatch ledger, the phase
ledger and the counter families of single modules, as snapshots and their
differences."""

#: ledger labels of the scan -> upload -> coalesce path; every other label
#: is a stage program (step, sizing, probe, filter, sort)
INGEST_PREFIXES = ("upload.", "coalesce.")


def is_ingest(label: str) -> bool:
    return label.startswith(INGEST_PREFIXES)


def ledger_by_label() -> dict:
    """{label: {"dispatches", "traces", "compile_ns"}} summed over the
    label's shape buckets."""
    from spark_rapids_tpu.obs import dispatch
    out = {}
    for p in dispatch.programs():
        rec = out.setdefault(p["label"],
                             {"dispatches": 0, "traces": 0, "compile_ns": 0})
        for k in rec:
            rec[k] += p[k]
    return out


def families() -> dict:
    """{family: the process's counters of one module}. A program older than
    a module's `counters()` reads as an empty family."""
    from spark_rapids_tpu.columnar import upload
    from spark_rapids_tpu.exec import aggregate
    return {"aggregate": getattr(aggregate, "counters", dict)(),
            "upload": getattr(upload, "counters", dict)()}


def family(obs, name: str) -> dict:
    """The window's delta of one counter family, for a reader; empty where
    the observation or the program carries none."""
    return obs.window.get("families", {}).get(name, {})


def snapshot() -> dict:
    from spark_rapids_tpu.obs import dispatch, phase
    return {"counters": dispatch.counters(), "labels": ledger_by_label(),
            "phases": phase.counters(), "families": families()}


def delta(a: dict, b: dict) -> dict:
    """b - a of two snapshots, label by label."""
    labels = {}
    for label, rec in b["labels"].items():
        old = a["labels"].get(label, {})
        d = {k: v - old.get(k, 0) for k, v in rec.items()}
        if any(d.values()):
            labels[label] = d
    return {
        "counters": {k: v - a["counters"].get(k, 0)
                     for k, v in b["counters"].items()},
        "labels": labels,
        "phases": {k: v - a["phases"].get(k, 0)
                   for k, v in b["phases"].items()},
        "families": {name: {k: v - a["families"].get(name, {}).get(k, 0)
                            for k, v in fam.items()}
                     for name, fam in b["families"].items()},
    }


class Observation:
    """What a per-layer metric's reader is given. A reader that finds
    nothing to read returns None and the metric is left out of the line."""

    def __init__(self, *, queries, window_s, window, setup, trace, work,
                 peaks, memory_peak_bytes):
        self.queries = queries        # queries completed in the traced window
        self.window_s = window_s      # its length on the host's clock
        self.window = window          # ledger/phase/families delta over it
        self.setup = setup            # the same over set-up
        self.trace = trace            # lib.trace.Reduced, or None
        self.work = work              # the configuration's work model
        self.peaks = peaks            # this device kind's peaks
        self.memory_peak_bytes = memory_peak_bytes  # fullest chip, after the window

    def dispatches(self, keep) -> int:
        """Window dispatches of the labels for which `keep(label)`."""
        return sum(rec["dispatches"]
                   for label, rec in self.window["labels"].items()
                   if keep(label))
