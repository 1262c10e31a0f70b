"""scan + upload + coalesce: share of the chip's busy time that the pairwise
concatenation of scanned batches took (`coalesce.concat_pair`, XLA module
`jit__concat_pair`): the first bottleneck PR 26's traces showed."""

MODULE = "jit__concat_pair"


def read(obs):
    if obs.trace is None or obs.trace.busy_s <= 0 or \
            MODULE not in obs.trace.module_s:
        return None
    return 100.0 * obs.trace.module_s[MODULE] / obs.trace.busy_s
