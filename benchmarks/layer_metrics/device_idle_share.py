"""device: share of the traced window in which no operation ran on the
chip: 1 - union of device-op intervals over the window."""


def read(obs):
    if obs.trace is None or obs.trace.window_s <= 0:
        return None
    return 100.0 * obs.trace.idle_s / obs.trace.window_s
