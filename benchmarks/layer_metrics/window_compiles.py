"""stage compiler: fresh traces inside the measured window. Expected 0: a
fresh DataFrame is served by the program cache (PR 14)."""


def read(obs):
    return float(obs.window["counters"]["traces"])
