"""What the readers of a phase's time share: milliseconds per query of one
phase of the program's phase ledger (`obs/phase.py`) over the window."""


def phase_ms(obs, phase: str):
    """None where the program has no such phase or no query completed; 0.0
    is a reading of a time."""
    if not obs.queries or phase not in obs.window["phases"]:
        return None
    return obs.window["phases"][phase] / 1e6 / obs.queries
