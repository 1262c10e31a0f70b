"""Plan-level speculative execution scope.

The masked-bucket aggregation kernel (ops/maskedagg.py) emits SMALL
partials plus a device `leftover` flag instead of paying for a
full-capacity exact fallback on every batch. Inside a speculation scope
the flag is never read per batch (a d2h sync costs more than the kernel);
it is recorded as a device scalar and checked ONCE when results are
materialized. If any flag tripped, the scope owner re-runs the plan with
speculation disabled (every aggregate takes its exact sync-free tier).

This is the engine's analog of the reference's optimistic
hash-aggregate-then-sort-fallback duality (GpuAggregateExec.scala:909),
lifted from per-batch to per-plan granularity because TPU host round
trips, not device memory, are the scarce resource.

A re-run is paid once a plan shape: an aggregate records its flag under its
plan fingerprint, a trip is remembered process-wide (`known_to_trip`), and
the next execution of an aggregate of that fingerprint takes its exact tier
at once, in the first and only pass (a group-by of thousands of groups
overflows the masked buckets in every query, for ever).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Dict, List, Optional

import numpy as np

#: plan fingerprints of aggregates whose masked buckets overflowed, most
#: recent last; LRU-capped so distinct plans cannot grow it unboundedly
_TRIPPED: Dict[str, None] = {}
_TRIPPED_MAX = 128
_TRIPPED_LOCK = threading.Lock()


def known_to_trip(owner: Optional[str]) -> bool:
    """Did an aggregate of this plan fingerprint overflow its masked
    buckets before, in this process?"""
    if owner is None:
        return False
    with _TRIPPED_LOCK:
        return owner in _TRIPPED


def _note_tripped(owner: str) -> None:
    with _TRIPPED_LOCK:
        _TRIPPED.pop(owner, None)
        _TRIPPED[owner] = None
        while len(_TRIPPED) > _TRIPPED_MAX:
            _TRIPPED.pop(next(iter(_TRIPPED)))


def reset_trip_memory() -> None:
    """Test isolation."""
    with _TRIPPED_LOCK:
        _TRIPPED.clear()


class SpeculationScope:
    def __init__(self):
        self.flags: List = []  # (device bool scalar, owner) pairs

    def record(self, flag, owner: Optional[str] = None) -> None:
        """`owner`: the plan fingerprint of the aggregate whose masked
        buckets the flag guards; None for a flag that says nothing about
        the plan's shape (a join's stale size cache)."""
        self.flags.append((flag, owner))

    def drain(self) -> List:
        out, self.flags = self.flags, []
        return out

    def tripped(self) -> bool:
        """ONE host sync over all recorded flags; the owners of the ones
        that tripped are remembered (`known_to_trip`)."""
        if not self.flags:
            return False
        import jax.numpy as jnp
        flags = self.drain()
        hit = np.asarray(jnp.stack([f for f, _ in flags]))
        for (_, owner), h in zip(flags, hit):
            if h and owner is not None:
                _note_tripped(owner)
        return bool(hit.any())


class _State(threading.local):
    def __init__(self):
        self.scope: Optional[SpeculationScope] = None
        self.forced_exact = False


_state = _State()


def current_scope() -> Optional[SpeculationScope]:
    return _state.scope


def capture_context():
    """(scope, forced_exact) of this thread — captured at a pipeline
    stage boundary so the producer thread inherits it."""
    return _state.scope, _state.forced_exact


def adopt_context(scope, forced_exact: bool) -> None:
    """Install a captured speculation context on this (producer)
    thread: aggregates running behind the boundary record their
    overflow flags into the CONSUMER's scope."""
    _state.scope = scope
    _state.forced_exact = forced_exact


def speculation_allowed(owner: Optional[str] = None) -> bool:
    """May an operator speculate here? Not outside a scope, not in a forced
    re-run, and not an aggregate (`owner`: its plan fingerprint) that is
    known to overflow: it would only buy a second pass."""
    return _state.scope is not None and not _state.forced_exact \
        and not known_to_trip(owner)


@contextmanager
def speculation_scope():
    prev = _state.scope
    scope = SpeculationScope()
    _state.scope = scope
    try:
        yield scope
    finally:
        _state.scope = prev


@contextmanager
def force_exact():
    prev = _state.forced_exact
    _state.forced_exact = True
    try:
        yield
    finally:
        _state.forced_exact = prev
