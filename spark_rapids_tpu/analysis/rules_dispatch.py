"""Dispatch-ledger chokepoint rules (ISSUE 13 satellite; ISSUE 40).

``dispatch-ledger``: every ``jax.jit(...)`` and ``pl.pallas_call(...)``
site in the package must route through the dispatch-ledger chokepoint
(``obs.dispatch.instrument``) or carry a justified suppression. A bare
jit site is a program the observability plane cannot see — its
dispatches, compiles and recompile storms vanish from
``QueryProfile.dispatch_summary()``, the bench ``{"dispatch"}`` deltas
and the storm detector, which is exactly the silent-throughput-loss
channel the plane exists to close.

Accepted suppressions by construction: Pallas ``pallas_call`` bodies
traced inline into an instrumented enclosing program (they are part of
the outer program, not a separate device dispatch). The chokepoint
module itself (``obs/dispatch.py``) owns the one real ``jax.jit`` call.

``host-sync-chokepoint`` (ISSUE 40): every ``jax.device_get(...)`` and
``.block_until_ready()`` in the package must go through
``obs.dispatch.host_read(site, value[, fetch=False])`` or carry a
justified suppression. A bare read is a host sync the ledger cannot
count and the device trace cannot name: its wait lands in the phase
ledger's derived `other` or hides inside an enclosing span's metric.
The door's own read and the hang bound's wait in ``obs/dispatch.py``
carry the suppressions.
"""

from __future__ import annotations

import ast

from .callgraph import ModuleGraph, attr_root
from .core import Finding, ModuleInfo


def check(module: ModuleInfo, graph: ModuleGraph, reg):
    if reg.scope_prefix not in module.path:
        return []  # tools/bench scripts may drive jax directly
    if module.path.endswith("obs/dispatch.py"):
        return []  # THE chokepoint: the one sanctioned jax.jit call
    out = []
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Attribute):
            continue
        if node.attr == "jit" and attr_root(node) == "jax":
            out.append(Finding(
                "dispatch-ledger", module.path, node.lineno,
                "<module>", "jax.jit",
                "bare `jax.jit` — route this program through "
                "obs.dispatch.instrument(label=...) so its dispatches/"
                "compiles reach the ledger, or suppress with the why"))
        elif node.attr == "pallas_call":
            out.append(Finding(
                "dispatch-ledger", module.path, node.lineno,
                "<module>", "pallas_call",
                "bare `pallas_call` — either instrument the enclosing "
                "jit entry point and suppress here (traced inline), or "
                "route the call through the ledger chokepoint"))
    return out


def check_host_sync(module: ModuleInfo, graph: ModuleGraph, reg):
    if reg.scope_prefix not in module.path:
        return []  # tools/bench scripts may read the device directly
    out = []
    for node in ast.walk(module.tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)):
            continue
        attr = node.func.attr
        if attr == "device_get" and attr_root(node.func) == "jax":
            key = "jax.device_get"
        elif attr == "block_until_ready":
            key = "block_until_ready"
        else:
            continue
        out.append(Finding(
            "host-sync-chokepoint", module.path, node.lineno,
            "<module>", key,
            f"bare `{key}` — read through obs.dispatch.host_read(site, "
            "value) (fetch=False for a wait) so the sync is counted by "
            "site and named in the trace, or suppress with the why"))
    return out
