"""Finds a cell, a configuration, a traffic mix and a per-layer metric by the
name `BENCHMARK.json` gives it. Each lives in a file of its own, so a later
PR adds files and entries and edits nothing that is there."""

import importlib.util
import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


class ManifestError(Exception):
    """The manifest and the files under `paths` disagree or lack something."""


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise ManifestError(f"no such file: {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _by_name(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"BENCHMARK.json names no {what} {name!r}; it has "
                        f"{[e['name'] for e in entries]}")


class Manifest:
    """`BENCHMARK.json` plus the files it points at, under `root`/`bench`
    (the tests point these at a copy with files added)."""

    def __init__(self, root: str = ROOT, bench: str = BENCH):
        self.root, self.bench = root, bench
        self.doc = _read_json(os.path.join(root, "BENCHMARK.json"))

    def cell(self, name: str) -> dict:
        """The manifest's entry merged over the cell's own file, which may
        add keys (`trace_queries`) and may not contradict the entry."""
        entry = _by_name(self.doc["workloads"], name, "workload")
        own = _read_json(os.path.join(self.bench, "workloads", name + ".json"))
        for k in ("name", "config", "traffic", "chips"):
            if k in own and own[k] != entry[k]:
                raise ManifestError(
                    f"workloads/{name}.json says {k}={own[k]!r}, "
                    f"BENCHMARK.json says {entry[k]!r}")
        return {**own, **entry}

    def config(self, name: str) -> dict:
        """The configuration as it is run; `_dir` is where its `query.py`
        and `reference.py` live."""
        entry = _by_name(self.doc["configs"], name, "configuration")
        path = os.path.join(self.root, entry["file"])
        cfg = _read_json(path)
        cfg["_dir"] = os.path.dirname(path)
        return cfg

    def config_module(self, cfg: dict, which: str):
        """`query` (the program's API) or `reference` (numpy only)."""
        return _load_module(os.path.join(cfg["_dir"], which + ".py"),
                            f"bench_{cfg['name']}_{which}".replace("-", "_"))

    def traffic(self, name: str) -> dict:
        return _read_json(os.path.join(self.bench, "traffic", name + ".json"))

    def metrics_of(self, cell: str, group: str) -> list:
        """Entries of `end_to_end` or `per_layer` that this cell reports: all
        without a `workloads` key, and those that list the cell."""
        return [m for m in self.doc[group]
                if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """`layer_metrics/<metric>.py`: `read(obs) -> float | None`."""
        mod = _load_module(
            os.path.join(self.bench, "layer_metrics", metric + ".py"),
            "bench_metric_" + metric.replace("-", "_").replace(".", "_"))
        if not callable(getattr(mod, "read", None)):
            raise ManifestError(f"layer_metrics/{metric}.py has no read(obs)")
        return mod.read


def manifest_at(root: str) -> Manifest:
    """The real benchmark seen from a root of its own, so that a run's
    `.bench_work/<cell>` is its own too: for a test that rehearses a cell
    while another worker rehearses the same cell in the checkout."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(BENCH, os.path.join(root, "benchmarks"))
    return Manifest(root=str(root), bench=os.path.join(root, "benchmarks"))


def apply_rehearsal(cfg: dict) -> dict:
    """The configuration at its `rehearse` size: a CPU rehearsal of the
    command, never a result."""
    out = dict(cfg)
    for key, small in cfg.get("rehearse", {}).items():
        out[key] = {**cfg[key], **small}
    return out


#: the lists of `BENCHMARK.json` whose entries later PRs may only add to
LISTS = ("configs", "workloads", "end_to_end", "per_layer")
#: prose: a cell's or a configuration's `why` may be rewritten
PROSE = {"configs": ("why",), "workloads": ("why",)}


def append_only(doc: dict, accepted: dict) -> list:
    """What `doc` did to the accepted benchmark other than append, in words;
    empty where nothing. The driver's rule for a PR that is not a
    `benchmark` PR: every accepted entry is still there, in its order, at
    the head of its list, and unchanged but for cells appended to its
    `workloads` (a `why` is prose; a `bound` may only tighten)."""
    wrong = []
    for group in LISTS:
        was = [e["name"] for e in accepted[group]]
        now = [e["name"] for e in doc[group]]
        today = {e["name"]: e for e in doc[group]}
        wrong += [f"{group}: accepted entry {n!r} is gone"
                  for n in was if n not in today]
        # what is left of the accepted entries, in yesterday's order and in
        # today's; they should be the head of today's list
        left = [n for n in was if n in today]
        order = [n for n in now if n in was]
        moved = [(a, b) for a, b in zip(order, left) if a != b]
        if moved:
            wrong.append(f"{group}: {moved[0][0]!r} stands where the "
                         f"accepted order has {moved[0][1]!r}")
        elif now[:len(left)] != left:
            new = [n for n in now[:len(left)] if n not in was]
            wrong.append(f"{group}: {new} stand before the accepted entry "
                         f"{left[-1]!r}; new entries go to the end")
        for old in accepted[group]:
            if old["name"] in today:
                wrong += [f"{group}: {old['name']}: {w}" for w in
                          _entry_changes(old, today[old["name"]],
                                         PROSE.get(group, ()))]
    return wrong


def _entry_changes(old: dict, new: dict, prose: tuple) -> list:
    wrong = []
    for key in sorted(set(old) | set(new)):
        if key in prose:
            continue
        if key not in new or key not in old:
            wrong.append(f"key {key!r} " +
                         ("removed" if key in old else "added"))
        elif key == "workloads":
            a, b = old[key], new[key]
            if b[:len(a)] != a:
                wrong.append(f"`workloads` {b} does not start with the "
                             f"accepted {a}; a cell is appended")
        elif key == "bound":
            if new[key] > old[key]:
                wrong.append(f"`bound` raised from {old[key]} to {new[key]}")
        elif new[key] != old[key]:
            wrong.append(f"{key!r} changed from {old[key]!r} to {new[key]!r}")
    return wrong
