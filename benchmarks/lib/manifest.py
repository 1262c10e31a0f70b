"""Finds a cell, a configuration, a traffic mix and a per-layer metric by the
name `BENCHMARK.json` gives it. Each lives in a file of its own, so a later
PR adds files and entries and edits nothing that is there."""

import importlib.util
import json
import os

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


def apply_rehearsal(cfg: dict) -> dict:
    """The configuration at its `rehearse` size: a CPU rehearsal of the
    command, never a result."""
    out = dict(cfg)
    for key, small in cfg.get("rehearse", {}).items():
        out[key] = {**cfg[key], **small}
    return out
