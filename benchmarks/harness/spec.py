"""What `BENCHMARK.json` says, and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own, found here by name:

    configuration  <paths[0]>/configs/<file>      (BENCHMARK.json gives the path)
    its family     <paths[0]>/families/<family>.py
    traffic mix    <paths[0]>/traffic/<traffic>.json
    layer metric   <paths[0]>/layer_metrics/<name>.py

A per-layer metric's name may carry a `.variant` suffix
(`device_idle_share.train`): `moves` names ONE end-to-end metric, so a
metric that exists in cells judged by different end-to-end metrics has one
entry per such metric, and all of them share the reader named by the part
before the dot.  So a new cell, configuration or per-layer metric is new
files and new entries in BENCHMARK.json, never an edit here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple      # metric entries of BENCHMARK.json for this cell
    per_layer: tuple


@dataclasses.dataclass(frozen=True)
class Spec:
    root: str
    doc: dict

    @property
    def home(self) -> str:
        """The benchmark's main directory: the first of `paths`."""
        return os.path.join(self.root, self.doc["paths"][0])

    @property
    def cells(self) -> list[Cell]:
        return [self.cell(w["name"]) for w in self.doc["workloads"]]

    def cell(self, name: str) -> Cell:
        by_name = {w["name"]: w for w in self.doc["workloads"]}
        if name not in by_name:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json; it has "
                f"{sorted(by_name)}")
        w = by_name[name]
        conf = {c["name"]: c for c in self.doc["configs"]}[w["config"]]
        e2e = tuple(m for m in self.doc["end_to_end"] if _applies(m, name))
        mine = {m["name"] for m in e2e}
        layer = tuple(m for m in self.doc["per_layer"]
                      if _applies(m, name) and m["moves"] in mine)
        return Cell(
            name=name, chips=int(w["chips"]),
            config=_read_json(os.path.join(self.root, conf["file"])),
            traffic=_read_json(os.path.join(
                self.home, "traffic", w["traffic"] + ".json")),
            end_to_end=e2e, per_layer=layer)

    def reader(self, metric_name: str):
        """The `read(ctx)` function of a per-layer metric."""
        stem = metric_name.split(".", 1)[0]
        path = os.path.join(self.home, "layer_metrics", stem + ".py")
        return _load_module(f"_layer_metric_{stem}", path).read


def _applies(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_module(name: str, path: str):
    """Import the file at `path`; one module object per file, whichever
    checkout it belongs to."""
    path = os.path.abspath(path)
    name = f"{name}_{abs(hash(path)):x}"
    if name in sys.modules:
        return sys.modules[name]
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    sys.modules[name] = mod
    mod_spec.loader.exec_module(mod)
    return mod


def load(root: str) -> Spec:
    return Spec(root=root,
                doc=_read_json(os.path.join(root, "BENCHMARK.json")))


def family(config: dict, home: str | None = None):
    """The module of the configuration's model family."""
    home = home or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    name = config["family"]
    return _load_module(f"_family_{name}",
                        os.path.join(home, "families", name + ".py"))
