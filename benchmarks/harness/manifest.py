"""Finding a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own::

    BENCHMARK.json  workloads[i].config   ->  benchmarks/configs/<config>.json
                    workloads[i].traffic  ->  benchmarks/traffic/<traffic>.json
                    per_layer[j].name     ->  benchmarks/layer_metrics/<name>.py
                                              (<name> up to its first ".")
    configs/<c>.json  "runner"            ->  benchmarks/harness/<runner>_runner.py
                      "reference.file"    ->  benchmarks/reference/<file>.py
                      "reference.weights_from" -> benchmarks/reference/<it>_weights.py

so a later PR adds a cell, a mix or a metric by adding files and entries,
and edits nothing that is here.
"""

from __future__ import annotations

import copy
import importlib
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_ROOT = os.path.dirname(BENCH_DIR)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def deep_merge(base: dict, over: dict) -> dict:
    """``over`` laid on a copy of ``base``; dicts merge key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = deep_merge(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


def resolve(dotted: str) -> Any:
    """``"package.module:Name"`` -> the object."""
    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix,
    the metrics that apply to it, all read from files."""

    def __init__(self, name: str, rehearse: bool = False):
        self.manifest = _load_json(os.path.join(REPO_ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(have: {sorted(cells)})")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in self.manifest["configs"]}[
            self.entry["config"]]
        self.config = _load_json(os.path.join(REPO_ROOT, cfg_entry["file"]))
        self.traffic = _load_json(os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        self.rehearse = rehearse
        if rehearse:
            # tiny sizes for a control-flow rehearsal on the CPU; each file
            # brings its own
            self.config = deep_merge(self.config,
                                     self.config.get("rehearse", {}))
            self.traffic = deep_merge(self.traffic,
                                      self.traffic.get("rehearse", {}))
        if self.traffic["kind"] != self.config["runner"]:
            raise SystemExit(
                f"cell {name}: traffic kind {self.traffic['kind']!r} does "
                f"not fit a {self.config['runner']!r} configuration")

    def _applies(self, metric: dict) -> bool:
        return "workloads" not in metric or self.name in metric["workloads"]

    @property
    def end_to_end(self) -> List[dict]:
        return [m for m in self.manifest["end_to_end"] if self._applies(m)]

    @property
    def per_layer(self) -> List[dict]:
        # a per-layer metric is reported only where the metric it moves is
        reported = {m["name"] for m in self.end_to_end}
        return [m for m in self.manifest["per_layer"]
                if self._applies(m) and m["moves"] in reported]

    def runner(self):
        return importlib.import_module(
            f"benchmarks.harness.{self.config['runner']}_runner")

    def reference(self):
        ref = self.config["reference"]
        return load_module(
            os.path.join(BENCH_DIR, "reference", ref["file"] + ".py"),
            "benchmarks_reference_" + ref["file"])

    def reference_weights(self, params):
        """The program's parameters as the reference reads them."""
        name = self.config["reference"]["weights_from"] + "_weights"
        adapter = load_module(
            os.path.join(BENCH_DIR, "reference", name + ".py"),
            "benchmarks_reference_" + name)
        return adapter.adapt(params, self.config["num_hidden_layers"])

    def layer_metric(self, name: str):
        # "<reader>" or "<reader>.<tag>": one reader serves the entries that
        # differ only in the end-to-end metric they move
        path = os.path.join(BENCH_DIR, "layer_metrics",
                            name.split(".", 1)[0] + ".py")
        if not os.path.exists(path):
            raise SystemExit(f"per-layer metric {name!r} has no reader "
                             f"({os.path.relpath(path, REPO_ROOT)})")
        return load_module(path, "benchmarks_layer_metric_" + name)


def peaks_for(device_kind: str) -> Dict[str, float]:
    """The published peaks of a device kind; an unknown kind raises."""
    table = _load_json(os.path.join(BENCH_DIR, "harness", "peaks.json"))
    try:
        return table["devices"][device_kind]
    except KeyError:
        raise SystemExit(
            f"no published peaks for device kind {device_kind!r} in "
            f"benchmarks/harness/peaks.json (known: "
            f"{sorted(table['devices'])}): add it with its source") from None
