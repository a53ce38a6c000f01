"""Finds everything a cell is made of by the names in ``BENCHMARK.json``:
the configuration file, the traffic file, the traffic kind's module, the
family's module, and each per-layer metric's definition and reader. There
is no registry to edit and no ``if workload ==`` anywhere: a later PR adds a
cell by adding files and one entry to the manifest.
"""

from __future__ import annotations

import copy
import importlib
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

# sizes a cut may never touch (the contract's "widths")
WIDTH_KEYS = ("hidden_size", "intermediate_size", "head_dim",
              "num_experts_per_tok", "num_attention_heads",
              "num_key_value_heads", "vocab_size", "num_local_experts")


class ManifestError(ValueError):
    """The manifest or one of the files it names is not usable."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def manifest() -> Dict[str, Any]:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def _named(entries: List[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise ManifestError(f"BENCHMARK.json has no {what} named {name!r} "
                        f"(it has: {[e['name'] for e in entries]})")


def merge(base: dict, override: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in override.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else copy.deepcopy(v)
    return out


class Cell:
    """One entry of ``workloads`` with its files loaded."""

    def __init__(self, name: str, rehearsal: bool = False):
        self.manifest = manifest()
        self.entry = _named(self.manifest["workloads"], name, "workload")
        self.name = name
        self.chips = self.entry["chips"]
        self.rehearsal = rehearsal
        cfg_entry = _named(self.manifest["configs"], self.entry["config"],
                           "configuration")
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            BENCH_DIR, "traffic", self.entry["traffic"] + ".json"))
        role = self.traffic["role"]
        if role not in self.config["roles"]:
            raise ManifestError(
                f"traffic {self.entry['traffic']!r} wants the role {role!r}, "
                f"which configuration {self.config['name']!r} does not give "
                f"(it gives: {sorted(self.config['roles'])})")
        self.role = copy.deepcopy(self.config["roles"][role])
        published = dict(self.config["published"])
        cut = dict(self.role["model"])
        for key in cut:
            if key not in self.config["reduced"] or key in WIDTH_KEYS:
                raise ManifestError(
                    f"{self.config['name']}: role {role!r} changes {key!r}, "
                    f"which is a width or is not listed under 'reduced'")
        if rehearsal:
            reh = self.config["rehearsal"]
            published.update(reh["published"])
            cut.update(reh["model"])
            if "engine" in self.role and "dtype" in self.role["engine"]:
                self.role["engine"] = merge(self.role["engine"],
                                            reh["serve_engine"])
            self.traffic = merge(self.traffic, self.traffic["rehearsal"])
        self.model = {**published, **cut}     # the configuration as it runs
        self.family = importlib.import_module(
            f"benchmark.families.{self.config['family']}")
        self.reference = importlib.import_module(
            f"benchmark.reference.{self.family.REFERENCE}")
        self.kind = importlib.import_module(
            f"benchmark.traffic_kinds.{self.traffic['kind']}")

    def metrics(self, group: str) -> List[dict]:
        """The manifest's metrics of ``group`` that this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]


def metric_definition(name: str) -> dict:
    """``benchmark/metrics/<name>.json``: the reader's name and its
    parameters."""
    return load_json(os.path.join(BENCH_DIR, "metrics", name + ".json"))


def reader(name: str):
    return importlib.import_module(f"benchmark.readers.{name}")
