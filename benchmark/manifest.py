"""``BENCHMARK.json`` and the files it names, found by name.

A cell is one entry of ``workloads``: a configuration under a traffic mix.
Whatever belongs to one of them sits in a file of its own under the
benchmark's first path, so a later PR adds a cell, a configuration or a metric
by adding files and entries and edits none that is there:

* ``configs/<config>.json`` — the configuration's ``file``: the published
  sizes as run, and how the job or the engine is sized on the chip;
* ``models/<model_type>.py`` — the architecture the configuration's
  ``model_type`` names (``models/__init__.py`` says what it holds), and
  ``reference/<module>.py`` — the plain reference its ``reference.module`` names;
* ``traffic/<traffic>.json`` — the mix's parameters, with ``generator`` naming
  the module ``traffic/<generator>.py`` that reads them;
* ``metrics/<metric>.py`` — one reader per metric: ``read(run)`` returns the
  value, or None where the run holds nothing to read.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from typing import Any, Callable, Dict, List, Optional

MANIFEST = "BENCHMARK.json"


class ManifestError(Exception):
    """The manifest or a file it names is missing or does not fit it."""


def published_keys(config: Dict[str, Any]) -> Dict[str, Any]:
    """The top level of a configuration's file without its nested groups: the
    published ``config.json`` keys as run, and the benchmark's two dtypes."""
    return {k: v for k, v in config.items() if not isinstance(v, (dict, list))}


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    why: str
    config_name: str
    config: Dict[str, Any]       # the configuration's file
    architecture: str            # module of the file's ``model_type``, by name
    reference: str               # module of the file's plain reference, by name
    traffic_name: str
    traffic: Dict[str, Any]      # the mix's file
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def metrics(self, traced: bool) -> List[Dict[str, Any]]:
        """What the last line must hold for this ``--trace`` value."""
        return self.per_layer if traced else self.end_to_end


class Manifest:
    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        path = os.path.join(self.root, MANIFEST)
        try:
            with open(path) as f:
                self.data = json.load(f)
        except (OSError, ValueError) as e:
            raise ManifestError(f"cannot read {path}: {e}") from e
        self.home = os.path.join(self.root, self.data["paths"][0])

    def cell_names(self) -> List[str]:
        return [w["name"] for w in self.data["workloads"]]

    def _read_json(self, rel: str) -> Dict[str, Any]:
        path = os.path.join(self.root, rel)
        try:
            with open(path) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            raise ManifestError(f"cannot read {path}: {e}") from e

    def _metrics_of(self, group: str, cell: str) -> List[Dict[str, Any]]:
        return [
            m for m in self.data[group]
            if "workloads" not in m or cell in m["workloads"]
        ]

    def cell(self, name: str) -> Cell:
        entry = next((w for w in self.data["workloads"] if w["name"] == name), None)
        if entry is None:
            raise ManifestError(
                f"no workload {name!r} in {MANIFEST}; it has {self.cell_names()}"
            )
        config = next(
            (c for c in self.data["configs"] if c["name"] == entry["config"]), None
        )
        if config is None:
            raise ManifestError(f"workload {name!r} names no known config")
        traffic_rel = os.path.join(
            self.data["paths"][0], "traffic", entry["traffic"] + ".json"
        )
        file = self._read_json(config["file"])
        return Cell(
            name=name, chips=int(entry["chips"]), why=entry["why"],
            config_name=config["name"], config=file,
            architecture=self._module(
                "models", file.get("model_type"), f"{config['file']} model_type"),
            reference=self._module(
                "reference", file.get("reference", {}).get("module"),
                f"{config['file']} reference.module"),
            traffic_name=entry["traffic"], traffic=self._read_json(traffic_rel),
            end_to_end=self._metrics_of("end_to_end", name),
            per_layer=self._metrics_of("per_layer", name),
        )

    def _module(self, folder: str, stem: Optional[str], named_by: str) -> str:
        """The name of the module ``<folder>/<stem>.py`` of the benchmark's
        package. By name, because the worker that holds the chip imports it too."""
        if not stem or not os.path.isfile(os.path.join(self.home, folder, stem + ".py")):
            raise ManifestError(
                f"{named_by} is {stem!r}, and there is no "
                f"{os.path.join(self.data['paths'][0], folder, str(stem))}.py"
            )
        return f"{os.path.basename(self.home)}.{folder}.{stem}"

    def generator(self, cell: Cell):
        """The module that turns the cell's traffic file into load."""
        return importlib.import_module(self._module(
            "traffic", cell.traffic.get("generator"),
            f"traffic {cell.traffic_name!r} generator"))

    def reader(self, metric: str) -> Callable[[Dict[str, Any]], Optional[float]]:
        path = os.path.join(self.home, "metrics", metric + ".py")
        spec = importlib.util.spec_from_file_location(
            "benchmark_metric_" + "".join(c if c.isalnum() else "_" for c in metric),
            path,
        )
        if spec is None or not os.path.isfile(path):
            raise ManifestError(f"metric {metric!r} has no reader at {path}")
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read
