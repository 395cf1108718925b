"""Finding a cell's files by the names in ``BENCHMARK.json``: a
configuration, a traffic mix, a driver, a plain reference, the limits of
``correct`` and a per-layer metric's reader are each a file of their own,
so that a later PR adds a cell by adding files and entries."""

from __future__ import annotations

import importlib.util
import json
import re
import sys
import types
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import a file by path: names such as ``unet-tconv`` are data, not
    Python identifiers."""
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    ident = "perfbench_file_" + re.sub(r"\W", "_", path.stem) + "_" + \
        re.sub(r"\W", "_", path.parent.name)
    if ident in sys.modules:
        return sys.modules[ident]
    spec = importlib.util.spec_from_file_location(ident, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[ident] = module
    spec.loader.exec_module(module)
    return module


class Bench:
    """``BENCHMARK.json`` of a checkout, and the files its names lead to."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.doc = load_json(self.root / "BENCHMARK.json")
        self.home = self.root / self.doc["paths"][0]

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                return load_json(self.root / c["file"])
        raise SystemExit(f"no configuration {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.home / "traffic" / f"{name}.json")

    def driver(self, name: str):
        return load_module(self.home / "drivers" / f"{name}.py")

    def reference(self, config: str):
        return load_module(self.home / "reference" / f"{config}.py")

    def limits(self, workload: str) -> dict:
        return load_json(self.home / "limits" / f"{workload}.json")

    def reader(self, metric: str):
        return load_module(self.home / "layer_metrics" / f"{metric}.py")

    def cell(self, workload: str, seed: int, seconds: float, workdir: Path):
        """What a driver is handed: the cell's entry with its files loaded."""
        entry = self.workload(workload)
        return types.SimpleNamespace(
            name=workload, chips=entry["chips"], seed=seed, seconds=seconds,
            config=self.config(entry["config"]),
            traffic=self.traffic(entry["traffic"]),
            reference=self.reference(entry["config"]), workdir=Path(workdir))

    def peaks(self, device_kind: str) -> dict:
        table = load_json(self.home / "lib" / "peaks.json")
        if device_kind not in table["devices"]:
            raise SystemExit(f"no peaks for device kind {device_kind!r}")
        return table["devices"][device_kind]

    def reports(self, metric: dict, workload: str) -> bool:
        """Whether a metric entry is reported in a cell."""
        return workload in metric.get(
            "workloads", [w["name"] for w in self.doc["workloads"]])
