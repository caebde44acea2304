"""Finds what a cell needs by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; each is a file of its own
under this directory (``configs/<name>.json``, ``traffic/<name>.json``),
with the configuration's plain reference beside it
(``reference/<name>.py``) and the way the program is told to run it
(``adapters/<name>.py``), the cell's output limits in
``limits/<cell>.json`` and one reader per per-layer metric in
``metrics/<metric>.py``.  Adding a cell, configuration, mix or metric is
adding files and entries; nothing here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

#: checkout root (benchmarks/chip/spec.py → ../../)
ROOT = Path(__file__).resolve().parents[2]
#: where the benchmark's own files live, relative to the checkout root
HOME = Path("benchmarks") / "chip"


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(path)
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` of a checkout and the files its names point to."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.home = self.root / HOME
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())

    def _json(self, kind: str, name: str) -> dict:
        return json.loads((self.home / kind / f"{name}.json").read_text())

    def cell(self, name: str) -> dict:
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> dict:
        return self._json("limits", cell)

    def reference(self, config: str):
        return _load_module(self.home / "reference" / f"{config}.py",
                            f"chip_reference_{config.replace('-', '_')}")

    def adapter(self, config: str):
        """``adapters/<config>.py``: how the program is told to run it."""
        return _load_module(self.home / "adapters" / f"{config}.py",
                            f"chip_adapter_{config.replace('-', '_')}")

    def metrics(self, cell: str, trace: bool) -> list[dict]:
        """The cell's end-to-end metrics, or with ``trace`` its per-layer
        ones: those that list the cell, or list no cells."""
        group = self.spec["per_layer" if trace else "end_to_end"]
        return [m for m in group if cell in m.get("workloads", [cell])]

    def reader(self, metric: str):
        """``metrics/<metric>.py``: its ``read(run)`` returns the value, or
        None where the run holds nothing to read."""
        return _load_module(self.home / "metrics" / f"{metric}.py",
                            f"chip_metric_{metric.replace('.', '_')}").read

    def peaks(self, device_kind: str) -> dict:
        table = json.loads((self.home / "peaks.json").read_text())
        if device_kind not in table or device_kind == "source":
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           "peaks.json")
        return table[device_kind]
