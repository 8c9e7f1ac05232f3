"""Find every piece of a benchmark cell by the names in ``BENCHMARK.json``.

A cell (``workloads`` entry) names a configuration and a traffic mix; each
piece lives in a file of its own, found by name:

    chipbench/configs/<config>.json   model sizes, deployment, HBM arithmetic
    chipbench/traffic/<traffic>.json  parameters of the one traffic generator
    chipbench/cells/<workload>.json   the correctness limit and its readings
    chipbench/metrics/<metric>.py     one reader per per-layer metric
    chipbench/families/<family>.py    a configuration's layers: widths,
                                      weights, reference and costs

so a cell, a mix, a metric or a family of layers is added by adding
files, with no edit here.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Callable, Optional

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent

# One module per file and process: a family's jitted reference layer is
# built, and compiled, once however many configurations or runs use it.
_LOADED: dict[Path, ModuleType] = {}


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[tuple]      # None: every cell that reports `moves`
    moves: str = ""                 # per-layer: the end-to-end metric

    def applies_to(self, workload: str) -> bool:
        return self.workloads is None or workload in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int


class Benchmark:
    """``BENCHMARK.json`` with lookups by name."""

    def __init__(self, path: Path = ROOT / "BENCHMARK.json",
                 bench_dir: Path = BENCH_DIR):
        self.path = Path(path)
        self.dir = Path(bench_dir)
        self.raw = json.loads(self.path.read_text())
        self.cells = {w["name"]: Cell(w["name"], w["config"], w["traffic"],
                                      int(w["chips"]))
                      for w in self.raw["workloads"]}
        self.run_seconds = int(self.raw["run_seconds"])

        def metric(m):
            wl = m.get("workloads")
            return Metric(m["name"], m["unit"],
                          tuple(wl) if wl is not None else None,
                          m.get("moves", ""))
        self.end_to_end = [metric(m) for m in self.raw["end_to_end"]]
        self.per_layer = [metric(m) for m in self.raw["per_layer"]]

    def cell(self, name: str) -> Cell:
        if name not in self.cells:
            raise KeyError(f"unknown workload {name!r}; known: "
                           f"{sorted(self.cells)}")
        return self.cells[name]

    def end_to_end_for(self, cell: str) -> list[Metric]:
        return [m for m in self.end_to_end if m.applies_to(cell)]

    def per_layer_for(self, cell: str) -> list[Metric]:
        reported = {m.name for m in self.end_to_end_for(cell)}
        return [m for m in self.per_layer
                if m.applies_to(cell) and m.moves in reported]

    # ------------------------------------------------------ files by name
    def _json(self, sub: str, name: str) -> dict:
        path = self.dir / sub / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {sub[:-1]} file for {name!r}: "
                                    f"{path}")
        return json.loads(path.read_text())

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def limits(self, cell: str) -> dict:
        return self._json("cells", cell)

    def _module(self, sub: str, name: str, what: str) -> ModuleType:
        path = (self.dir / sub / f"{name}.py").resolve()
        if not path.is_file():
            raise FileNotFoundError(f"no {what} {name!r}: {path}")
        if path not in _LOADED:
            tag = hashlib.sha1(str(path).encode()).hexdigest()[:12]
            spec = importlib.util.spec_from_file_location(
                f"chipbench_{sub}_{name.replace('.', '_')}_{tag}", path)
            mod = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = mod  # dataclasses look their module up
            spec.loader.exec_module(mod)
            _LOADED[path] = mod
        return _LOADED[path]

    def reader(self, metric: str) -> Callable:
        """The ``read(run)`` function of ``metrics/<metric>.py``."""
        return self._module("metrics", metric, "reader for metric").read

    def family(self, name: str) -> ModuleType:
        """The module ``families/<name>.py``: ``resolve``, ``layout``,
        ``hidden``, ``KERNELS`` and ``request_work`` of one family of
        layers (see ``families/dense.py``)."""
        return self._module("families", name, "family of layers")
