"""Finds a cell's parts by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, driver or
per-layer metric sits in a file of its own, so that a later change adds
a cell by adding files and entries and edits none:

- configuration: the ``file`` its entry names (``bench/configs/``);
- traffic mix: ``bench/traffic/<traffic>.json``, a data file of
  parameters that the driver reads;
- field family a mix draws from: the mix's ``family`` key,
  ``bench/families/<family>.py``, a module with
  ``make(dims, seed, i) -> np.ndarray``;
- driver (how a kind of deployment is driven): the configuration's
  ``driver`` key, ``bench/drivers/<driver>.py``;
- per-layer metric reader: ``bench/metrics/<metric>.py``, a module with
  ``read(run) -> float | None``.
"""

from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import List


@dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def benchmark(root: Path) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(root: Path, name: str) -> Cell:
    root = Path(root)
    bm = benchmark(root)
    try:
        wl = next(w for w in bm["workloads"] if w["name"] == name)
    except StopIteration:
        known = [w["name"] for w in bm["workloads"]]
        raise KeyError(f"no workload {name!r}; known: {known}") from None
    cfg_entry = next(c for c in bm["configs"] if c["name"] == wl["config"])
    config = json.loads((root / cfg_entry["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{wl['traffic']}.json").read_text())
    return Cell(root=root, name=name, chips=int(wl["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bm["end_to_end"] if _applies(m, name)],
                per_layer=[m for m in bm["per_layer"] if _applies(m, name)])


def _load(path: Path, label: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{label}: no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{label}_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(root: Path, name: str) -> ModuleType:
    return _load(Path(root) / "bench" / "drivers" / f"{name}.py", "driver")


def metric_reader(root: Path, name: str) -> ModuleType:
    return _load(Path(root) / "bench" / "metrics" / f"{name}.py", "metric")


def family(root: Path, name: str) -> ModuleType:
    return _load(Path(root) / "bench" / "families" / f"{name}.py", "family")
