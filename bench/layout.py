"""Where each part of a cell lives, found by the names ``BENCHMARK.json``
gives it:

* configuration ``<c>``: ``configs/<c>.json`` (the sizes as run; the file
  the configuration's entry names), ``configs/<c>.py`` (``build(cfg,
  weights)``: the system under test) and ``configs/<c>_ref.py``
  (``weights``, ``forward``, ``gemm_shapes``: the plain reference);
* traffic mix ``<t>``: ``traffic/<t>.json``, read by :mod:`bench.loadgen`;
* per-layer metric ``<m>``: ``metrics/<m>.py`` with ``read(readings)``.

Adding any of them takes new files and new entries, never an edit.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def net_module(self) -> ModuleType:
        return load_module(BENCH / "configs" / f"{self.config_name}.py")

    def ref_module(self) -> ModuleType:
        return load_module(BENCH / "configs" / f"{self.config_name}_ref.py")


def load_module(path: Path) -> ModuleType:
    """Import a file of the benchmark by its path (names may hold dots)."""
    name = "bench._loaded." + path.relative_to(BENCH).as_posix() \
        .removesuffix(".py").replace("/", ".").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no benchmark file {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _named(entries: List[dict], name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json; known: "
                   f"{sorted(e['name'] for e in entries)}")


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    lists; an end-to-end metric with no list (``setup_s``) is in every
    cell, and a per-layer metric has to have one."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        raise ValueError(f"per-layer metric {metric['name']!r} lists no "
                         f"workloads")
    return True


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bm = benchmark(root)
    work = _named(bm["workloads"], name, "workload")
    conf = _named(bm["configs"], work["config"], "configuration")
    e2e = [m for m in bm["end_to_end"] if reports(m, name)]
    per_layer = [m for m in bm["per_layer"] if reports(m, name)]
    return Cell(
        name=name, chips=work["chips"], config_name=conf["name"],
        config=json.loads((root / conf["file"]).read_text()),
        traffic_name=work["traffic"],
        traffic=json.loads((BENCH / "traffic" / f"{work['traffic']}.json")
                           .read_text()),
        end_to_end=e2e, per_layer=per_layer)


def metric_reader(name: str):
    """The ``read`` function of per-layer metric ``name``."""
    return load_module(BENCH / "metrics" / f"{name}.py").read
