"""Find a cell and everything it names, by name, from files.

``BENCHMARK.json`` at the checkout root lists configurations, cells and
metrics.  Each name resolves to a file of its own, so a later change adds
a cell by adding files and entries and never edits a file that exists:

  configuration   the ``file`` its ``configs`` entry gives
                  (``bench/configs/<name>.json``)
  traffic mix     ``bench/traffic/<name>.json``
  serving driver  ``bench/drivers/<driver>.py``, ``driver`` from the mix
  reference       ``bench/references/<reference>.py``, from the config
  limits          ``bench/limits/<cell>.json``
  per-layer       ``bench/metrics/<metric>.py``, one reader each

A configuration may state ``layers``, a stack of recurrent layers of
width ``hidden`` (default one), and ``cell``, the recurrent cell of every
layer (``"gru"``, the default ΔGRU, or ``"lstm"``, a ΔLSTM with four
gates and a cell vector c; ``bench/model.py``'s ``CELLS``): the weights
(``bench/model.py``, which names the keys of the layers above the first),
the work counts (``bench/costs.py``), the program check
(``bench/harness.py``), the reference and the state the check compares
(every layer's h, side by side) follow both, so a stacked or a ΔLSTM
configuration is a file of its own too.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from types import ModuleType

ROOT = pathlib.Path(__file__).resolve().parent.parent


class SpecError(ValueError):
    """A name in BENCHMARK.json that resolves to no usable file."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    limits: dict
    end_to_end: tuple      # the entries of the metrics this cell reports
    per_layer: tuple
    root: pathlib.Path


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise SpecError(f"no BENCHMARK.json at {root}")
    return json.loads(path.read_text())


def _json(path: pathlib.Path, what: str) -> dict:
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path, what: str) -> ModuleType:
    """Import one file by path (readers, drivers and references are files
    found by name, not modules of a package)."""
    if not path.is_file():
        raise SpecError(f"{what}: no file {path}")
    mod_name = ("bench_file_" + str(path.resolve()).replace("/", "_")
                .replace("-", "_").replace(".", "_"))
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = module
    try:
        spec.loader.exec_module(module)
    except BaseException:
        del sys.modules[mod_name]
        raise
    return module


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def find_cell(bench: dict, name: str, root: pathlib.Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {name!r} names unknown config "
                        f"{w['config']!r}")
    config = _json(root / configs[w["config"]]["file"],
                   f"config {w['config']}")
    traffic = _json(root / "bench" / "traffic" / f"{w['traffic']}.json",
                    f"traffic {w['traffic']}")
    limits = _json(root / "bench" / "limits" / f"{name}.json",
                   f"limits of {name}")
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=config, traffic_name=w["traffic"], traffic=traffic,
        limits=limits,
        end_to_end=tuple(m for m in bench["end_to_end"] if _applies(m, name)),
        per_layer=tuple(m for m in bench["per_layer"] if _applies(m, name)),
        root=root)


def driver(cell: Cell) -> ModuleType:
    return load_module(cell.root / "bench" / "drivers"
                       / f"{cell.traffic['driver']}.py",
                       f"driver of traffic {cell.traffic_name}")


def reference(cell: Cell) -> ModuleType:
    return load_module(cell.root / "bench" / "references"
                       / f"{cell.config['reference']}.py",
                       f"reference of config {cell.config_name}")


def reader(cell: Cell, metric: str) -> ModuleType:
    return load_module(cell.root / "bench" / "metrics" / f"{metric}.py",
                       f"reader of metric {metric}")
