"""Find a cell's pieces by name: ``BENCHMARK.json`` names the cell, its
configuration file and its metrics; the configuration's public
``model_type`` names its architecture file ``bench/archs/<model_type>.py``;
the traffic mix is ``bench/traffic/<traffic>.json`` and each metric's
reader is ``bench/metrics/<metric>.py``.  Adding a cell, configuration,
architecture, mix or metric is adding files and entries, never editing
code."""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    #: end-to-end metric entries of BENCHMARK.json that this cell reports
    end_to_end: List[dict]
    #: per-layer metric entries that this cell reports
    per_layer: List[dict]
    root: Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files read;
    KeyError names what is missing."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer,
                root=root)


def _load(path: Path, module_name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(module_name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs: a dataclass looks its module up by name
    sys.modules[module_name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, root: Path = ROOT) -> Callable:
    """``read(run) -> float | None`` from ``bench/metrics/<name>.py``
    (loaded by path: metric names may hold dots)."""
    return _load(root / "bench" / "metrics" / f"{name}.py",
                 f"bench_metric_{name}").read


def arch_module(model_type: str, root: Path = ROOT) -> ModuleType:
    """The architecture file ``bench/archs/<model_type>.py``; its four
    functions are described in ``bench/archs/llama.py``."""
    path = root / "bench" / "archs" / f"{model_type}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no architecture file {path} for "
                                f"model_type {model_type!r}")
    return _arch_file(path.resolve(), model_type)


@functools.lru_cache(maxsize=None)
def _arch_file(path: Path, model_type: str) -> ModuleType:
    """Each file loaded once per process: its ``parse`` result keys the
    weights' and the reference's compiled programs, so a second load, with
    classes of its own, would miss them."""
    return _load(path, f"bench_arch_{model_type}")


def read_metrics(entries: List[dict], run, root: Path = ROOT
                 ) -> Dict[str, dict]:
    """Each of the cell's metrics with its unit.  A reader returning None
    (a kernel taken off the path leaves its roofline nothing to read) leaves
    its metric out of the line and names it on standard error; the line
    then lacks a metric that ``BENCHMARK.json`` lists for the cell, which
    the benchmark's check refuses, so it cannot drop out unseen."""
    out: Dict[str, dict] = {}
    for m in entries:
        value: Optional[float] = metric_reader(m["name"], root)(run)
        if value is None:
            print(f"[metrics] {m['name']}: nothing to read in this run, "
                  f"left out of the line", file=sys.stderr, flush=True)
            continue
        out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out
