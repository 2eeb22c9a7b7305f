"""Finds a cell's parts by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's
file is the one ``BENCHMARK.json`` gives; the mix is
``mixes/<traffic>.json``, the limits of its output comparison are
``limits/<cell>.json``, a per-layer metric's reader is
``metrics/<metric>.py`` and a configuration's plain reference is
``references/<reference>.py``.  Adding a cell, a mix, a metric or a
configuration adds files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict                  # the configuration's file
    mix: dict                     # the traffic mix's file
    limits: Optional[dict]        # limits/<cell>.json, None if absent
    end_to_end: list[dict]        # BENCHMARK.json entries this cell reports
    per_layer: list[dict]


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_file: Path = ROOT / "BENCHMARK.json",
              base: Path = HERE) -> Cell:
    bench = _load_json(bench_file)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {bench_file}; "
                       f"have {sorted(cells)}")
    w = cells[name]
    cfgs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(bench_file.parent / cfgs[w["config"]]["file"])
    mix = _load_json(base / "mixes" / f"{w['traffic']}.json")
    lim_path = base / "limits" / f"{name}.json"
    limits = _load_json(lim_path) if lim_path.exists() else None
    return Cell(name=name, chips=int(w["chips"]), config=config, mix=mix,
                limits=limits,
                end_to_end=[m for m in bench["end_to_end"]
                            if _reports(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _reports(m, name)])


def metric_reader(name: str, base: Path = HERE) -> ModuleType:
    """``metrics/<name>.py``; its ``read(ctx)`` returns the value, or None
    where the run gave it nothing to read."""
    return _module(base / "metrics" / f"{name}.py", "bench_metric_" + name.replace(".", "_"))


def reference(name: str, base: Path = HERE) -> ModuleType:
    return _module(base / "references" / f"{name}.py", f"bench_ref_{name}")
