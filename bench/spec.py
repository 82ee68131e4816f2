"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives them.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix; the configuration's ``file`` holds the deployment, the mix is
``bench/traffic/<traffic>.json``, and every metric is read by
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a mix or a
metric is adding files and entries: nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    kind: str                  # "end_to_end" | "per_layer"
    read: Callable


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    metrics: List[Metric]      # the cell's metrics for the run's mode


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def reader(name: str, bench: Path = BENCH) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``.  A metric that
    reads the same quantity as another in other cells (split by the
    end-to-end metric it moves) takes that one's reader through this."""
    path = bench / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {name}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _applies(metric: dict, cell: str, e2e_names) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:          # per-layer: every cell of its metric
        return metric["moves"] in e2e_names
    return True


def load_cell(root: Path, name: str, trace: bool,
              bench: Path = BENCH) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with the end-to-end
    metrics (``trace`` False) or the per-layer ones (``trace`` True)."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"] if _applies(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    chosen = [m for m in spec["per_layer"]
              if _applies(m, name, e2e_names)] if trace else e2e
    kind = "per_layer" if trace else "end_to_end"
    metrics = [Metric(m["name"], m["unit"], kind, reader(m["name"], bench))
               for m in chosen]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, metrics=metrics)


def peaks(kind: str, bench: Path = BENCH) -> Dict[str, float]:
    """The published peaks of ``kind`` (``device_kind``); a device not in
    ``peaks.json`` is an error, not a default."""
    table = load_json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]
