"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives them.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix; the configuration's ``file`` holds the deployment, the mix is
``bench/traffic/<traffic>.json``, and every metric is read by
``bench/metrics/<metric>.py``.  The plans of a configuration are judged by
the reference it names in ``check.reference``, the file
``bench/references/<reference>.py``, or by ``bench/reference.py`` where it
names none.  Adding a cell, a configuration, a mix, a metric or a
reference is adding files and entries: nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
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
    reference: type            # the ``Planner`` that judges the plans


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(kind: str, name: str, path: Path):
    """The module in the file ``path``, loaded by path."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no {kind} {path} for {name}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod      # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, bench: Path = BENCH) -> Callable:
    """The ``read`` function of ``bench/metrics/<name>.py``.  A metric that
    reads the same quantity as another in other cells (split by the
    end-to-end metric it moves) takes that one's reader through this."""
    return _module("metric", name, bench / "metrics" / f"{name}.py").read


def reference(config: dict, bench: Path = BENCH) -> type:
    """The ``Planner`` class that judges ``config``'s plans: that of
    ``bench/references/<name>.py`` where ``check.reference`` names one,
    else that of ``bench/reference.py``.  A reference module exposes
    ``Planner(config, schema, precision="float64" | "control")`` with
    ``prefetch(queries)``, ``plan(tables)``, ``compare(plan, tables)``
    (a dict that holds every key of ``check.limits``) and ``searches``."""
    name = config["check"].get("reference")
    if name is None:
        from bench.reference import Planner
        return Planner
    return _module("reference", name,
                   bench / "references" / f"{name}.py").Planner


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
                traffic=traffic, metrics=metrics,
                reference=reference(config, bench))


def peaks(kind: str, bench: Path = BENCH) -> Dict[str, float]:
    """The published peaks of ``kind`` (``device_kind``); a device not in
    ``peaks.json`` is an error, not a default."""
    table = load_json(bench / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]
