"""The check follows the configuration: the reference it names, and every
key of its limits."""
import json
import shutil
import types

import pytest

from bench import run_cell, spec
from bench.reference import Planner as Selinger
from bench.tests import cells
from bench.window import Offer


def test_a_configuration_without_a_reference_gets_the_selinger_one():
    for cfg in ("raqo-sec7-grid1k", "raqo-sec7c-grid10m"):
        config = spec.load_json(spec.BENCH / "configs" / f"{cfg}.json")
        assert spec.reference(config) is Selinger


def test_an_unknown_reference_fails_at_load(tmp_path):
    shutil.copy(cells.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    bj = json.loads((tmp_path / "BENCHMARK.json").read_text())
    conf = next(c for c in bj["configs"] if c["name"] == "raqo-sec7-grid1k")
    config = spec.load_json(cells.ROOT / conf["file"])
    config["check"]["reference"] = "no_such_reference"
    (tmp_path / conf["file"]).parent.mkdir(parents=True)
    (tmp_path / conf["file"]).write_text(json.dumps(config))
    with pytest.raises(FileNotFoundError,
                       match="references/no_such_reference.py"):
        spec.load_cell(tmp_path, "grid1k.recur16.closed256", False)


class _Returns:
    """A reference whose every comparison returns ``READS``."""
    READS = {"plan_gap": 0.0, "cost_gap": 0.0}

    def __init__(self, config, schema, precision="float64"):
        self.searches = 0

    def prefetch(self, queries):
        list(queries)

    def compare(self, plan, tables):
        return dict(self.READS)


def _check(limits):
    offers = [Offer(("t0", "t1"), 0, ticket=types.SimpleNamespace(
        joint=types.SimpleNamespace(plan="a plan"))) for _ in range(3)]
    dep = types.SimpleNamespace(schema=None, config={
        "check": {"sample_queries": 2, "limits": limits}})
    window = types.SimpleNamespace(resolved=offers, counted=offers)
    return run_cell.check(dep, window, 1, _Returns)


def test_the_check_judges_every_limit_it_is_given():
    out = _check({"plan_gap": 1e-5, "cost_gap": 1e-10})
    assert out["correct"]
    assert list(out["checks"]) == ["plan_gap", "cost_gap", "compared",
                                   "unresolved"]
    out = _check({"cost_gap": -1.0})
    assert not out["correct"] and out["failed"] == 2
    assert list(out["checks"])[0] == "cost_gap"


def test_a_limit_the_reference_does_not_return_fails_the_check():
    with pytest.raises(KeyError, match="op_gap"):
        _check({"plan_gap": 1e-5, "op_gap": 1e-5})
