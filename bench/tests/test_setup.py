"""A configuration the program would not run as it states fails at set-up,
before anything compiles or is served: a planner that the planner service
does not serve, or a ``planner_params`` key that ``RAQO`` does not take."""
import copy

import jax
import pytest

from bench import run_cell
from bench.tests import cells

CELL = "grid1k.recur16.closed256"


def _deployment(config_change=lambda c: c):
    cell = cells.tiny(CELL)
    return run_cell.Deployment(config_change(cell.config), cell.traffic)


def test_the_service_refuses_a_planner_it_does_not_serve():
    with pytest.raises(ValueError, match="'fastrandomized'"):
        _deployment(cells.fast_randomized)
    assert _deployment().raqo.planner == "selinger"


def test_a_planner_the_service_declares_is_built(monkeypatch):
    from repro.service import StreamingPlannerService
    monkeypatch.setattr(StreamingPlannerService, "planners",
                        ("selinger", "fastrandomized"), raising=False)
    assert _deployment(cells.fast_randomized).raqo.planner == \
        "fastrandomized"


def test_a_run_stops_before_anything_compiles_or_is_served(monkeypatch):
    def warm_widths(self, widths):
        raise AssertionError("set-up went on past the deployment")
    monkeypatch.setattr(run_cell.Deployment, "warm_widths", warm_widths)
    cell = cells.tiny(CELL)
    cell.config = cells.fast_randomized(cell.config)
    with pytest.raises(ValueError, match="'fastrandomized'"):
        run_cell.run(cell, 7, 1.5, False, jax, cells.device(jax))


def _with_params(params):
    def change(config):
        config = copy.deepcopy(config)
        config["planner_params"] = params
        return config
    return change


def test_planner_params_reach_the_program():
    assert _deployment(_with_params({"seed": 7})).raqo.seed == 7
    assert _deployment().raqo.seed == 0


@pytest.mark.parametrize("params", [{"iterations": 20},
                                    {"seed": 7, "population": 6}])
def test_planner_params_the_program_does_not_take_fail(params):
    with pytest.raises(TypeError, match=next(k for k in params
                                             if k != "seed")):
        _deployment(_with_params(params))
