"""The harness, with its look for a chip skipped and the timed path
broken underneath, reports ``correct`` false.  One case per fault a cell
of this system can have; a one-chip cell has no exchange between chips
to leave out."""
import jax
import pytest

from bench import run_cell
from bench.tests import cells

CELLS = ["grid1k.recur16.closed256", "grid1k.wide100.poisson",
         "grid10m.wide100.closed32"]


def _patch_scan(monkeypatch, change):
    """Route every stacked scan's results through ``change``."""
    from repro.core.planning_backend import JaxPlanBackend
    orig = JaxPlanBackend.argmin_grid_many_async

    def broken(self, fn, cluster, params_many, **kw):
        fin = orig(self, fn, cluster, params_many, **kw)
        return lambda: change(fin(), cluster)
    monkeypatch.setattr(JaxPlanBackend, "argmin_grid_many_async", broken)


def _altered(results, cluster):
    """A winner altered where it is produced: one container more (or
    fewer at the top of the grid)."""
    hi = cluster.dims[0].hi
    return [(None, c) if r is None else
            ((r[0] + 1 if r[0] < hi else r[0] - 1,) + tuple(r[1:]), c)
            for r, c in results]


def _half(results, cluster):
    """Half of the batch left out: the second half of the requests read
    an output never written (flat id 0, cost 0)."""
    first = cluster.min_config()
    n = len(results)
    return results[:n - n // 2] + [(first, 0.0)] * (n // 2)


def _run(name):
    cell = cells.tiny(name)
    return run_cell.run(cell, 7, 1.5, False, jax, cells.device(jax))


@pytest.mark.parametrize("name", CELLS)
def test_sound_run_is_correct(name):
    out = _run(name)
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [_altered, _half])
@pytest.mark.parametrize("name", CELLS[:2])
def test_broken_scan_is_not_correct(monkeypatch, name, fault):
    _patch_scan(monkeypatch, fault)
    out = _run(name)
    assert not out["correct"]
    assert out["checks"]["plan_gap"]["value"] > \
        out["checks"]["plan_gap"]["limit"]


def test_step_that_changes_nothing_is_not_correct(monkeypatch):
    from repro.service import StreamingPlannerService
    real_step = StreamingPlannerService.step
    real_enter = run_cell.Traced.__enter__
    stuck = []

    def step(self):             # once the window opens, waves do nothing
        return 0 if stuck else real_step(self)

    def enter(self):
        stuck.append(True)
        return real_enter(self)
    monkeypatch.setattr(StreamingPlannerService, "step", step)
    monkeypatch.setattr(run_cell.Traced, "__enter__", enter)
    out = _run("grid1k.recur16.closed256")
    assert not out["correct"]
    assert out["checks"]["compared"]["value"] == 0
