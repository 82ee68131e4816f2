"""The control (the reference one precision down) fails the check that
sound runs pass, on three seeds, at a size the CPU runs in seconds."""
import pytest

from bench import control
from bench.tests import cells


@pytest.mark.parametrize("name", ["grid1k.recur16.closed256",
                                  "grid1k.wide100.poisson",
                                  "grid10m.wide100.closed32"])
def test_control_fails_a_limit_on_every_seed(name):
    cell = cells.tiny(name)
    limits = cell.config["check"]["limits"]
    for seed in (3, 4, 2**31 + 11):
        got = control.readings(cell.config, cell.traffic, seed)
        assert got["compared"] == cell.config["check"]["sample_queries"]
        assert any(got[k] > limits[k] for k in limits), (seed, got)
