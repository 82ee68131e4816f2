"""The plain reference agrees with the planner on the numpy backend."""
import math
from pathlib import Path

import numpy as np
import pytest

from bench import spec
from bench.reference import Planner, Surfaces, to_bfloat16
from bench.traffic import generator as gen
from repro.core import schema as rschema
from repro.core.cluster import paper_cluster
from repro.core.cost_model import HiveSimulator, simulator_cost_models
from repro.core.raqo import RAQO

CONFIG = spec.load_json(Path(spec.BENCH) / "configs" /
                        "raqo-sec7-grid1k.json")


def test_surfaces_equal_the_simulator():
    surf, sim = Surfaces(CONFIG), HiveSimulator()
    rng = np.random.default_rng(0)
    for _ in range(200):
        ss, ls = sorted(rng.uniform(0.01, 300.0, size=2))
        nc, cs = int(rng.integers(1, 101)), int(rng.integers(1, 11))
        for impl in ("SMJ", "BHJ"):
            want = sim.cost(impl, ss, ls, cs, nc)
            assert surf.scalar(impl, ss, ls, nc, cs) == want


def test_exhaustive_argmin_equals_a_loop():
    surf, sim = Surfaces(CONFIG), HiveSimulator()
    for impl, ss, ls in (("SMJ", 0.3, 40.0), ("BHJ", 2.5, 9.0),
                         ("BHJ", 50.0, 60.0)):
        best = min(((sim.cost(impl, ss, ls, cs, nc), (nc, cs))
                    for nc in range(1, 101) for cs in range(1, 11)),
                   key=lambda x: x[0])
        res, cost = surf.argmin(impl, ss, ls)
        if math.isinf(best[0]):
            assert res is None
        else:
            assert cost == best[0]
            assert sim.cost(impl, ss, ls, res[1], res[0]) == best[0]


def test_bfloat16_rounding():
    x = np.array([1.0, 1.00390625, 1.005859375, 3.0e38, np.inf],
                 dtype=np.float32)
    got = to_bfloat16(x)
    assert got[0] == 1.0 and got[1] == 1.0          # tie to even
    assert got[2] == np.float32(1.0078125)
    assert np.isinf(got[4])


@pytest.mark.parametrize("n_tables", [16, 100])
def test_reference_equals_raqo_on_numpy(n_tables):
    schema = gen.random_schema(n_tables, 0)
    theirs = rschema.random_schema(n_tables, 0)
    raqo = RAQO(schema=theirs, models=simulator_cost_models(),
                cluster=paper_cluster(100, 10), resource_planning="batched",
                backend="numpy")
    ref = Planner(CONFIG, schema)
    for k in range(2, 7):
        tables = gen.random_query(schema, k, seed=10 + k)
        plan = raqo.joint(tables).plan
        assert ref.optimum(tables) == pytest.approx(plan.total_cost,
                                                    rel=1e-12)
        got = ref.compare(plan, tables)
        assert got["plan_gap"] <= 1e-12 and got["cost_gap"] <= 1e-12


def test_an_invalid_plan_reads_infinite():
    schema = gen.random_schema(16, 0)
    theirs = rschema.random_schema(16, 0)
    raqo = RAQO(schema=theirs, models=simulator_cost_models(),
                cluster=paper_cluster(100, 10), resource_planning="batched",
                backend="numpy")
    tables = gen.random_query(schema, 4, seed=3)
    plan = raqo.joint(tables).plan
    ref = Planner(CONFIG, schema)
    assert ref.compare(plan, tables[:3])["plan_gap"] == math.inf
    assert ref.compare(None, tables)["plan_gap"] == math.inf
    off = plan.__class__(**{**plan.__dict__, "resources": (101, 1)})
    assert ref.compare(off, tables)["plan_gap"] == math.inf
