"""The FastRandomized reference replays the planner's own search on the
numpy backend, reads ``inf`` for a plan that is not a plan of the query,
and its control fails the check."""
import dataclasses
import math
from pathlib import Path

import pytest

from bench import control, spec
from bench.tests import cells
from bench.traffic import generator as gen
from repro.core import schema as rschema
from repro.core.cluster import paper_cluster
from repro.core.cost_model import simulator_cost_models
from repro.core.fast_randomized import fast_randomized_plan
from repro.core.raqo import RAQO

CONFIG = cells.fast_randomized(spec.load_json(
    Path(spec.BENCH) / "configs" / "raqo-sec7-grid1k.json"))
CONFIG["cluster"]["dims"] = cells.TINY_DIMS
Planner = spec.reference(CONFIG)


def _same(a, b) -> bool:
    """The same tree, implementations and resources."""
    if a.is_leaf or b.is_leaf:
        return a.is_leaf and b.is_leaf and set(a.tables) == set(b.tables)
    return (a.impl == b.impl and
            tuple(int(v) for v in a.resources) == tuple(b.resources) and
            _same(a.left, b.left) and _same(a.right, b.right))


QUERIES = [(16, k) for k in range(4, 13)] + [(100, 100)]
# a longer search on the strict Pareto archive, in which the best plan
# comes from a mutation in most of QUERIES
SEARCH = {"iterations": 30, "population": 6, "eps": 0.0, "seed": 5}


def _served(n_tables, k, params):
    raqo = RAQO(schema=rschema.random_schema(n_tables, 0),
                models=simulator_cost_models(),
                cluster=paper_cluster(100, 10), planner="fastrandomized",
                resource_planning="batched", backend="numpy")
    tables = gen.random_query(gen.random_schema(n_tables, 0), k,
                              seed=20 + k)
    assert len(tables) == k
    if params is None:
        return tables, raqo.joint(tables).plan
    plan, _ = fast_randomized_plan(raqo.schema, tables, raqo._costing(),
                                   **params)
    return tables, plan


@pytest.mark.parametrize("params", [None, SEARCH], ids=["served", "search"])
@pytest.mark.parametrize("n_tables,k", QUERIES)
def test_replay_plans_as_the_program(n_tables, k, params):
    tables, served = _served(n_tables, k, params)
    config = dict(CONFIG, planner_params=params or {})
    ref = Planner(config, gen.random_schema(n_tables, 0))
    assert _same(served, ref.plan(tables))
    got = ref.compare(served, tables)
    assert set(got) == set(CONFIG["check"]["limits"])
    assert all(v <= 1e-12 for v in got.values()), got


def _chain(schema):
    """Tables a, b, c with join edges a-b and b-c and none a-c."""
    linked = {frozenset((e.a, e.b)) for e in schema.edges}
    for b in schema.relations:
        ns = schema.neighbors(b)
        for a in ns:
            for c in ns:
                if a != c and frozenset((a, c)) not in linked:
                    return a, b, c
    raise AssertionError("no chain of three tables")


def _tree(ref, shape, resources=(4, 2)):
    """A plan of nested pairs of table names, every join an SMJ."""
    if isinstance(shape, str):
        return ref._leaf(shape)
    l, r = (_tree(ref, s, resources) for s in shape)
    return dataclasses.replace(ref._shape(l, r), impl="SMJ",
                               resources=resources)


@pytest.mark.parametrize("fault", ["missing_table", "no_edge", "overlap",
                                   "off_grid"])
def test_an_invalid_plan_reads_infinite(fault):
    schema = gen.random_schema(16, 0)
    ref = Planner(CONFIG, schema)
    a, b, c = _chain(schema)
    tables = (a, b, c)
    shape, res = {"missing_table": ((a, b), (4, 2)),
                  "no_edge": (((a, c), b), (4, 2)),
                  "overlap": (((a, b), (b, c)), (4, 2)),
                  "off_grid": (((a, b), c), (101, 2))}[fault]
    assert ref.compare(_tree(ref, shape, res), tables)["plan_gap"] == \
        math.inf
    assert ref.compare(_tree(ref, ((a, b), c)), tables)["plan_gap"] < \
        math.inf


def test_control_fails_a_limit_on_every_seed():
    cell = cells.tiny("grid1k.recur16.closed256")
    config = cells.fast_randomized(cell.config)
    limits = config["check"]["limits"]
    for seed in (3, 4, 2**31 + 11):
        got = control.readings(config, cell.traffic, seed)
        assert got["compared"] == config["check"]["sample_queries"]
        assert got["op_gap"] > limits["op_gap"] or \
            got["plan_gap"] > limits["plan_gap"], (seed, got)
