"""The benchmark's copied generators give the planner's schemas and
queries; its stream keeps the work of a window fixed across seeds."""
import pytest

from bench.traffic import generator as gen
from repro.core import schema as rschema


@pytest.mark.parametrize("n_tables,seed", [(16, 0), (100, 0), (40, 7)])
def test_random_schema_matches_the_planners(n_tables, seed):
    mine = gen.random_schema(n_tables, seed)
    theirs = rschema.random_schema(n_tables, seed)
    assert {n: (r.rows, r.row_bytes) for n, r in mine.relations.items()} \
        == {n: (r.rows, r.row_bytes) for n, r in theirs.relations.items()}
    assert [(e.a, e.b, e.selectivity) for e in mine.edges] == \
        [(e.a, e.b, e.selectivity) for e in theirs.edges]


@pytest.mark.parametrize("n_tables", [16, 100])
def test_random_query_matches_the_planners(n_tables):
    mine = gen.random_schema(n_tables, 0)
    theirs = rschema.random_schema(n_tables, 0)
    for k in range(2, 7):
        for seed in range(20):
            assert gen.random_query(mine, k, seed) == \
                rschema.random_query(theirs, k, seed)


def test_stream_keeps_sizes_and_gaps_across_seeds():
    schema = gen.random_schema(100, 0)
    a = gen.stream(schema, 100, 1, tenants=64, tables_range=(2, 6),
                   rate=40.0, gap_seed=7)
    b = gen.stream(schema, 100, 2**31 + 5, tenants=64, tables_range=(2, 6),
                   rate=40.0, gap_seed=7)
    assert sorted(len(q.tables) for q in a) == \
        sorted(len(q.tables) for q in b) == sorted([2, 3, 4, 5, 6] * 20)
    assert a[-1].t == pytest.approx(b[-1].t)
    assert [q.tables for q in a] != [q.tables for q in b]
    assert gen.stream(schema, 100, 1, tenants=64, tables_range=(2, 6),
                      rate=40.0, gap_seed=7) == a


@pytest.mark.parametrize("rate", [0.0, 40.0])
def test_a_fixed_set_serves_every_seed_the_same_queries(rate):
    from bench.run_cell import window_queries
    schema = gen.random_schema(100, 0)
    traffic = {"arrival": {"loop": "open" if rate else "closed",
                           "process": "poisson", "gap_seed": 7},
               "tenants": 64, "tables_range": [2, 6],
               "fixed_set": {"seed": 2, "block": 32}}
    a = window_queries(schema, 100, 1, traffic, rate)
    b = window_queries(schema, 100, 2**31 + 5, traffic, rate)
    assert [q.tables for q in a] != [q.tables for q in b]
    for i in range(0, 100, 32):
        assert sorted((q.tenant, q.tables) for q in a[i:i + 32]) == \
            sorted((q.tenant, q.tables) for q in b[i:i + 32])
    assert [q.t for q in a] == [q.t for q in b] == sorted(q.t for q in a)
    assert window_queries(schema, 100, 1, traffic, rate) == a


def test_an_unknown_arrival_process_is_refused():
    from bench.run_cell import queries
    traffic = {"arrival": {"loop": "open", "process": "bursty",
                           "gap_seed": 7},
               "tenants": 64, "tables_range": [2, 6]}
    with pytest.raises(ValueError, match="arrival process 'bursty'"):
        queries(gen.random_schema(16, 0), 10, 1, traffic, rate=10.0)
