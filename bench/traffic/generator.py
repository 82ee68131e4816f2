"""The benchmark's traffic generator: schemas, queries and arrivals.

One general generator reads every traffic mix (``bench/traffic/<mix>.json``).
Its schema and query code is a copy of the planner's own seeded generators
(``random_schema`` / ``random_query`` of the paper's §VII set-up), kept
here so that the yardstick does not move when the program's copy does;
``bench/tests/test_generator.py`` holds the two copies equal at today's
seeds.  ``stream`` departs from the planner's arrival traces on purpose:
it fixes the amount of work per seed, as set out below.

A mix fixes the schema, the query sizes, the tenants and the arrival
process.  A run's window stream is drawn from ``--seed``; the warm-up
stream from the mix's fixed ``warmup_seed``.  To keep the work of a window
the same from seed to seed, every seed gets the same multiset of query
sizes (each size once per block, blocks shuffled by the seed) and, in an
open loop, the same multiset of inter-arrival gaps (drawn once from the
mix's ``gap_seed``, permuted by the seed).  The seed changes which tables
a query joins and the order of sizes and gaps, not their amount.

Where a window holds few queries, which tables they join still changes
its work (the searches a query needs follow its join graph).  A mix that
names ``fixed_set`` then draws its window's queries once, from that
seed, and the run's seed only permutes them within blocks
(``shuffled_in_blocks``): every seed serves the same queries, in another
order.
"""
from __future__ import annotations

import dataclasses
import random
from typing import Dict, List, Tuple

import numpy as np


# ----------------------------- schemas ------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Relation:
    name: str
    rows: int
    row_bytes: int


@dataclasses.dataclass(frozen=True)
class JoinEdge:
    a: str
    b: str
    selectivity: float          # |a join b| = rows(a) * rows(b) * sel


@dataclasses.dataclass
class Schema:
    relations: Dict[str, Relation]
    edges: List[JoinEdge]

    def __post_init__(self):
        adj: Dict[str, List[str]] = {t: [] for t in self.relations}
        for e in self.edges:
            adj[e.a].append(e.b)
            adj[e.b].append(e.a)
        self._adj = adj

    def neighbors(self, t: str) -> List[str]:
        return self._adj[t]


def random_schema(n_tables: int, seed: int = 0,
                  extra_edge_frac: float = 0.3) -> Schema:
    """Paper §VII: tables of 100K-2M rows of 100-200 bytes, a random
    spanning tree of join edges plus ``extra_edge_frac * n_tables`` more,
    each with TPC-H-like FK selectivity 1 / rows of the larger side."""
    rng = random.Random(seed)
    rel = {}
    for i in range(n_tables):
        name = f"t{i}"
        rel[name] = Relation(name, rng.randint(100_000, 2_000_000),
                             rng.randint(100, 200))
    names = list(rel)
    edges = []
    seen = set()
    for i in range(1, n_tables):
        j = rng.randrange(i)
        a, b = names[i], names[j]
        edges.append(JoinEdge(a, b, 1.0 / max(rel[a].rows, rel[b].rows)))
        seen.add(frozenset((a, b)))
    n_extra = int(extra_edge_frac * n_tables)
    while n_extra > 0:
        a, b = rng.sample(names, 2)
        if frozenset((a, b)) in seen:
            continue
        seen.add(frozenset((a, b)))
        edges.append(JoinEdge(a, b, 1.0 / max(rel[a].rows, rel[b].rows)))
        n_extra -= 1
    return Schema(rel, edges)


def random_query(schema: Schema, n_relations: int, seed: int = 0
                 ) -> Tuple[str, ...]:
    """A connected random subset of ``n_relations`` tables."""
    rng = random.Random(seed)
    names = list(schema.relations)
    chosen = [rng.choice(names)]
    while len(chosen) < n_relations:
        cands = sorted({n for t in chosen for n in schema.neighbors(t)
                        if n not in chosen})
        if not cands:
            break
        chosen.append(rng.choice(cands))
    return tuple(chosen)


# ----------------------------- streams ------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Query:
    """One query of a stream: its due time in seconds after the stream
    starts (0 in a closed loop), the tenant and the tables to join."""
    t: float
    tenant: int
    tables: Tuple[str, ...]


def balanced_sizes(n: int, rng: np.random.Generator,
                   tables_range: Tuple[int, int]) -> np.ndarray:
    """``n`` query sizes in which every size of ``tables_range`` appears
    once per block, in a seeded order within each block."""
    lo, hi = tables_range
    block = np.arange(lo, hi + 1)
    reps = -(-n // len(block))
    return np.concatenate([rng.permutation(block)
                           for _ in range(reps)])[:n]


def stream(schema: Schema, n: int, seed: int, *, tenants: int,
           tables_range: Tuple[int, int], rate: float = 0.0,
           gap_seed: int = 0) -> List[Query]:
    """The benchmark's stream of ``n`` queries for ``seed``: balanced
    sizes, uniform tenants, and, where ``rate`` > 0, due times from a
    fixed multiset of exponential gaps (``gap_seed``) in a seeded order."""
    rng = np.random.default_rng(seed)
    sizes = balanced_sizes(n, rng, tables_range)
    tens = rng.integers(0, tenants, size=n)
    qseeds = rng.integers(0, 2**31 - 1, size=n)
    if rate > 0:
        gaps = np.random.default_rng(gap_seed).exponential(1.0 / rate,
                                                           size=n)
        times = np.cumsum(rng.permutation(gaps))
    else:
        times = np.zeros(n)
    return [Query(float(t), int(k), random_query(schema, int(z), int(s)))
            for t, k, z, s in zip(times, tens, sizes, qseeds)]


def shuffled_in_blocks(items: List[Query], block: int,
                       seed: int) -> List[Query]:
    """``items`` with each run of ``block`` consecutive items permuted by
    ``seed``: every prefix of whole blocks holds the same items, and so
    the same work, for every seed."""
    rng = np.random.default_rng(seed)
    out: List[Query] = []
    for i in range(0, len(items), block):
        part = items[i:i + block]
        out += [part[int(j)] for j in rng.permutation(len(part))]
    return out


def build_schema(spec: dict) -> Schema:
    """The schema a mix names: ``{"kind": "random", "n_tables", "seed",
    "extra_edge_frac"}``."""
    if spec["kind"] != "random":
        raise ValueError(f"unknown schema kind {spec['kind']!r}")
    return random_schema(int(spec["n_tables"]), int(spec["seed"]),
                         float(spec.get("extra_edge_frac", 0.3)))
