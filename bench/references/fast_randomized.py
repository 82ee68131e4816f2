"""The plain reference of the FastRandomized planner, in float64 numpy.

A replay of the fast randomized multi-objective query planner of Trummer
& Koch (SIGMOD'16) with the mutations of Steinbrunn et al., run as the
configuration's ``planner_params`` set it (``iterations``,
``population``, ``eps``, ``seed``, ``dollars_per_gb_hour``; the defaults
below are the planner's own):

- a population of random bushy plans: two forest members drawn at a
  time, joined when a join edge links them;
- each round, one mutation per plan: a join drawn from the plan's joins
  in pre-order, then its kind, commute, associativity or exchange; the
  ancestors of the mutated join are costed anew;
- a hill-climb step on time: a mutant replaces its plan where it is
  faster;
- a (1+eps) Pareto archive over (time s, money $) of every plan seen;
- the archive's best-time plan at the end.

Every join is the better of SMJ and BHJ at its exhaustive float64 grid
optimum (``bench.reference.Surfaces``); a query's draws come from its
own ``random.Random(seed)``.  The joins a stage of the search costs
follow from the draws and the schema alone, so each stage's grid scans
run on ``THREADS`` threads before the stage is costed.  Nothing of the
planner is imported.

``compare`` judges one served plan and returns

- ``plan_gap``: (float64 re-cost of the plan - the replay's best cost) /
  the replay's best cost; ``inf`` unless the plan is a bushy tree of
  exactly the query's tables whose every join has disjoint children
  linked by a join edge, an implementation of SMJ or BHJ and resources
  on the grid;
- ``cost_gap``: the largest relative gap between a cost the plan reports
  (an operator's, a subtree's total time or total money) and its float64
  re-cost;
- ``op_gap``: the largest, over the plan's joins, of (re-cost of the
  join at its implementation and resources - least float64 cost of both
  implementations over the whole grid at the join's inputs) / that least
  cost: the grid search of every operator, whichever path the search
  took.

``Planner(precision="control")`` is the control: the same replay with
its grid search in bfloat16 and its costs in float32.
"""
from __future__ import annotations

import dataclasses
import math
import random
from concurrent.futures import ThreadPoolExecutor
from typing import (Dict, FrozenSet, Iterable, List, Optional, Sequence,
                    Tuple)

import numpy as np

from bench.reference import GB, IMPLS, THREADS, Surfaces, _rel
from bench.reference import Planner as Selinger

DEFAULTS = {"iterations": 10, "population": 4, "eps": 0.05, "seed": 0,
            "dollars_per_gb_hour": 0.05}
KINDS = ("commute", "assoc", "exchange")
BUSHY_GUARD = 10_000        # draws before a random plan is given up


@dataclasses.dataclass(frozen=True)
class Node:
    """A plan tree node; a join with ``impl`` None is not costed yet."""
    tables: FrozenSet[str]
    rows: float
    row_bytes: float
    left: Optional["Node"] = None
    right: Optional["Node"] = None
    impl: Optional[str] = None
    resources: Optional[Tuple[int, int]] = None
    op_cost: float = 0.0
    total_cost: float = 0.0
    total_money: float = 0.0

    @property
    def is_leaf(self) -> bool:
        return self.left is None

    @property
    def size_gb(self) -> float:
        return self.rows * self.row_bytes / GB


def _dominates(a: Tuple[float, float], b: Tuple[float, float],
               eps: float) -> bool:
    """``a`` (1+eps)-dominates ``b``."""
    return all(x <= (1 + eps) * y for x, y in zip(a, b)) and a != b


def _gap(got: float, best: float) -> float:
    if math.isinf(got) or math.isinf(best):
        return 0.0 if got == best else math.inf
    return (got - best) / best if best else abs(got)


class Planner:
    """The FastRandomized replay, memoized per query and per (impl, ss,
    ls) grid scan.  ``precision`` is ``"float64"`` (the reference) or
    ``"control"`` (bfloat16 search, float32 costs)."""

    def __init__(self, config: dict, schema, precision: str = "float64"):
        if precision not in ("float64", "control"):
            raise ValueError(f"unknown precision {precision!r}")
        self.surf = Surfaces(config)
        self.schema = schema
        self.control = precision == "control"
        self.params = dict(DEFAULTS, **config.get("planner_params", {}))
        self._sel = {frozenset((e.a, e.b)): e.selectivity
                     for e in schema.edges}
        self._best: Dict[Tuple[str, float, float], tuple] = {}
        self._plans: Dict[Tuple[str, ...], Optional[Node]] = {}
        self.searches = 0

    # -- float arithmetic of the stated precision --------------------------- #
    def _f(self, x: float) -> float:
        return float(np.float32(x)) if self.control else x

    # -- trees -------------------------------------------------------------- #
    def _leaf(self, table: str) -> Node:
        rel = self.schema.relations[table]
        return Node(frozenset({table}), float(rel.rows),
                    float(rel.row_bytes))

    def _linked(self, l: Node, r: Node) -> bool:
        return any(frozenset((a, b)) in self._sel
                   for a in l.tables for b in r.tables)

    def _shape(self, l: Node, r: Node) -> Node:
        """The join of ``l`` and ``r``, not costed: rows multiply by the
        selectivity of every join edge between them, row bytes add."""
        sel = 1.0
        for a in l.tables:
            for b in r.tables:
                s = self._sel.get(frozenset((a, b)))
                if s is not None:
                    sel *= s
        return Node(l.tables | r.tables, l.rows * r.rows * sel,
                    l.row_bytes + r.row_bytes, l, r)

    def _keys(self, node: Node):
        """The grid scans that costing ``node`` needs."""
        if node.is_leaf or node.impl is not None:
            return
        yield from self._keys(node.left)
        yield from self._keys(node.right)
        a, b = node.left.size_gb, node.right.size_gb
        for impl in IMPLS:
            yield impl, min(a, b), max(a, b)

    def _cost(self, node: Node) -> Node:
        """``node`` with every join that is not costed yet costed: the
        better implementation at its best grid point (SMJ on a tie)."""
        if node.is_leaf or node.impl is not None:
            return node
        l, r = self._cost(node.left), self._cost(node.right)
        a, b = l.size_gb, r.size_gb
        ss, ls = min(a, b), max(a, b)
        best = None
        for impl in IMPLS:
            res, cost = self._best[(impl, ss, ls)]
            if best is None or cost < best[2]:
                best = (impl, res, cost)
        impl, res, cost = best
        money = self._money(impl, ss, ls, res)
        return dataclasses.replace(
            self._shape(l, r), impl=impl, resources=res,
            op_cost=self._f(cost),
            total_cost=self._f(self._f(l.total_cost + r.total_cost) + cost),
            total_money=self._f(self._f(l.total_money + r.total_money)
                                + money))

    def _money(self, impl: str, ss: float, ls: float, res) -> float:
        """Serverless billing of the join: container-GB-hours times the
        configuration's price."""
        if res is None:
            return math.inf
        nc, cs = res
        t = self.surf.scalar(impl, ss, ls, nc, cs)
        if not math.isfinite(t):
            return math.inf
        return self._f(t / 3600.0 * cs * nc *
                       self.params["dollars_per_gb_hour"])

    # -- grid scans --------------------------------------------------------- #
    # (resources, cost) of one (impl, ss, ls) at the stated precision: the
    # Selinger reference's exhaustive scan, which reads ``surf``, ``control``
    _search = Selinger._search

    def _ensure(self, keys: Iterable[Tuple[str, float, float]]) -> None:
        """Scan the grid for every key not scanned yet, on ``THREADS``
        threads (numpy releases the interpreter lock)."""
        todo = sorted(set(keys) - self._best.keys())
        if not todo:
            return
        with ThreadPoolExecutor(THREADS) as pool:
            for key, got in zip(todo, pool.map(self._search, todo)):
                self._best[key] = got
        self.searches += len(todo)

    # -- the randomized search ---------------------------------------------- #
    def _random_bushy(self, tables: Sequence[str],
                      rng: random.Random) -> Optional[Node]:
        forest = [self._leaf(t) for t in tables]
        for _ in range(BUSHY_GUARD):
            if len(forest) == 1:
                return forest[0]
            i, j = rng.sample(range(len(forest)), 2)
            if not self._linked(forest[i], forest[j]):
                continue
            a = forest.pop(max(i, j))
            b = forest.pop(min(i, j))
            forest.append(self._shape(a, b))
        return forest[0] if len(forest) == 1 else None

    def _mutant(self, plan: Node, rng: random.Random) -> Optional[Node]:
        """Draw one mutation of ``plan`` and apply it, not costed; None
        where it does not apply."""
        joins: List[Node] = []

        def preorder(n: Node) -> None:
            if not n.is_leaf:
                joins.append(n)
                preorder(n.left)
                preorder(n.right)
        preorder(plan)
        if not joins:
            return None
        node = rng.choice(joins)
        kind = rng.choice(KINDS)
        repl = None
        if kind == "commute":
            repl = self._shape(node.right, node.left)
        elif not node.left.is_leaf:
            a, b, c = node.left.left, node.left.right, node.right
            if kind == "assoc" and self._linked(b, c):
                # (A |><| B) |><| C  ->  A |><| (B |><| C)
                bc = self._shape(b, c)
                if self._linked(a, bc):
                    repl = self._shape(a, bc)
            elif kind == "exchange" and self._linked(a, c):
                # (A |><| B) |><| C  ->  (A |><| C) |><| B
                ac = self._shape(a, c)
                if self._linked(ac, b):
                    repl = self._shape(ac, b)
        if repl is None:
            return None
        return self._replace(plan, node, repl)

    def _replace(self, n: Node, target: Node, repl: Node) -> Node:
        """``n`` with ``target`` swapped for ``repl``; every ancestor of
        ``target`` becomes a join to cost anew, the rest is kept."""
        if n is target:
            return repl
        if n.is_leaf:
            return n
        l = self._replace(n.left, target, repl)
        r = self._replace(n.right, target, repl)
        if l is n.left and r is n.right:
            return n
        return self._shape(l, r)

    def _offer(self, archive: List[Node], p: Node) -> None:
        v = (p.total_cost, p.total_money)
        eps = self.params["eps"]
        if any(_dominates((q.total_cost, q.total_money), v, eps)
               for q in archive):
            return
        archive[:] = [q for q in archive if not _dominates(
            v, (q.total_cost, q.total_money), 0.0)]
        archive.append(p)

    def _replay(self, tables: Tuple[str, ...]):
        """The search of one query, as a generator: it yields the trees
        that the next stage costs, and returns the best-time plan."""
        p = self.params
        rng = random.Random(p["seed"])
        seeds: List[Node] = []
        for _ in range(p["population"] * 3):
            s = self._random_bushy(tables, rng)
            if s is not None:
                seeds.append(s)
            if len(seeds) >= p["population"]:
                break
        yield seeds
        archive: List[Node] = []
        pop = [self._cost(s) for s in seeds]
        for plan in pop:
            self._offer(archive, plan)
        for _ in range(p["iterations"] if pop else 0):
            mutants = [self._mutant(plan, rng) for plan in pop]
            yield [m for m in mutants if m is not None]
            nxt = []
            for plan, m in zip(pop, mutants):
                if m is None:
                    nxt.append(plan)
                    continue
                q = self._cost(m)
                self._offer(archive, q)
                nxt.append(q if q.total_cost < plan.total_cost else plan)
            pop = nxt
        return min(archive, key=lambda q: q.total_cost) if archive else None

    def prefetch(self, queries: Iterable[Sequence[str]]) -> None:
        """Replay the searches of ``queries`` side by side: each stage's
        grid scans of every query run together on the threads."""
        runs = {}
        for q in queries:
            q = tuple(q)
            if q not in self._plans and q not in runs:
                runs[q] = self._replay(q)
        stage = {q: next(g) for q, g in runs.items()}
        while stage:
            self._ensure(k for trees in stage.values() for t in trees
                         for k in self._keys(t))
            nxt = {}
            for q, trees in stage.items():
                try:
                    nxt[q] = runs[q].send(None)
                except StopIteration as done:
                    self._plans[q] = done.value
            stage = nxt

    def plan(self, tables: Sequence[str]) -> Optional[Node]:
        """The replay's best-time plan of ``tables``; None when no random
        plan joins them along edges."""
        self.prefetch([tables])
        return self._plans[tuple(tables)]

    # -- judging a served plan ---------------------------------------------- #
    def _recost(self, plan, want: FrozenSet[str]):
        """(the float64 re-costed tree of ``plan``, or None where it is
        not a valid plan of ``want``; the largest cost gap seen)."""
        seen: List[str] = []
        worst = 0.0

        def walk(n) -> Optional[Node]:
            nonlocal worst
            if n.is_leaf:
                ts = frozenset(n.tables)
                if len(ts) != 1 or not ts <= want:
                    return None
                seen.append(next(iter(ts)))
                return self._leaf(next(iter(ts)))
            if n.right is None:
                return None
            l, r = walk(n.left), walk(n.right)
            if l is None or r is None or l.tables & r.tables or \
                    not self._linked(l, r) or n.impl not in IMPLS or \
                    n.resources is None or \
                    not self.surf.on_grid(tuple(n.resources)):
                return None
            a, b = l.size_gb, r.size_gb
            ss, ls = min(a, b), max(a, b)
            nc, cs = (int(v) for v in n.resources)
            op = self.surf.scalar(n.impl, ss, ls, nc, cs)
            got = dataclasses.replace(
                self._shape(l, r), impl=n.impl, resources=(nc, cs),
                op_cost=op, total_cost=l.total_cost + r.total_cost + op,
                total_money=l.total_money + r.total_money +
                self._money(n.impl, ss, ls, (nc, cs)))
            worst = max(worst, _rel(n.op_cost, got.op_cost),
                        _rel(n.total_cost, got.total_cost),
                        _rel(n.total_money, got.total_money))
            return got

        got = walk(plan)
        if got is None or got.tables != want or len(seen) != len(want):
            return None, worst
        return got, worst

    def compare(self, plan, tables: Sequence[str]) -> Dict[str, float]:
        """``plan_gap``, ``cost_gap`` and ``op_gap`` of one served plan
        (module docstring)."""
        best = self.plan(tables)
        opt = math.inf if best is None else best.total_cost
        if plan is None:
            return {"plan_gap": 0.0 if best is None else math.inf,
                    "cost_gap": math.inf, "op_gap": math.inf}
        got, cost_gap = self._recost(plan, frozenset(tables))
        if got is None:
            return {"plan_gap": math.inf, "cost_gap": cost_gap,
                    "op_gap": math.inf}
        ops: List[Node] = []

        def joins(n: Node) -> None:
            if not n.is_leaf:
                ops.append(n)
                joins(n.left)
                joins(n.right)
        joins(got)
        sides = [(n, min(a, b), max(a, b)) for n in ops
                 for a, b in [(n.left.size_gb, n.right.size_gb)]]
        self._ensure((impl, ss, ls) for _, ss, ls in sides
                     for impl in IMPLS)
        op_gap = max((_gap(n.op_cost, min(self._best[(impl, ss, ls)][1]
                                          for impl in IMPLS))
                      for n, ss, ls in sides), default=0.0)
        return {"plan_gap": _gap(got.total_cost, opt), "cost_gap": cost_gap,
                "op_gap": op_gap}
