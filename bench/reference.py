"""The plain reference: joint query + resource planning in float64 numpy.

A straightforward Selinger dynamic program over left-deep join orders
(paper §VI-C), where every candidate join costs each operator
implementation at its best resource configuration, found by an exhaustive
scan of the whole cluster grid (paper §VI-B1).  The cost surfaces are the
Hive-on-YARN simulator's SMJ and BHJ formulas, written out here from the
configuration's constants.  Nothing of the planner is imported or reused:
no cost model, plan tree, broker or backend.

``compare`` judges one served plan: it checks that the plan is a left-deep
join of exactly the query's tables along join edges, with each operator
on a configuration of the grid, re-costs it in float64, and returns

- ``plan_gap``: (re-costed plan - reference optimum) / optimum, ``inf``
  for a plan that is not a valid plan of the query;
- ``cost_gap``: the largest relative difference between a cost the plan
  reports (each operator's and the total) and its float64 re-cost.

``Planner(precision="control")`` is the control of the correctness check:
the same reference with its grid search in bfloat16 and its committed
costs in float32, the step below the float32 search and float64 commit
the configuration states.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, FrozenSet, Iterable, Optional, Sequence, Tuple

import numpy as np

GB = 1 << 30
IMPLS = ("SMJ", "BHJ")
ROW_BLOCK = 8192            # grid rows (first dimension) per numpy block
THREADS = min(8, os.cpu_count() or 1)   # grid scans run side by side


# ----------------------------- cost surfaces ------------------------------- #

class Surfaces:
    """SMJ and BHJ cost in seconds over the (num_containers, container_gb)
    grid of a configuration, in the simulator's operation order."""

    def __init__(self, config: dict):
        dims = config["cluster"]["dims"]
        if [d["name"] for d in dims] != ["num_containers", "container_gb"]:
            raise ValueError(f"unexpected cluster dims {dims}")
        self.grids = [np.arange(d["lo"], d["hi"] + 1, d["step"],
                                dtype=np.float64) for d in dims]
        self.shape = tuple(len(g) for g in self.grids)
        self.size = self.shape[0] * self.shape[1]
        c = config["cost_model"]["constants"]
        self.disk = float(c["disk_gbps"])
        self.net = float(c["net_gbps"])
        self.sort_const = float(c["sort_const"])
        self.build = float(c["build_gbps"])
        self.probe = float(c["probe_gbps"])
        self.startup = float(c["container_startup_s"])
        self.mem_frac = float(c["bhj_mem_frac"])

    def on_grid(self, res) -> bool:
        return len(res) == 2 and all(
            lo <= v <= hi and (v - lo) % st == 0
            for v, (lo, hi, st) in zip(res, self._bounds()))

    def _bounds(self):
        return [(int(g[0]), int(g[-1]), int(g[1] - g[0]) if len(g) > 1
                 else 1) for g in self.grids]

    def cost(self, impl: str, ss: float, ls: float, nc, cs, xp=np):
        """Cost of ``impl`` joining ``ss`` GB with ``ls`` GB on ``nc``
        containers of ``cs`` GB; ``nc``/``cs`` broadcast, ``math`` scalars
        give the scalar path."""
        ls = max(ls, ss)
        if impl == "SMJ":
            total = ss + ls
            shuffle = total / (self.net * nc)
            per_c = total / nc
            spill = xp.maximum(1.0, per_c / xp.maximum(cs * 0.5, 1e-3))
            sort = self.sort_const * total * math.log2(max(total * 8, 2)) \
                * spill / (self.disk * 80 * nc)
            merge = total / (self.probe * nc)
            return self.startup + shuffle + sort + merge
        if impl == "BHJ":
            broadcast = ss * nc / (self.net * nc) + ss / self.net * 0.1
            build = ss / self.build
            probe = ls / (self.probe * nc)
            out = self.startup + broadcast + build + probe
            return xp.where(ss > self.mem_frac * cs, xp.inf, out)
        raise ValueError(f"unknown join implementation {impl!r}")

    def scalar(self, impl: str, ss: float, ls: float, nc: int,
               cs: int) -> float:
        return float(self.cost(impl, ss, ls, float(nc), float(cs), xp=_Math))

    def argmin(self, impl: str, ss: float, ls: float,
               dtype=np.float64, round_bf16: bool = False
               ) -> Tuple[Optional[Tuple[int, int]], float]:
        """Exhaustive scan of the whole grid: the first configuration (in
        row-major order, containers slowest) of least cost."""
        cs = self.grids[1].astype(dtype)[None, :]
        best, best_flat = np.inf, -1
        for lo in range(0, self.shape[0], ROW_BLOCK):
            nc = self.grids[0][lo:lo + ROW_BLOCK].astype(dtype)[:, None]
            c = np.asarray(self.cost(impl, dtype(ss), dtype(ls), nc, cs),
                           dtype=dtype)
            c = np.broadcast_to(c, (nc.shape[0], cs.shape[1]))
            if round_bf16:
                c = to_bfloat16(c)
            j = int(np.argmin(c))
            v = float(c.flat[j])
            if v < best:
                best, best_flat = v, lo * self.shape[1] + j
        if best_flat < 0 or math.isinf(best):
            return None, math.inf
        i, j = divmod(best_flat, self.shape[1])
        return (int(self.grids[0][i]), int(self.grids[1][j])), best


class _Math:
    """The scalar namespace of ``Surfaces.cost``."""
    inf = math.inf

    @staticmethod
    def maximum(a, b):
        return max(a, b)

    @staticmethod
    def where(c, a, b):
        return a if c else b


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Round float32 values to the nearest bfloat16 (ties to even),
    returned as float32."""
    b = np.ascontiguousarray(x, dtype=np.float32).view(np.uint32)
    finite = np.isfinite(x)
    r = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return np.where(finite, r.view(np.float32), x).astype(np.float32)


# ------------------------------- plans ------------------------------------- #

@dataclasses.dataclass(frozen=True)
class Plan:
    """A plan tree with the fields ``compare`` reads; the planner's own
    plan nodes carry the same names."""
    tables: FrozenSet[str]
    left: Optional["Plan"] = None
    right: Optional["Plan"] = None
    impl: Optional[str] = None
    resources: Optional[Tuple[int, int]] = None
    op_cost: float = 0.0
    total_cost: float = 0.0

    @property
    def is_leaf(self) -> bool:
        return self.left is None


class Planner:
    """Selinger over left-deep orders with exhaustive per-operator grid
    search, memoized per (impl, ss, ls).  ``precision`` is ``"float64"``
    (the reference) or ``"control"`` (bfloat16 search, float32 costs)."""

    def __init__(self, config: dict, schema, precision: str = "float64"):
        if precision not in ("float64", "control"):
            raise ValueError(f"unknown precision {precision!r}")
        self.surf = Surfaces(config)
        self.schema = schema
        self.control = precision == "control"
        self._edges = {frozenset((e.a, e.b)) for e in schema.edges}
        self._sizes: Dict[FrozenSet[str], float] = {}
        self._best: Dict[Tuple[str, float, float], tuple] = {}
        self.searches = 0

    # -- sizes and edges ---------------------------------------------------- #
    def size_gb(self, tables: FrozenSet[str]) -> float:
        """Size of the join of ``tables``: rows multiply, each join edge
        inside the set applies its selectivity once, row bytes add."""
        got = self._sizes.get(tables)
        if got is None:
            rows, rb = 1.0, 0.0
            for t in sorted(tables):
                rel = self.schema.relations[t]
                rows *= rel.rows
                rb += rel.row_bytes
            for e in self.schema.edges:
                if e.a in tables and e.b in tables:
                    rows *= e.selectivity
            got = self._sizes[tables] = rows * rb / GB
        return got

    def joins(self, left: FrozenSet[str], right: FrozenSet[str]) -> bool:
        return any(frozenset((a, b)) in self._edges
                   for a in left for b in right)

    # -- one operator ------------------------------------------------------- #
    def _search(self, key: Tuple[str, float, float]):
        impl, ss, ls = key
        if not self.control:
            return self.surf.argmin(impl, ss, ls)
        res, _ = self.surf.argmin(impl, ss, ls, dtype=np.float32,
                                  round_bf16=True)
        if res is None:
            return None, math.inf
        return res, float(np.float32(self.surf.cost(
            impl, np.float32(ss), np.float32(ls), np.float32(res[0]),
            np.float32(res[1]))))

    def best_op(self, impl: str, ss: float, ls: float):
        """(resources, committed cost) of ``impl`` at its best grid point."""
        key = (impl, ss, ls)
        got = self._best.get(key)
        if got is None:
            self.searches += 1
            got = self._best[key] = self._search(key)
        return got

    def prefetch(self, queries: Iterable[Sequence[str]]) -> None:
        """Search every operator the plans of ``queries`` will cost, on
        ``THREADS`` threads (numpy releases the interpreter lock)."""
        keys = sorted({k for q in queries for k in self._keys(tuple(q))
                       if k not in self._best})
        with ThreadPoolExecutor(THREADS) as pool:
            for key, got in zip(keys, pool.map(self._search, keys)):
                self._best[key] = got
        self.searches += len(keys)

    def _keys(self, tables: Tuple[str, ...]):
        """The (impl, ss, ls) of every candidate join of ``tables``."""
        joinable = {frozenset({t}) for t in tables}
        for k in range(2, len(tables) + 1):
            for combo in itertools.combinations(tables, k):
                s = frozenset(combo)
                for t in combo:
                    right = frozenset({t})
                    if s - right in joinable and self.joins(s - right, right):
                        joinable.add(s)
                        a, b = self.size_gb(s - right), self.size_gb(right)
                        for impl in IMPLS:
                            yield impl, min(a, b), max(a, b)

    # -- the dynamic program ------------------------------------------------ #
    def plan(self, tables: Sequence[str]) -> Optional[Plan]:
        """The cheapest left-deep plan of ``tables``; None when no
        left-deep order joins them along edges."""
        tables = tuple(tables)
        add = (lambda a, b: float(np.float32(a) + np.float32(b))) \
            if self.control else (lambda a, b: a + b)
        best: Dict[FrozenSet[str], Plan] = {
            frozenset({t}): Plan(frozenset({t})) for t in tables}
        for k in range(2, len(tables) + 1):
            for combo in itertools.combinations(tables, k):
                s = frozenset(combo)
                cand = None
                for t in combo:
                    sub = best.get(s - {t})
                    right = frozenset({t})
                    if sub is None or not self.joins(sub.tables, right):
                        continue
                    a, b = self.size_gb(sub.tables), self.size_gb(right)
                    ss, ls = min(a, b), max(a, b)
                    op = None
                    for impl in IMPLS:
                        res, cost = self.best_op(impl, ss, ls)
                        if op is None or cost < op[2]:
                            op = (impl, res, cost)
                    total = add(sub.total_cost, op[2])
                    if cand is None or total < cand.total_cost:
                        cand = Plan(s, sub, best[right], op[0], op[1],
                                    op[2], total)
                if cand is not None:
                    best[s] = cand
        return best.get(frozenset(tables)) if len(tables) > 1 else \
            best[frozenset(tables)]

    def optimum(self, tables: Sequence[str]) -> float:
        p = self.plan(tables)
        return math.inf if p is None else p.total_cost

    # -- judging a served plan ---------------------------------------------- #
    def recost(self, plan, tables: Sequence[str]) -> Tuple[float, float]:
        """(float64 cost of ``plan``, largest relative gap between a cost
        it reports and its re-cost); cost ``inf`` when ``plan`` is not a
        left-deep plan of exactly ``tables`` along join edges with every
        operator on the grid."""
        want = frozenset(tables)
        seen = []
        worst = 0.0

        def walk(n) -> Tuple[FrozenSet[str], float]:
            nonlocal worst
            if n.is_leaf:
                ts = frozenset(n.tables)
                if len(ts) != 1 or not ts <= want:
                    return ts, math.inf
                seen.append(next(iter(ts)))
                return ts, 0.0
            lt, lc = walk(n.left)
            rt, rc = walk(n.right)
            if not n.right.is_leaf or lt & rt or \
                    not self.joins(lt, rt) or n.impl not in IMPLS or \
                    n.resources is None or \
                    not self.surf.on_grid(tuple(n.resources)):
                return lt | rt, math.inf
            a, b = self.size_gb(lt), self.size_gb(rt)
            nc, cs = (int(v) for v in n.resources)
            op = self.surf.scalar(n.impl, min(a, b), max(a, b), nc, cs)
            total = lc + rc + op
            worst = max(worst, _rel(n.op_cost, op), _rel(n.total_cost, total))
            return lt | rt, total

        if plan is None:
            return math.inf, math.inf
        ts, cost = walk(plan)
        if ts != want or len(seen) != len(want):
            cost = math.inf
        return cost, worst

    def compare(self, plan, tables: Sequence[str]) -> Dict[str, float]:
        """``plan_gap`` and ``cost_gap`` of one served plan (module
        docstring)."""
        opt = self.optimum(tables)
        cost, cost_gap = self.recost(plan, tables)
        if math.isinf(opt) and math.isinf(cost):
            gap = 0.0 if plan is None else math.inf
        elif math.isinf(cost) or math.isinf(opt):
            gap = math.inf
        else:
            gap = (cost - opt) / opt if opt else abs(cost)
        return {"plan_gap": gap, "cost_gap": cost_gap}


def _rel(got: float, want: float) -> float:
    if got == want:
        return 0.0
    if not (math.isfinite(got) and math.isfinite(want)):
        return math.inf
    return abs(got - want) / max(abs(want), 1e-300)
