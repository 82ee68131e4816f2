"""Cost models f(d, r) -> C (paper §VI-A).

Three layers:

1. ``PAPER_SMJ`` / ``PAPER_BHJ``: the paper's *published* linear-regression
   coefficients over the feature vector [ss, ss^2, cs, cs^2, nc, nc^2,
   cs*nc] — kept verbatim as the profiled-Hive ground truth.

2. ``HiveSimulator``: an analytic simulator of the Hive/YARN join operators
   with the qualitative structure reported in §III (BHJ loves memory, OOMs
   below ss/cs thresholds; SMJ loves parallelism).  It generates the
   "profile runs" that the paper collects from a physical cluster — we use
   it to (re)train regression models and decision trees, reproducing the
   switch-point *structure* of Figs 3-7, 9.

3. ``RegressionModel.fit``: ordinary least squares (numpy lstsq) over the
   same feature vector — the paper's training procedure.

Every model exposes two evaluation paths with bit-identical arithmetic:

* ``cost(ss, cs, nc, ls)``   — one configuration, scalar floats.
* ``cost_grid(ss, ls, configs, xp=np)`` — an ``(N, 2)`` array of
  ``(nc, cs)`` configurations evaluated in a single vectorized call.
  Both paths share the same elementwise expression (same operation
  order), so a batched argmin over the grid selects exactly the
  configuration the scalar loop would — the property the planners rely on
  when they swap the inner resource-planning loop for an array program.

The ``xp`` parameter selects the array namespace (numpy by default,
``jax.numpy`` for the jitted ``JaxPlanBackend``); with ``xp=jnp`` the
grid expression is traceable, so ``ss``/``ls`` may be traced scalars and
the whole cost surface fuses into the search program
(repro.core.planning_backend).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Sequence, Tuple

import numpy as np

from repro.core.cluster import as_configs

FEATURES = ("ss", "ss2", "cs", "cs2", "nc", "nc2", "cs_nc")


def feature_vector(ss: float, cs: float, nc: float) -> np.ndarray:
    return np.array([ss, ss * ss, cs, cs * cs, nc, nc * nc, cs * nc],
                    dtype=np.float64)


def _split_configs(configs, xp=np) -> Tuple[np.ndarray, np.ndarray]:
    """(N, 2) array of (nc, cs) resource configurations -> float columns."""
    a = as_configs(configs, xp)
    if a.ndim != 2 or a.shape[1] != 2:
        raise ValueError(f"expected (N, 2) (nc, cs) configs, got {a.shape}")
    if xp is np:
        a = a.astype(np.float64)
        return a[:, 0], a[:, 1]
    # jax: weak-promote the integer columns to the default float dtype
    one = xp.asarray(1.0)
    return a[:, 0] * one, a[:, 1] * one


def _oom_mask(oom_fn, ss, cs, xp=np):
    """Vectorize an (ss, cs) -> bool OOM predicate over a cs column.  The
    broker's stacked many-request path passes ``ss`` as a (Q, 1) column
    broadcasting against the (N,) cs column, so the mask shape is the
    broadcast of both (identical values to Q scalar-ss evaluations)."""
    if xp is not np:            # traced path: predicate must be elementwise
        return oom_fn(ss, cs)
    shape = np.broadcast_shapes(np.shape(ss), np.shape(cs))
    try:
        m = oom_fn(ss, cs)
        return np.broadcast_to(np.asarray(m, dtype=bool), shape)
    except (TypeError, ValueError):          # non-numpy-compatible predicate
        cs_col = np.ravel(cs)
        if np.size(ss) == 1:                 # per-request scalar ss
            s = float(np.reshape(np.asarray(ss), ()))
            return np.broadcast_to(
                np.array([bool(oom_fn(s, float(c))) for c in cs_col]),
                shape)
        # stacked (Q, 1) ss column: one predicate row per request
        rows = [np.array([bool(oom_fn(float(s), float(c)))
                          for c in cs_col]) for s in np.ravel(ss)]
        return np.broadcast_to(np.stack(rows), shape)


def _sort_log2(total, xp=np):
    """log2 term of the external-sort cost; scalar ``total`` keeps the
    exact math.log2 arithmetic of the scalar path, traced ``total`` uses
    the xp equivalent."""
    if isinstance(total, (int, float)):
        return math.log2(max(total * 8, 2))
    return xp.log2(xp.maximum(total * 8.0, 2.0))


# --- the paper's published coefficients (§VI-A), verbatim ------------------- #
PAPER_SMJ = np.array([1.62643613e+01, 9.68774888e-01, 1.33866542e-02,
                      1.60639851e-01, -7.82618920e-03, -3.91309460e-01,
                      1.10387975e-01])
PAPER_BHJ = np.array([1.00739509e+04, -6.72184592e+02, -1.37392901e+01,
                      -1.64871481e+02, 2.44721676e-02, 1.22360838e+00,
                      -1.37319484e+02])


@dataclasses.dataclass
class RegressionModel:
    """Linear model over FEATURES; cost in seconds."""
    name: str
    coef: np.ndarray
    oom_fn: Callable[[float, float], bool] | None = None   # (ss, cs) -> OOM?

    # Linear regression without intercept (the paper's form) extrapolates
    # negative outside the profiled region — both for the paper's published
    # coefficients and for refits.  Clamp at a small positive floor so the
    # planners never chase negative-cost corners.
    floor: float = 1e-3

    def _eval(self, ss, cs, nc):
        # Shared by cost/cost_grid: one fixed elementwise operation order so
        # scalar and batched evaluation agree bit-for-bit.
        c = self.coef
        return (c[0] * ss + c[1] * (ss * ss) + c[2] * cs + c[3] * (cs * cs)
                + c[4] * nc + c[5] * (nc * nc) + c[6] * (cs * nc))

    def cost(self, ss: float, cs: float, nc: float, ls: float = 0.0) -> float:
        # NOTE: the paper's feature vector contains only the *smaller* input
        # size — the large side (ls) is not a feature; accepted and ignored.
        if self.oom_fn is not None and self.oom_fn(ss, cs):
            return math.inf
        return max(float(self._eval(ss, cs, nc)), self.floor)

    def cost_grid(self, ss, ls, configs, xp=np):
        """Vectorized ``cost`` over an (N, 2) array of (nc, cs) configs."""
        nc, cs = _split_configs(configs, xp)
        out = xp.maximum(self._eval(ss, cs, nc), self.floor)
        if self.oom_fn is not None:
            out = xp.where(_oom_mask(self.oom_fn, ss, cs, xp), xp.inf, out)
        return out

    @classmethod
    def fit(cls, name: str, xs: Sequence[Tuple[float, float, float]],
            ys: Sequence[float], oom_fn=None) -> "RegressionModel":
        A = np.stack([feature_vector(*x) for x in xs])
        coef, *_ = np.linalg.lstsq(A, np.asarray(ys, np.float64), rcond=None)
        return cls(name, coef, oom_fn)


def paper_models() -> Dict[str, RegressionModel]:
    """The published Hive models.  BHJ OOMs when the hash side exceeds a
    fraction of container memory (Hive default-settings behaviour, §III-A)."""
    return {
        "SMJ": RegressionModel("SMJ", PAPER_SMJ),
        "BHJ": RegressionModel("BHJ", PAPER_BHJ,
                               oom_fn=lambda ss, cs: ss > 0.7 * cs),
    }


# --------------------------------------------------------------------------- #
# Analytic operator simulator (the "profiled system").
# Units: ss/ls = relation sizes in GB, cs = container GB, nc = containers.
# --------------------------------------------------------------------------- #

@dataclasses.dataclass(frozen=True)
class HiveSimulator:
    """Analytic Hive-on-YARN join timing with the paper's §III structure.

    SMJ: shuffle both sides across nc containers, external sort (spill
    pressure shrinks with container memory), merge.
    BHJ: broadcast small side to every container (cost grows with nc),
    build in-memory hash (fails if it does not fit), stream big side.
    """
    disk_gbps: float = 0.10        # per-container effective scan bandwidth
    net_gbps: float = 0.125        # per-container shuffle bandwidth
    sort_const: float = 0.35
    build_gbps: float = 0.40       # hash build rate
    probe_gbps: float = 0.45
    container_startup_s: float = 1.2
    bhj_mem_frac: float = 0.7      # usable fraction of container memory

    def smj(self, ss: float, ls: float, cs: float, nc: float) -> float:
        total = ss + ls
        shuffle = total / (self.net_gbps * nc)
        # external sort: spill factor grows when per-container data >> memory
        per_c = total / nc
        spill = max(1.0, per_c / max(cs * 0.5, 1e-3))
        sort = self.sort_const * total * math.log2(max(total * 8, 2)) \
            * spill / (self.disk_gbps * 80 * nc)
        merge = total / (self.probe_gbps * nc)
        return self.container_startup_s + shuffle + sort + merge

    def bhj(self, ss: float, ls: float, cs: float, nc: float) -> float:
        if ss > self.bhj_mem_frac * cs:
            return math.inf                       # OOM (paper Fig 3a)
        broadcast = ss * nc / (self.net_gbps * nc) + ss / self.net_gbps * 0.1
        build = ss / self.build_gbps              # replicated on every container
        probe = ls / (self.probe_gbps * nc)
        return self.container_startup_s + broadcast + build + probe

    def cost(self, impl: str, ss: float, ls: float, cs: float,
             nc: float) -> float:
        return self.smj(ss, ls, cs, nc) if impl == "SMJ" else \
            self.bhj(ss, ls, cs, nc)

    # -- vectorized twins: identical expressions over (nc, cs) columns ------ #

    def smj_grid(self, ss, ls, cs, nc, xp=np):
        total = ss + ls
        shuffle = total / (self.net_gbps * nc)
        per_c = total / nc
        spill = xp.maximum(1.0, per_c / xp.maximum(cs * 0.5, 1e-3))
        sort = self.sort_const * total * _sort_log2(total, xp) \
            * spill / (self.disk_gbps * 80 * nc)
        merge = total / (self.probe_gbps * nc)
        return self.container_startup_s + shuffle + sort + merge

    def bhj_grid(self, ss, ls, cs, nc, xp=np):
        broadcast = ss * nc / (self.net_gbps * nc) + ss / self.net_gbps * 0.1
        build = ss / self.build_gbps
        probe = ls / (self.probe_gbps * nc)
        out = self.container_startup_s + broadcast + build + probe
        return xp.where(ss > self.bhj_mem_frac * cs, xp.inf, out)

    def cost_grid(self, impl: str, ss, ls, cs, nc, xp=np):
        return self.smj_grid(ss, ls, cs, nc, xp) if impl == "SMJ" else \
            self.bhj_grid(ss, ls, cs, nc, xp)

    # "profile runs" -> training data for regression / decision trees
    def profile(self, ss_grid, cs_grid, nc_grid, ls: float = 74.0):
        xs, y_smj, y_bhj = [], [], []
        for ss in ss_grid:
            for cs in cs_grid:
                for nc in nc_grid:
                    xs.append((ss, cs, nc))
                    y_smj.append(self.smj(ss, ls, cs, nc))
                    b = self.bhj(ss, ls, cs, nc)
                    y_bhj.append(b if math.isfinite(b) else 1e6)
        return xs, y_smj, y_bhj


def simulator_models(sim: HiveSimulator | None = None,
                     ls: float = 74.0) -> Dict[str, RegressionModel]:
    """Regression models trained on simulator profile runs (the paper's
    §VI-A procedure, with the simulator standing in for the cluster)."""
    sim = sim or HiveSimulator()
    # the paper's profiled regime (§III: 10-40 containers, 1-10 GB).  The
    # quadratic feature vector CANNOT fit the 1/nc-shaped cost over a 1-100
    # container grid (mean rel. error >5x — an honest limitation of the
    # published model form); inside the profiled regime it interpolates to
    # ~30%.  The planners use SimulatorCostModel for wide grids.
    ss_grid = np.linspace(0.1, 9.0, 14)
    cs_grid = np.arange(1, 11, 1.0)
    nc_grid = np.arange(10, 41, 2.0)
    xs, y_smj, y_bhj = sim.profile(ss_grid, cs_grid, nc_grid, ls=ls)
    finite = [i for i, y in enumerate(y_bhj) if y < 1e5]
    return {
        "SMJ": RegressionModel.fit("SMJ", xs, y_smj),
        "BHJ": RegressionModel.fit(
            "BHJ", [xs[i] for i in finite], [y_bhj[i] for i in finite],
            oom_fn=lambda ss, cs: ss > sim.bhj_mem_frac * cs),
    }


@dataclasses.dataclass
class SimulatorCostModel:
    """Analytic operator model usable directly by the planners (positive,
    1/nc-shaped — the regression features only fit well inside the profiled
    region, see RegressionModel).  Implements the same .cost interface."""
    name: str
    sim: HiveSimulator = dataclasses.field(default_factory=HiveSimulator)

    def cost(self, ss: float, cs: float, nc: float, ls: float = 74.0) -> float:
        return self.sim.cost(self.name, ss, max(ls, ss), cs, nc)

    def cost_grid(self, ss, ls, configs, xp=np):
        nc, cs = _split_configs(configs, xp)
        big = max(ls, ss) if isinstance(ls, (int, float)) \
            and isinstance(ss, (int, float)) else xp.maximum(ls, ss)
        return self.sim.cost_grid(self.name, ss, big, cs, nc, xp)


def simulator_cost_models(sim: HiveSimulator | None = None
                          ) -> Dict[str, SimulatorCostModel]:
    sim = sim or HiveSimulator()
    return {"SMJ": SimulatorCostModel("SMJ", sim),
            "BHJ": SimulatorCostModel("BHJ", sim)}


def monetary_cost(exec_time_s: float, cs: float, nc: float,
                  dollars_per_gb_hour: float = 0.05) -> float:
    """Serverless billing (§III-C): pay for total container-GB-hours."""
    return exec_time_s / 3600.0 * cs * nc * dollars_per_gb_hour


# --------------------------------------------------------------------------- #
# plan-lint registration: expose the shipped DB cost surfaces to the static
# analyzer (``python -m repro.analysis``).  Factories are lazy — nothing
# here builds a model or imports jax until the lint traces a surface.
# --------------------------------------------------------------------------- #

def _register_lint_surfaces() -> None:
    from repro.analysis.registry import CostSurface, register_cost_surface

    def db_surface(name: str, make_model: Callable) -> None:
        def make_fn(xp):
            model = make_model()

            def fn(configs, params):
                # params = [ss, ls]: the per-request relation sizes, the
                # same parameterization plans.py uses so degraded/recurring
                # requests share one compiled search program
                return model.cost_grid(params[0], params[1], configs, xp=xp)
            return fn

        def make_cluster():
            from repro.core.cluster import paper_cluster
            return paper_cluster()

        register_cost_surface(CostSurface(
            name=name, domain="db", make_fn=make_fn,
            make_cluster=make_cluster, params=(2.0, 74.0)))

    db_surface("db/paper/SMJ", lambda: paper_models()["SMJ"])
    db_surface("db/paper/BHJ", lambda: paper_models()["BHJ"])
    db_surface("db/sim/SMJ", lambda: simulator_cost_models()["SMJ"])
    db_surface("db/sim/BHJ", lambda: simulator_cost_models()["BHJ"])


_register_lint_surfaces()
